"""Recurrent cells (ref: python/mxnet/gluon/rnn/rnn_cell.py).

Explicit per-step cells for custom unrolling (the un-fused fallback the
reference keeps beside the cuDNN layer).  ``unroll`` runs the python
loop; hybridize captures it into one XLA graph (XLA unrolls it).
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock


class RecurrentCell(HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._modified = False

    def state_info(self, batch_size=0):
        raise NotImplementedError

    def begin_state(self, batch_size=0, func=None, **kwargs):
        from ...ndarray import ndarray as _nd

        func = func or _nd.zeros
        return [func(info["shape"], **kwargs)
                for info in self.state_info(batch_size)]

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None, valid_length=None):
        """Ref: RecurrentCell.unroll."""
        from ... import ndarray as F

        axis = 1 if layout == "NTC" else 0
        if begin_state is None:
            bs = inputs.shape[1 - axis] if axis == 1 else inputs.shape[1]
            bs = inputs.shape[0] if layout == "NTC" else inputs.shape[1]
            begin_state = self.begin_state(bs)
        states = begin_state
        outputs = []
        for t in range(length):
            x_t = inputs[:, t] if layout == "NTC" else inputs[t]
            out, states = self(x_t, states)
            outputs.append(out)
        if merge_outputs is None or merge_outputs:
            outputs = F.stack(*outputs, axis=axis)
        if valid_length is not None:
            outputs = F.SequenceMask(
                outputs if layout == "TNC" else outputs.swapaxes(0, 1),
                valid_length, use_sequence_length=True)
            if layout == "NTC":
                outputs = outputs.swapaxes(0, 1)
        return outputs, states

    def __call__(self, x, states=None, **kwargs):
        if states is None:
            states = self.begin_state(x.shape[0])
        return super().__call__(x, *states)


class RNNCell(RecurrentCell):
    def __init__(self, hidden_size, activation="tanh", input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 **kwargs):
        super().__init__(**kwargs)
        self._hidden_size = hidden_size
        self._activation = activation
        self.i2h_weight = self.params.get(
            "i2h_weight", shape=(hidden_size, input_size),
            init=i2h_weight_initializer, allow_deferred_init=True)
        self.h2h_weight = self.params.get(
            "h2h_weight", shape=(hidden_size, hidden_size),
            init=h2h_weight_initializer, allow_deferred_init=True)
        self.i2h_bias = self.params.get(
            "i2h_bias", shape=(hidden_size,), init=i2h_bias_initializer,
            allow_deferred_init=True)
        self.h2h_bias = self.params.get(
            "h2h_bias", shape=(hidden_size,), init=h2h_bias_initializer,
            allow_deferred_init=True)

    def state_info(self, batch_size=0):
        return [{"shape": (batch_size, self._hidden_size)}]

    def infer_shape(self, x, *args):
        self.i2h_weight.shape = (self._hidden_size, x.shape[-1])

    def hybrid_forward(self, F, x, h, i2h_weight, h2h_weight, i2h_bias,
                       h2h_bias):
        i2h = F.FullyConnected(x, i2h_weight, i2h_bias,
                               num_hidden=self._hidden_size)
        h2h = F.FullyConnected(h, h2h_weight, h2h_bias,
                               num_hidden=self._hidden_size)
        out = F.Activation(i2h + h2h, act_type=self._activation)
        return out, [out]


class LSTMCell(RecurrentCell):
    """Gate order (i, f, g, o) — matches ops/rnn.py."""

    def __init__(self, hidden_size, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 **kwargs):
        super().__init__(**kwargs)
        self._hidden_size = hidden_size
        h = hidden_size
        self.i2h_weight = self.params.get(
            "i2h_weight", shape=(4 * h, input_size),
            init=i2h_weight_initializer, allow_deferred_init=True)
        self.h2h_weight = self.params.get(
            "h2h_weight", shape=(4 * h, h),
            init=h2h_weight_initializer, allow_deferred_init=True)
        self.i2h_bias = self.params.get(
            "i2h_bias", shape=(4 * h,), init=i2h_bias_initializer,
            allow_deferred_init=True)
        self.h2h_bias = self.params.get(
            "h2h_bias", shape=(4 * h,), init=h2h_bias_initializer,
            allow_deferred_init=True)

    def state_info(self, batch_size=0):
        return [{"shape": (batch_size, self._hidden_size)},
                {"shape": (batch_size, self._hidden_size)}]

    def infer_shape(self, x, *args):
        self.i2h_weight.shape = (4 * self._hidden_size, x.shape[-1])

    def hybrid_forward(self, F, x, h, c, i2h_weight, h2h_weight, i2h_bias,
                       h2h_bias):
        gates = F.FullyConnected(x, i2h_weight, i2h_bias,
                                 num_hidden=4 * self._hidden_size) + \
            F.FullyConnected(h, h2h_weight, h2h_bias,
                             num_hidden=4 * self._hidden_size)
        i, f, g, o = F.split(gates, num_outputs=4, axis=-1)
        c_new = F.sigmoid(f) * c + F.sigmoid(i) * F.tanh(g)
        h_new = F.sigmoid(o) * F.tanh(c_new)
        return h_new, [h_new, c_new]


class GRUCell(RecurrentCell):
    """Gate order (r, z, n) — matches ops/rnn.py."""

    def __init__(self, hidden_size, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 **kwargs):
        super().__init__(**kwargs)
        self._hidden_size = hidden_size
        h = hidden_size
        self.i2h_weight = self.params.get(
            "i2h_weight", shape=(3 * h, input_size),
            init=i2h_weight_initializer, allow_deferred_init=True)
        self.h2h_weight = self.params.get(
            "h2h_weight", shape=(3 * h, h),
            init=h2h_weight_initializer, allow_deferred_init=True)
        self.i2h_bias = self.params.get(
            "i2h_bias", shape=(3 * h,), init=i2h_bias_initializer,
            allow_deferred_init=True)
        self.h2h_bias = self.params.get(
            "h2h_bias", shape=(3 * h,), init=h2h_bias_initializer,
            allow_deferred_init=True)

    def state_info(self, batch_size=0):
        return [{"shape": (batch_size, self._hidden_size)}]

    def infer_shape(self, x, *args):
        self.i2h_weight.shape = (3 * self._hidden_size, x.shape[-1])

    def hybrid_forward(self, F, x, h, i2h_weight, h2h_weight, i2h_bias,
                       h2h_bias):
        gi = F.FullyConnected(x, i2h_weight, i2h_bias,
                              num_hidden=3 * self._hidden_size)
        gh = F.FullyConnected(h, h2h_weight, h2h_bias,
                              num_hidden=3 * self._hidden_size)
        ir, iz, inn = F.split(gi, num_outputs=3, axis=-1)
        hr, hz, hn = F.split(gh, num_outputs=3, axis=-1)
        r = F.sigmoid(ir + hr)
        z = F.sigmoid(iz + hz)
        n = F.tanh(inn + r * hn)
        h_new = (1 - z) * n + z * h
        return h_new, [h_new]


class SequentialRNNCell(RecurrentCell):
    """Stack cells (ref: SequentialRNNCell)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self._cells = []

    def add(self, cell):
        self.register_child(cell, str(len(self._cells)))
        self._cells.append(cell)

    def state_info(self, batch_size=0):
        out = []
        for c in self._cells:
            out.extend(c.state_info(batch_size))
        return out

    def __call__(self, x, states=None, **kwargs):
        if states is None:
            states = self.begin_state(x.shape[0])
        next_states = []
        i = 0
        for cell in self._cells:
            n = len(cell.state_info())
            x, cell_states = cell(x, states[i:i + n])
            next_states.extend(cell_states)
            i += n
        return x, next_states

    def forward(self, x, *states):
        return self.__call__(x, list(states) if states else None)


class DropoutCell(RecurrentCell):
    def __init__(self, rate, **kwargs):
        super().__init__(**kwargs)
        self._rate = rate

    def state_info(self, batch_size=0):
        return []

    def __call__(self, x, states=None, **kwargs):
        from ... import ndarray as F

        return F.Dropout(x, p=self._rate), states or []


class ModifierCell(RecurrentCell):
    """Base for cells wrapping another cell (ref: rnn_cell.ModifierCell)."""

    def __init__(self, base_cell, **kwargs):
        super().__init__(**kwargs)
        base_cell._modified = True
        self.base_cell = base_cell

    def state_info(self, batch_size=0):
        return self.base_cell.state_info(batch_size)

    def begin_state(self, batch_size=0, func=None, **kwargs):
        self.base_cell._modified = False
        begin = self.base_cell.begin_state(batch_size, func=func, **kwargs)
        self.base_cell._modified = True
        return begin

    def reset(self):
        pass


class ResidualCell(ModifierCell):
    def __call__(self, x, states=None, **kwargs):
        out, states = self.base_cell(x, states)
        return out + x, states


class ZoneoutCell(ModifierCell):
    """Zoneout regularization: randomly keep previous states
    (ref: rnn_cell.ZoneoutCell)."""

    def __init__(self, base_cell, zoneout_outputs=0.0, zoneout_states=0.0,
                 **kwargs):
        super().__init__(base_cell, **kwargs)
        self.zoneout_outputs = zoneout_outputs
        self.zoneout_states = zoneout_states
        self._prev_output = None

    def reset(self):
        self._prev_output = None

    def __call__(self, x, states=None, **kwargs):
        from ... import autograd
        from ... import ndarray as F

        out, next_states = self.base_cell(x, states)
        if not autograd.is_training():
            return out, next_states

        def zone(p, new, old):
            if p == 0.0 or old is None:
                return new
            mask = F.random.uniform(shape=new.shape) < p
            return F.where(mask.astype(new.dtype) > 0, old, new)

        prev = self._prev_output
        if prev is None:
            from ...ndarray import ndarray as _nd

            prev = _nd.zeros(out.shape)
        out = zone(self.zoneout_outputs, out, prev)
        self._prev_output = out
        if states is not None:
            next_states = [zone(self.zoneout_states, n, o)
                           for n, o in zip(next_states, states)]
        return out, next_states


class BidirectionalCell(RecurrentCell):
    """Run one cell forward and another backward over the sequence,
    concatenating outputs per step (ref: rnn_cell.BidirectionalCell —
    unroll-only, like the reference)."""

    def __init__(self, l_cell, r_cell, **kwargs):
        super().__init__(**kwargs)
        # a plain list bypasses Block.__setattr__ auto-registration, so
        # each cell registers exactly once under the reference's child
        # names (l_cell/r_cell) — checkpoint keys stay compatible
        self._cells = [l_cell, r_cell]
        self.register_child(l_cell, "l_cell")
        self.register_child(r_cell, "r_cell")

    @property
    def _l(self):
        return self._cells[0]

    @property
    def _r(self):
        return self._cells[1]

    def state_info(self, batch_size=0):
        return self._l.state_info(batch_size) + \
            self._r.state_info(batch_size)

    def __call__(self, x, states=None, **kwargs):
        raise NotImplementedError(
            "BidirectionalCell cannot step one timestep at a time "
            "(the backward direction needs the full sequence); "
            "call unroll() (reference behavior)")

    def unroll(self, length, inputs, begin_state=None, layout="NTC",
               merge_outputs=None, valid_length=None):
        from ... import ndarray as F

        axis = 1 if layout == "NTC" else 0

        def _rev(seq):
            """Time-reverse, honoring valid_length padding."""
            if valid_length is None:
                return F.reverse(seq, axis=axis)
            out = F.SequenceReverse(
                seq if layout == "TNC" else seq.swapaxes(0, 1),
                valid_length, use_sequence_length=True)
            return out.swapaxes(0, 1) if layout == "NTC" else out

        nl = len(self._l.state_info())
        if begin_state is None:
            bs = inputs.shape[0] if layout == "NTC" else inputs.shape[1]
            begin_state = self.begin_state(bs)
        l_out, l_states = self._l.unroll(
            length, inputs, begin_state[:nl], layout=layout,
            merge_outputs=True, valid_length=valid_length)
        r_out, r_states = self._r.unroll(
            length, _rev(inputs), begin_state[nl:], layout=layout,
            merge_outputs=True, valid_length=valid_length)
        out = F.concat(l_out, _rev(r_out), dim=2)
        states = l_states + r_states
        if merge_outputs is False:
            steps = [out[:, t] if layout == "NTC" else out[t]
                     for t in range(length)]
            return steps, states
        return out, states


class HybridSequentialRNNCell(SequentialRNNCell):
    """Hybridizable stacked cells (ref: HybridSequentialRNNCell — same
    stacking semantics; hybridization happens through the containing
    block here)."""
