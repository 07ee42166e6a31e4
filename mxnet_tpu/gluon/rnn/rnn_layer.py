"""Fused recurrent layers (ref: python/mxnet/gluon/rnn/rnn_layer.py).

RNN/LSTM/GRU over the fused scan op (ops/rnn.py — the cuDNN-RNN
equivalent).  Parameters are per-layer i2h/h2h weights/biases like the
reference; forward packs them into the op's flat vector (XLA fuses the
concat away).
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError
from ..block import HybridBlock


class _RNNLayer(HybridBlock):
    def __init__(self, mode, hidden_size, num_layers, layout,
                 dropout, bidirectional, input_size=0,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer="zeros", h2h_bias_initializer="zeros",
                 **kwargs):
        super().__init__(**kwargs)
        assert layout in ("TNC", "NTC"), layout
        self._mode = mode
        self._hidden_size = hidden_size
        self._num_layers = num_layers
        self._layout = layout
        self._dropout = dropout
        self._dir = 2 if bidirectional else 1
        self._input_size = input_size
        self._gates = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]

        g, h = self._gates, hidden_size
        for layer in range(num_layers):
            in_sz = input_size if layer == 0 else hidden_size * self._dir
            for d in range(self._dir):
                sfx = ["l", "r"][d]
                setattr(self, f"{sfx}{layer}_i2h_weight", self.params.get(
                    f"{sfx}{layer}_i2h_weight", shape=(g * h, in_sz),
                    init=i2h_weight_initializer, allow_deferred_init=True))
                setattr(self, f"{sfx}{layer}_h2h_weight", self.params.get(
                    f"{sfx}{layer}_h2h_weight", shape=(g * h, h),
                    init=h2h_weight_initializer, allow_deferred_init=True))
                setattr(self, f"{sfx}{layer}_i2h_bias", self.params.get(
                    f"{sfx}{layer}_i2h_bias", shape=(g * h,),
                    init=i2h_bias_initializer, allow_deferred_init=True))
                setattr(self, f"{sfx}{layer}_h2h_bias", self.params.get(
                    f"{sfx}{layer}_h2h_bias", shape=(g * h,),
                    init=h2h_bias_initializer, allow_deferred_init=True))

    def infer_shape(self, x, *args):
        in_sz = x.shape[2] if self._layout == "TNC" else x.shape[2]
        g, h = self._gates, self._hidden_size
        for layer in range(self._num_layers):
            cur = in_sz if layer == 0 else h * self._dir
            for d in range(self._dir):
                sfx = ["l", "r"][d]
                self._reg_params[f"{sfx}{layer}_i2h_weight"].shape = \
                    (g * h, cur)

    def state_info(self, batch_size=0):
        raise NotImplementedError

    def begin_state(self, batch_size=0, func=None, **kwargs):
        """Ref: _RNNLayer.begin_state — ``kwargs`` (``ctx=``) go to the
        creation function, as in the reference."""
        from ...ndarray import ndarray as _nd

        func = func or _nd.zeros
        return [func(info["shape"], **kwargs)
                for info in self.state_info(batch_size)]

    def _flat_params(self, F, params):
        """Pack per-layer params into the fused op's flat layout."""
        weights, biases = [], []
        for layer in range(self._num_layers):
            for d in range(self._dir):
                sfx = ["l", "r"][d]
                weights.append(F.reshape(
                    params[f"{sfx}{layer}_i2h_weight"], shape=(-1,)))
                weights.append(F.reshape(
                    params[f"{sfx}{layer}_h2h_weight"], shape=(-1,)))
        for layer in range(self._num_layers):
            for d in range(self._dir):
                sfx = ["l", "r"][d]
                biases.append(params[f"{sfx}{layer}_i2h_bias"])
                biases.append(params[f"{sfx}{layer}_h2h_bias"])
        return F.concat(*(weights + biases), dim=0)

    def hybrid_forward(self, F, x, *states, **params):
        if self._layout == "NTC":
            x = F.swapaxes(x, dim1=0, dim2=1)
        flat = self._flat_params(F, params)
        batch_axis_states = list(states)
        if not batch_axis_states:
            raise MXNetError(
                f"{type(self).__name__} requires begin_state(); call "
                "layer(x, layer.begin_state(batch_size)) or pass states")
        rnn_args = [x, flat] + batch_axis_states
        out = F.RNN(*rnn_args, state_size=self._hidden_size,
                    num_layers=self._num_layers, mode=self._mode,
                    bidirectional=self._dir == 2, p=self._dropout,
                    state_outputs=True)
        output, *out_states = out
        if self._layout == "NTC":
            output = F.swapaxes(output, dim1=0, dim2=1)
        return output, out_states

    def __call__(self, x, states=None, **kwargs):
        from ...ndarray.ndarray import NDArray

        skip_states = states is None
        if skip_states:
            if isinstance(x, NDArray):
                bs = x.shape[0] if self._layout == "NTC" else x.shape[1]
                # the implicit state lives where the input lives (ref:
                # begin_state(batch_size, ctx=inputs.context)), not on
                # the default context — the host, even beside a chip;
                # under a trace there is no device and a constant is
                # placed by the compiler
                from ..block import is_tracing

                ctx = None if is_tracing() else x.context
                states = self.begin_state(bs, ctx=ctx)
            else:
                states = []
        if isinstance(states, (list, tuple)) and states and \
                not isinstance(states, NDArray):
            pass
        out = super().__call__(x, *states)
        output, out_states = out
        if skip_states:
            return output
        return output, out_states


class RNN(_RNNLayer):
    """Vanilla multi-layer RNN (ref: gluon.rnn.RNN)."""

    def __init__(self, hidden_size, num_layers=1, activation="relu",
                 layout="TNC", dropout=0, bidirectional=False,
                 input_size=0, **kwargs):
        mode = "rnn_relu" if activation == "relu" else "rnn_tanh"
        super().__init__(mode, hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, **kwargs)

    def state_info(self, batch_size=0):
        return [{"shape": (self._num_layers * self._dir, batch_size,
                           self._hidden_size)}]


class LSTM(_RNNLayer):
    """Multi-layer LSTM (ref: gluon.rnn.LSTM)."""

    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0, **kwargs):
        super().__init__("lstm", hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, **kwargs)

    def state_info(self, batch_size=0):
        shape = (self._num_layers * self._dir, batch_size,
                 self._hidden_size)
        return [{"shape": shape}, {"shape": shape}]


class GRU(_RNNLayer):
    """Multi-layer GRU (ref: gluon.rnn.GRU)."""

    def __init__(self, hidden_size, num_layers=1, layout="TNC", dropout=0,
                 bidirectional=False, input_size=0, **kwargs):
        super().__init__("gru", hidden_size, num_layers, layout, dropout,
                         bidirectional, input_size, **kwargs)

    def state_info(self, batch_size=0):
        return [{"shape": (self._num_layers * self._dir, batch_size,
                           self._hidden_size)}]
