"""Model quantization: calibration + INT8 graph rewrite.

Ref: python/mxnet/contrib/quantization.py (quantize_model, quantize_net,
_LayerOutputCollector, _get_optimal_threshold / KL calibration) and
src/operator/quantization/calibrate.cc — the fork owner's upstream
specialty (MKL-DNN INT8); here the int8 compute runs on the TPU MXU.

Two entry points, mirroring the reference:
  * ``quantize_model(sym, arg_params, aux_params, ...)`` — rewrites a
    symbolic graph: every FullyConnected/Convolution (unless excluded)
    becomes quantize→quantized_op→dequantize with weights quantized
    offline into the returned qarg_params.
  * ``quantize_net(net, ...)`` — replaces Dense/Conv2D children of a
    Gluon block with int8 wrappers in place.

Calibration modes: 'none' (dynamic per-batch ranges), 'naive' (min/max
over calibration data), 'entropy' (KL-divergence-optimal thresholds).

The gluon path (``quantize_net``) is COMPILE-NATIVE: Dense/Conv2D
layers become real HybridBlocks (:class:`QuantizedDense` /
:class:`QuantizedConv`) whose quantize → int8 matmul/conv →
requantize/bias → dequantize chain traces through
``gluon.block.traced_apply`` into one CachedOp executable — quantized
weights, per-output-channel scales, and calibrated ranges are proper
Parameters (runtime graph inputs), so the whole net hybridizes,
AOT-warms through ModelServer/DecodeServer, checkpoints, and
hot-reloads like any other block.  A range-fusion pass folds adjacent
``dequantize → quantize`` boundaries in calibrated chains into one
``requantize`` so activations stay int8 between quantized layers
(docs/quantization.md).
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import ndarray as nd
from .. import profiler
from .. import symbol as sym
from ..base import MXNetError
from ..gluon import block as _gluon_block
from ..ndarray.ndarray import NDArray
from ..symbol.symbol import Group, Symbol, _make_op_symbol, _topo_order

_QUANTIZABLE = ("FullyConnected", "Convolution")

_NUM_BINS = 8001


# ---------------------------------------------------------------------------
# window-scoped module counters: the profiler's `quantize` section
# (registered below; exported to /metrics as
# mxtpu_quantize_* gauges by the section collector)

_sec_lock = threading.Lock()
_sec = {"layers_quantized": 0, "calib_batches": 0, "calib_ms": 0.0,
        "requant_folds": 0, "int8_serve_batches": 0}


def _sec_bump(**deltas):
    with _sec_lock:
        for k, n in deltas.items():
            _sec[k] += n


def quantize_stats():
    """Window snapshot of the INT8 quantization counters (layers
    quantized, calibration batches + wall time, requantize folds, and
    compiled int8 batch executions through the serve tier)."""
    with _sec_lock:
        d = dict(_sec)
    d["calib_ms"] = round(d["calib_ms"], 3)
    return d


def reset_quantize_stats():
    with _sec_lock:
        for k in _sec:
            _sec[k] = 0.0 if k == "calib_ms" else 0


profiler.register_section(
    "quantize", quantize_stats, reset_quantize_stats, profiler.rows_table(
        "INT8 Quantization",
        (("layers quantized", "layers_quantized"),
         ("calibration batches", "calib_batches"),
         ("calibration time (ms)", "calib_ms"),
         ("requantize folds", "requant_folds"),
         ("int8 serve batches", "int8_serve_batches"))))


def note_int8_serve_batch(n=1):
    """Book ``n`` compiled int8 batch executions (ModelServer batches,
    DecodeServer prefill groups and token steps through a quantized
    net) — called by the serve tier, outside any trace."""
    _sec_bump(int8_serve_batches=n)


# ---------------------------------------------------------------------------
# Calibration


def _get_optimal_threshold(arr, num_bins=8001, num_quantized_bins=255):
    """KL-divergence-optimal |x| clipping threshold (ref:
    _get_optimal_threshold in python/mxnet/contrib/quantization.py —
    the TensorRT-style entropy calibration).
    """
    a = np.abs(np.asarray(arr, np.float64).ravel())
    amax = float(a.max()) if a.size else 0.0
    if amax == 0.0:
        return 1e-8
    hist, edges = np.histogram(a, bins=num_bins, range=(0.0, amax))
    return _optimal_threshold_from_hist(hist, edges, num_quantized_bins)


def _optimal_threshold_from_hist(hist, edges, num_quantized_bins=255):
    """Histogram-based core of the KL search: the calibration collector
    feeds an incrementally-built |x| histogram (fixed memory per tensor,
    ref: calibrate.cc keeps histograms, never raw samples)."""
    num_bins = len(hist)
    amax = float(edges[-1])
    if amax <= 0.0 or hist.sum() == 0:
        return 1e-8

    def smooth(d, eps=1e-4):
        # redistribute eps mass onto zero bins (ref: _smooth_distribution)
        nz = d > 0
        if not nz.any():
            return None
        out = d.astype(np.float64).copy()
        n_zero = d.size - nz.sum()
        if n_zero:
            take = eps * n_zero / nz.sum()
            out[nz] -= take * out[nz] / out[nz].max()
            out[~nz] = eps
        return out / out.sum()

    best_kl, best_t = np.inf, amax
    for i in range(num_quantized_bins, num_bins + 1,
                   max(1, num_bins // 200)):
        sliced = hist[:i].astype(np.float64)
        # P includes the clipped tail mass in its edge bin; Q is built
        # from the histogram WITHOUT that mass — an aggressive threshold
        # gives P an edge spike Q cannot represent, which is exactly
        # what penalizes over-clipping.
        p = sliced.copy()
        p[-1] += hist[i:].sum()
        if p.sum() == 0:
            continue
        nm = i // num_quantized_bins
        q = np.zeros(i, np.float64)
        for j in range(num_quantized_bins):
            lo = j * nm
            hi = i if j == num_quantized_bins - 1 else lo + nm
            seg = sliced[lo:hi]
            nz = np.count_nonzero(seg)
            if nz:
                q[lo:hi] = seg.sum() / nz
        q[sliced == 0] = 0
        pn, qn = smooth(p), smooth(q)
        if pn is None or qn is None:
            continue
        mask = pn > 0
        kl = float(np.sum(pn[mask] * np.log(pn[mask] / qn[mask])))
        if kl < best_kl:
            best_kl = kl
            best_t = float(edges[i if i < len(edges) else -1])
    return max(best_t, 1e-8)


def _k_calib_stats(x, *, entropy=False, bins=_NUM_BINS):
    """Device-side calibration statistics for one batch: min/max, and in
    entropy mode the batch's |x| max plus a fixed-bin |x| histogram over
    [0, batch amax] — ONE device dispatch per (tensor, batch), with the
    host sync deferred to ``_Stats.finalize()``.  The old hook path
    called ``.asnumpy()`` on every layer's input AND output per batch
    (2·L blocking syncs per calibration batch)."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    mn = jnp.min(xf)
    mx = jnp.max(xf)
    if not entropy:
        return mn, mx
    ab = jnp.abs(xf).ravel()
    amax = jnp.max(ab)
    idx = jnp.clip((ab * (bins / jnp.maximum(amax, 1e-30)))
                   .astype(jnp.int32), 0, bins - 1)
    hist = jnp.zeros((bins,), jnp.float32).at[idx].add(1.0)
    return mn, mx, amax, hist


class _Stats:
    """Running calibration statistics for one tensor.

    Entropy mode keeps one fixed-size |x| histogram per tensor, updated
    batch-by-batch (ref: calibrate.cc accumulates histograms, never raw
    activations) — host memory is O(num_bins) regardless of how much
    calibration data flows through.

    Two update paths: ``update(numpy)`` accumulates on the host;
    ``update_nd(NDArray)`` accumulates per-batch partials ON DEVICE
    (min, max, |x| histogram against the batch's own amax) and defers
    the host transfer to ``finalize()`` — one sync per tensor per
    ``DRAIN_EVERY`` batches (one total for typical calibration sets),
    and device memory stays bounded at ``DRAIN_EVERY`` histograms per
    tensor however much data flows through."""

    NUM_BINS = _NUM_BINS
    #: auto-finalize threshold: caps device-resident partials at
    #: DRAIN_EVERY x (NUM_BINS+3) floats per tensor (~2 MB) so a huge
    #: calibration sweep cannot accumulate per-batch histograms without
    #: bound — the sync amortizes 1/DRAIN_EVERY per batch instead of
    #: the old path's 2 blocking syncs per (tensor, batch)
    DRAIN_EVERY = 64

    def __init__(self, mode):
        self.mode = mode
        self.mn = np.inf
        self.mx = -np.inf
        self.hist = None
        self.amax = 0.0
        self._dev = []  # per-batch device partials, drained by finalize

    def update_nd(self, arr):
        from .._imperative import invoke

        outs = invoke(_k_calib_stats, arr, nondiff=True,
                      entropy=self.mode == "entropy")
        self._dev.append(outs)
        if len(self._dev) >= self.DRAIN_EVERY:
            self.finalize()

    def finalize(self):
        """Pull every device partial in ONE host sync and merge."""
        if not self._dev:
            return
        import jax.numpy as jnp

        parts = []
        for outs in self._dev:
            parts.extend(o._data.reshape(-1).astype(jnp.float32)
                         for o in outs)
        host = np.asarray(jnp.concatenate(parts))  # the one sync
        pos = 0
        rows = []
        for _ in self._dev:
            mn, mx = host[pos], host[pos + 1]
            pos += 2
            row = [float(mn), float(mx)]
            if self.mode == "entropy":
                amax = float(host[pos])
                pos += 1
                hist = host[pos:pos + self.NUM_BINS]
                pos += self.NUM_BINS
                row += [amax, hist]
            rows.append(row)
        self._dev = []
        self.mn = min([self.mn] + [r[0] for r in rows])
        self.mx = max([self.mx] + [r[1] for r in rows])
        if self.mode != "entropy":
            return
        gmax = max([self.amax] + [r[2] for r in rows])
        if gmax <= 0.0:
            return
        if self.hist is not None and gmax > self.amax:
            self.hist = self._rebin(self.hist, self.amax, gmax)
        merged = self.hist.astype(np.float64) if self.hist is not None \
            else np.zeros(self.NUM_BINS, np.float64)
        for _mn, _mx, amax, hist in rows:
            if amax <= 0.0:
                continue
            merged += self._rebin(hist.astype(np.float64), amax, gmax)
        self.hist = merged
        self.amax = gmax

    @classmethod
    def _rebin(cls, hist, from_amax, to_amax):
        """Map a histogram over [0, from_amax] onto [0, to_amax] by bin
        center (one-bin blur at worst) — the widening rule the host
        update path applies incrementally, reused for the batched
        device partials."""
        if from_amax == to_amax:
            return hist
        centers = (np.arange(cls.NUM_BINS) + 0.5) * (from_amax
                                                     / cls.NUM_BINS)
        new_idx = np.minimum(
            (centers / to_amax * cls.NUM_BINS).astype(np.int64),
            cls.NUM_BINS - 1)
        widened = np.zeros(cls.NUM_BINS, hist.dtype)
        np.add.at(widened, new_idx, hist)
        return widened

    def update(self, a):
        a = np.asarray(a)
        self.mn = min(self.mn, float(a.min()))
        self.mx = max(self.mx, float(a.max()))
        if self.mode != "entropy":
            return
        ab = np.abs(a.ravel().astype(np.float64))
        bmax = float(ab.max()) if ab.size else 0.0
        if self.hist is None:
            self.amax = max(bmax, 1e-12)
            self.hist = np.histogram(
                ab, bins=self.NUM_BINS, range=(0.0, self.amax))[0]
            return
        if bmax > self.amax:
            # widen: rebin the existing histogram onto the larger range
            # by bin center (one-bin blur at worst)
            self.hist = self._rebin(self.hist, self.amax, bmax)
            self.amax = bmax
        self.hist = self.hist + np.histogram(
            ab, bins=self.NUM_BINS, range=(0.0, self.amax))[0]

    def range(self):
        self.finalize()
        if self.mode == "entropy" and self.hist is not None:
            edges = np.linspace(0.0, self.amax, self.NUM_BINS + 1)
            t = _optimal_threshold_from_hist(self.hist, edges)
            return -t, t
        return self.mn, self.mx


def _iter_calib_batches(calib_data, num_calib_examples=None):
    """Yield numpy data batches from an iterator / NDArray / ndarray."""
    if isinstance(calib_data, (NDArray, np.ndarray)):
        yield np.asarray(calib_data.asnumpy() if isinstance(
            calib_data, NDArray) else calib_data)
        return
    seen = 0
    if hasattr(calib_data, "reset"):
        calib_data.reset()
    for batch in calib_data:
        # DataBatch duck-typing must not trip over numpy's .data
        # memoryview attribute
        data = batch.data[0] if (hasattr(batch, "data") and
                                 not isinstance(batch,
                                                (np.ndarray, NDArray))) \
            else batch
        if isinstance(data, (list, tuple)):
            data = data[0]
        arr = data.asnumpy() if isinstance(data, NDArray) else np.asarray(data)
        yield arr
        seen += arr.shape[0]
        if num_calib_examples is not None and seen >= num_calib_examples:
            return


def _collect_layer_stats(symbol, arg_params, aux_params, targets, calib_data,
                         calib_mode, data_name, num_calib_examples, ctx):
    """Forward calibration batches through the fp32 graph, recording
    stats for each target node's data input and output (ref:
    _LayerOutputCollector / collect_quantized_stat)."""
    handles = []
    keys = []
    for node in targets:
        src, oi = node.inputs[0]
        handles.append(Symbol(src, oi))
        keys.append((node.name, "data"))
        handles.append(Symbol(node, 0))
        keys.append((node.name, "out"))
    group = Group(handles)
    stats = {k: _Stats(calib_mode) for k in keys}
    # materialize batches once: calib_data may be a non-resettable
    # generator, and the first batch is needed for binding anyway
    batches = list(_iter_calib_batches(calib_data, num_calib_examples))
    if not batches:
        raise MXNetError("calibration data yielded no batches")
    args = dict(arg_params)
    args[data_name] = nd.array(batches[0], ctx=ctx)
    ex = group.bind(ctx, args, grad_req="null",
                    aux_states=dict(aux_params) if aux_params else None)
    t0 = time.monotonic()
    with profiler.op_scope("quantize.calibrate", cat="quantize"):
        for arr in batches:
            outs = ex.forward(is_train=False,
                              **{data_name: nd.array(arr, ctx=ctx)})
            # stats accumulate on device; range() below syncs each
            # tensor's partials exactly once
            for k, o in zip(keys, outs):
                stats[k].update_nd(o)
            _sec_bump(calib_batches=1)
        ranges = {k: s.range() for k, s in stats.items()}
    _sec_bump(calib_ms=(time.monotonic() - t0) * 1e3)
    return ranges


# ---------------------------------------------------------------------------
# Symbolic graph rewrite


def _offline_quantize(name, arr, qarg_params):
    """Quantize a parameter offline; store q/min/max (ref: the reference
    stores `<param>_quantize` plus range params in qarg_params)."""
    a = arr.asnumpy() if isinstance(arr, NDArray) else np.asarray(arr)
    q, qmin, qmax = _np_quantize(a)
    qarg_params[name + "_quantize"] = q
    qarg_params[name + "_min"] = qmin
    qarg_params[name + "_max"] = qmax
    return (sym.var(name + "_quantize"), sym.var(name + "_min"),
            sym.var(name + "_max"))


def quantize_model(symbol, arg_params, aux_params=None, data_names=("data",),
                   excluded_sym_names=(), calib_mode="none", calib_data=None,
                   num_calib_examples=None, quantized_dtype="int8",
                   ctx=None, logger=None):
    """Quantize a symbolic model to INT8 (ref: quantize_model in
    python/mxnet/contrib/quantization.py).

    Returns ``(qsym, qarg_params, aux_params)``.  FullyConnected and
    Convolution nodes are rewritten to int8 kernels; everything else
    stays fp32, with dequantize stitching the boundaries.
    """
    from ..context import current_context

    if quantized_dtype not in ("int8", "auto"):
        raise MXNetError(f"unsupported quantized_dtype {quantized_dtype!r}"
                         " (TPU build quantizes to signed int8)")
    ctx = ctx or current_context()
    aux_params = aux_params or {}
    nodes = _topo_order([symbol._node])
    targets = [n for n in nodes if n.op in _QUANTIZABLE
               and n.name not in set(excluded_sym_names)
               and n.inputs[1][0].op is None]  # weight must be a variable

    calib_tbl = {}
    if calib_mode != "none":
        if calib_data is None:
            raise MXNetError(f"calib_mode={calib_mode!r} needs calib_data")
        calib_tbl = _collect_layer_stats(
            symbol, arg_params, aux_params, targets, calib_data, calib_mode,
            data_names[0], num_calib_examples, ctx)
        if logger:
            for k, v in calib_tbl.items():
                logger.info("calib %s: [%g, %g]", k, *v)

    qarg_params = {}
    target_ids = {id(n) for n in targets}
    rewritten = {}  # id(node) -> new node (for Symbol(node, idx) handles)

    def handle(src, oi):
        return Symbol(rewritten[id(src)], oi)

    for n in nodes:
        if n.op is None:
            rewritten[id(n)] = sym.var(n.name)._node
            continue
        ins = [handle(s, oi) for s, oi in n.inputs]
        if id(n) not in target_ids:
            rewritten[id(n)] = _make_op_symbol(n.op, ins, dict(n.attrs),
                                               name=n.name)._node
            continue
        # --- the quantized replacement -----------------------------------
        data_in = ins[0]
        dr = calib_tbl.get((n.name, "data"))
        qattrs = {"out_type": "int8"}
        if dr is not None:
            qattrs.update(min_calib_range=dr[0], max_calib_range=dr[1])
        qd = _make_op_symbol("_contrib_quantize_v2", [data_in], qattrs,
                             name=n.name + "_quantize")
        wname = n.inputs[1][0].name
        qw, wmin, wmax = _offline_quantize(wname, arg_params[wname],
                                           qarg_params)
        no_bias = len(n.inputs) < 3 or bool(n.attrs.get("no_bias", False))
        if not no_bias:
            bname = n.inputs[2][0].name
            qb, bmin, bmax = _offline_quantize(bname, arg_params[bname],
                                               qarg_params)
            q_ins = [qd[0], qw, qb, qd[1], qd[2], wmin, wmax, bmin, bmax]
        else:
            q_ins = [qd[0], qw, None, qd[1], qd[2], wmin, wmax]
            q_ins = [x for x in q_ins if x is not None]
        qop = ("_contrib_quantized_fully_connected"
               if n.op == "FullyConnected" else "_contrib_quantized_conv")
        attrs = dict(n.attrs)
        attrs.pop("cudnn_tune", None), attrs.pop("cudnn_off", None)
        attrs.pop("workspace", None)
        attrs["no_bias"] = no_bias
        qnode = _make_op_symbol(qop, q_ins, attrs, name=n.name + "_int8")
        out, omin, omax = qnode[0], qnode[1], qnode[2]
        orr = calib_tbl.get((n.name, "out"))
        if orr is not None:
            rq = _make_op_symbol(
                "_contrib_requantize", [out, omin, omax],
                {"min_calib_range": orr[0], "max_calib_range": orr[1]},
                name=n.name + "_requantize")
            out, omin, omax = rq[0], rq[1], rq[2]
        deq = _make_op_symbol("_contrib_dequantize", [out, omin, omax], {},
                              name=n.name + "_dequantize")
        rewritten[id(n)] = deq._node

    qsym = Symbol(rewritten[id(symbol._node)], symbol._index)
    # carry over the fp32 params the rewritten graph still references
    # (replaced weights drop out of list_arguments automatically)
    for name in qsym.list_arguments():
        if name not in qarg_params and name in arg_params:
            qarg_params[name] = arg_params[name]
    return qsym, qarg_params, dict(aux_params)


# ---------------------------------------------------------------------------
# Gluon net quantization


def _np_quantize(a):
    r = float(np.max(np.abs(a))) or 1e-8
    q = np.clip(np.round(a * (127.0 / r)), -127, 127).astype(np.int8)
    return nd.array(q), nd.array(np.float32(-r).reshape(())), \
        nd.array(np.float32(r).reshape(()))


def _np_quantize_per_channel(a, per_channel=True):
    """Offline symmetric int8 weight quantization with PER-OUTPUT-CHANNEL
    ranges (axis 0 for both Dense ``(U, I)`` and Conv ``(O, I, *k)``
    weights).  Per-tensor mode returns a length-1 range vector so the
    per-channel kernels serve both without a second code path."""
    a = np.asarray(a, np.float32)
    if per_channel and a.ndim >= 2:
        r = np.abs(a.reshape(a.shape[0], -1)).max(axis=1)
    else:
        r = np.abs(a).max().reshape(1)
    r = np.maximum(r, 1e-8).astype(np.float32)
    scale = 127.0 / r.reshape((-1,) + (1,) * (a.ndim - 1))
    q = np.clip(np.round(a * scale), -127, 127).astype(np.int8)
    return q, r


def _quantized_dense_forward(F, x, qweight, wscale, bias, in_min, in_max,
                             out_min, out_max, *, units, flatten, act,
                             calibrated, out_int8):
    """The compiled int8 Dense chain: quantize → int8×int8→int32 matmul
    (per-channel scales, bias folded into the int32 accumulator) →
    requantize → dequantize.  Runs identically eager and under graph
    capture; an int8 input (a folded upstream boundary) skips the
    quantize stage and is interpreted at the in_min/in_max range.
    Everything after ``*`` is a STATIC structural attribute (the
    kw-only convention the trace-safety lints key on)."""
    if str(x.dtype) == "int8":
        if not calibrated:
            raise MXNetError(
                "an int8 input needs calibrated ranges to interpret "
                "it: this quantized layer was built without "
                "calibration (dynamic ranges) — quantize the whole "
                "chain with calib_data= so the boundary range is known")
        qx, dmn, dmx = x, in_min, in_max
    elif calibrated:
        qx, dmn, dmx = F.contrib.quantize(x, in_min, in_max)
    else:
        qx, dmn, dmx = F.contrib.quantize_v2(x)
    if bias is None:
        acc, omn, omx = F.contrib.quantized_dense_pc(
            qx, qweight, wscale, dmn, dmx, num_hidden=units,
            no_bias=True, flatten=flatten)
    else:
        acc, omn, omx = F.contrib.quantized_dense_pc(
            qx, qweight, wscale, bias, dmn, dmx, num_hidden=units,
            flatten=flatten)
    return _finish_quantized(F, acc, omn, omx, out_min, out_max,
                             act=act, calibrated=calibrated,
                             out_int8=out_int8)


def _quantized_conv_forward(F, x, qweight, wscale, bias, in_min, in_max,
                            out_min, out_max, *, conv_kwargs, act,
                            calibrated, out_int8):
    """The compiled int8 Convolution chain (see
    ``_quantized_dense_forward``)."""
    if str(x.dtype) == "int8":
        if not calibrated:
            raise MXNetError(
                "an int8 input needs calibrated ranges to interpret "
                "it: this quantized layer was built without "
                "calibration (dynamic ranges) — quantize the whole "
                "chain with calib_data= so the boundary range is known")
        qx, dmn, dmx = x, in_min, in_max
    elif calibrated:
        qx, dmn, dmx = F.contrib.quantize(x, in_min, in_max)
    else:
        qx, dmn, dmx = F.contrib.quantize_v2(x)
    if bias is None:
        acc, omn, omx = F.contrib.quantized_conv_pc(
            qx, qweight, wscale, dmn, dmx, no_bias=True, **conv_kwargs)
    else:
        acc, omn, omx = F.contrib.quantized_conv_pc(
            qx, qweight, wscale, bias, dmn, dmx, **conv_kwargs)
    return _finish_quantized(F, acc, omn, omx, out_min, out_max,
                             act=act, calibrated=calibrated,
                             out_int8=out_int8)


def _finish_quantized(F, acc, omn, omx, out_min, out_max, *, act,
                      calibrated, out_int8):
    """Close the chain: calibrated relu/linear layers requantize the
    int32 accumulator to the calibrated int8 range (relu applied in
    int8 — symmetric scaling commutes with it), then either hand the
    int8 tensor straight to a folded consumer or dequantize to fp32.
    Other activations dequantize first (requantizing a pre-activation
    accumulator to a post-activation range would clip wrongly)."""
    if calibrated and act in (None, "relu"):
        q8, rmn, rmx = F.contrib.requantize_v2(acc, omn, omx, out_min,
                                               out_max, act=act)
        if out_int8:
            return q8
        return F.contrib.dequantize(q8, rmn, rmx)
    out = F.contrib.dequantize(acc, omn, omx)
    if act:
        out = F.Activation(out, act_type=act)
    return out


class _QuantizedBase:
    """Shared machinery of the int8 wrapper blocks: parameter creation
    from concrete host arrays, calibrated-range parameters, and hot
    re-quantization for fp32 weight reloads."""

    def _adopt_params(self, layer, data_range, out_range, per_channel):
        self._per_channel = bool(per_channel)
        self._calibrated = data_range is not None
        self._out_int8 = False
        ctxs = layer.weight.list_ctx()
        q, r = _np_quantize_per_channel(layer.weight.data().asnumpy(),
                                        self._per_channel)
        self.qweight = self._make_param("qweight", q, ctxs)
        self.wscale = self._make_param("wscale", r, ctxs)
        self.bias = (self._make_param(
            "bias", layer.bias.data().asnumpy(), ctxs)
            if layer.bias is not None else None)
        if self._calibrated:
            self.in_min = self._make_param(
                "in_min", np.float32(data_range[0]), ctxs)
            self.in_max = self._make_param(
                "in_max", np.float32(data_range[1]), ctxs)
            orr = out_range if out_range is not None else data_range
            self.out_min = self._make_param(
                "out_min", np.float32(orr[0]), ctxs)
            self.out_max = self._make_param(
                "out_max", np.float32(orr[1]), ctxs)

    def _make_param(self, name, arr, ctxs):
        arr = np.asarray(arr)
        p = self.params.get(name, shape=arr.shape, dtype=str(arr.dtype),
                            differentiable=False)
        p._data = {c: nd.array(arr, ctx=c, dtype=str(arr.dtype))
                   for c in ctxs}
        return p

    def requantize_from(self, weight, bias=None):
        """Re-quantize this layer from fresh fp32 weights AGAINST THE
        STORED per-channel scales (and keep the calibrated activation
        ranges) — the hot-reload contract: every range/scale is a
        runtime graph input, so a reload swaps numbers without a single
        recompile.  Weights that drifted beyond the stored scale clip;
        re-run ``quantize_net`` on a fresh twin if calibration is
        stale."""
        w = weight.asnumpy() if isinstance(weight, NDArray) \
            else np.asarray(weight, np.float32)
        r = self.wscale.data().asnumpy()
        scale = 127.0 / r.reshape((-1,) + (1,) * (w.ndim - 1))
        q = np.clip(np.round(w * scale), -127, 127).astype(np.int8)
        self.qweight.set_data(nd.array(q))
        if self.bias is not None:
            if bias is None:
                raise MXNetError(
                    f"quantized layer {self.name!r} has a bias but the "
                    "reload supplied none")
            b = bias if isinstance(bias, NDArray) else nd.array(
                np.asarray(bias, np.float32))
            self.bias.set_data(b)


def _check_nd_input(x):
    if not isinstance(x, NDArray):
        raise MXNetError(
            "quantized blocks do not support symbolic export; serve "
            "them directly through ModelServer/DecodeServer (the "
            "compiled path) instead")


class QuantizedDense(_QuantizedBase, _gluon_block.HybridBlock):
    """Compile-native int8 replacement for ``nn.Dense``.

    A REAL HybridBlock: the quantize → int8 matmul → requantize/bias →
    dequantize chain re-traces through ``traced_apply`` into whatever
    graph contains it (a hybridized net's CachedOp, a DecodeServer
    CachedStepOp), and the quantized weight, per-channel scale vector,
    fp32 bias, and calibrated ranges are Parameters — runtime inputs of
    the compiled graph, so checkpointing, ``save_parameters`` and hot
    weight reloads all work with zero recompiles."""

    def __init__(self, layer, data_range=None, out_range=None,
                 per_channel=True):
        super().__init__(prefix=layer._prefix, params=None)
        self._units = layer._units
        self._flatten = layer._flatten
        self._activation = layer._activation
        self._adopt_params(layer, data_range, out_range, per_channel)

    def hybrid_forward(self, F, x, qweight, wscale, bias=None,
                       in_min=None, in_max=None, out_min=None,
                       out_max=None):
        _check_nd_input(x)
        return _quantized_dense_forward(
            F, x, qweight, wscale, bias, in_min, in_max, out_min,
            out_max, units=self._units, flatten=self._flatten,
            act=self._activation, calibrated=self._calibrated,
            out_int8=self._out_int8)


class QuantizedConv(_QuantizedBase, _gluon_block.HybridBlock):
    """Compile-native int8 replacement for ``nn.Conv2D`` (NCHW-layout
    forward convolutions; see :class:`QuantizedDense`)."""

    def __init__(self, layer, data_range=None, out_range=None,
                 per_channel=True):
        super().__init__(prefix=layer._prefix, params=None)
        kw = dict(layer._kwargs)
        for drop in ("layout", "no_bias", "adj"):
            kw.pop(drop, None)
        self._kwargs = kw
        self._activation = layer._activation
        self._adopt_params(layer, data_range, out_range, per_channel)

    def hybrid_forward(self, F, x, qweight, wscale, bias=None,
                       in_min=None, in_max=None, out_min=None,
                       out_max=None):
        _check_nd_input(x)
        return _quantized_conv_forward(
            F, x, qweight, wscale, bias, in_min, in_max, out_min,
            out_max, conv_kwargs=self._kwargs, act=self._activation,
            calibrated=self._calibrated, out_int8=self._out_int8)


def _quantizable(child, exclude):
    """Dense, or a forward NC*-layout Convolution block (the transpose
    and channel-last variants stay fp32 — the bypass matrix in
    docs/quantization.md)."""
    from ..gluon import nn as gnn
    from ..gluon.nn.conv_layers import _Conv

    if child.name in exclude:
        return False
    if isinstance(child, gnn.Dense):
        return True
    return (isinstance(child, _Conv)
            and getattr(child, "_op_name", None) == "Convolution"
            and not getattr(child, "_channel_last", False))


def _release_stale_caches(block):
    """Drop compiled fp32 graphs after the rewrite — a hybridized
    ancestor would otherwise keep serving the ORIGINAL layers out of
    its CachedOp.  Hybridization itself stays active: the next call
    re-captures through the int8 wrappers into a fresh executable."""
    op = getattr(block, "_cached_op", None)
    if op is not None:
        op.release()
        block._cached_op = None
    for child in getattr(block, "_children", {}).values():
        _release_stale_caches(child)


def _calibrate_gluon(network, targets, calib_data, calib_mode,
                     num_calib_examples, calib_forward):
    """Forward calibration batches through the fp32 net with hooks on
    every target layer accumulating min/max (and entropy histograms)
    ON DEVICE — one host sync per (layer, tensor) at the end, not
    2·L syncs per batch."""
    stats = {id(t[2]): (_Stats(calib_mode), _Stats(calib_mode))
             for t in targets}
    hooks = []
    for _, _, layer in targets:
        def hook(block, inputs, output, _s=stats):
            s_in, s_out = _s[id(block)]
            s_in.update_nd(inputs[0])
            out = output[0] if isinstance(output, (tuple, list)) \
                else output
            s_out.update_nd(out)
        hooks.append(layer.register_forward_hook(hook))
    # calibration needs EAGER child forwards (hooks fire per batch with
    # concrete tensors); temporarily deactivate any hybridized block so
    # a CachedOp can't swallow the layer calls, restore after
    deactivated = []

    def _deact(b):
        if getattr(b, "_active", False):
            deactivated.append(b)
            b._active = False
        for c in getattr(b, "_children", {}).values():
            _deact(c)

    _deact(network)
    t0 = time.monotonic()
    try:
        with profiler.op_scope("quantize.calibrate", cat="quantize"):
            n = 0
            for arr in _iter_calib_batches(calib_data,
                                           num_calib_examples):
                x = nd.array(arr)
                if calib_forward is not None:
                    calib_forward(network, x)
                else:
                    network(x)
                n += 1
                _sec_bump(calib_batches=1)
            if n == 0:
                raise MXNetError("calibration data yielded no batches")
            ranges = {}
            uncovered = []
            for _, _, layer in targets:
                s_in, s_out = stats[id(layer)]
                # range() drains each tensor's device partials in one
                # sync
                r_in, r_out = s_in.range(), s_out.range()
                # a layer the calibration forward never exercised has
                # (inf, -inf) stats; silently installing those as
                # calibrated ranges would serve NaNs with no error
                if not np.isfinite(r_in).all() \
                        or not np.isfinite(r_out).all():
                    uncovered.append(layer.name)
                    continue
                ranges[id(layer)] = (r_in, r_out)
            if uncovered:
                raise MXNetError(
                    f"calibration never exercised quantizable layer(s) "
                    f"{uncovered}: the calibration forward "
                    f"({'calib_forward' if calib_forward is not None else 'network(x)'}) "
                    "must run every layer being quantized — cover the "
                    "missing path or list the layer in exclude_layers")
    finally:
        for h in hooks:
            h.detach()
        for b in deactivated:
            b._active = True
    _sec_bump(calib_ms=(time.monotonic() - t0) * 1e3)
    return ranges


def _fold_requantize(network):
    """Range-propagation fusion: for consecutive calibrated quantized
    layers inside a Sequential/HybridSequential, fold the producer's
    ``requantize → dequantize`` + the consumer's ``quantize`` boundary
    into the producer's single requantize — the producer emits int8 at
    its calibrated output range and the consumer consumes it at that
    exact range (both hooks saw the same tensor, so the dequantize →
    quantize round trip this removes was the identity up to fp32
    rounding).  Only linear/relu producers fold: symmetric int8
    commutes with relu, not with other activations."""
    folds = 0

    def walk(block):
        nonlocal folds
        layers = getattr(block, "_layers", None)
        if layers:
            for a, b in zip(layers, layers[1:]):
                if (isinstance(a, (QuantizedDense, QuantizedConv))
                        and isinstance(b, (QuantizedDense,
                                           QuantizedConv))
                        and a._calibrated and b._calibrated
                        and a._activation in (None, "relu")):
                    a._out_int8 = True
                    # the int8 boundary travels at the PRODUCER's
                    # calibrated output range
                    b.in_min.set_data(a.out_min.data())
                    b.in_max.set_data(a.out_max.data())
                    folds += 1
        for child in getattr(block, "_children", {}).values():
            walk(child)

    walk(network)
    return folds


def quantize_net(network, calib_data=None, calib_mode="naive",
                 exclude_layers=None, num_calib_examples=None,
                 quantized_dtype="int8", per_channel=True, fold=True,
                 calib_forward=None):
    """Quantize a Gluon network's Dense/Conv2D layers to INT8 in place
    (ref: quantize_net in python/mxnet/contrib/quantization.py) — the
    result is a COMPILABLE net: it hybridizes into one XLA executable
    whose int8×int8→int32 matmuls/convs hit the MXU natively, serves
    through ModelServer/DecodeServer with zero post-warmup compiles,
    checkpoints through CheckpointManager, and hot-reloads fp32
    training weights via re-quantization.

    With ``calib_data``, activation ranges are calibrated ('naive'
    min/max or 'entropy' KL) by device-side hooks (one host sync per
    layer); without, ranges are computed per batch inside the compiled
    graph.  ``per_channel`` uses per-output-channel weight scales
    (default; per-tensor otherwise); ``fold`` keeps activations int8
    across adjacent calibrated layers; ``calib_forward(net, batch)``
    overrides the calibration forward for models without a plain
    ``__call__`` (e.g. decode models: ``lambda m, x: m.prefill(...)``).
    """
    if quantized_dtype not in ("int8", "auto"):
        raise MXNetError(f"unsupported quantized_dtype {quantized_dtype!r}"
                         " (TPU build quantizes to signed int8)")
    exclude = set(exclude_layers or ())
    targets = []  # (parent, child_key, layer)

    def walk(block):
        for key, child in list(block._children.items()):
            if _quantizable(child, exclude):
                targets.append((block, key, child))
            else:
                walk(child)

    walk(network)
    ranges = {}
    if calib_data is not None and calib_mode != "none":
        ranges = _calibrate_gluon(network, targets, calib_data,
                                  calib_mode, num_calib_examples,
                                  calib_forward)

    from ..gluon import nn as gnn

    for parent, key, layer in targets:
        dr, orr = ranges.get(id(layer), (None, None))
        wrapper_cls = (QuantizedDense if isinstance(layer, gnn.Dense)
                       else QuantizedConv)
        wrapper = wrapper_cls(layer, data_range=dr, out_range=orr,
                              per_channel=per_channel)
        parent._children[key] = wrapper
        # Sequential/HybridSequential iterate _layers, not _children
        layers = getattr(parent, "_layers", None)
        if layers is not None:
            for i, l in enumerate(layers):
                if l is layer:
                    layers[i] = wrapper
        # keep attribute access (net.fc1) pointing at the wrapper too
        for attr, val in list(vars(parent).items()):
            if val is layer:
                object.__setattr__(parent, attr, wrapper)
    _sec_bump(layers_quantized=len(targets))

    if fold and ranges:
        folds = _fold_requantize(network)
        _sec_bump(requant_folds=folds)

    _release_stale_caches(network)
    network._int8_quantized = True
    return network


# ---------------------------------------------------------------------------
# serving-tier reload: fp32 training checkpoints into a quantized net


def _iter_quantized(block, prefix=""):
    for name, child in getattr(block, "_children", {}).items():
        p = prefix + name + "."
        if isinstance(child, (QuantizedDense, QuantizedConv)):
            yield p, child
        else:
            yield from _iter_quantized(child, p)


def apply_fp32_params(qnet, loaded):
    """Re-quantize a quantized net in place from an fp32 twin's
    structural ``name -> NDArray`` dict (what a training checkpoint or
    ``save_parameters`` of the un-quantized architecture holds): each
    quantized layer's weight is re-quantized against its STORED
    per-channel scales, biases are copied, calibrated activation
    ranges are kept, and every non-quantized parameter lands directly.
    Loud on any structural mismatch."""
    loaded = dict(loaded)
    wrappers = dict(_iter_quantized(qnet))
    if not wrappers:
        raise MXNetError(
            "apply_fp32_params: network has no quantized layers — run "
            "contrib.quantization.quantize_net first")
    for path, wrapper in wrappers.items():
        wkey = path + "weight"
        if wkey not in loaded:
            raise MXNetError(
                f"fp32 reload: checkpoint is missing {wkey!r} for "
                f"quantized layer {wrapper.name!r} — was it saved from "
                "a different architecture?")
        w = loaded.pop(wkey)
        b = loaded.pop(path + "bias", None)
        wrapper.requantize_from(w, b)
    rest = {k: v for k, v in
            qnet._collect_params_with_prefix().items()
            if not any(k.startswith(p) for p in wrappers)}
    extra = sorted(set(loaded) - set(rest))
    missing = sorted(set(rest) - set(loaded))
    if extra or missing:
        raise MXNetError(
            "fp32 reload: parameter names do not line up with the "
            f"quantized net (extra in checkpoint: {extra}; missing "
            f"from checkpoint: {missing})")
    for k, v in loaded.items():
        rest[k].set_data(v)


def load_serving_params(net, loaded):
    """Hot-reload dispatch for quantized serving nets: an int8-native
    dict (saved FROM the quantized net) restores directly; an fp32
    dict (the training twin's checkpoint) re-quantizes through
    :func:`apply_fp32_params`.  ModelServer/DecodeServer
    ``reload_weights()`` route here when the served net is quantized."""
    if not loaded:
        raise MXNetError(
            "reload: checkpoint holds no parameters (saved without "
            "params=?)")
    own = net._collect_params_with_prefix()
    if any(k.endswith("qweight") for k in loaded):
        extra = sorted(set(loaded) - set(own))
        missing = sorted(set(own) - set(loaded))
        if extra or missing:
            raise MXNetError(
                "int8 reload: parameter names do not line up with the "
                f"quantized net (extra in checkpoint: {extra}; missing "
                f"from checkpoint: {missing})")
        for k, p in own.items():
            p.set_data(loaded[k])
    else:
        apply_fp32_params(net, loaded)
