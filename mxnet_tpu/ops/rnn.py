"""Fused multi-layer RNN op: vanilla/LSTM/GRU, optionally bidirectional.

Ref: src/operator/rnn.{cc,cu}, rnn-inl.h, nn/cudnn/cudnn_rnn-inl.h — the
cuDNN-backed fused RNN.  TPU-native design: the whole multi-layer,
multi-timestep recurrence is ONE ``lax.scan`` per layer/direction, so
XLA compiles a single fused while-loop whose body is a (batch, 4H)
matmul on the MXU — the same fusion cuDNN provides, expressed
compiler-first.  A Pallas variant can later replace the scan body; the
parameter layout here is the stable contract.

Parameter layout (flat vector, mirrors the reference's packed cuDNN
canonical layout): for each layer, for each direction:
``i2h_weight (G*H, in)``, ``h2h_weight (G*H, H)``; then, after all
weights, for each layer/direction: ``i2h_bias (G*H)``, ``h2h_bias
(G*H)``.  Gate order: LSTM (i, f, g, o); GRU (r, z, n).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers, input_size, state_size, mode,
                   bidirectional=False, projection_size=None):
    """Total length of the flat parameter vector."""
    g = _GATES[mode]
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        for _ in range(d):
            size += g * state_size * (in_sz + state_size)  # weights
    size += num_layers * d * 2 * g * state_size  # biases
    return size


def _unpack(params, num_layers, input_size, state_size, mode, d):
    g = _GATES[mode]
    ws, off = [], 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        per_dir = []
        for _ in range(d):
            wi = params[off:off + g * state_size * in_sz].reshape(
                g * state_size, in_sz)
            off += wi.size
            wh = params[off:off + g * state_size * state_size].reshape(
                g * state_size, state_size)
            off += wh.size
            per_dir.append([wi, wh])
        ws.append(per_dir)
    for layer in range(num_layers):
        for dd in range(d):
            bi = params[off:off + g * state_size]
            off += g * state_size
            bh = params[off:off + g * state_size]
            off += g * state_size
            ws[layer][dd] += [bi, bh]
    return ws


def _step_fn(mode):
    if mode == "lstm":
        def step(carry, x_t, wi, wh, bi, bh):
            h, c = carry
            gates = x_t @ wi.T + bi + h @ wh.T + bh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h
        return step
    if mode == "gru":
        def step(carry, x_t, wi, wh, bi, bh):
            (h,) = carry
            gi = x_t @ wi.T + bi
            gh = h @ wh.T + bh
            ir, iz, inn = jnp.split(gi, 3, axis=-1)
            hr, hz, hn = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(ir + hr)
            z = jax.nn.sigmoid(iz + hz)
            n = jnp.tanh(inn + r * hn)
            h = (1 - z) * n + z * h
            return (h,), h
        return step
    act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh

    def step(carry, x_t, wi, wh, bi, bh):
        (h,) = carry
        h = act(x_t @ wi.T + bi + h @ wh.T + bh)
        return (h,), h
    return step


def _scan_dir(step, xs, init, wi, wh, bi, bh, reverse):
    def body(carry, x_t):
        return step(carry, x_t, wi, wh, bi, bh)

    carry, ys = lax.scan(body, init, xs, reverse=reverse)
    return carry, ys


def _use_pallas_rnn():
    """Pallas LSTM/GRU recurrence kernels on TPU
    (MXTPU_RNN_IMPL=auto|pallas|scan)."""
    from ..base import getenv

    impl = getenv("RNN_IMPL", "auto").lower()
    if impl == "scan":
        return False
    if impl == "pallas":
        return True
    # auto: the kernel on TPU wherever _pallas_lstm_fits admits the
    # shape; a Mosaic rejection there is a loud compile error
    return jax.default_backend() == "tpu"


def _pallas_lstm_fits(N, H, G=4):
    """Static VMEM guard: the kernel holds Wh (G*H,H) + an x_proj block
    (N,G*H) + states/gates, double-buffered by Mosaic. Stay well under
    the ~16 MB/core VMEM or fall back to lax.scan (same guard idea as
    flash-attention's _tiles_ok)."""
    est = 4 * (G * H * H          # Wh
               + 3 * N * G * H    # x_proj block + gates out + dgates
               + 6 * N * H)       # h/c scratch + ys/cs blocks
    return 2 * est < 12 * 1024 * 1024


def _pallas_gru_dir(xs, init, wi, wh, bi, bh, reverse):
    """Same cuDNN-style split as the LSTM; bh stays a kernel input (the
    reset gate multiplies its n-slot, so it cannot fold into x_proj)."""
    from .pallas.rnn import gru_layer

    if reverse:
        xs = jnp.flip(xs, axis=0)
    x_proj = xs @ wi.T + bi
    (h0,) = init
    ys, hn = gru_layer(x_proj, wh, bh, h0)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return (hn,), ys


def _pallas_lstm_dir(xs, init, wi, wh, bi, bh, reverse):
    """cuDNN-style split: time-batched input GEMM in XLA (MXU-tiled),
    sequential recurrence in the Pallas kernel (ops/pallas/rnn.py)."""
    from .pallas.rnn import lstm_layer

    if reverse:
        xs = jnp.flip(xs, axis=0)
    x_proj = xs @ wi.T + (bi + bh)
    h0, c0 = init
    ys, hn, cn = lstm_layer(x_proj, wh, h0, c0)
    if reverse:
        ys = jnp.flip(ys, axis=0)
    return (hn, cn), ys


def _k_rnn(data, parameters, state, state_cell=None, key=None, *,
           state_size, num_layers, mode="lstm", bidirectional=False,
           p=0.0, state_outputs=False, projection_size=None,
           lstm_state_clip_min=None, lstm_state_clip_max=None,
           use_sequence_length=False, _train=False):
    """data: (seq, batch, input) [TNC].  Returns (out, h_n[, c_n])."""
    d = 2 if bidirectional else 1
    T, N, I = data.shape
    H = state_size
    ws = _unpack(parameters, num_layers, I, H, mode, d)
    step = _step_fn(mode)
    is_lstm = mode == "lstm"

    pallas_lstm = is_lstm and _use_pallas_rnn()
    pallas_gru = mode == "gru" and _use_pallas_rnn()
    x = data
    h_states, c_states = [], []
    for layer in range(num_layers):
        outs = []
        for dd in range(d):
            wi, wh, bi, bh = ws[layer][dd]
            idx = layer * d + dd
            h0 = state[idx]
            init = (h0, state_cell[idx]) if is_lstm else (h0,)
            if pallas_lstm and _pallas_lstm_fits(N, H):
                # kernel takes Wh as (4H, H); its step does dgp @ Wh
                carry, ys = _pallas_lstm_dir(x, init, wi, wh, bi, bh,
                                             reverse=(dd == 1))
            elif pallas_gru and _pallas_lstm_fits(N, H, G=3):
                carry, ys = _pallas_gru_dir(x, init, wi, wh, bi, bh,
                                            reverse=(dd == 1))
            else:
                carry, ys = _scan_dir(step, x, init, wi, wh, bi, bh,
                                      reverse=(dd == 1))
            outs.append(ys)
            h_states.append(carry[0])
            if is_lstm:
                c_states.append(carry[1])
        x = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0 and _train and key is not None and layer < num_layers - 1:
            k = jax.random.fold_in(key, layer)
            mask = jax.random.bernoulli(k, 1 - p, x.shape).astype(x.dtype)
            x = x * mask / (1 - p)
    h_n = jnp.stack(h_states, axis=0)
    if is_lstm:
        return x, h_n, jnp.stack(c_states, axis=0)
    return x, h_n


register("RNN", _k_rnn,
         arg_names=("data", "parameters", "state", "state_cell"),
         aliases=("rnn",), train_aware=True, needs_rng=True, num_outputs=-1)
