"""Pallas fused LSTM/GRU recurrence kernels for TPU.

Ref: src/operator/rnn.{cc,cu}, nn/cudnn/cudnn_rnn-inl.h — the cuDNN
fused RNN. The BASELINE north star names this explicitly ("LSTM cell
kernels → Pallas").

TPU-native split (the same one cuDNN uses): the input projection
``x @ Wi.T + bi + bh`` is a single big batched GEMM over all timesteps
— left to XLA, which tiles it perfectly onto the MXU. What the compiler
CANNOT fuse well is the sequential recurrence; that is the Pallas
kernel here:

- forward: grid over T; per step one (N,H)x(H,4H) MXU matmul + VPU
  gate math, hidden/cell state living in VMEM scratch across grid
  steps (Mosaic double-buffers the x_proj block DMAs automatically).
- backward: a second Pallas kernel running the grid in reverse
  (index_map ``T-1-t``), accumulating dWh in VMEM scratch and
  producing per-step dgates for the XLA-side input-GEMM VJP.

Forward saves post-activation gates + cell states (the cuDNN
"reserveSpace" trick) so backward needs no recompute.

Parity contract: `lstm_layer(x_proj, wh, h0, c0)` == the lax.scan
reference in ops/rnn.py for the same flat-parameter layout; tested in
interpret mode on CPU (tests/test_pallas_rnn.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _lstm_fwd_kernel(xp_ref, wht_ref, h0_ref, c0_ref,
                     ys_ref, hn_ref, cn_ref, gates_ref, cs_ref,
                     h_scr, c_scr):
    # gate-axis layout: xp (1,N,4,H), wht (4,H,H), gates (1,N,4,H).
    # The 4 gates live on their own (sublane-side) axis, so no op ever
    # slices or concatenates at a non-128 offset of the lane axis — the
    # kernel is Mosaic-tileable for ANY H (DeepAR's H=40 included).
    # Mosaic's tpu.matmul is strictly 2-D (no batched contraction — the
    # first chip session rejected the (N,H)x(4,H,H) dot_general), so the
    # gate matmuls are a static 4-way unroll of clean (N,H)x(H,H) MXU
    # dots; wht is pre-transposed on the host so each is h @ Wh[g].T.
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    c = c_scr[:]
    xp = xp_ref[0].astype(jnp.float32)        # (N, 4, H)
    gp = [xp[:, g, :] + jnp.dot(h, wht_ref[g].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
          for g in range(4)]
    i = jax.nn.sigmoid(gp[0])
    f = jax.nn.sigmoid(gp[1])
    g = jnp.tanh(gp[2])
    o = jax.nn.sigmoid(gp[3])
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)

    h_scr[:] = h_new
    c_scr[:] = c_new
    ys_ref[0] = h_new.astype(ys_ref.dtype)
    cs_ref[0] = c_new.astype(cs_ref.dtype)
    for gi, v in enumerate((i, f, g, o)):
        gates_ref[0, :, gi, :] = v.astype(gates_ref.dtype)
    hn_ref[:] = h_new.astype(hn_ref.dtype)
    cn_ref[:] = c_new.astype(cn_ref.dtype)


def _lstm_forward(x_proj, wh, h0, c0):
    T, N, G4 = x_proj.shape
    H = wh.shape[1]
    xp4 = x_proj.reshape(T, N, 4, H)
    # pre-transpose per-gate so the kernel's dots need no in-kernel .T
    wh4 = wh.reshape(4, H, H).transpose(0, 2, 1)
    outs = pallas_call(
        _lstm_fwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, 4, H), lambda t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4, H, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((T, N, H), x_proj.dtype),    # ys
            jax.ShapeDtypeStruct((N, H), x_proj.dtype),       # h_n
            jax.ShapeDtypeStruct((N, H), x_proj.dtype),       # c_n
            jax.ShapeDtypeStruct((T, N, 4, H), jnp.float32),  # gates ifgo
            jax.ShapeDtypeStruct((T, N, H), jnp.float32),     # c states
        ),
        out_specs=(
            pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, 4, H), lambda t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((N, H), jnp.float32),
        ],
    )(xp4, wh4, h0, c0)
    return outs


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _lstm_bwd_kernel(dy_ref, gates_ref, cs_ref, cprev_ref, hprev_ref,
                     wh_ref, dhn_ref, dcn_ref,
                     dxp_ref, dwh_ref, dh0_ref, dc0_ref,
                     dh_scr, dc_scr, dwh_scr):
    # grid index runs 0..T-1 but index_maps feed step t = T-1-idx
    idx = pl.program_id(0)

    @pl.when(idx == 0)
    def _():
        dh_scr[:] = dhn_ref[:].astype(jnp.float32)
        dc_scr[:] = dcn_ref[:].astype(jnp.float32)
        dwh_scr[:] = jnp.zeros_like(dwh_scr)

    dh = dh_scr[:] + dy_ref[0].astype(jnp.float32)
    i = gates_ref[0, :, 0, :]                 # (N, H) post-activation
    f = gates_ref[0, :, 1, :]
    g = gates_ref[0, :, 2, :]
    o = gates_ref[0, :, 3, :]
    c_t = cs_ref[0]
    c_prev = cprev_ref[0]
    tc = jnp.tanh(c_t)

    do = dh * tc
    dc = dh * o * (1.0 - tc * tc) + dc_scr[:]
    # pre-activation gate grads, order i,f,g,o — kept as four (N,H)
    # arrays so every matmul below is a 2-D tpu.matmul (Mosaic has no
    # batched contraction; see the forward kernel note)
    dgp = (
        (dc * g) * i * (1.0 - i),
        (dc * c_prev) * f * (1.0 - f),
        (dc * i) * (1.0 - g * g),
        do * o * (1.0 - o),
    )

    hp = hprev_ref[0].astype(jnp.float32)
    dh_new = None
    for gi in range(4):
        # param grads: dWh[g] += dgp_g.T @ h_prev -> (H, H)
        dwh_scr[gi] += jnp.dot(dgp[gi].T, hp,
                               preferred_element_type=jnp.float32)
        # dh_prev = sum_g dgp_g @ wh[g]
        contrib = jnp.dot(dgp[gi], wh_ref[gi].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        dh_new = contrib if dh_new is None else dh_new + contrib
        dxp_ref[0, :, gi, :] = dgp[gi].astype(dxp_ref.dtype)
    dh_scr[:] = dh_new
    dc_scr[:] = dc * f

    dwh_ref[:] = dwh_scr[:].astype(dwh_ref.dtype)
    dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)
    dc0_ref[:] = dc_scr[:].astype(dc0_ref.dtype)


def _lstm_backward(wh, h0, c0, ys, gates, cs, dys, dhn, dcn):
    T, N = gates.shape[0], gates.shape[1]
    H = wh.shape[1]
    wh4 = wh.reshape(4, H, H)
    f32 = jnp.float32
    # h_prev / c_prev sequences (cuDNN reserve-space equivalents)
    h_prev = jnp.concatenate([h0[None].astype(f32), ys[:-1].astype(f32)], 0)
    c_prev = jnp.concatenate([c0[None].astype(f32), cs[:-1]], 0)

    rev3 = lambda t: (T - 1 - t, 0, 0)     # noqa: E731
    rev4 = lambda t: (T - 1 - t, 0, 0, 0)  # noqa: E731
    outs = pallas_call(
        _lstm_bwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, H), rev3, memory_space=pltpu.VMEM),  # dy
            pl.BlockSpec((1, N, 4, H), rev4,
                         memory_space=pltpu.VMEM),                   # gates
            pl.BlockSpec((1, N, H), rev3, memory_space=pltpu.VMEM),  # c_t
            pl.BlockSpec((1, N, H), rev3,
                         memory_space=pltpu.VMEM),                   # c_prev
            pl.BlockSpec((1, N, H), rev3,
                         memory_space=pltpu.VMEM),                   # h_prev
            pl.BlockSpec((4, H, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),                   # wh
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),                   # dh_n
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),                   # dc_n
        ],
        out_shape=(
            jax.ShapeDtypeStruct((T, N, 4, H), jnp.float32),  # dx_proj
            jax.ShapeDtypeStruct((4, H, H), jnp.float32),     # dwh
            jax.ShapeDtypeStruct((N, H), jnp.float32),        # dh0
            jax.ShapeDtypeStruct((N, H), jnp.float32),        # dc0
        ),
        out_specs=(
            pl.BlockSpec((1, N, 4, H), rev4, memory_space=pltpu.VMEM),
            pl.BlockSpec((4, H, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((4, H, H), jnp.float32),
        ],
    )(dys, gates, cs, c_prev, h_prev, wh4, dhn, dcn)
    return outs


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@jax.custom_vjp
def lstm_layer(x_proj, wh, h0, c0):
    """One LSTM layer/direction over time.

    x_proj: (T, N, 4H) input projection ``x @ Wi.T + bi + bh`` (both
    biases folded — they are additive constants in the pre-activation).
    wh: (4H, H); h0, c0: (N, H). Gate order i, f, g, o (the reference's
    canonical LSTM layout). Returns (ys (T,N,H), h_n, c_n).
    """
    ys, hn, cn, _, _ = _lstm_forward(x_proj, wh, h0, c0)
    return ys, hn, cn


def _lstm_fwd_rule(x_proj, wh, h0, c0):
    ys, hn, cn, gates, cs = _lstm_forward(x_proj, wh, h0, c0)
    return (ys, hn, cn), (wh, h0, c0, ys, gates, cs)


def _lstm_bwd_rule(res, cotangents):
    wh, h0, c0, ys, gates, cs = res
    dys, dhn, dcn = cotangents
    dys = jnp.zeros_like(ys) if _is_zero(dys) else dys
    dhn = jnp.zeros_like(h0) if _is_zero(dhn) else dhn
    dcn = jnp.zeros_like(c0) if _is_zero(dcn) else dcn
    dxp, dwh, dh0, dc0 = _lstm_backward(
        wh, h0, c0, ys, gates, cs,
        dys.astype(jnp.float32), dhn, dcn)
    T, N = dxp.shape[0], dxp.shape[1]
    H = wh.shape[1]
    # back to the packed (T,N,4H) / (4H,H) caller layout
    return (dxp.reshape(T, N, 4 * H).astype(ys.dtype),
            dwh.reshape(4 * H, H).astype(wh.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype))


def _is_zero(x):
    return x is None or isinstance(
        x, jax.custom_derivatives.SymbolicZero)


lstm_layer.defvjp(_lstm_fwd_rule, _lstm_bwd_rule)


# ---------------------------------------------------------------------------
# GRU recurrence (same cuDNN-style split as the LSTM above: XLA does the
# time-batched input GEMM, the kernel does the sequential part).
# Cell (ops/rnn.py _step_fn('gru'), the cuDNN linear-before-reset form):
#   r = sigmoid(xp_r + h Wh_r^T + bh_r)
#   z = sigmoid(xp_z + h Wh_z^T + bh_z)
#   n = tanh(xp_n + r * (h Wh_n^T + bh_n))
#   h' = (1-z) n + z h
# Saves (r, z, n) and the n-gate recurrent linear term hn_lin for the
# backward (the reserve-space trick); bh rides INSIDE the kernel — its
# n-slot cannot be folded into x_proj because r multiplies it.
# ---------------------------------------------------------------------------


def _gru_fwd_kernel(xp_ref, wht_ref, bh_ref, h0_ref,
                    ys_ref, hn_ref, gates_ref, hnlin_ref, h_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)

    h = h_scr[:]
    xp = xp_ref[0].astype(jnp.float32)        # (N, 3, H)
    gh = [jnp.dot(h, wht_ref[g].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
          + bh_ref[g, 0, :].astype(jnp.float32)[None, :]
          for g in range(3)]
    r = jax.nn.sigmoid(xp[:, 0, :] + gh[0])
    z = jax.nn.sigmoid(xp[:, 1, :] + gh[1])
    n = jnp.tanh(xp[:, 2, :] + r * gh[2])
    h_new = (1.0 - z) * n + z * h

    h_scr[:] = h_new
    ys_ref[0] = h_new.astype(ys_ref.dtype)
    for gi, v in enumerate((r, z, n)):
        gates_ref[0, :, gi, :] = v
    hnlin_ref[0] = gh[2]
    hn_ref[:] = h_new.astype(hn_ref.dtype)


def _gru_forward(x_proj, wh, bh, h0):
    T, N, G3 = x_proj.shape
    H = wh.shape[1]
    xp3 = x_proj.reshape(T, N, 3, H)
    wh3 = wh.reshape(3, H, H).transpose(0, 2, 1)
    bh3 = bh.reshape(3, 1, H)
    return pallas_call(
        _gru_fwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, 3, H), lambda t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, H, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 1, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((T, N, H), x_proj.dtype),    # ys
            jax.ShapeDtypeStruct((N, H), x_proj.dtype),       # h_n
            jax.ShapeDtypeStruct((T, N, 3, H), jnp.float32),  # r,z,n
            jax.ShapeDtypeStruct((T, N, H), jnp.float32),     # hn_lin
        ),
        out_specs=(
            pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, 3, H), lambda t: (t, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, H), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((N, H), jnp.float32)],
    )(xp3, wh3, bh3, h0)


def _gru_bwd_kernel(dy_ref, gates_ref, hnlin_ref, hprev_ref, wh_ref,
                    dhn_ref,
                    dxp_ref, dwh_ref, dbh_ref, dh0_ref,
                    dh_scr, dwh_scr, dbh_scr):
    idx = pl.program_id(0)

    @pl.when(idx == 0)
    def _():
        dh_scr[:] = dhn_ref[:].astype(jnp.float32)
        dwh_scr[:] = jnp.zeros_like(dwh_scr)
        dbh_scr[:] = jnp.zeros_like(dbh_scr)

    dh = dh_scr[:] + dy_ref[0].astype(jnp.float32)
    r = gates_ref[0, :, 0, :]
    z = gates_ref[0, :, 1, :]
    n = gates_ref[0, :, 2, :]
    hn_lin = hnlin_ref[0]
    hp = hprev_ref[0].astype(jnp.float32)

    dn = dh * (1.0 - z)
    dz = dh * (hp - n)
    dgn = dn * (1.0 - n * n)          # n-gate pre-activation grad
    dr = dgn * hn_lin
    dhnlin = dgn * r                  # grad into (h Wh_n^T + bh_n)
    dgr = dr * r * (1.0 - r)
    dgz = dz * z * (1.0 - z)

    dh_new = dh * z
    # per-gate recurrent VJPs: dh_prev += dgate @ Wh_g ; dWh_g += dgate.T @ h_prev
    for gi, dg in ((0, dgr), (1, dgz), (2, dhnlin)):
        dwh_scr[gi] += jnp.dot(dg.T, hp,
                               preferred_element_type=jnp.float32)
        dbh_scr[gi, 0, :] += jnp.sum(dg, axis=0)
        dh_new = dh_new + jnp.dot(dg, wh_ref[gi].astype(jnp.float32),
                                  preferred_element_type=jnp.float32)
        # x-projection grads: r and z slots take their pre-act grads;
        # the n slot takes dgn (xp_n enters the cell un-multiplied)
        dxp_ref[0, :, gi, :] = (dg if gi != 2 else dgn) \
            .astype(dxp_ref.dtype)
    dh_scr[:] = dh_new

    dwh_ref[:] = dwh_scr[:].astype(dwh_ref.dtype)
    dbh_ref[:] = dbh_scr[:].astype(dbh_ref.dtype)
    dh0_ref[:] = dh_scr[:].astype(dh0_ref.dtype)


def _gru_backward(wh, h0, ys, gates, hn_lin, dys, dhn):
    T, N = gates.shape[0], gates.shape[1]
    H = wh.shape[1]
    wh3 = wh.reshape(3, H, H)
    f32 = jnp.float32
    h_prev = jnp.concatenate([h0[None].astype(f32), ys[:-1].astype(f32)],
                             0)
    rev3 = lambda t: (T - 1 - t, 0, 0)     # noqa: E731
    rev4 = lambda t: (T - 1 - t, 0, 0, 0)  # noqa: E731
    return pallas_call(
        _gru_bwd_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, N, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, 3, H), rev4, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, N, H), rev3, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, H, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((T, N, 3, H), jnp.float32),  # dx_proj
            jax.ShapeDtypeStruct((3, H, H), jnp.float32),     # dwh
            jax.ShapeDtypeStruct((3, 1, H), jnp.float32),     # dbh
            jax.ShapeDtypeStruct((N, H), jnp.float32),        # dh0
        ),
        out_specs=(
            pl.BlockSpec((1, N, 3, H), rev4, memory_space=pltpu.VMEM),
            pl.BlockSpec((3, H, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 1, H), lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((N, H), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((N, H), jnp.float32),
            pltpu.VMEM((3, H, H), jnp.float32),
            pltpu.VMEM((3, 1, H), jnp.float32),
        ],
    )(dys, gates, hn_lin, h_prev, wh3, dhn)


@jax.custom_vjp
def gru_layer(x_proj, wh, bh, h0):
    """One GRU layer/direction over time.

    x_proj: (T, N, 3H) input projection ``x @ Wi.T + bi``; wh: (3H, H);
    bh: (3H,) recurrent bias (NOT foldable into x_proj — the reset
    gate multiplies its n-slot); h0: (N, H). Gate order r, z, n.
    Returns (ys (T,N,H), h_n)."""
    ys, hn, _, _ = _gru_forward(x_proj, wh, bh, h0)
    return ys, hn


def _gru_fwd_rule(x_proj, wh, bh, h0):
    ys, hn, gates, hn_lin = _gru_forward(x_proj, wh, bh, h0)
    return (ys, hn), (wh, h0, ys, gates, hn_lin)


def _gru_bwd_rule(res, cotangents):
    wh, h0, ys, gates, hn_lin = res
    dys, dhn = cotangents
    dys = jnp.zeros_like(ys) if _is_zero(dys) else dys
    dhn = jnp.zeros_like(h0) if _is_zero(dhn) else dhn
    dxp, dwh, dbh, dh0 = _gru_backward(
        wh, h0, ys, gates, hn_lin, dys.astype(jnp.float32), dhn)
    T, N = dxp.shape[0], dxp.shape[1]
    H = wh.shape[1]
    return (dxp.reshape(T, N, 3 * H).astype(ys.dtype),
            dwh.reshape(3 * H, H).astype(wh.dtype),
            dbh.reshape(3 * H).astype(wh.dtype),
            dh0.astype(h0.dtype))


gru_layer.defvjp(_gru_fwd_rule, _gru_bwd_rule)
