"""Pallas conv(1x1)+BN+ReLU epilogue-fusion kernels for TPU.

Ref: src/operator/nn/batch_norm.cu + the cuDNN fused-op era
(CUDNN_FUSED_SCALE_BIAS_ACTIVATION_CONV_BNSTATS): the reference's
headline ResNet configs lean on conv kernels whose epilogue computes
BN statistics and whose prologue applies scale/bias+ReLU.  XLA:TPU
does NOT fuse elementwise BN passes into its convolutions (their
share of a ResNet-50 step on today's code: not measured, ROADMAP S3).
These kernels rebuild the cuDNN fusion tpu-style for the
1x1 convolutions (2/3 of a bottleneck's convs, carrying the widest
activations), which on NHWC are plain matmuls:

- ``matmul_bn_stats(x2d, w2d)``: blocked MXU matmul whose epilogue
  accumulates per-output-channel sum/sum-of-squares in VMEM while the
  output tile is still on-chip — the separate stats read pass over the
  conv output disappears (1 full activation read saved per layer).
- ``bn_act_matmul(x2d, scale, shift, w2d)``: applies the PREVIOUS
  BN's normalize (+ReLU) to each input tile on the VPU while the MXU
  contracts it — the separate normalize+ReLU read+write pass over the
  conv input disappears (1 read + 1 write saved per layer).

Together a conv1x1→BN→ReLU→conv1x1 chain goes from 4 activation-sized
HBM transfers per layer to 2 (write raw conv out, read it back into
the next matmul).  Both kernels carry custom VJPs (the backward runs
as plain XLA matmuls — the forward traffic is what bounds the step).

Used by ops/conv_fused_ops.py (the `_contrib_conv1x1_bn_act` /
`_contrib_bn_fold` registry ops) behind the
``MXTPU_CONV_EPILOGUE=pallas`` resnet BottleneckV1 path; falls back to
jnp reference forms when shapes don't tile, off-TPU, or when Pallas is
disabled (``MXTPU_DISABLE_PALLAS=1``).  Interpret-mode parity tests:
tests/test_conv_fused.py (forced via MXTPU_CONV_FUSED_INTERPRET=1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call


def _pick(total, candidates, limit_bytes, row_bytes):
    for c in candidates:
        if total % c == 0 and c * row_bytes <= limit_bytes:
            return c
    return None


def _tile_plan(M, K, N, itemsize):
    """(bm, bk, bn) dividing (M, K, N) within a VMEM budget, or None."""
    bk = _pick(K, (512, 256, 128, 64), 2 ** 30, 1)
    bn = _pick(N, (256, 128, 64), 2 ** 30, 1)
    if bk is None or bn is None:
        return None
    # x tile (bm, bk) double-buffered + f32 acc (bm, bn): stay ~<4MB
    bm = _pick(M, (1024, 512, 256, 128, 64, 32, 16, 8),
               2 * 1024 * 1024, bk * itemsize + bn * 4)
    if bm is None:
        return None
    return bm, bk, bn


def _use_pallas():
    from ...base import getenv

    if getenv("DISABLE_PALLAS", False, bool):
        return False
    if getenv("CONV_FUSED_INTERPRET", False, bool):
        return True  # tests: pallas_call monkeypatched to interpret
    # off-TPU the kernels would fail at XLA lowering (pallas on CPU is
    # interpret-only), past any trace-time try/except — fall back to
    # the jnp reference forms instead
    # on TPU the kernels are dispatched wherever _tile_plan admits the
    # shape; a Mosaic rejection there is a compile error the user sees
    # (tests/test_aot_tpu.py compiles the family for the chip off-chip)
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# kernel 1: matmul with BN-stats epilogue


def _mm_stats_kernel(x_ref, w_ref, y_ref, s_ref, q_ref, acc_ref, *, nk):
    i, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(x_ref[:], w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[:].astype(y_ref.dtype)
        y_ref[:] = y
        # stats of the STORED (possibly bf16) activation, so the
        # normalize step downstream sees self-consistent moments
        yf = y.astype(jnp.float32)
        s = jnp.sum(yf, axis=0, keepdims=True)
        q = jnp.sum(yf * yf, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _first():
            s_ref[:] = s
            q_ref[:] = q

        @pl.when(i > 0)
        def _rest():
            s_ref[:] += s
            q_ref[:] += q


def _mm_stats_pallas(x, w):
    M, K = x.shape
    N = w.shape[1]
    bm, bk, bn = _tile_plan(M, K, N, x.dtype.itemsize)
    nk = K // bk
    y, s, q = pallas_call(
        functools.partial(_mm_stats_kernel, nk=nk),
        grid=(N // bn, M // bm, nk),  # j, i, k: stats block resident
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda j, i, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=(jax.ShapeDtypeStruct((M, N), x.dtype),
                   jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        out_specs=(pl.BlockSpec((bm, bn), lambda j, i, k: (i, j),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bn), lambda j, i, k: (0, j),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bn), lambda j, i, k: (0, j),
                                memory_space=pltpu.VMEM)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, w)
    return y, s, q


def _mm_stats_ref(x, w):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    yf = y.astype(jnp.float32)
    return (y, jnp.sum(yf, axis=0, keepdims=True),
            jnp.sum(yf * yf, axis=0, keepdims=True))


@jax.custom_vjp
def matmul_bn_stats(x, w):
    """y = x @ w plus per-column (sum, sum_sq) of y, computed in the
    matmul's epilogue so the stats pass never re-reads y from HBM.

    x (M, K), w (K, N) -> (y (M, N) in x.dtype, sum (1, N) f32,
    sumsq (1, N) f32)."""
    if _use_pallas() and _tile_plan(*x.shape, w.shape[1],
                                    x.dtype.itemsize):
        return _mm_stats_pallas(x, w)
    return _mm_stats_ref(x, w)


def _mm_stats_fwd(x, w):
    out = matmul_bn_stats(x, w)
    return out, (x, w, out[0])


def _mm_stats_bwd(res, g):
    x, w, y = res
    gy, gs, gq = g
    # s = sum_m y, q = sum_m y^2  =>  dy = gy + gs + 2*y*gq
    dy = (gy.astype(jnp.float32) + gs
          + 2.0 * y.astype(jnp.float32) * gq).astype(x.dtype)
    dx = jnp.dot(dy, w.T, preferred_element_type=jnp.float32
                 ).astype(x.dtype)
    dw = jnp.dot(x.T, dy, preferred_element_type=jnp.float32
                 ).astype(w.dtype)
    return dx, dw


matmul_bn_stats.defvjp(_mm_stats_fwd, _mm_stats_bwd)


# ---------------------------------------------------------------------------
# kernel 2: normalize(+ReLU) fused into the matmul's input read


def _bn_act_mm_kernel(x_ref, sc_ref, sh_ref, w_ref, y_ref, acc_ref, *,
                      nk, relu):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    a = x_ref[:].astype(jnp.float32) * sc_ref[:] + sh_ref[:]
    if relu:
        a = jnp.maximum(a, 0.0)
    acc_ref[:] += jnp.dot(a.astype(x_ref.dtype), w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y_ref[:] = acc_ref[:].astype(y_ref.dtype)


def _bn_act_mm_pallas(x, scale, shift, w, relu):
    M, K = x.shape
    N = w.shape[1]
    bm, bk, bn = _tile_plan(M, K, N, x.dtype.itemsize)
    nk = K // bk
    return pallas_call(
        functools.partial(_bn_act_mm_kernel, nk=nk, relu=relu),
        grid=(N // bn, M // bm, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda j, i, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, k: (i, j),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, scale, shift, w)


def _bn_act_ref(x, scale, shift, relu):
    a = x.astype(jnp.float32) * scale + shift
    if relu:
        a = jnp.maximum(a, 0.0)
    return a.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def bn_act_matmul(x, scale, shift, w, relu=True):
    """y = act(x * scale + shift) @ w with the normalize+activation
    applied per input tile on the VPU while the MXU contracts — the
    separate elementwise pass over x (1 read + 1 write of the widest
    activation) disappears.

    x (M, K); scale/shift (1, K) f32 (the folded BN affine:
    scale = gamma/sqrt(var+eps), shift = beta - mean*scale);
    w (K, N) -> y (M, N) in x.dtype."""
    if _use_pallas() and _tile_plan(*x.shape, w.shape[1],
                                    x.dtype.itemsize):
        return _bn_act_mm_pallas(x, scale, shift, w, relu)
    return jnp.dot(_bn_act_ref(x, scale, shift, relu), w,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _bn_act_mm_fwd(x, scale, shift, w, relu):
    return bn_act_matmul(x, scale, shift, w, relu), (x, scale, shift, w)


def _bn_act_mm_bwd(relu, res, gy):
    x, scale, shift, w = res
    a = x.astype(jnp.float32) * scale + shift
    h = jnp.maximum(a, 0.0) if relu else a
    gh = jnp.dot(gy.astype(jnp.float32), w.T.astype(jnp.float32))
    if relu:
        gh = gh * (a > 0)
    dx = (gh * scale).astype(x.dtype)
    dscale = jnp.sum(gh * x.astype(jnp.float32), axis=0, keepdims=True)
    dshift = jnp.sum(gh, axis=0, keepdims=True)
    dw = jnp.dot(h.astype(x.dtype).T, gy,
                 preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dscale, dshift, dw


bn_act_matmul.defvjp(_bn_act_mm_fwd, _bn_act_mm_bwd)


# ---------------------------------------------------------------------------
# kernel 3: both fusions at once — normalize(+ReLU) on the input read,
# BN-stats on the output epilogue (the middle of a conv→BN→act→conv
# chain where both neighbours are fused 1x1 convs)


def _bn_act_mm_stats_kernel(x_ref, sc_ref, sh_ref, w_ref, y_ref, s_ref,
                            q_ref, acc_ref, *, nk, relu):
    i, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    a = x_ref[:].astype(jnp.float32) * sc_ref[:] + sh_ref[:]
    if relu:
        a = jnp.maximum(a, 0.0)
    acc_ref[:] += jnp.dot(a.astype(x_ref.dtype), w_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[:].astype(y_ref.dtype)
        y_ref[:] = y
        yf = y.astype(jnp.float32)
        s = jnp.sum(yf, axis=0, keepdims=True)
        q = jnp.sum(yf * yf, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _first():
            s_ref[:] = s
            q_ref[:] = q

        @pl.when(i > 0)
        def _rest():
            s_ref[:] += s
            q_ref[:] += q


def _bn_act_mm_stats_pallas(x, scale, shift, w, relu):
    M, K = x.shape
    N = w.shape[1]
    bm, bk, bn = _tile_plan(M, K, N, x.dtype.itemsize)
    nk = K // bk
    return pallas_call(
        functools.partial(_bn_act_mm_stats_kernel, nk=nk, relu=relu),
        grid=(N // bn, M // bm, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, i, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk), lambda j, i, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda j, i, k: (k, j),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=(jax.ShapeDtypeStruct((M, N), x.dtype),
                   jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        out_specs=(pl.BlockSpec((bm, bn), lambda j, i, k: (i, j),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bn), lambda j, i, k: (0, j),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, bn), lambda j, i, k: (0, j),
                                memory_space=pltpu.VMEM)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, scale, shift, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def bn_act_matmul_stats(x, scale, shift, w, relu=True):
    """y = act(x*scale+shift) @ w plus per-column (sum, sum_sq) of y —
    kernels 1 and 2 composed into a single pass (see module
    docstring)."""
    if _use_pallas() and _tile_plan(*x.shape, w.shape[1],
                                    x.dtype.itemsize):
        return _bn_act_mm_stats_pallas(x, scale, shift, w, relu)
    h = _bn_act_ref(x, scale, shift, relu)
    return _mm_stats_ref(h, w)


def _bn_act_mm_stats_fwd(x, scale, shift, w, relu):
    out = bn_act_matmul_stats(x, scale, shift, w, relu)
    return out, (x, scale, shift, w, out[0])


def _bn_act_mm_stats_bwd(relu, res, g):
    x, scale, shift, w, y = res
    gy, gs, gq = g
    dy = (gy.astype(jnp.float32) + gs
          + 2.0 * y.astype(jnp.float32) * gq).astype(x.dtype)
    a = x.astype(jnp.float32) * scale + shift
    h = jnp.maximum(a, 0.0) if relu else a
    gh = jnp.dot(dy.astype(jnp.float32), w.T.astype(jnp.float32))
    if relu:
        gh = gh * (a > 0)
    dx = (gh * scale).astype(x.dtype)
    dscale = jnp.sum(gh * x.astype(jnp.float32), axis=0, keepdims=True)
    dshift = jnp.sum(gh, axis=0, keepdims=True)
    dw = jnp.dot(h.astype(x.dtype).T, dy,
                 preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dscale, dshift, dw


bn_act_matmul_stats.defvjp(_bn_act_mm_stats_fwd, _bn_act_mm_stats_bwd)
