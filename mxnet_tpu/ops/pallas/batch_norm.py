"""Pallas one-pass BatchNorm statistics kernel for TPU.

Ref: src/operator/nn/batch_norm.cu / cudnn BN — the reference computes
mean and variance in one fused pass over the activation.  XLA emits TWO
separate reduction fusions for ``mean(x)`` and ``mean(x*x)`` (profiled:
those two HBM passes were ~half the ResNet-50 training step), so this
kernel reads the activation ONCE and accumulates both sums in VMEM.

Contract: ``bn_stats(x2d)`` with x2d of shape (M, C) — the free
channel-last [N*H*W, C] view — returns (sum, sumsq) in fp32.
Differentiable via custom_vjp (d sum = broadcast, d sumsq = 2x·ct).
Used by ops/nn._k_batch_norm on the TPU train path; falls back to the
jnp two-pass form when no suitable block divides M (or off-TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call


def _block_rows(M, C):
    """Largest row block that divides M, keeps sublane alignment, and
    stays well under VMEM with double buffering."""
    budget = 2 * 1024 * 1024  # bytes per x block (Mosaic double-buffers)
    for bm in (8192, 4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8):
        if M % bm == 0 and bm * C * 4 <= budget:
            return bm
    return None


def _stats_kernel(x_ref, sum_ref, sq_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        sq_ref[:] = jnp.zeros_like(sq_ref)

    x = x_ref[:].astype(jnp.float32)
    sum_ref[:] += jnp.sum(x, axis=0, keepdims=True)
    sq_ref[:] += jnp.sum(x * x, axis=0, keepdims=True)


def _stats_pallas(x2d):
    M, C = x2d.shape
    bm = _block_rows(M, C)
    s, q = pallas_call(
        _stats_kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_shape=(jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)),
        out_specs=(pl.BlockSpec((1, C), lambda i: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, C), lambda i: (0, 0),
                                memory_space=pltpu.VMEM)),
    )(x2d)
    return s[0], q[0]


@jax.custom_vjp
def bn_stats(x2d):
    """(M, C) -> (sum[C], sumsq[C]) fp32 in one HBM pass."""
    return _stats_pallas(x2d)


def _bn_stats_fwd(x2d):
    return _stats_pallas(x2d), x2d


def _bn_stats_bwd(x2d, cts):
    ds, dq = cts
    dx = ds[None, :].astype(jnp.float32) \
        + 2.0 * x2d.astype(jnp.float32) * dq[None, :]
    return (dx.astype(x2d.dtype),)


bn_stats.defvjp(_bn_stats_fwd, _bn_stats_bwd)


def stats_supported(M, C):
    """Host-side gate: True when the kernel can run for this shape.

    C must be sublane-aligned (Mosaic pads lanes, but ragged C like 6
    fails at lowering — which happens inside the OUTER jit compile,
    past any try/except around the call site, so gate it here)."""
    return C % 8 == 0 and _block_rows(M, C) is not None
