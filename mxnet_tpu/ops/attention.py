"""Attention ops.

Ref: src/operator/contrib/transformer.{cc,cu} (_contrib interleaved
matmul selfatt ops) — the Sockeye-era building blocks — upgraded to a
fused scaled-dot-product attention op (capability upgrade per SURVEY
§2.2 'Fused attention as Pallas flash-attention kernel, still
API-compatible').

Two paths: a Pallas flash-attention kernel on TPU (ops/pallas/
flash_attention.py) and this XLA form, which is also the oracle.
Selection is by shape and by the platform the computation is LOWERED
for (MXTPU_DISABLE_PALLAS=1 forces the XLA form); a kernel that fails
to trace or compile on a shape its gates admit is an error, never a
quiet switch of path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..base import getenv
from .registry import register


def sdpa_reference(q, k, v, mask=None, *, scale=None, causal=False,
                   window=None):
    """Scaled dot-product attention, XLA fallback / numeric oracle.

    q: (batch, heads, seq, head_dim); k, v the same, or with fewer
    heads (a divisor of q's: query head j reads K/V head
    j // (heads // kv_heads)).  mask: additive (b,1,sq,sk) or bool;
    causal adds a lower-triangular mask, and `window` (with causal)
    keeps of it the pairs with i - j < window.
    """
    if window is not None and not causal:
        raise ValueError("attention window needs causal=True")
    d = q.shape[-1]
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    s = scale if scale is not None else 1.0 / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        if window is not None:
            causal_mask &= ~jnp.tril(jnp.ones((sq, sk), bool),
                                     sk - sq - window)
        logits = jnp.where(causal_mask, logits, jnp.asarray(-1e9, q.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.asarray(-1e9, q.dtype))
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _k_sdpa(q, k, v, mask=None, *, scale=None, causal=False,
            dropout_p=0.0, window=None):
    """q: (batch, heads, seq, head_dim).  K/V may carry fewer heads
    than Q (grouped-query attention, read from the shapes); `window`
    with `causal` is sliding-window attention: position i sees j with
    0 <= i - j < window."""
    if getenv("DISABLE_PALLAS", False, bool):
        return sdpa_reference(q, k, v, mask, scale=scale, causal=causal,
                              window=window)
    from .pallas.flash_attention import flash_attention

    # the branch is picked when the computation is lowered, by the
    # platform it is lowered FOR — not by the process's default
    # backend: on a TPU host a block living on mx.cpu() takes the XLA
    # form, and a step compiled ahead of time for a described chip
    # takes the kernel
    def _xla(q, k, v, mask):
        # both branches return the query's dtype (an additive f32 mask
        # would otherwise promote the XLA form of a bf16 model to f32)
        return sdpa_reference(q, k, v, mask, scale=scale, causal=causal,
                              window=window).astype(q.dtype)

    def _tpu(q, k, v, mask):
        from ..parallel.mesh import per_batch_shard

        return per_batch_shard(
            functools.partial(flash_attention, scale=scale,
                              causal=causal, window=window), q, k, v, mask
        ).astype(q.dtype)

    return jax.lax.platform_dependent(q, k, v, mask, tpu=_tpu,
                                      default=_xla)


register("scaled_dot_product_attention", _k_sdpa,
         arg_names=("q", "k", "v", "mask"),
         aliases=("_contrib_sdpa",))


def _k_multihead_attention(query, key, value, in_weight, in_bias,
                           out_weight, out_bias, mask=None, *,
                           num_heads, causal=False):
    """Full fused MHA: qkv projection + sdpa + output projection.

    query/key/value: (batch, seq, model_dim); in_weight: (3*model, model)
    packed q,k,v projections; out_weight: (model, model).
    """
    b, sq, m = query.shape
    h = num_heads
    hd = m // h
    wq, wk, wv = jnp.split(in_weight, 3, axis=0)
    bq, bk, bv = jnp.split(in_bias, 3, axis=0)

    def proj(x, w, bias):
        return (x @ w.T + bias).reshape(x.shape[0], x.shape[1], h, hd) \
            .transpose(0, 2, 1, 3)

    qh = proj(query, wq, bq)
    kh = proj(key, wk, bk)
    vh = proj(value, wv, bv)
    out = _k_sdpa(qh, kh, vh, mask, scale=None, causal=causal)
    out = out.transpose(0, 2, 1, 3).reshape(b, sq, m)
    return out @ out_weight.T + out_bias


register("multihead_attention", _k_multihead_attention,
         arg_names=("query", "key", "value", "in_weight", "in_bias",
                    "out_weight", "out_bias", "mask"))


# Sockeye-era interleaved ops for parity with the reference's contrib
# (ref: src/operator/contrib/transformer.cc)

def _k_interleaved_matmul_selfatt_qk(qkv, *, heads):
    # qkv: (seq, batch, 3*model) interleaved per head
    s, b, m3 = qkv.shape
    m = m3 // 3
    hd = m // heads
    x = qkv.reshape(s, b, heads, 3, hd)
    q = x[:, :, :, 0]
    k = x[:, :, :, 1]
    q = q.transpose(1, 2, 0, 3) / jnp.sqrt(jnp.asarray(hd, qkv.dtype))
    k = k.transpose(1, 2, 0, 3)
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    return att.reshape(b * heads, s, s)


register("_contrib_interleaved_matmul_selfatt_qk",
         _k_interleaved_matmul_selfatt_qk, arg_names=("queries_keys_values",))


def _k_interleaved_matmul_selfatt_valatt(qkv, att, *, heads):
    s, b, m3 = qkv.shape
    m = m3 // 3
    hd = m // heads
    v = qkv.reshape(s, b, heads, 3, hd)[:, :, :, 2]
    v = v.transpose(1, 2, 0, 3)
    att = att.reshape(b, heads, s, s)
    out = jnp.einsum("bhqk,bhkd->bhqd", att, v)
    return out.transpose(2, 0, 1, 3).reshape(s, b, m)


register("_contrib_interleaved_matmul_selfatt_valatt",
         _k_interleaved_matmul_selfatt_valatt,
         arg_names=("queries_keys_values", "attention"))
