"""Sparse-expert feed-forward layer as ONE chip of an expert-parallel
deployment sees it.

The op is told which experts it holds (`first_expert`, and as many as
its stacked weights have), routes every token over ALL the router's
experts, and computes the part of the layer's result that its own
experts give: `sum over e in top_k(t), e held here, of w[t, e] *
E_e(x_t)`.  What the absent experts would add is left out (their chips
add it, after the exchange this op does not stand in for).  Every
assignment to a held expert is computed, whatever the imbalance: the
assignments are sorted by expert, the held experts' rows to the front
as ragged groups of one grouped product (`jax.lax.ragged_dot`, a Mosaic
grouped matmul on the TPU that works on the tiles the groups cover),
the assignments to absent experts a trailing group that is never
multiplied; the shapes are static at the worst case, tokens x top_k
rows, and the grouped product works on the tiles the groups cover.

`parallel/moe.py` is the older GShard layer (capacity that drops,
one-hot dispatch tensors); it shares nothing with this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, top_k):
    """Row r of the result is token order[r] // top_k's row of `x`: the
    assignments in sorted order.  Its transpose is `_combine`: both
    directions are gathers, never a scatter-add (a scatter-add of a
    quarter of the rows took longer on the chip than a gather of all
    of them: PERF.md, PR 29)."""
    del inverse
    return x[order // top_k]


def _dispatch_fwd(x, order, inverse, top_k):
    return x[order // top_k], (order, inverse)


def _dispatch_bwd(top_k, res, g):
    order, inverse = res
    return _combine(g, order, inverse, top_k), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, order, inverse, top_k):
    """Token t's row is the sum of its top_k assignments' rows, each
    found at its place in the sorted order."""
    del order
    back = rows[inverse]
    return back.reshape(-1, top_k, rows.shape[-1]).astype(
        jnp.float32).sum(axis=1).astype(rows.dtype)


def _combine_fwd(rows, order, inverse, top_k):
    return _combine(rows, order, inverse, top_k), (order, inverse)


def _combine_bwd(top_k, res, g):
    order, inverse = res
    return _dispatch(g, order, inverse, top_k), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _k_moe_ffn(data, router_weight, expert_in_weight, expert_out_weight, *,
               first_expert=0, top_k=8, scale=1.0):
    """data (..., h); router_weight (h, experts of the whole layer);
    expert_in_weight (held, h, 2 * width) packing [gate | up];
    expert_out_weight (held, width, h).  Returns (the held experts'
    part of the layer's output, in data's shape; float32 (held + 1,)
    rows each held expert got, then the assignments that went to
    absent experts).

    Router: logits, softmax and the top_k weights in float32, the
    weights renormalised over the top_k and times `scale`, applied to
    the experts' OUTPUT.  Experts: SwiGLU."""
    shape = data.shape
    x = data.reshape(-1, shape[-1])
    tokens = x.shape[0]
    held = expert_in_weight.shape[0]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = jnp.dot(x.astype(jnp.float32),
                             router_weight.astype(jnp.float32),
                             precision="highest")
            weights, experts = jax.lax.top_k(
                jax.nn.softmax(logits, axis=-1), top_k)
            weights = weights / jnp.sum(weights, -1, keepdims=True) * scale
        with jax.named_scope("dispatch"):
            local = experts - first_expert
            here = (local >= 0) & (local < held)
            group = jnp.where(here, local, held).reshape(-1)
            order = jnp.argsort(group, stable=True)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype),
                unique_indices=True)
            sizes = jnp.sum(group[:, None] == jnp.arange(held + 1)[None, :],
                            axis=0, dtype=jnp.int32)
            # rows past the held experts' groups belong to absent
            # experts: zero in, and their (never computed) result and
            # cotangent are dropped by the same select
            live = (jnp.arange(tokens * top_k) < jnp.sum(sizes[:held]))[
                :, None]
            rows = jnp.where(live, _dispatch(x, order, inverse, top_k), 0)
        with jax.named_scope("experts"):
            hidden = jax.lax.ragged_dot(rows, expert_in_weight, sizes[:held])
            gate, up = jnp.split(hidden.astype(jnp.float32), 2, axis=-1)
            act = (jax.nn.silu(gate) * up).astype(x.dtype)
            out = jax.lax.ragged_dot(act, expert_out_weight, sizes[:held])
        with jax.named_scope("combine"):
            by_row = jnp.where(here, weights, 0.0).reshape(-1)[order]
            out = jnp.where(live, out.astype(jnp.float32) * by_row[:, None],
                            0).astype(x.dtype)
            y = _combine(out, order, inverse, top_k)
    return (y.reshape(shape),
            jax.lax.stop_gradient(sizes.astype(jnp.float32)))


register("moe_ffn", _k_moe_ffn,
         arg_names=("data", "router_weight", "expert_in_weight",
                    "expert_out_weight"), num_outputs=2)


def _k_moe_routing_log(rows, log, *, layer=0):
    """Writes one expert layer's routing counts (`moe_ffn`'s second
    output) into row `layer` of the model's routing log, a
    non-trainable parameter: it leaves a compiled training step the way
    BatchNorm's running statistics do."""
    new = jax.lax.stop_gradient(log.at[layer].set(rows.astype(log.dtype)))
    return rows, new


register("moe_routing_log", _k_moe_routing_log, arg_names=("rows", "log"),
         num_outputs=2, mutate_aux=((1, 1),), nondiff=True)
