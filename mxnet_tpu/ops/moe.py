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
multiplied.

What is static and what is chosen on the device.  The held experts'
rows are the first `sum(sizes[:held])` of the sorted order, as a rule a
small share of tokens x top_k (a sixteenth where 16 of 256 experts are
held).  The path from the row gather to the combine is compiled at a
short list of static row counts fixed here (`capacities`: an eighth, a
quarter and the whole of tokens x top_k), and a `jax.lax.switch` on
that live-row count runs the smallest that holds the rows: no host
read, no recompile, no option.  The last capacity is tokens x top_k
itself, so nothing is ever dropped and no capacity factor exists;
a share that holds the whole router is compiled at it alone.  The
router, the top-k, the sort and the routing counts lie outside the
switch.

`parallel/moe.py` is the older GShard layer (capacity that drops,
one-hot dispatch tensors); it shares nothing with this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register


# The expert path's static row counts, as shares of tokens x top_k: the
# smallest that holds the held experts' rows is chosen on the device.
# The last is the whole, so no routing is ever too uneven to compute.
_CAPACITY_SHARES = (8, 4, 1)


def capacities(assignments):
    """The row counts the expert path is compiled at for a layer of
    `assignments` = tokens x top_k, ascending; the last is
    `assignments` itself."""
    return tuple(sorted({-(-assignments // share)
                         for share in _CAPACITY_SHARES}))


def _branch(rows_here, caps):
    """Which of `caps` holds `rows_here` rows: a python int from a
    python number, a traced one from a traced one."""
    return sum(rows_here > c for c in caps[:-1])


def capacity(rows_here, assignments):
    """The rows the expert path works on in a step whose held experts
    got `rows_here` of the layer's `assignments`."""
    caps = capacities(assignments)
    return caps[_branch(rows_here, caps)]


def _plan(capacity, order, inverse, here, rows_here, top_k):
    """The index arrays both directions of a branch share.  `token`:
    the token of each of the first `capacity` sorted rows; `live`: the
    rows among them that belong to a held expert (the first
    `rows_here`).  Under the top capacity the way back is `inverse`, a
    place for every assignment.  Under a smaller one it is the live
    rows ordered by token (dead rows last): `perm` orders them,
    `by_token` names each ordered row's token, `head` is where token
    t's run starts and `has` whether it has one."""
    tokens = order.shape[0] // top_k
    token = order[:capacity] // top_k
    live = jnp.arange(capacity) < rows_here
    if capacity == order.shape[0]:
        return token, live[:, None], (inverse,)
    by_token, perm = jax.lax.sort_key_val(
        jnp.where(live, token, tokens),
        jnp.arange(capacity, dtype=order.dtype))
    count = jnp.sum(here, axis=-1)
    head = jnp.minimum(jnp.cumsum(count) - count, capacity - 1)
    return token, live[:, None], (perm, by_token, head, (count > 0)[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, plan, top_k):
    """Row r of the result is its token's row of `x`, zero where r is
    not live: the assignments in sorted order, as far as the capacity
    goes.  Its transpose is `_combine`: both directions are gathers,
    never a scatter-add (a scatter-add of a quarter of the rows took
    longer on the chip than a gather of all of them: PERF.md, PR 29)."""
    token, live, _ = plan
    return jnp.where(live, x[token], 0)


def _dispatch_fwd(x, plan, top_k):
    return _dispatch(x, plan, top_k), plan


def _dispatch_bwd(top_k, plan, g):
    return _combine(g, plan, top_k), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine(rows, plan, top_k):
    """Token t's row is the sum, in float32, of its live assignments'
    rows.  Under the top capacity each of its top_k assignments is
    found at its place in the sorted order (a gather of tokens x top_k
    rows).  Under a smaller one the rows are gathered into token order,
    every run of one token's rows is summed onto its first row
    (`_sum_runs`), and each token gathers the head of its run: gathers
    of `capacity` and of `tokens` rows."""
    _, live, back = plan
    if len(back) == 1:
        back = jnp.where(live, rows, 0)[back[0]]
        return back.reshape(-1, top_k, rows.shape[-1]).astype(
            jnp.float32).sum(axis=1).astype(rows.dtype)
    perm, by_token, head, has = back
    # a dead row's (never computed) value may be anything: zero it, or
    # the band's 0 times it would be NaN
    ordered = jnp.where((by_token < has.shape[0])[:, None], rows[perm], 0)
    return jnp.where(has, _sum_runs(ordered, by_token, top_k)[head], 0)


def _sum_runs(rows, by_token, top_k):
    """Row i of the result is the sum, accumulated in float32, of rows
    i, i + 1, ... as far as they are of row i's token (`by_token`
    ascends, a token's rows are a run of at most top_k): the whole
    run's sum where i heads it.  One banded product a block of rows,
    each block with the top_k - 1 rows after it: its 0/1 band says
    which later rows share a row's token.  (Shifted adds over the
    rows cost the chip a misaligned copy a step.)"""
    count, width = rows.shape
    block, halo = 128, top_k - 1
    blocks = -(-count // block)
    pad = (blocks + 1) * block - count
    token = jnp.pad(by_token, (0, pad), constant_values=-1) \
        .reshape(blocks + 1, block)
    value = jnp.pad(rows, ((0, pad), (0, 0))).reshape(
        blocks + 1, block, width)
    later = jnp.concatenate([token[:-1], token[1:, :halo]], axis=1)
    value = jnp.concatenate([value[:-1], value[1:, :halo]], axis=1)
    band = (later[:, None, :] == token[:-1, :, None]) & (
        jnp.arange(block + halo) >= jnp.arange(block)[:, None])
    sums = jnp.einsum("bij,bjw->biw", band.astype(rows.dtype), value,
                      precision="highest",
                      preferred_element_type=jnp.float32)
    return sums.astype(rows.dtype).reshape(blocks * block, width)[:count]


def _combine_fwd(rows, plan, top_k):
    return _combine(rows, plan, top_k), plan


def _combine_bwd(top_k, plan, g):
    return _dispatch(g, plan, top_k), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _experts_at(capacity, routing, x, expert_in_weight, expert_out_weight,
                gates, *, top_k, activation="swiglu"):
    """The held experts' part of the layer on the first `capacity`
    sorted rows: they hold every live row (`sum(sizes) <= capacity`)."""
    order, inverse, here, sizes = routing
    with jax.named_scope("dispatch"):
        plan = _plan(capacity, order, inverse, here, jnp.sum(sizes), top_k)
        # rows past the held experts' groups belong to absent experts
        # (or, past the live rows, to nobody): zero in, and their
        # (never computed) result and cotangent are never read back
        rows = _dispatch(x, plan, top_k)
    with jax.named_scope("experts"):
        hidden = jax.lax.ragged_dot(rows, expert_in_weight, sizes)
        if activation == "relu2":
            act = jnp.square(jax.nn.relu(hidden.astype(jnp.float32))) \
                .astype(x.dtype)
        else:
            gate, up = jnp.split(hidden.astype(jnp.float32), 2, axis=-1)
            act = (jax.nn.silu(gate) * up).astype(x.dtype)
        out = jax.lax.ragged_dot(act, expert_out_weight, sizes)
    with jax.named_scope("combine"):
        by_row = gates[order[:capacity]]
        out = (out.astype(jnp.float32) * by_row[:, None]).astype(x.dtype)
        return _combine(out, plan, top_k)


def _switch(caps, routing, branch, *operands):
    """`branch(capacity, *operands)` at the smallest of `caps` that
    holds the held experts' rows, chosen on the device."""
    *_, sizes = routing
    return jax.lax.switch(
        _branch(jnp.sum(sizes), caps),
        [functools.partial(branch, c) for c in caps], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _experts(caps, how, routing, *operands):
    """`_experts_at` the capacity the routing asks for; `how` is its
    keywords, (top_k, activation).  Its backward pass keeps the
    operands alone and differentiates the chosen branch inside a switch
    of its own: a differentiated `switch` would keep every branch's
    residuals side by side (zeros where not taken), the top capacity's
    among them."""
    return _switch(caps, routing, _at(how), routing, *operands)


def _at(how):
    top_k, activation = how
    return functools.partial(_experts_at, top_k=top_k, activation=activation)


def _experts_fwd(caps, how, routing, *operands):
    return _experts(caps, how, routing, *operands), (routing, operands)


def _experts_bwd(caps, how, res, g):
    routing, operands = res

    def back(capacity, operands, g):
        return jax.vjp(functools.partial(_at(how), capacity, routing),
                       *operands)[1](g)

    return None, *_switch(caps, routing, back, operands, g)


_experts.defvjp(_experts_fwd, _experts_bwd)


def _k_moe_ffn(data, router_weight, expert_in_weight, expert_out_weight,
               correction_bias=None, *, first_expert=0, top_k=8, scale=1.0,
               scoring="softmax", activation="swiglu"):
    """data (..., h); router_weight (h, experts of the whole layer);
    expert_in_weight (held, h, 2 * width) packing [gate | up], or
    (held, h, width) under `activation="relu2"`; expert_out_weight
    (held, width, h); correction_bias (experts of the whole layer,),
    read under `scoring="sigmoid"` alone.  Returns (the held experts'
    part of the layer's output, in data's shape; float32 (held + 1,)
    rows each held expert got, then the assignments that went to
    absent experts).

    Router: logits and the top_k weights in float32.  `softmax`: the
    top_k of the softmax, renormalised over the top_k.  `sigmoid`: the
    top_k of sigmoid(logits) + correction_bias (the bias chooses and
    takes no gradient), their sigmoid scores renormalised over the
    top_k (1e-20 added to the sum, as DeepSeek-V3's router).  Either
    way times `scale`, applied to the experts' OUTPUT.  Experts:
    SwiGLU, or relu(x W_in)^2 W_out (`relu2`, no gate)."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"moe_ffn: scoring {scoring!r}")
    if activation not in ("swiglu", "relu2"):
        raise ValueError(f"moe_ffn: activation {activation!r}")
    shape = data.shape
    x = data.reshape(-1, shape[-1])
    held = expert_in_weight.shape[0]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = jnp.dot(x.astype(jnp.float32),
                             router_weight.astype(jnp.float32),
                             precision="highest")
            if scoring == "softmax":
                weights, experts = jax.lax.top_k(
                    jax.nn.softmax(logits, axis=-1), top_k)
                weights = weights / jnp.sum(weights, -1, keepdims=True) \
                    * scale
            else:
                scores = jax.nn.sigmoid(logits)
                choice = scores if correction_bias is None else \
                    scores + jax.lax.stop_gradient(
                        correction_bias.astype(jnp.float32))
                experts = jax.lax.top_k(choice, top_k)[1]
                weights = jnp.take_along_axis(scores, experts, -1)
                weights = weights / (jnp.sum(weights, -1, keepdims=True)
                                     + 1e-20) * scale
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
        # a share that holds the whole router has every row live
        caps = capacities(order.shape[0])[
            -1 if held >= router_weight.shape[-1] else 0:]
        y = _experts(caps, (top_k, activation),
                     (order, inverse, here, sizes[:held]), x,
                     expert_in_weight, expert_out_weight,
                     jnp.where(here, weights, 0.0).reshape(-1))
    return (y.reshape(shape),
            jax.lax.stop_gradient(sizes.astype(jnp.float32)))


register("moe_ffn", _k_moe_ffn,
         arg_names=("data", "router_weight", "expert_in_weight",
                    "expert_out_weight", "correction_bias"), num_outputs=2)


def _k_moe_routing_log(rows, log, *, layer=0):
    """Writes one expert layer's routing counts (`moe_ffn`'s second
    output) into row `layer` of the model's routing log, a
    non-trainable parameter: it leaves a compiled training step the way
    BatchNorm's running statistics do."""
    new = jax.lax.stop_gradient(log.at[layer].set(rows.astype(log.dtype)))
    return rows, new


register("moe_routing_log", _k_moe_routing_log, arg_names=("rows", "log"),
         num_outputs=2, mutate_aux=((1, 1),), nondiff=True)
