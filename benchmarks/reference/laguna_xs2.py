"""Plain reference for the `laguna_xs2` configuration: the decoder's
forward pass, next-token loss, gradients and AdamW in straightforward
float32 `jax.numpy` with `precision="highest"`.  Imports nothing of
mxnet_tpu (only the benchmark's own rounding helper for the control).

Follows poolside's Laguna-XS.2 `config.json` and the layer equations of
ISSUE 29 / docs/decoder_lm.md, with the departures the configuration's
`assumed` lists (per-head sigmoid output gate on the normed layer
input, softmax scoring with renormalised top-k weights, no gate on the
shared expert, silu, no auxiliary loss, decay on every leaf).  The same
share of the deployment as the program: the held experts of a
`router_width`-wide router, the held rows of the vocabulary; what the
absent experts would add is left out.

Departures of FORM, made so that the whole fits one chip in float32:
the experts are a dense loop over the held ones with masks (no sort, no
ragged product); attention is computed in blocks of query rows, one
sequence at a time, and the dense feed-forwards and the head in blocks
of tokens, every block recomputed in the backward pass, as is every
layer; AdamW's two moments wait on the host while a gradient is
computed.  None changes a value.

Parameters are a flat list in the order the program's
`block._ordered_params()` gives them: the routing log (not trained),
the embedding, each layer's leaves, the final norm and the head.
"""
from __future__ import annotations

import functools
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import lowprec

ATTENTION_LEAVES = 7      # norm, q, k, v, gate, out, ffn norm
QUERY_ROWS = 256          # rows of a sequence an attention block holds
TOKEN_ROWS = 4096         # tokens a feed-forward or head block holds


def _layers(config):
    """[(heads, kind, sparse)] of the layers held."""
    return [(config["num_attention_heads_per_layer"][i],
             config["layer_types"][i],
             config["mlp_layer_types"][i] == "sparse")
            for i in range(config["num_hidden_layers"])]


def _has_log(config):
    return any(sparse for _, _, sparse in _layers(config))


def param_specs(config):
    """[(shape, kind, scale)] in program order."""
    h, d, kv = (config["hidden_size"], config["head_dim"],
                config["num_key_value_heads"])
    vocab, std = config["vocab_size"], config["assumed"]["init_stdev"]
    held, width = config["num_experts"], config["moe_intermediate_size"]
    shared = config["shared_expert_intermediate_size"]
    w = lambda *shape: (shape, "trunc_normal", std)  # noqa: E731
    ones = ((h,), "ones", 0.0)
    layers = _layers(config)
    specs = []
    if _has_log(config):
        specs.append(((sum(s for _, _, s in layers), held + 1), "zeros", 0.0))
    specs.append(w(vocab, h))
    for n, _, sparse in layers:
        specs += [ones, w(n * d, h), w(kv * d, h), w(kv * d, h), w(n, h),
                  w(h, n * d), ones]
        if sparse:
            specs += [w(h, config["router_width"]), w(held, h, 2 * width),
                      w(held, width, h), w(2 * shared, h), w(h, shared)]
        else:
            ffn = config["intermediate_size"]
            specs += [w(2 * ffn, h), w(h, ffn)]
    return specs + [ones, w(vocab, h)]


def leaf_parts(config):
    """How many of the model's tensors each leaf packs along its first
    axis: [gate | up] of a SwiGLU input are two, the stacked experts one
    each (so the comparison reads every expert on its own)."""
    held = config["num_experts"]
    parts = [1] * (1 + _has_log(config))
    for _, _, sparse in _layers(config):
        parts += [1] * ATTENTION_LEAVES
        parts += [1, held, held, 2, 1] if sparse else [2, 1]
    return parts + [1, 1]


def leaf_names(config):
    """A name for each part `leaf_parts` counts, in its order: what a
    reading's `..._leaf` index points at."""
    held = config["num_experts"]
    names = ["routing_log"] * _has_log(config) + ["embed"]
    for i, (_, _, sparse) in enumerate(_layers(config)):
        names += [f"layer{i}.{n}" for n in (
            "attn_norm", "q", "k", "v", "gate", "out", "ffn_norm")]
        if sparse:
            names.append(f"layer{i}.router")
            names += [f"layer{i}.expert_{io}[{e}]" for io in ("in", "out")
                      for e in range(held)]
            names += [f"layer{i}.shared_in.gate", f"layer{i}.shared_in.up",
                      f"layer{i}.shared_out"]
        else:
            names += [f"layer{i}.ffn_in.gate", f"layer{i}.ffn_in.up",
                      f"layer{i}.ffn_out"]
    return names + ["norm", "head"]


def trainable(config):
    return [not (_has_log(config) and i == 0)
            for i in range(len(param_specs(config)))]


def _part_norms(leaves, parts):
    return jnp.stack([
        jnp.linalg.norm(chunk.ravel()) for x, k in zip(leaves, parts)
        for chunk in (jnp.split(x, k, axis=0) if k > 1 else [x])])


def _matmul(precision):
    q_in, q_out = lowprec.rounding(precision)
    return lambda a, b: q_out(jnp.matmul(q_in(a), q_in(b),
                                         precision="highest"))


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def rotary_tables(config, kind, seq):
    """(cos, sin) of shape (seq, r / 2) and r, the rotated dimensions,
    for a layer kind: HF's default, or `_compute_yarn_parameters`."""
    rope = config["rope_parameters"][kind]
    r = int(config["head_dim"] * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    scaling = 1.0
    if rope["rope_type"] == "yarn":
        factor, original = rope["factor"], rope[
            "original_max_position_embeddings"]

        def correction(rotations):
            return r * math.log(original / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction(rope["beta_fast"])), 0)
        high = min(math.ceil(correction(rope["beta_slow"])), r - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scaling = rope.get("attention_factor") or 0.1 * math.log(factor) + 1
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(angles) * scaling, jnp.float32),
            jnp.asarray(np.sin(angles) * scaling, jnp.float32), r)


def _rotate(x, cos, sin, r):
    """HF apply_rotary_pos_emb on the first r dimensions of each head:
    x * cos + rotate_half(x) * sin, rotate_half = [-x2, x1]."""
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(mm, u, leaves, heads, kind, config):
    wq, wk, wv, wg, wo = leaves
    b, s, _ = u.shape
    d, kv = config["head_dim"], config["num_key_value_heads"]
    group = heads // kv
    window = config["sliding_window"] if kind == "sliding_attention" else None
    cos, sin, r = rotary_tables(config, kind, s)
    q = _rotate(mm(u, wq.T).reshape(b, s, heads, d).transpose(0, 2, 1, 3),
                cos, sin, r).reshape(b, kv, group, s, d)
    k = _rotate(mm(u, wk.T).reshape(b, s, kv, d).transpose(0, 2, 1, 3),
                cos, sin, r)
    v = mm(u, wv.T).reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    rows = min(QUERY_ROWS, s)
    key_pos = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(q_rows, first, k_seq, v_seq):
        # q_rows (kv, group, rows, d) of one sequence from row `first`
        logits = mm(q_rows, k_seq[:, None].swapaxes(-1, -2)) / math.sqrt(d)
        query_pos = first + jnp.arange(rows)[:, None]
        seen = key_pos <= query_pos
        if window is not None:
            seen &= query_pos - key_pos < window
        # the mask as a (rows, seq) term added to every head's scores
        # (a select would keep a mask the scores' size for the backward)
        probs = jax.nn.softmax(
            logits + jnp.where(seen, 0.0, -jnp.inf), axis=-1)
        return mm(probs, v_seq[:, None])

    def sequence(args):
        q_seq, k_seq, v_seq = args
        blocks = q_seq.reshape(kv, group, s // rows, rows, d) \
            .transpose(2, 0, 1, 3, 4)
        out = jax.lax.map(
            lambda a: block(a[0], a[1], k_seq, v_seq),
            (blocks, jnp.arange(0, s, rows)))
        return out.transpose(1, 2, 0, 3, 4).reshape(heads, s, d)

    att = jax.lax.map(sequence, (q, k, v))            # (b, heads, s, d)
    gate = jax.nn.sigmoid(mm(u, wg.T))                # (b, s, heads)
    att = att.transpose(0, 2, 1, 3) * gate[..., None]
    return mm(att.reshape(b, s, heads * d), wo.T)


def _by_tokens(fn, *arrays):
    """`fn` over blocks of TOKEN_ROWS tokens of (batch, seq, ...)
    arrays, each block recomputed in the backward pass; the blocks'
    results stacked back into (batch, seq, ...)."""
    b, s = arrays[0].shape[:2]
    rows = min(TOKEN_ROWS, b * s)
    blocks = [a.reshape((b * s // rows, rows) + a.shape[2:]) for a in arrays]
    out = jax.lax.map(lambda block: jax.checkpoint(fn)(*block), blocks)
    return out.reshape((b, s) + out.shape[2:])


def _swiglu(mm, u, w_in, w_out):
    def block(rows):
        gate, up = jnp.split(mm(rows, w_in.T), 2, axis=-1)
        return mm(jax.nn.silu(gate) * up, w_out.T)

    return _by_tokens(block, u)


def _experts(mm, u, leaves, config):
    """(the held experts' part of the layer's output, the rows each got
    and the assignments that went elsewhere)."""
    router, w_in, w_out = leaves
    held, first = config["num_experts"], config["first_expert"]
    top_k = config["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(u, router), axis=-1)
    top, experts = jax.lax.top_k(probs, top_k)
    weights = top / jnp.sum(top, -1, keepdims=True) \
        * config["moe_routed_scaling_factor"]

    def routed(u, experts, weights):
        def expert(total, args):
            e, e_in, e_out = args
            coefficient = jnp.sum(
                jnp.where(experts == first + e, weights, 0.0), axis=-1,
                keepdims=True)
            gate, up = jnp.split(mm(u, e_in), 2, axis=-1)
            return total + coefficient * mm(jax.nn.silu(gate) * up,
                                            e_out), None

        return jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                            (jnp.arange(held), w_in, w_out))[0]

    # by blocks of tokens: the backward pass keeps the running sum of
    # every expert's step, a block's worth each
    routed = _by_tokens(routed, u, experts, weights)
    rows = jnp.sum(experts[..., None] == first + jnp.arange(held),
                   axis=tuple(range(experts.ndim))).astype(jnp.float32)
    elsewhere = experts.size - jnp.sum(rows)
    return routed, jax.lax.stop_gradient(
        jnp.concatenate([rows, elsewhere[None]]))


def loss_and_routing(flat, ids, labels, *, config, precision):
    mm = _matmul(precision)
    eps = config["rms_norm_eps"]
    flat = list(flat)
    if _has_log(config):
        flat = flat[1:]
    x = flat[0][ids]
    at = 1
    logs = []
    for heads, kind, sparse in _layers(config):
        n = ATTENTION_LEAVES + (5 if sparse else 2)
        leaves, at = flat[at:at + n], at + n

        @jax.checkpoint
        def layer(x, leaves, heads=heads, kind=kind, sparse=sparse):
            g1, wq, wk, wv, wg, wo, g2 = leaves[:ATTENTION_LEAVES]
            a = x + _attention(mm, _rms_norm(x, g1, eps),
                               (wq, wk, wv, wg, wo), heads, kind, config)
            u = _rms_norm(a, g2, eps)
            if not sparse:
                return a + _swiglu(mm, u, *leaves[ATTENTION_LEAVES:]), None
            router, e_in, e_out, s_in, s_out = leaves[ATTENTION_LEAVES:]
            routed, rows = _experts(mm, u, (router, e_in, e_out), config)
            return a + _swiglu(mm, u, s_in, s_out) + routed, rows

        x, rows = layer(x, leaves)
        if sparse:
            logs.append(rows)
    norm, head = flat[at:at + 2]

    def picked(rows, row_labels):
        logp = jax.nn.log_softmax(mm(_rms_norm(rows, norm, eps), head.T))
        return jnp.take_along_axis(logp, row_labels[..., None], -1)

    loss = -jnp.mean(_by_tokens(picked, x, labels))
    return loss, (jnp.stack(logs) if logs else None)


def adamw(p, g, m, v, t, opt):
    """The program's AdamW: decay added to the update, on every leaf."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) \
        + opt["wd"] * p
    return p - opt["learning_rate"] * upd, m, v


def follow(config, params0, batches, seed, *, precision="float32",
           rows=None, watch=None):
    """Train `len(batches)` steps from `params0` on `batches` and return
    what the comparison reads: each step's loss, the per-leaf norm of
    the first gradient, the per-leaf norm of the parameters' change
    after the last step (the routing log's: of the last step's counts).
    `rows` keeps only the first `rows` sequences of every batch (the
    half-batch fault).  `watch(t, routing, gradient norms)` is called
    with every step's (benchmarks/look.py).  `seed` is unused: the
    model draws nothing."""
    del seed
    opt = config["assumed"]["optimizer"]
    flags = trainable(config)
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        loss_and_routing, config=config, precision=precision),
        has_aux=True))

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def update(params, grads, ms, vs, t):
        new = [adamw(p, g, m, v, t, opt) if tr else (p, m, v)
               for p, g, m, v, tr in zip(params, grads, ms, vs, flags)]
        return tuple(list(x) for x in zip(*new))

    norms = jax.jit(functools.partial(_part_norms,
                                      parts=leaf_parts(config)))
    params = list(params0)
    # the two moments live on the host while a gradient is computed:
    # beside params0, the parameters and the gradient they would leave
    # the backward pass too little of the chip
    moments = None
    losses, grad_norms = [], None
    seconds = {"gradient": [], "update": [], "moments": []}
    clock = time.perf_counter
    for t, (ids, labels) in enumerate(batches):
        n = rows or ids.shape[0]
        t0 = clock()
        (loss, routing), grads = grad_fn(
            params, jnp.asarray(ids[:n]), jnp.asarray(labels[:n]))
        losses.append(float(loss))
        if t == 0:
            grad_norms = np.asarray(norms(grads))
        if watch is not None:
            watch(t, None if routing is None else np.asarray(routing),
                  np.asarray(norms(grads)))
        t1 = clock()
        ms, vs = ([jnp.zeros_like(p) for p in params] for _ in range(2)) \
            if moments is None else jax.device_put(moments)
        t2 = clock()
        params, ms, vs = update(params, grads, ms, vs, float(t + 1))
        del grads
        jax.block_until_ready(params)
        t3 = clock()
        moments = jax.device_get((ms, vs))
        del ms, vs
        if routing is not None:
            params[0] = routing
        seconds["gradient"].append(round(t1 - t0, 2))
        seconds["update"].append(round(t3 - t2, 2))
        seconds["moments"].append(round(t2 - t1 + clock() - t3, 2))
    # where the reference's time goes (the first gradient compiles)
    print(f"reference-note seconds a step {seconds!r}", file=sys.stderr)
    change = np.asarray(norms([a - c for a, c in zip(params, params0)]))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
