"""Plain reference for the `qwen3_next_80b` configuration: the decoder's
forward pass, next-token loss, gradients and AdamW in straightforward
float32 `jax.numpy` with `precision="highest"`.  Imports nothing of
mxnet_tpu (only the benchmark's own rounding helper for the control).

Follows Qwen's Qwen3-Next-80B-A3B-Instruct `config.json` (model_type
`qwen3_next`) and the layer equations of ISSUE 34 / docs/decoder_lm.md,
read from the PUBLISHED keys (`full_attention_interval`,
`decoder_sparse_step`, `mlp_only_layers`, `rope_theta`,
`partial_rotary_factor`, the `linear_*` sizes), not from the per-layer
lists the program's builder derives from them:

    norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)
    every layer: a = x + Mixer(norm_1(x)),  y = a + MoE(norm_2(a))
    Mixer: Gated DeltaNet on layers i with (i + 1) % interval != 0,
           gated softmax attention on the others
    MoE: softmax router, top-k renormalised, the held experts' SwiGLU
         + sigmoid(u w_sg) * SwiGLU_shared(u)

The gated delta rule is the RECURRENT form, one token at a time (a
`lax.scan` over tokens; the program computes the chunked form, another
derivation of the same function).  The departures the configuration's
`assumed` lists apply (no multi-token-prediction module, no auxiliary
loss, contiguous [q | k | v | z], [b | a] and [q | gate] layouts,
seeded initialisers, decay on every leaf).  The same share of the
deployment as the program: the held experts of a `router_width`-wide
router, the held rows of the vocabulary; what the absent experts would
add is left out.

Departures of FORM, made so that the whole fits one chip in float32:
the token scan is checkpointed in blocks of tokens; attention is
computed in blocks of query rows, one sequence at a time; the experts
are a dense loop over the held ones with masks; the shared expert and
the head work in blocks of tokens; every block and every layer is
recomputed in the backward pass; AdamW's two moments wait on the host
while a gradient is computed.  None changes a value.

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

QUERY_ROWS = 256          # rows of a sequence an attention block holds
TOKEN_ROWS = 4096         # tokens a feed-forward or head block holds
SCAN_TOKENS = 64          # tokens of the rule's scan a checkpoint spans
MOE_LEAVES = 6            # router, experts in, out, shared in, out, gate


def _kinds(config):
    """`linear` or `full` for each layer held, by the published
    interval: every `full_attention_interval`-th layer is softmax
    attention."""
    every = config["full_attention_interval"]
    return ["full" if (i + 1) % every == 0 else "linear"
            for i in range(config["num_hidden_layers"])]


def _check_every_layer_is_sparse(config):
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("the reference knows the published pattern alone: "
                         "an expert layer in every decoder layer")


def _linear_sizes(config):
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return hk, hv, dk, dv, hk * dk, hv * dv


def _mixer_shapes(config, kind):
    """[(leaf name, shape, initialiser kind, parts)] of one mixer, its
    pre-norm first, in program order.  `parts`: the tensors a leaf
    packs along its first axis."""
    h, d = config["hidden_size"], config["head_dim"]
    if kind == "full":
        n, kv = config["num_attention_heads"], config["num_key_value_heads"]
        return [("attn_norm", (h,), "zeros", 1),
                ("q|gate", (2 * n * d, h), "matrix", 2),
                ("k", (kv * d, h), "matrix", 1),
                ("v", (kv * d, h), "matrix", 1),
                ("q_norm", (d,), "zeros", 1), ("k_norm", (d,), "zeros", 1),
                ("out", (h, n * d), "matrix", 1)]
    _, hv, _, dv, key, value = _linear_sizes(config)
    piece = math.gcd(key, value)
    return [("attn_norm", (h,), "zeros", 1),
            ("q|k|v|z", (2 * key + 2 * value, h), "matrix",
             (2 * key + 2 * value) // piece),
            ("b|a", (2 * hv, h), "matrix", 2),
            ("conv", (2 * key + value, config["linear_conv_kernel_dim"]),
             "conv", (2 * key + value) // piece),
            ("a_log", (hv,), "a_log", 1), ("dt_bias", (hv,), "ones", 1),
            ("o_norm", (dv,), "ones", 1),
            ("out", (h, value), "matrix", 1)]


def _moe_shapes(config):
    h, held = config["hidden_size"], config["num_experts"]
    width = config["moe_intermediate_size"]
    shared = config["shared_expert_intermediate_size"]
    return [("ffn_norm", (h,), "zeros", 1),
            ("router", (h, config["router_width"]), "matrix", 1),
            ("expert_in", (held, h, 2 * width), "matrix", held),
            ("expert_out", (held, width, h), "matrix", held),
            ("shared_in.gate|up", (2 * shared, h), "matrix", 2),
            ("shared_out", (h, shared), "matrix", 1),
            ("shared_gate", (1, h), "matrix", 1)]


def _leaves(config):
    """[(name, shape, initialiser kind, parts)] of every leaf."""
    _check_every_layer_is_sparse(config)
    h, vocab = config["hidden_size"], config["vocab_size"]
    kinds = _kinds(config)
    out = [("routing_log", (len(kinds), config["num_experts"] + 1),
            "zeros", 1), ("embed", (vocab, h), "matrix", 1)]
    for i, kind in enumerate(kinds):
        out += [(f"layer{i}.{name}", shape, init, parts) for
                name, shape, init, parts in
                _mixer_shapes(config, kind) + _moe_shapes(config)]
    return out + [("norm", (h,), "zeros", 1), ("head", (vocab, h),
                                               "matrix", 1)]


def param_specs(config):
    """[(shape, kind, scale)] in program order, for harness/weights.py:
    every matrix TruncNorm(init_stdev); the convolution's taps
    TruncNorm(conv_init_stdev); A_log ~ Normal(0, 1); dt_bias and the
    plain gains 1; the zero-centred gains 0."""
    assumed = config["assumed"]
    kinds = {"zeros": ("zeros", 0.0), "ones": ("ones", 0.0),
             "matrix": ("trunc_normal", assumed["init_stdev"]),
             "conv": ("trunc_normal", assumed["conv_init_stdev"]),
             "a_log": ("normal", 1.0)}
    return [(shape,) + kinds[init] for _, shape, init, _ in _leaves(config)]


def leaf_parts(config):
    """How many of the model's tensors each leaf packs along its first
    axis: [q | gate], [b | a] and a SwiGLU's [gate | up] are two, the
    stacked experts one each, [q | k | v | z] and the convolution's
    taps pieces of the key width (q, k, the halves of v and of z)."""
    return [parts for _, _, _, parts in _leaves(config)]


def leaf_names(config):
    """A name for each part `leaf_parts` counts, in its order."""
    return [name if parts == 1 else f"{name}[{j}]"
            for name, _, _, parts in _leaves(config) for j in range(parts)]


def trainable(config):
    return [name != "routing_log" for name, _, _, _ in _leaves(config)]


def _part_norms(leaves, parts):
    return jnp.stack([
        jnp.linalg.norm(chunk.ravel()) for x, k in zip(leaves, parts)
        for chunk in (jnp.split(x, k, axis=0) if k > 1 else [x])])


def _matmul(precision):
    q_in, q_out = lowprec.rounding(precision)
    return lambda a, b: q_out(jnp.matmul(q_in(a), q_in(b),
                                         precision="highest"))


def _norm(x, w, eps):
    """Zero-centred gain: x_hat * (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rotary_tables(config, seq):
    """(cos, sin) of shape (seq, r / 2) and r, the rotated dimensions:
    HF's default rotary embedding on the first `partial_rotary_factor`
    of the head."""
    r = int(config["head_dim"] * config["partial_rotary_factor"])
    inv = 1.0 / float(config["rope_theta"]) ** (
        np.arange(0, r, 2, dtype=np.float64) / r)
    angles = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32), r)


def _rotate(x, cos, sin, r):
    """HF apply_rotary_pos_emb on the first r dimensions of each head:
    x * cos + rotate_half(x) * sin, rotate_half = [-x2, x1]."""
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(mm, u, leaves, config):
    """Gated softmax attention: q and k normed a head, rotary on part
    of the head, causal, K/V shared by groups, the output times
    sigmoid(gate) element by element."""
    w_qg, wk, wv, q_norm, k_norm, wo = leaves
    b, s, _ = u.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    group = heads // kv
    cos, sin, r = rotary_tables(config, s)
    q, gate = jnp.split(mm(u, w_qg.T), 2, axis=-1)
    q = _norm(q.reshape(b, s, heads, d), q_norm, eps).transpose(0, 2, 1, 3)
    k = _norm(mm(u, wk.T).reshape(b, s, kv, d), k_norm, eps) \
        .transpose(0, 2, 1, 3)
    q = _rotate(q, cos, sin, r).reshape(b, kv, group, s, d)
    k = _rotate(k, cos, sin, r)
    v = mm(u, wv.T).reshape(b, s, kv, d).transpose(0, 2, 1, 3)
    rows = min(QUERY_ROWS, s)
    key_pos = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(q_rows, first, k_seq, v_seq):
        # q_rows (kv, group, rows, d) of one sequence from row `first`
        logits = mm(q_rows, k_seq[:, None].swapaxes(-1, -2)) / math.sqrt(d)
        seen = key_pos <= first + jnp.arange(rows)[:, None]
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
    att = att.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
    return mm(att * jax.nn.sigmoid(gate), wo.T)


def _causal_conv(x, taps):
    """y_t = sum_j taps[:, j] x_(t - 3 + j) for 4 taps: each channel
    its own, zeros before the sequence's start; x (b, s, channels)."""
    count = taps.shape[1]
    padded = jnp.pad(x, ((0, 0), (count - 1, 0), (0, 0)))
    return sum(padded[:, j:j + x.shape[1]] * taps[:, j]
               for j in range(count))


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def delta_rule(mm, q, k, v, g, beta):
    """The gated delta rule token by token.  q, k (b, s, heads, dk); v
    (b, s, heads, dv); g, beta (b, s, heads).  State S (dk x dv) a
    head, from 0:  S' = exp(g_t) S;  d = beta_t (v_t - S'^T k_t);
    S = S' + k_t d^T;  o_t = S^T q_t."""
    b, s, heads, dk = k.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (
            v_t - mm(k_t[..., None, :], state)[..., 0, :])
        state = state + mm(k_t[..., :, None], d[..., None, :])
        return state, mm(q_t[..., None, :], state)[..., 0, :]

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    span = max(c for c in range(1, min(SCAN_TOKENS, s) + 1) if s % c == 0)
    xs = [jnp.moveaxis(x, 1, 0).reshape((s // span, span) + x.shape[:1]
                                        + x.shape[2:])
          for x in (q, k, v, g, beta)]
    _, o = jax.lax.scan(block, jnp.zeros((b, heads, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def _linear_attention(mm, u, leaves, config):
    """Gated DeltaNet mixer."""
    w_qkvz, w_ba, taps, a_log, dt_bias, o_norm, wo = leaves
    b, s, _ = u.shape
    hk, hv, dk, dv, key, value = _linear_sizes(config)
    mixed = mm(u, w_qkvz.T)
    qkv, z = mixed[..., :2 * key + value], mixed[..., 2 * key + value:]
    qkv = jax.nn.silu(_causal_conv(qkv, taps))
    q = qkv[..., :key].reshape(b, s, hk, dk)
    k = qkv[..., key:2 * key].reshape(b, s, hk, dk)
    v = qkv[..., 2 * key:].reshape(b, s, hv, dv)
    # key head j serves value heads j * r ... j * r + r - 1
    q = jnp.repeat(_l2norm(q) / math.sqrt(dk), hv // hk, axis=2)
    k = jnp.repeat(_l2norm(k), hv // hk, axis=2)
    ba = mm(u, w_ba.T)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
    o = delta_rule(mm, q, k, v, g, beta)                # (b, s, hv, dv)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + config["rms_norm_eps"]) * o_norm \
        * jax.nn.silu(z.reshape(b, s, hv, dv))
    return mm(o.reshape(b, s, value), wo.T)


def _by_tokens(fn, *arrays):
    """`fn` over blocks of TOKEN_ROWS tokens of (batch, seq, ...)
    arrays, each block recomputed in the backward pass; the blocks'
    results stacked back into (batch, seq, ...)."""
    b, s = arrays[0].shape[:2]
    rows = min(TOKEN_ROWS, b * s)
    blocks = [a.reshape((b * s // rows, rows) + a.shape[2:]) for a in arrays]
    out = jax.lax.map(lambda block: jax.checkpoint(fn)(*block), blocks)
    return out.reshape((b, s) + out.shape[2:])


def _shared_expert(mm, u, w_in, w_out, w_gate):
    def block(rows):
        gate, up = jnp.split(mm(rows, w_in.T), 2, axis=-1)
        return jax.nn.sigmoid(mm(rows, w_gate.T)) \
            * mm(jax.nn.silu(gate) * up, w_out.T)

    return _by_tokens(block, u)


def _experts(mm, u, leaves, config):
    """(the held experts' part of the layer's output, the rows each got
    and the assignments that went elsewhere)."""
    router, w_in, w_out = leaves
    held, first = config["num_experts"], config["first_expert"]
    top_k = config["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(u, router), axis=-1)
    top, experts = jax.lax.top_k(probs, top_k)
    weights = top / jnp.sum(top, -1, keepdims=True)

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

    routed = _by_tokens(routed, u, experts, weights)
    rows = jnp.sum(experts[..., None] == first + jnp.arange(held),
                   axis=tuple(range(experts.ndim))).astype(jnp.float32)
    elsewhere = experts.size - jnp.sum(rows)
    return routed, jax.lax.stop_gradient(
        jnp.concatenate([rows, elsewhere[None]]))


def loss_and_routing(flat, ids, labels, *, config, precision):
    _check_every_layer_is_sparse(config)
    mm = _matmul(precision)
    eps = config["rms_norm_eps"]
    flat = list(flat)[1:]               # the routing log is not read
    x = flat[0][ids]
    at = 1
    logs = []
    for kind in _kinds(config):
        n = len(_mixer_shapes(config, kind)) + MOE_LEAVES + 1
        leaves, at = flat[at:at + n], at + n

        @jax.checkpoint
        def layer(x, leaves, kind=kind):
            mixer = _attention if kind == "full" else _linear_attention
            *mix, g2, router, e_in, e_out, s_in, s_out, s_gate = leaves[1:]
            a = x + mixer(mm, _norm(x, leaves[0], eps), mix, config)
            u = _norm(a, g2, eps)
            routed, rows = _experts(mm, u, (router, e_in, e_out), config)
            return a + _shared_expert(mm, u, s_in, s_out, s_gate) \
                + routed, rows

        x, rows = layer(x, leaves)
        logs.append(rows)
    norm, head = flat[at:at + 2]

    def picked(rows, row_labels):
        logp = jax.nn.log_softmax(mm(_norm(rows, norm, eps), head.T))
        return jnp.take_along_axis(logp, row_labels[..., None], -1)

    loss = -jnp.mean(_by_tokens(picked, x, labels))
    return loss, jnp.stack(logs)


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
    # the two moments live on the host while a gradient is computed
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
            watch(t, np.asarray(routing), np.asarray(norms(grads)))
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
        params[0] = routing
        seconds["gradient"].append(round(t1 - t0, 2))
        seconds["update"].append(round(t3 - t2, 2))
        seconds["moments"].append(round(t2 - t1 + clock() - t3, 2))
    # where the reference's time goes (the first gradient compiles)
    print(f"reference-note seconds a step {seconds!r}", file=sys.stderr)
    change = np.asarray(norms([a - c for a, c in zip(params, params0)]))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
