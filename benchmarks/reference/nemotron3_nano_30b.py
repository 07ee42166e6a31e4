"""Plain reference for the `nemotron3_nano_30b` configuration: the
decoder's forward pass, next-token loss, gradients and AdamW in
straightforward float32 `jax.numpy` with `precision="highest"`.  Imports
nothing of mxnet_tpu (only the benchmark's own rounding helper for the
control).

Follows NVIDIA's NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 `config.json`
(model_type `nemotron_h`) and HF transformers' NemotronH layers, read
from the PUBLISHED keys (`hybrid_override_pattern`, the `mamba_*`,
`n_groups`, `ssm_state_size`, `conv_kernel`, the expert keys), not from
the per-layer lists the program's builder derives from them:

    norm(x) = x * rsqrt(mean(x^2) + eps) * w        (eps 1e-5)
    every layer: x <- x + Part(norm(x)), Part by the pattern's letter
    M: Mamba-2;  *: GQA attention, no rotary embedding;
    E: sigmoid router with a correction bias, top-6 renormalised times
       2.5, the held experts' relu(u W_up)^2 W_down + the shared expert

The SSD is the RECURRENT form, one token at a time (a `lax.scan` over
tokens; the program computes the chunked form, another derivation of
the same function):  S_t = exp(dt_t A) S + dt_t x_t B_t^T,  y_t = S_t C_t
+ D x_t.  The departures the configuration's `assumed` lists apply (no
auxiliary loss, the correction bias held at 0, in_proj as [x | B | C |
z] and dt, seeded initialisers, decay on every leaf).  The same share
of the deployment as the program: the held experts of a
`router_width`-wide router, the held rows of the vocabulary; what the
absent experts would add is left out.

Departures of FORM, made so that the whole fits one chip in float32:
the token scan is checkpointed in blocks of tokens; attention is
computed in blocks of query rows, one sequence at a time; the experts
are a dense loop over the held ones with masks; the shared expert and
the head work in blocks of tokens; every block and every layer is
recomputed in the backward pass; AdamW's two moments wait on the host
and each leaf is updated on its own.  None changes a value.

Parameters are a flat list in the order the program's
`block._ordered_params()` gives them: the routing log and the
correction bias (neither trained), the embedding, each layer's leaves,
the final norm and the head.
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
SCAN_TOKENS = 64          # tokens of the SSD's scan a checkpoint spans
LETTERS = "ME*"


def _pattern(config):
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(
            LETTERS):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: one of "
                         f"{LETTERS!r} a layer held")
    return pattern


def _mamba_sizes(config):
    heads, size = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    return heads, size, groups, state, heads * size


def _part_shapes(config, letter):
    """[(leaf name, shape, initialiser kind, parts)] of one layer, its
    pre-norm first, in program order.  `parts`: the tensors a leaf
    packs along its first axis."""
    h, d = config["hidden_size"], config["head_dim"]
    if letter == "M":
        heads, size, groups, state, inner = _mamba_sizes(config)
        conv = inner + 2 * groups * state
        piece = math.gcd(inner, groups * state)
        return [("norm", (h,), "ones", 1),
                ("x|B|C|z", (conv + inner, h), "matrix",
                 (conv + inner) // piece),
                ("dt", (heads, h), "matrix", 1),
                ("conv", (conv, config["conv_kernel"]), "conv",
                 conv // piece),
                ("conv_bias", (conv,), "zeros", conv // piece),
                ("a_log", (heads,), "a_log", 1), ("d", (heads,), "ones", 1),
                ("dt_bias", (heads,), "zeros", 1),
                ("mamba_norm", (inner,), "ones", 1),
                ("out", (h, inner), "matrix", 1)]
    if letter == "*":
        n, kv = config["num_attention_heads"], config["num_key_value_heads"]
        return [("norm", (h,), "ones", 1), ("q", (n * d, h), "matrix", 1),
                ("k", (kv * d, h), "matrix", 1),
                ("v", (kv * d, h), "matrix", 1),
                ("out", (h, n * d), "matrix", 1)]
    held = config["n_routed_experts"]
    width = config["moe_intermediate_size"]
    shared = config["moe_shared_expert_intermediate_size"]
    return [("norm", (h,), "ones", 1),
            ("router", (h, config["router_width"]), "matrix", 1),
            ("expert_in", (held, h, width), "matrix", held),
            ("expert_out", (held, width, h), "matrix", held),
            ("shared_in", (shared, h), "matrix", 1),
            ("shared_out", (h, shared), "matrix", 1)]


def _leaves(config):
    """[(name, shape, initialiser kind, parts)] of every leaf."""
    h, vocab = config["hidden_size"], config["vocab_size"]
    pattern = _pattern(config)
    sparse = pattern.count("E")
    out = [("routing_log", (sparse, config["n_routed_experts"] + 1),
            "zeros", 1),
           ("correction_bias", (sparse, config["router_width"]), "zeros", 1),
           ("embed", (vocab, h), "matrix", 1)]
    for i, letter in enumerate(pattern):
        out += [(f"layer{i}.{name}", shape, init, parts) for
                name, shape, init, parts in _part_shapes(config, letter)]
    return out + [("norm", (h,), "ones", 1), ("head", (vocab, h),
                                              "matrix", 1)]


def param_specs(config):
    """[(shape, kind, scale)] in program order, for harness/weights.py:
    every matrix TruncNorm(init_stdev); the convolution's taps
    TruncNorm(conv_init_stdev); A_log ~ Normal(0, a_log_stdev); D and
    the gains 1; dt_bias, the convolution's bias and the correction
    bias 0."""
    assumed = config["assumed"]
    kinds = {"zeros": ("zeros", 0.0), "ones": ("ones", 0.0),
             "matrix": ("trunc_normal", assumed["init_stdev"]),
             "conv": ("trunc_normal", assumed["conv_init_stdev"]),
             "a_log": ("normal", assumed["a_log_stdev"])}
    return [(shape,) + kinds[init] for _, shape, init, _ in _leaves(config)]


def leaf_parts(config):
    """How many of the model's tensors each leaf packs along its first
    axis: [x | B | C | z] and the convolution's taps and bias in pieces
    of the group width (x and z four each, B, C one each), the stacked
    experts one each."""
    return [parts for _, _, _, parts in _leaves(config)]


def leaf_names(config):
    """A name for each part `leaf_parts` counts, in its order."""
    return [name if parts == 1 else f"{name}[{j}]"
            for name, _, _, parts in _leaves(config) for j in range(parts)]


def trainable(config):
    return [name not in ("routing_log", "correction_bias")
            for name, _, _, _ in _leaves(config)]


def _part_norms(leaves, parts):
    return jnp.stack([
        jnp.linalg.norm(chunk.ravel()) for x, k in zip(leaves, parts)
        for chunk in (jnp.split(x, k, axis=0) if k > 1 else [x])])


def _matmul(precision):
    q_in, q_out = lowprec.rounding(precision)
    return lambda a, b: q_out(jnp.matmul(q_in(a), q_in(b),
                                         precision="highest"))


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attention(mm, u, leaves, config):
    """GQA attention, causal, no rotary embedding, no gate."""
    wq, wk, wv, wo = leaves
    b, s, _ = u.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    group = heads // kv
    q = mm(u, wq.T).reshape(b, s, kv, group, d).transpose(0, 2, 3, 1, 4)
    k = mm(u, wk.T).reshape(b, s, kv, d).transpose(0, 2, 1, 3)
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
    return mm(att.transpose(0, 2, 1, 3).reshape(b, s, heads * d), wo.T)


def _causal_conv(x, taps, bias):
    """y_t = sum_j taps[:, j] x_(t - (taps - 1) + j) + bias: each
    channel its own, zeros before the sequence's start."""
    count = taps.shape[1]
    padded = jnp.pad(x, ((0, 0), (count - 1, 0), (0, 0)))
    return sum(padded[:, j:j + x.shape[1]] * taps[:, j]
               for j in range(count)) + bias


def ssd(mm, x, dt, a, b, c, d):
    """The SSD token by token.  x (batch, s, heads, p); dt (batch, s,
    heads); a, d (heads,); b, c (batch, s, heads, n), each head's group
    already.  State S (p x n) a head, from 0:  S = exp(dt_t a) S + dt_t
    x_t b_t^T;  y_t = S c_t + d x_t."""
    batch, s, heads, p = x.shape

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + mm((dt_t[..., None] * x_t)[..., :, None], b_t[..., None, :])
        y = mm(state, c_t[..., :, None])[..., 0]
        return state, y + d[:, None] * x_t

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    span = max(k for k in range(1, min(SCAN_TOKENS, s) + 1) if s % k == 0)
    xs = [jnp.moveaxis(v, 1, 0).reshape((s // span, span) + v.shape[:1]
                                        + v.shape[2:])
          for v in (x, dt, b, c)]
    _, y = jax.lax.scan(block, jnp.zeros((batch, heads, p, b.shape[-1])),
                        xs)
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def _mamba(mm, u, leaves, config):
    """Mamba-2 mixer."""
    w_in, w_dt, taps, conv_bias, a_log, d, dt_bias, norm_w, wo = leaves
    batch, s, _ = u.shape
    heads, size, groups, state, inner = _mamba_sizes(config)
    conv = inner + 2 * groups * state
    mixed = mm(u, w_in.T)
    xbc = jax.nn.silu(_causal_conv(mixed[..., :conv], taps, conv_bias))
    z = mixed[..., conv:]
    x = xbc[..., :inner].reshape(batch, s, heads, size)
    # head h reads group h // (heads / groups)
    b, c = (jnp.repeat(v.reshape(batch, s, groups, state), heads // groups,
                       axis=2)
            for v in (xbc[..., inner:inner + groups * state],
                      xbc[..., inner + groups * state:]))
    dt = jax.nn.softplus(mm(u, w_dt.T) + dt_bias)
    y = ssd(mm, x, dt, -jnp.exp(a_log), b, c, d).reshape(batch, s, inner)
    # gate before the norm; the norm over each group's channels
    gated = (y * jax.nn.silu(z)).reshape(batch, s, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True)
                                   + config["layer_norm_epsilon"])
    return mm(normed.reshape(batch, s, inner) * norm_w, wo.T)


def _by_tokens(fn, *arrays):
    """`fn` over blocks of TOKEN_ROWS tokens of (batch, seq, ...)
    arrays, each block recomputed in the backward pass; the blocks'
    results stacked back into (batch, seq, ...)."""
    b, s = arrays[0].shape[:2]
    rows = min(TOKEN_ROWS, b * s)
    blocks = [a.reshape((b * s // rows, rows) + a.shape[2:]) for a in arrays]
    out = jax.lax.map(lambda block: jax.checkpoint(fn)(*block), blocks)
    return out.reshape((b, s) + out.shape[2:])


def _relu2(mm, rows, w_in, w_out):
    return mm(jnp.square(jax.nn.relu(mm(rows, w_in))), w_out)


def _experts(mm, u, leaves, bias, config):
    """(the held experts' part of the layer's output and the shared
    expert's, the rows each held expert got and the assignments that
    went elsewhere)."""
    router, w_in, w_out, s_in, s_out = leaves
    held, first = config["n_routed_experts"], config["first_expert"]
    top_k = config["num_experts_per_tok"]
    scores = jax.nn.sigmoid(mm(u, router))
    experts = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)[1]
    weights = jnp.take_along_axis(scores, experts, -1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20) \
        * config["routed_scaling_factor"]

    def routed(u, experts, weights):
        def expert(total, args):
            e, e_in, e_out = args
            coefficient = jnp.sum(
                jnp.where(experts == first + e, weights, 0.0), axis=-1,
                keepdims=True)
            return total + coefficient * _relu2(mm, u, e_in, e_out), None

        out = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(u),
                           (jnp.arange(held), w_in, w_out))[0]
        return out + _relu2(mm, u, s_in.T, s_out.T)

    out = _by_tokens(routed, u, experts, weights)
    rows = jnp.sum(experts[..., None] == first + jnp.arange(held),
                   axis=tuple(range(experts.ndim))).astype(jnp.float32)
    elsewhere = experts.size - jnp.sum(rows)
    return out, jax.lax.stop_gradient(
        jnp.concatenate([rows, elsewhere[None]]))


def loss_and_routing(flat, ids, labels, *, config, precision):
    mm = _matmul(precision)
    eps = config["layer_norm_epsilon"]
    flat = list(flat)
    biases = flat[1]                    # the routing log is not read
    x = flat[2][ids]
    at = 3
    logs = []
    for letter in _pattern(config):
        n = len(_part_shapes(config, letter))
        leaves, at = flat[at:at + n], at + n

        @jax.checkpoint
        def layer(x, leaves, bias, letter=letter):
            u = _norm(x, leaves[0], eps)
            if letter == "M":
                return x + _mamba(mm, u, leaves[1:], config), None
            if letter == "*":
                return x + _attention(mm, u, leaves[1:], config), None
            out, rows = _experts(mm, u, leaves[1:], bias, config)
            return x + out, rows

        x, rows = layer(x, leaves, biases[len(logs)] if letter == "E"
                        else None)
        if rows is not None:
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
    half-batch fault); where that is none (`rows` 0: a batch of one
    sequence, whose half is no whole sequence), the first half of each
    sequence's tokens, so that the fault still leaves half of the
    batch's tokens out.  `watch(t, routing, gradient norms)` is called
    with every step's (benchmarks/look.py).  `seed` is unused: the
    model draws nothing."""
    del seed
    opt = config["assumed"]["optimizer"]
    flags = trainable(config)
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        loss_and_routing, config=config, precision=precision),
        has_aux=True))
    # one leaf at a time: its moments come from the host and go back
    update = jax.jit(functools.partial(adamw, opt=opt),
                     donate_argnums=(1, 2, 3))
    norms = jax.jit(functools.partial(_part_norms,
                                      parts=leaf_parts(config)))
    params = list(params0)
    moments = [None] * len(params)
    losses, grad_norms = [], None
    seconds = {"gradient": [], "update": []}
    clock = time.perf_counter
    for t, (ids, labels) in enumerate(batches):
        if rows is None:
            kept = ids, labels
        elif rows:
            kept = ids[:rows], labels[:rows]
        else:
            kept = ids[:, :ids.shape[1] // 2], labels[:, :ids.shape[1] // 2]
        t0 = clock()
        (loss, routing), grads = grad_fn(
            params, *(jnp.asarray(a) for a in kept))
        losses.append(float(loss))
        if t == 0:
            grad_norms = np.asarray(norms(grads))
        if watch is not None:
            watch(t, np.asarray(routing), np.asarray(norms(grads)))
        t1 = clock()
        for i, tr in enumerate(flags):
            if not tr:
                continue
            m, v = (jnp.zeros_like(params[i]), jnp.zeros_like(params[i])) \
                if moments[i] is None else jax.device_put(moments[i])
            params[i], m, v = update(params[i], grads[i], m, v,
                                     jnp.float32(t + 1))
            grads[i] = None
            moments[i] = jax.device_get((m, v))
        del grads
        params[0] = routing
        seconds["gradient"].append(round(t1 - t0, 2))
        seconds["update"].append(round(clock() - t1, 2))
    # where the reference's time goes (the first gradient compiles)
    print(f"reference-note seconds a step {seconds!r}", file=sys.stderr)
    change = np.asarray(norms([a - c for a, c in zip(params, params0)]))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
