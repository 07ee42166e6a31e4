"""Linear attention with a gated delta rule (Gated DeltaNet), and the
two small ops that feed it: a causal depthwise short convolution and
an l2 norm over the head.

The rule, for one value head with state S (key size x value size),
S_0 = 0, everything in float32:

    S'_t = alpha_t S_(t-1)              alpha_t = exp(g_t), g_t <= 0
    d_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t d_t^T
    o_t  = S_t^T q_t

`gated_delta_rule` computes it in the CHUNKED form: the sequence in
chunks of `CHUNK` tokens; inside a chunk the d_t of all its tokens at
once (the WY representation: with G_i the running sum of g inside the
chunk, (I + A) D = beta V - (beta e^G K) S_0 for the strictly lower
A_ij = beta_i e^(G_i - G_j) k_i.k_j, so D = U - W S_0 with U, W two
products of (I + A)^-1), across chunks a `lax.scan` that carries S in
float32.  Every exponent formed is a difference G_i - G_j <= 0 of
positions i >= j of one chunk, so nothing overflows whatever the
decay.  (I + A)^-1 is exact and made of matrix products alone: the
diagonal blocks of 16 by the product form of a nilpotent matrix,
(I + N)^-1 = (I - N)(I + N^2)(I + N^4)(I + N^8), the blocks below them
by the same identity one level up (a strictly block-lower matrix of 4
x 4 blocks has a zero fourth power).  The whole 64-step product form
would be as exact on paper and loses everything to cancellation where
keys repeat (its powers grow like binomial coefficients).

Memory.  No pass holds a state a TOKEN.  The heads are worked on in
groups, one after another (`head_groups`), each group recomputed inside
its own backward pass, which is the chunk scan's own derivative: it
keeps the float32 state at every chunk's start (`state_bytes_kept`:
sequence / chunk x batch x heads x key x value x 4 bytes, 537 MB for 2
x 8,192 tokens and 32 heads of 128 x 128, a quarter of it alive at a
time there).  The output is NAMED (`RESIDUAL_NAMES`): a
`jax.checkpoint` whose policy saves it (`DataParallelTrainer(remat=
True)`) recomputes what follows the rule without running the rule
again, so a step runs the rule's forward twice (the pass itself, and a
group at a time inside the backward), not three times.

Products take their operands in the dtype of q, k, v and accumulate in
float32 (`precision="highest"`: exact float32 products for float32
operands); the state, the decays, the inverse and every sum are
float32.  A length that is no multiple of the chunk is padded with
tokens that leave the state as it is (beta 0, g 0) and whose outputs
are dropped.

The XLA form below is the only form: a Pallas kernel for the rule is
ROADMAP R6's next step.  Every rule traced is counted (the profiler
section `linearAttention`).
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import profiler
from . import registry
from .registry import register

CHUNK = 64      # tokens a chunk
_BLOCK = 16     # diagonal blocks inverted by the nilpotent product form
_PAIRS = 16     # (sequence, value head) pairs a pass of the rule holds
# the name the rule gives its output: a `jax.checkpoint` policy that
# saves it keeps the rule out of the recomputation of what follows it
RESIDUAL_NAMES = registry.RESIDUAL_NAMES["gated_delta_rule"]

# every rule traced: (batch, value heads, seq, key size, value size,
# dtype) -> traces
_traced = collections.Counter()


def _k_l2_norm(data, *, eps=1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, in float32."""
    x = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * inv).astype(data.dtype)


register("l2_norm", _k_l2_norm, arg_names=("data",))


def _k_causal_conv1d(data, weight):
    """SiLU of the depthwise causal convolution along the sequence of
    (batch, seq, channels): y_t = silu(sum_j weight[:, j] *
    x_(t - (taps - 1) + j)), zeros before the sequence's start, no
    bias; weight (channels, taps), the last tap on x_t itself.  A tap
    is one shifted multiply-add in float32."""
    seq, taps = data.shape[1], weight.shape[1]
    with jax.named_scope("conv"):
        x = jnp.pad(data, ((0, 0), (taps - 1, 0), (0, 0)))
        w = weight.astype(jnp.float32)
        y = sum(x[:, j:j + seq].astype(jnp.float32) * w[:, j]
                for j in range(taps))
        return jax.nn.silu(y).astype(data.dtype)


register("causal_conv1d", _k_causal_conv1d, arg_names=("data", "weight"))


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular `a` (..., CHUNK, CHUNK),
    float32, by matrix products alone (module docstring)."""
    eye = jnp.eye(CHUNK, dtype=a.dtype)

    def mm(x, y):
        return jnp.matmul(x, y, precision="highest")

    def nilpotent_inverse(n, index):
        # (I - n)^-1 for n^index = 0: the product of (I + n^(2^j))
        out, power, reach = eye + n, n, 2
        while reach < index:
            power = mm(power, power)
            out = out + mm(out, power)
            reach *= 2
        return out

    block = jnp.arange(CHUNK) // _BLOCK
    same = block[:, None] == block[None, :]
    diagonal = nilpotent_inverse(-jnp.where(same, a, 0.0), _BLOCK)
    below = mm(diagonal, jnp.where(same, 0.0, a))
    return mm(nilpotent_inverse(-below, CHUNK // _BLOCK), diagonal)


def _rule(q, k, v, g, beta):
    """The chunked rule for heads that are worked on together: q, k, v
    (b, h, seq, size), g and beta (b, h, seq), seq a multiple of
    `CHUNK`."""
    b, h, seq, dv = v.shape
    dk, dtype, n = k.shape[-1], v.dtype, seq // CHUNK

    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          precision="highest",
                          preferred_element_type=jnp.float32)

    q, k, v = (x.reshape(b, h, n, CHUNK, x.shape[-1]) for x in (q, k, v))
    beta = beta.astype(jnp.float32).reshape(b, h, n, CHUNK)
    total = jnp.cumsum(g.astype(jnp.float32).reshape(b, h, n, CHUNK), -1)
    # decay[i, j] = exp(G_i - G_j) for i >= j, 0 above the diagonal
    seen = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.where(seen, jnp.exp(jnp.where(
        seen, total[..., :, None] - total[..., None, :], 0.0)), 0.0)
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)
    a = jnp.where(strict, beta[..., None] * decay
                  * dot("...id,...jd->...ij", k, k), 0.0)
    inverse = _unit_lower_inverse(a)
    into = jnp.exp(total)[..., None]            # from the chunk's start
    u = dot("...ij,...jd->...id", inverse, v * beta[..., None])
    w = dot("...ij,...jd->...id", inverse, k * (beta[..., None] * into))
    within = decay * dot("...id,...jd->...ij", q, k)
    # what each token's k d^T is worth at the chunk's end
    k_out = k * jnp.exp(total[..., -1:] - total)[..., None]

    def step(state, xs):
        u, w, within, q_in, k_out, last = xs
        d = u - dot("...id,...de->...ie", w, state)
        o = dot("...id,...de->...ie", q_in, state) \
            + dot("...ij,...je->...ie", within, d)
        state = state * last[..., None, None] \
            + dot("...id,...ie->...de", k_out, d)
        return state, o.astype(dtype)

    by_chunk = [jnp.moveaxis(x, 2, 0) for x in (
        u, w.astype(dtype), within.astype(dtype), (q * into).astype(dtype),
        k_out.astype(dtype), jnp.exp(total[..., -1]))]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32),
                        by_chunk)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, seq, dv)


def head_groups(batch, key_heads, value_heads):
    """Into how many groups of heads the rule is cut: the groups are
    worked on one after another, each recomputed in its own backward
    pass, so that the intermediates of `_PAIRS` (sequence, value head)
    pairs are alive at a time, not of all."""
    return max(c for c in range(1, key_heads + 1) if key_heads % c == 0
               and (c == 1 or batch * value_heads // c >= _PAIRS))


def _k_gated_delta_rule(q, k, v, g, beta):
    """The gated delta rule (module docstring) in its chunked form.

    q, k: (batch, key heads, seq, key size), already normalised and
    scaled; v: (batch, value heads, seq, value size), value heads a
    multiple of the key heads (key head j serves value heads j * r ...
    j * r + r - 1); g (log decay, <= 0) and beta: (batch, value heads,
    seq).  Returns o, (batch, value heads, seq, value size) in v's
    dtype, named `delta_rule_out` for a `jax.checkpoint` policy."""
    b, h, seq, dv = v.shape
    hk, dk = k.shape[1], k.shape[-1]
    if h % hk:
        raise ValueError(f"gated_delta_rule: {h} value heads over "
                         f"{hk} key heads")
    _traced[(b, h, seq, dk, dv, jnp.dtype(v.dtype).name)] += 1
    groups = head_groups(b, hk, h)

    def one_group(xs):
        q, k, v, g, beta = xs
        q, k = (jnp.repeat(x, h // hk, axis=1) for x in (q, k))
        return _rule(q, k, v, g, beta)

    with jax.named_scope("delta_rule"):
        pad = -seq % CHUNK
        if pad:
            # beta 0 and g 0: the state passes a padded token unchanged
            q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                       for x in (q, k, v))
            g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
                       for x in (g, beta))
        if groups == 1:
            o = one_group((q, k, v, g, beta))
        else:
            by_group = [jnp.moveaxis(x.reshape(
                (b, groups, x.shape[1] // groups) + x.shape[2:]), 1, 0)
                for x in (q, k, v, g, beta)]
            o = jax.lax.map(jax.checkpoint(one_group), by_group)
            o = jnp.moveaxis(o, 0, 1).reshape(b, h, seq + pad, dv)
        return checkpoint_name(o[:, :, :seq], RESIDUAL_NAMES[0])


register("gated_delta_rule", _k_gated_delta_rule,
         arg_names=("q", "k", "v", "g", "beta"))


def linear_attention_stats():
    """The `linearAttention` profiler section: the delta rules traced
    since the last reset (a trace a layer's pass, as `flashAttention`
    counts: a layer whose jaxpr JAX reuses does not count again).
    `layers`: the distinct shapes; `traces`, `chunk`,
    `chunks_per_sequence` and `state_bytes_kept` (the float32 states at
    the chunks' starts that the scan's derivative keeps for the layer
    being differentiated) by shape."""
    out = {"layers": len(_traced), "traces": {}, "chunk": {},
           "chunks_per_sequence": {}, "state_bytes_kept": {}}
    for (b, h, seq, dk, dv, dtype), n in _traced.items():
        key = f"b{b} h{h} s{seq} k{dk} v{dv} {dtype}"
        chunks = -(-seq // CHUNK)
        out["traces"][key] = n
        out["chunk"][key] = CHUNK
        out["chunks_per_sequence"][key] = chunks
        out["state_bytes_kept"][key] = chunks * b * h * dk * dv * 4
    return out


def reset_linear_attention_stats():
    _traced.clear()


def _stats_table(stats):
    out = ["Linear Attention (delta rules traced):"]
    for key in sorted(stats["traces"]):
        out.append(f"  {key}: x{stats['traces'][key]}, "
                   f"{stats['chunks_per_sequence'][key]} chunks of "
                   f"{stats['chunk'][key]}, keeps "
                   f"{stats['state_bytes_kept'][key]} bytes of states")
    return out


profiler.register_section("linearAttention", linear_attention_stats,
                          reset_linear_attention_stats, _stats_table)
