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

The intra-chunk part is ONE function with its own derivative
(`_wy_xla`).  For one (sequence, value head) and one chunk, from q, k (the
key head's: value head h reads key head h // r), v, g, beta it returns
what the chunk scan consumes, the chunk axis first:

    G = cumsum(g)    D_ij = exp(G_i - G_j), i >= j
    A = strict_lower(beta_i D_ij (k k^T)_ij)         T = (I + A)^-1
    u = T (beta v)   w = T (beta e^G k)   within = D * (q k^T)
    q_in = q e^G     k_out = k exp(G_C - G)          last = exp(G_C)

and, given their cotangents, the gradients by the closed form, never
through the inverse's products:

    dT = du (beta v)^T + dw (beta e^G k)^T
    d(beta v) = T^T du    d(beta e^G k) = T^T dw
    dA = -strict_lower(T^T dT T^T)        (exact float32, as T itself)
    with M = k k^T, N = q k^T:
    dM = dA * beta_i D_ij    dN = dwithin * D
    dD = dA * beta_i M + dwithin * N
    dk += (dM + dM^T) k + dN^T q          dq += dN k
    dbeta_i += sum_j dA_ij D_ij M_ij
    dG_i += sum_j dD_ij D_ij              dG_j -= sum_i dD_ij D_ij

plus the element-wise terms of beta v, beta e^G k, q e^G, k exp(G_C -
G) and exp(G_C); dg is the reverse running sum of dG inside the chunk,
dq and dk sum over the r value heads of a key head.  The backward
keeps the five inputs and T (float32, 33.5 MB a head group at 2 x
8,192 tokens), none of the inverse's intermediate powers.  Two forms
of it, one mathematics: `_wy_xla` in `jax.numpy`, and the kernel pair
of ops/pallas/delta_rule.py, taken where the computation is LOWERED
for the TPU (`lax.platform_dependent`) and the shapes allow it: an even
number of chunks, key and value sizes multiples of 128, bf16 or
float32.  tests/test_linear_attention.py holds `_wy_xla` to
`jax.grad` of the plainly differentiated rule and the kernels to
`_wy_xla`.

The chunk scan, for one pair and chunk in order, from S = 0:

    d = u - w S      o = q_in S + within d      S <- last S + k_out^T d

and, in reverse, from the state's cotangent dS = 0 and the kept S:

    dd = k_out dS + within^T do           (du = dd)
    dwithin = do d^T   dk_out = d dS^T   dq_in = do S^T   dw = -dd S^T
    dlast = sum(S * dS)    dS <- last dS + q_in^T do - w^T dd

Two forms of it: `_scan_xla`, a `lax.scan` that JAX differentiates, and
the kernel pair of ops/pallas/delta_rule.py (`delta_rule.scan`, with
its own `jax.custom_vjp`), taken under the same `lax.platform_dependent`
and the same shapes as the intra-chunk kernels.  The kernels keep a
block of pairs' float32 states (and in the backward their cotangents)
in VMEM across the chunks and round exactly where `_scan_xla` and its
derivative do: a product's operands to the inputs' dtype (the state
too, as an operand only); a float32 cotangent against a bf16 operand
goes in whole (three bf16 parts, the float32 product of
`precision="highest"`), and a cotangent of a bf16 value is rounded to
bf16 as JAX rounds it.  tests/test_linear_attention.py holds the
kernels to `_scan_xla`, values and the six gradients.

Memory.  No pass holds a state a TOKEN.  The heads are worked on in
groups, one after another (`head_groups`), each group recomputed inside
its own backward pass, which keeps the float32 state at every chunk's
start (`state_bytes_kept`: sequence / chunk x batch x heads x key x
value x 4 bytes, 537 MB for 2 x 8,192 tokens and 32 heads of 128 x
128, a quarter of it alive at a time there): `_scan_xla`'s derivative
stacks them as its residuals, the kernels' backward writes them with a
forward of its own (the pass's forward writes none) and reads them in
reverse.  The output is NAMED (`RESIDUAL_NAMES`): a
`jax.checkpoint` whose policy saves it (`DataParallelTrainer(remat=
True)`) recomputes what follows the rule without running the rule
again, so a step runs the rule's forward twice (the pass itself, and a
group at a time inside the backward), not three times.

Products take their operands in the dtype of q, k, v and accumulate in
float32 (`precision="highest"`: exact float32 products for float32
operands), the cotangents' products too; the state, the decays, the
inverse, its derivative's two products and every sum are float32.  A
length that is no multiple of the chunk is padded with tokens that
leave the state as it is (beta 0, g 0) and whose outputs are dropped.

The short convolution (`causal_conv1d`) has two forms too: `_conv_xla`,
shifted multiply-adds over a padded copy that JAX differentiates, and
the kernel pair of ops/pallas/short_conv.py, taken under
`lax.platform_dependent` where the channels are whole lanes, the taps at
most 8 and the sequence whole 16-row tiles (bf16 or float32).  Both
multiply and add the taps in float32 in tap order, take SiLU in float32
and round once.  The kernels read the channels where the packed
projection holds them; their backward recomputes the pre-activation in
VMEM, sums dx's taps in float32 and rounds once (JAX's derivative of
`_conv_xla` rounds each tap's part to the input's dtype and adds them
there), accumulates dw in float32 and keeps the inputs only.

Every rule and every convolution traced is counted, and whether its
shapes are the kernels' (the profiler section `linearAttention`).
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
# dtype) -> traces, those of them whose shapes the intra-chunk kernels
# take, and those whose chunk scan takes the scan's kernel pair
_traced = collections.Counter()
_kernel_traced = collections.Counter()
_scan_kernel_traced = collections.Counter()
# every short convolution traced: (batch, seq, channels, taps, dtype)
# -> traces, and those of them whose shapes the conv kernels take
_conv_traced = collections.Counter()
_conv_kernel_traced = collections.Counter()


def _k_l2_norm(data, *, eps=1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, in float32."""
    x = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * inv).astype(data.dtype)


register("l2_norm", _k_l2_norm, arg_names=("data",))


def _conv_xla(data, weight, bias=None):
    """The convolution as shifted multiply-adds over a padded copy,
    which JAX differentiates: the off-TPU path and the kernels'
    oracle."""
    seq, (channels, taps) = data.shape[1], weight.shape
    x = jnp.pad(data[:, :, :channels], ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = sum(x[:, j:j + seq].astype(jnp.float32) * w[:, j]
            for j in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(data.dtype)


def _k_causal_conv1d(data, weight, bias=None):
    """SiLU of the depthwise causal convolution along the sequence of
    (batch, seq, width) over its first `channels` columns (all of them,
    or the `[q | k | v]` of a packed `[q | k | v | z]`): y_t =
    silu(sum_j weight[:, j] * x_(t - (taps - 1) + j) + bias), zeros
    before the sequence's start, no bias where `bias` is None; weight
    (channels, taps), the last tap on x_t itself, bias (channels,);
    returns (batch, seq, channels) in data's dtype.  The taps are
    multiplied and added in float32 in tap order, the bias after them,
    SiLU in float32, one rounding at the end.

    Where the computation is lowered for the TPU and the shapes allow
    it (channels of whole lanes, at most 8 taps, a sequence of whole
    16-row tiles, bf16 or float32) the kernel pair of
    ops/pallas/short_conv.py, which reads the packed array where it
    lies; everywhere else `_conv_xla`."""
    from .pallas import short_conv

    b, seq, _ = data.shape
    channels, taps = weight.shape
    key = (b, seq, channels, taps, jnp.dtype(data.dtype).name)
    _conv_traced[key] += 1
    with jax.named_scope("conv"):
        if not short_conv.admits(data.shape, weight.shape, data.dtype):
            return _conv_xla(data, weight, bias)
        _conv_kernel_traced[key] += 1
        if bias is None:
            def kernels(data, weight):
                from ..parallel.mesh import per_batch_shard

                return per_batch_shard(
                    short_conv.conv, weight.astype(jnp.float32).T[None], data)

            return jax.lax.platform_dependent(data, weight, tpu=kernels,
                                              default=_conv_xla)

        def biased(data, weight, bias):
            from ..parallel.mesh import per_batch_shard

            # the bias as the weight's last row
            rows = jnp.concatenate([weight.T, bias[None]]).astype(
                jnp.float32)
            return per_batch_shard(short_conv.conv_biased, rows[None], data)

        return jax.lax.platform_dependent(data, weight, bias, tpu=biased,
                                          default=_conv_xla)


register("causal_conv1d", _k_causal_conv1d,
         arg_names=("data", "weight", "bias"))


def _exact(x, y):
    return jnp.matmul(x, y, precision="highest")


def _dot(dtype):
    """Products of two operands rounded to `dtype`, accumulated in
    float32 (exact float32 products for float32 operands)."""
    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          precision="highest",
                          preferred_element_type=jnp.float32)

    return dot


def _unit_lower_inverse(a):
    """(I + a)^-1 for strictly lower triangular `a` (..., CHUNK, CHUNK),
    float32, by matrix products alone (module docstring)."""
    eye = jnp.eye(CHUNK, dtype=a.dtype)

    def nilpotent_inverse(n, index):
        # (I - n)^-1 for n^index = 0: the product of (I + n^(2^j))
        out, power, reach = eye + n, n, 2
        while reach < index:
            power = _exact(power, power)
            out = out + _exact(out, power)
            reach *= 2
        return out

    block = jnp.arange(CHUNK) // _BLOCK
    same = block[:, None] == block[None, :]
    diagonal = nilpotent_inverse(-jnp.where(same, a, 0.0), _BLOCK)
    below = _exact(diagonal, jnp.where(same, 0.0, a))
    return _exact(nilpotent_inverse(-below, CHUNK // _BLOCK), diagonal)


def _wy_prelude(q, k, v, g, beta):
    """What the forward and the backward of the intra-chunk part both
    start from, by chunk: q, k (repeated to the value heads), v as (b,
    h, n, CHUNK, size), beta and the running sum G of g inside each
    chunk as (b, h, n, CHUNK) float32, decay[i, j] = exp(G_i - G_j) for
    i >= j and 0 above the diagonal."""
    h, n = v.shape[1], v.shape[2] // CHUNK

    def chunks(x):
        return x.reshape(x.shape[:2] + (n, CHUNK) + x.shape[3:])

    q, k = (jnp.repeat(chunks(x), h // k.shape[1], axis=1) for x in (q, k))
    beta = chunks(beta.astype(jnp.float32))
    total = jnp.cumsum(chunks(g.astype(jnp.float32)), -1)
    seen = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.where(seen, jnp.exp(jnp.where(
        seen, total[..., :, None] - total[..., None, :], 0.0)), 0.0)
    return q, k, chunks(v), beta, total, decay


def _strict_lower(x):
    return jnp.where(jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1), x, 0.0)


def _wy_forward(q, k, v, g, beta):
    """The intra-chunk part in `jax.numpy` (module docstring): the six
    arrays the chunk scan consumes, the chunk axis first, and the
    inverse T (b, h, n, CHUNK, CHUNK) that the backward keeps."""
    dtype, dot = v.dtype, _dot(v.dtype)
    q, k, v, beta, total, decay = _wy_prelude(q, k, v, g, beta)
    a = _strict_lower(beta[..., None] * decay
                      * dot("...id,...jd->...ij", k, k))
    inverse = _unit_lower_inverse(a)
    into = jnp.exp(total)[..., None]            # from the chunk's start
    u = dot("...ij,...jd->...id", inverse, v * beta[..., None])
    w = dot("...ij,...jd->...id", inverse, k * (beta[..., None] * into))
    within = decay * dot("...id,...jd->...ij", q, k)
    # what each token's k d^T is worth at the chunk's end
    k_out = k * jnp.exp(total[..., -1:] - total)[..., None]
    outs = (u, w.astype(dtype), within.astype(dtype),
            (q * into).astype(dtype), k_out.astype(dtype),
            jnp.exp(total[..., -1]))
    return tuple(jnp.moveaxis(x, 2, 0) for x in outs), inverse


def _wy_backward(q, k, v, g, beta, inverse, cotangents):
    """The derivative of `_wy_forward` by its closed form (module
    docstring): nothing is differentiated through the inverse."""
    shapes = [(x.shape, x.dtype) for x in (q, k, v, g, beta)]
    hk, dot = q.shape[1], _dot(v.dtype)
    q, k, v, beta, total, decay = _wy_prelude(q, k, v, g, beta)
    du, dw, dwithin, dq_in, dk_out, dlast = (
        jnp.moveaxis(x, 0, 2).astype(jnp.float32) for x in cotangents)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    m = dot("...id,...jd->...ij", k, k)
    n = dot("...id,...jd->...ij", q, k)
    into = jnp.exp(total)[..., None]
    out = jnp.exp(total[..., -1:] - total)[..., None]
    b_col = beta[..., None]
    # u = T (beta v), w = T (beta e^G k): T's cotangent, then A's
    d_t = dot("...id,...jd->...ij", du, v32 * b_col) \
        + dot("...id,...jd->...ij", dw, k32 * (b_col * into))
    d_bv = dot("...ji,...jd->...id", inverse, du)
    d_bk = dot("...ji,...jd->...id", inverse, dw)
    transposed = jnp.swapaxes(inverse, -1, -2)
    d_a = -_strict_lower(_exact(_exact(transposed, d_t), transposed))
    d_m = d_a * b_col * decay
    d_n = dwithin * decay
    through_decay = (d_a * b_col * m + dwithin * n) * decay
    d_k = dot("...ij,...jd->...id", d_m + jnp.swapaxes(d_m, -1, -2), k) \
        + dot("...ji,...jd->...id", d_n, q)
    d_q = dot("...ij,...jd->...id", d_n, k)
    # the element-wise terms: beta v, beta e^G k, q e^G, k e^(G_C - G)
    bk_k = (d_bk * k32).sum(-1)
    at_end = (dk_out * k32).sum(-1) * out[..., 0]
    d_v = b_col * d_bv
    d_beta = (d_a * decay * m).sum(-1) + (d_bv * v32).sum(-1) \
        + into[..., 0] * bk_k
    d_k = d_k + (b_col * into) * d_bk + dk_out * out
    d_q = d_q + dq_in * into
    d_total = through_decay.sum(-1) - through_decay.sum(-2) \
        + into[..., 0] * (beta * bk_k + (dq_in * q32).sum(-1)) - at_end
    d_end = at_end.sum(-1) + dlast * jnp.exp(total[..., -1])
    # g reaches G_i of every later token of its chunk, G_C included
    d_g = jnp.flip(jnp.cumsum(jnp.flip(d_total, -1), -1), -1) \
        + d_end[..., None]
    d_q, d_k = (x.reshape((x.shape[0], hk, -1) + x.shape[2:]).sum(2)
                for x in (d_q, d_k))
    return tuple(x.reshape(shape).astype(dtype) for x, (shape, dtype)
                 in zip((d_q, d_k, d_v, d_g, d_beta), shapes))


@jax.custom_vjp
def _wy_xla(q, k, v, g, beta):
    return _wy_forward(q, k, v, g, beta)[0]


def _wy_xla_fwd(*xs):
    outs, inverse = _wy_forward(*xs)
    return outs, xs + (inverse,)


_wy_xla.defvjp(_wy_xla_fwd,
               lambda kept, cotangents: _wy_backward(*kept, cotangents))


def _scan_xla(u, w, within, q_in, k_out, last):
    """The chunk scan as a `lax.scan` that carries the float32 state
    and is differentiated by JAX: over what `_wy_xla` hands it, the
    chunk axis first; o (b, h, seq, dv) in w's dtype."""
    n, b, h, _, dv = u.shape
    dk, dtype, dot = w.shape[-1], w.dtype, _dot(w.dtype)

    def step(state, xs):
        u, w, within, q_in, k_out, last = xs
        d = u - dot("...id,...de->...ie", w, state)
        o = dot("...id,...de->...ie", q_in, state) \
            + dot("...ij,...je->...ie", within, d)
        state = state * last[..., None, None] \
            + dot("...id,...ie->...de", k_out, d)
        return state, o.astype(dtype)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32),
                        (u, w, within, q_in, k_out, last))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, n * CHUNK, dv)


def _rule_xla(q, k, v, g, beta):
    return _scan_xla(*_wy_xla(q, k, v, g, beta))


def _rule(q, k, v, g, beta):
    """The chunked rule for heads that are worked on together: q, k
    (b, key heads, seq, size), v (b, h, seq, size), g and beta (b, h,
    seq), seq a multiple of `CHUNK`.  The intra-chunk part and the
    chunk scan are the kernel pairs of ops/pallas/delta_rule.py where
    the computation is lowered for the TPU and they take the shapes,
    `_wy_xla` and `_scan_xla` everywhere else."""
    from .pallas import delta_rule

    if not delta_rule.admits(q, k, v):
        return _rule_xla(q, k, v, g, beta)

    def kernels(*xs):
        from ..parallel.mesh import per_batch_shard

        # a step that is partitioned over the batch runs them a shard
        # of it each
        return per_batch_shard(
            lambda *xs: delta_rule.scan(*delta_rule.wy(*xs)), *xs)

    return jax.lax.platform_dependent(q, k, v, g, beta, tpu=kernels,
                                      default=_rule_xla)


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
    from .pallas import delta_rule

    key = (b, h, seq, dk, dv, jnp.dtype(v.dtype).name)
    _traced[key] += 1
    groups = head_groups(b, hk, h)

    def one_group(xs):
        return _rule(*xs)

    with jax.named_scope("delta_rule"):
        pad = -seq % CHUNK
        if pad:
            # beta 0 and g 0: the state passes a padded token unchanged
            q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                       for x in (q, k, v))
            g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
                       for x in (g, beta))
        _kernel_traced[key] += delta_rule.admits(q, k, v)
        _scan_kernel_traced[key] += delta_rule.admits(q, k, v)
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
    `layers`: the distinct shapes; `traces`, `kernel_traces` (of shapes
    the intra-chunk kernels take), `scan_kernel_traces` (whose chunk
    scan takes the scan's kernel pair), `xla_traces` (of neither),
    `chunk`, `chunks_per_sequence` and `state_bytes_kept` (the float32
    states at the chunks' starts that the scan's derivative keeps for
    the layer being differentiated) by shape; and the short
    convolutions traced, by their own shape: `conv_kernel_traces` (of
    shapes the conv kernels take) and `conv_xla_traces`."""
    out = {"layers": len(_traced), "traces": {}, "kernel_traces": {},
           "scan_kernel_traces": {}, "xla_traces": {}, "chunk": {},
           "chunks_per_sequence": {}, "state_bytes_kept": {},
           "conv_kernel_traces": {}, "conv_xla_traces": {}}
    for shape, n in _conv_traced.items():
        key = "b{} s{} c{} t{} {}".format(*shape)
        out["conv_kernel_traces"][key] = _conv_kernel_traced[shape]
        out["conv_xla_traces"][key] = n - _conv_kernel_traced[shape]
    for shape, n in _traced.items():
        b, h, seq, dk, dv, dtype = shape
        key = f"b{b} h{h} s{seq} k{dk} v{dv} {dtype}"
        chunks = -(-seq // CHUNK)
        out["traces"][key] = n
        out["kernel_traces"][key] = _kernel_traced[shape]
        out["scan_kernel_traces"][key] = _scan_kernel_traced[shape]
        out["xla_traces"][key] = n - _kernel_traced[shape]
        out["chunk"][key] = CHUNK
        out["chunks_per_sequence"][key] = chunks
        out["state_bytes_kept"][key] = chunks * b * h * dk * dv * 4
    return out


def reset_linear_attention_stats():
    _traced.clear()
    _kernel_traced.clear()
    _scan_kernel_traced.clear()
    _conv_traced.clear()
    _conv_kernel_traced.clear()


def _stats_table(stats):
    out = ["Linear Attention (delta rules traced):"]
    for key in sorted(stats["traces"]):
        out.append(f"  {key}: x{stats['traces'][key]} "
                   f"({stats['kernel_traces'][key]} of shapes the kernels "
                   f"take, {stats['scan_kernel_traces'][key]} with the "
                   f"scan's), "
                   f"{stats['chunks_per_sequence'][key]} chunks of "
                   f"{stats['chunk'][key]}, keeps "
                   f"{stats['state_bytes_kept'][key]} bytes of states")
    for key in sorted(stats["conv_kernel_traces"]):
        out.append(f"  conv {key}: {stats['conv_kernel_traces'][key]} of "
                   f"shapes the kernels take, "
                   f"{stats['conv_xla_traces'][key]} not")
    return out


profiler.register_section("linearAttention", linear_attention_stats,
                          reset_linear_attention_stats, _stats_table)
