"""The state-space dual (SSD) scan of a Mamba-2 mixer.

For one head with state S (head size p x state size n), S_0 = 0, the
head's decay A < 0, a step size dt_t > 0 a token, and B_t, C_t (n) of
the head's group (head h reads group h // (heads / groups)):

    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

`ssd` computes it in the CHUNKED form of Mamba-2's `ssd_minimal`: the
sequence in chunks of `chunk` tokens, G_i the running sum of dt A
inside a chunk (float32), and

    inside a chunk   y_i += sum_(j <= i) exp(G_i - G_j) (C_i . B_j) dt_j x_j
    a chunk's state  s_c = sum_j exp(G_T - G_j) dt_j x_j B_j^T
    across chunks    S_c = sum_(c' < c) exp(H_(c-1) - H_c') s_c'
    from the state   y_i += exp(G_i) S_c C_i

with H_c the running sum of the chunks' totals G_T.  C . B is made once
a GROUP, not a head; the state passes across the chunks as ONE product
with the (chunks x chunks) decay matrix, no loop.  Every exponent formed
is a difference G_i - G_j <= 0 (or G_i itself, from the chunk's start),
so nothing overflows whatever the decay.  Products take their operands
in x's dtype and accumulate in float32 (`precision="highest"`: exact
float32 products for float32 operands); the decays, the states, the
skip and every sum are float32, and the across-chunk product is float32
throughout.  A length that is no multiple of the chunk is padded with
tokens of dt 0, which leave the state as it is and whose outputs are
dropped.

Where the computation is lowered for the TPU (`lax.platform_dependent`)
and the shapes allow it (heads of 64, two to a 128-lane unit, or of a
multiple of 128; a group's heads on whole lanes; a state size of whole
lanes; chunks of 128; bf16 or float32: `ssd.admits`), the scan is the
kernel pair of ops/pallas/ssd.py, with its own `jax.custom_vjp`: a
group's float32 states stay in VMEM across the chunks, and the decays
and C B^T of a chunk are made in VMEM and never written to HBM.
Everywhere else, and on every other backend, it is `_ssd_chunked`
below, the oracle, in `jax.numpy`.

Memory.  Both forms keep their inputs alone from the pass to the
backward.  The `jax.numpy` form is one `jax.checkpoint`: its backward
recomputes its forward and holds the float32 states at the chunks'
starts (`state_bytes_kept`: chunks x batch x heads x p x n x 4 bytes,
134 MB for 8,192 tokens and 64 heads of 64 x 128) and the decays inside
each chunk (batch x heads x seq x chunk x 4, 268 MB there).  The
kernels' backward runs a forward that writes the same states, and
nothing of chunk x chunk.  The output is NAMED (`RESIDUAL_NAMES`): a
`jax.checkpoint` whose policy saves it (`DataParallelTrainer(remat=
True)`) recomputes what follows the scan without running the scan
again, so a step runs the scan's forward twice (the pass, and inside
its own backward), not three times.

Every scan traced is counted (the profiler section `stateSpace`), and
those of shapes the kernels take.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import profiler
from . import registry
from .registry import register

# the name the scan gives its output: a `jax.checkpoint` policy that
# saves it keeps the scan out of the recomputation of what follows it
RESIDUAL_NAMES = registry.RESIDUAL_NAMES["ssd"]

# every scan traced: (batch, heads, seq, head size, groups, state size,
# chunk, dtype) -> traces; and those of shapes the kernels take
_traced = collections.Counter()
_kernel_traced = collections.Counter()


def _dot(dtype):
    """Products of two operands rounded to `dtype`, accumulated in
    float32."""
    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          precision="highest",
                          preferred_element_type=jnp.float32)

    return dot


def _lower_decay(total, size):
    """exp(total_i - total_j) for i >= j, 0 above the diagonal, over the
    last axis of `total` (..., size) -> (..., size, size), float32."""
    seen = jnp.tril(jnp.ones((size, size), bool))
    return jnp.where(seen, jnp.exp(jnp.where(
        seen, total[..., :, None] - total[..., None, :], 0.0)), 0.0)


def _ssd_chunked(x, dt, a, b, c, d, chunk):
    """The chunked scan (module docstring) over a sequence that is a
    whole number of chunks: x (batch, seq, heads, p), dt (batch, seq,
    heads) float32, a and d (heads,) float32, b and c (batch, seq,
    groups, n).  y (batch, seq, heads, p) in x's dtype."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, chunks, dtype = heads // groups, seq // chunk, x.dtype
    dot = _dot(dtype)
    f32 = jnp.float32
    # (batch, chunks, chunk, groups, heads of a group, ...)
    xc = x.reshape(batch, chunks, chunk, groups, r, p)
    bc = b.reshape(batch, chunks, chunk, groups, n)
    cc = c.reshape(batch, chunks, chunk, groups, n)
    dtc = dt.astype(f32).reshape(batch, chunks, chunk, groups, r)
    total = jnp.cumsum(dtc * a.astype(f32).reshape(groups, r), axis=2)
    # the running sum with the chunk's tokens last: (b, chunks, g, r, T)
    along = jnp.moveaxis(total, 2, -1)
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)
    # inside a chunk: (C_i . B_j) once a group, times each head's decay
    cb = dot("bcign,bcjgn->bcgij", cc, bc)
    within = _lower_decay(along, chunk) * cb[:, :, :, None]
    y = dot("bcgrij,bcjgrp->bcigrp", within, xdt)
    # each chunk's own state, what its tokens leave at its end
    to_end = jnp.moveaxis(jnp.exp(along[..., -1:] - along), -1, 2)
    states = dot("bcjgrp,bcjgn->bcgrpn",
                 xdt.astype(f32) * to_end[..., None], bc)
    # across the chunks: the state entering chunk i, one product
    ends = jnp.cumsum(along[..., -1], axis=1)            # (b, chunks, g, r)
    starts = ends - along[..., -1]
    strict = jnp.tril(jnp.ones((chunks, chunks), bool), -1)[
        None, :, :, None, None]
    across = jnp.where(strict, jnp.exp(jnp.where(
        strict, starts[:, :, None] - ends[:, None, :], 0.0)), 0.0)
    entering = jnp.einsum("bijgr,bjgrpn->bigrpn", across, states,
                          precision="highest")
    # from the entering state, decayed to each token
    y = y + dot("bcign,bcgrpn->bcigrp", cc, entering) \
        * jnp.exp(total)[..., None]
    y = y + d.astype(f32).reshape(groups, r)[:, :, None] * xc.astype(f32)
    return y.reshape(batch, seq, heads, p).astype(dtype)


def _scan(x, dt, a, b, c, d, chunk):
    """The chunked scan over whole chunks: the kernel pair of
    ops/pallas/ssd.py where the computation is lowered for the TPU and
    it takes the shapes, `_ssd_chunked` (one `jax.checkpoint`)
    everywhere else."""
    from .pallas import ssd

    def oracle(x, dt, a, b, c, d):
        return jax.checkpoint(_ssd_chunked, static_argnums=(6,))(
            x, dt, a, b, c, d, chunk)

    if not ssd.admits(x, b, chunk):
        return oracle(x, dt, a, b, c, d)

    def kernels(x, dt, a, b, c, d):
        from ..parallel.mesh import per_batch_shard

        # a step that is partitioned over the batch runs them a shard
        # of it each; a and d go as rows, shared by the shards
        return per_batch_shard(
            lambda x, dt, a, b, c, d: ssd.scan(x, dt, a[0], b, c, d[0],
                                               chunk),
            x, dt, a[None], b, c, d[None])

    return jax.lax.platform_dependent(x, dt, a, b, c, d, tpu=kernels,
                                      default=oracle)


def _k_ssd(x, dt, a, b, c, d, *, chunk=128):
    """The Mamba-2 state-space scan (module docstring) in its chunked
    form.

    x: (batch, seq, heads, head size); dt (the step size after its
    softplus, > 0): (batch, seq, heads); a (the decay, < 0) and d (the
    skip): (heads,); b, c: (batch, seq, groups, state size), heads a
    multiple of the groups (group j serves heads j * r ... j * r + r -
    1).  Returns y, (batch, seq, heads, head size) in x's dtype, named
    `ssd_out` for a `jax.checkpoint` policy."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"ssd: {heads} heads over {groups} groups")
    from .pallas import ssd

    key = (batch, heads, seq, p, groups, n, chunk, jnp.dtype(x.dtype).name)
    _traced[key] += 1
    with jax.named_scope("ssd"):
        pad = -seq % chunk
        if pad:
            # dt 0: the state passes a padded token unchanged
            x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for v in (x, b, c))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        _kernel_traced[key] += ssd.admits(x, b, chunk)
        y = _scan(x, dt, a, b, c, d, chunk)
        return checkpoint_name(y[:, :seq], RESIDUAL_NAMES[0])


register("ssd", _k_ssd, arg_names=("x", "dt", "a", "b", "c", "d"))


def state_space_stats():
    """The `stateSpace` profiler section: the scans traced since the
    last reset (a trace a layer's pass, as `linearAttention` counts).
    `layers`: the distinct shapes; by shape, `traces`, `kernel_traces`
    (of shapes the kernels take: on the TPU they run there),
    `xla_traces` (of the others, the `jax.numpy` form everywhere),
    `chunk`, `chunks_per_sequence`, `groups` and `state_bytes_kept` (the
    float32 states at the chunks' starts that the scan's backward
    holds)."""
    out = {"layers": len(_traced), "traces": {}, "kernel_traces": {},
           "xla_traces": {}, "chunk": {}, "chunks_per_sequence": {},
           "groups": {}, "state_bytes_kept": {}}
    for shape, count in _traced.items():
        b, h, seq, p, g, n, chunk, dtype = shape
        key = f"b{b} h{h} s{seq} p{p} g{g} n{n} {dtype}"
        chunks = -(-seq // chunk)
        out["traces"][key] = count
        out["kernel_traces"][key] = _kernel_traced[shape]
        out["xla_traces"][key] = count - _kernel_traced[shape]
        out["chunk"][key] = chunk
        out["chunks_per_sequence"][key] = chunks
        out["groups"][key] = g
        out["state_bytes_kept"][key] = chunks * b * h * p * n * 4
    return out


def reset_state_space_stats():
    _traced.clear()
    _kernel_traced.clear()


def _stats_table(stats):
    out = ["State Space (SSD scans traced):"]
    for key in sorted(stats["traces"]):
        out.append(f"  {key}: x{stats['traces'][key]} "
                   f"({stats['kernel_traces'][key]} of shapes the kernels "
                   f"take, {stats['xla_traces'][key]} in jax.numpy), "
                   f"{stats['chunks_per_sequence'][key]} chunks of "
                   f"{stats['chunk'][key]}, {stats['groups'][key]} groups, "
                   f"keeps {stats['state_bytes_kept'][key]} bytes of states")
    return out


profiler.register_section("stateSpace", state_space_stats,
                          reset_state_space_stats, _stats_table)
