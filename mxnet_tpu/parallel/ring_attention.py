"""Ring attention: sequence/context parallelism over the ICI ring.

Ref capability: ABSENT in the reference (SURVEY §2.3 'SP/CP/ring-
attention: ABSENT — reference predates long-context'); this is the
capability upgrade the build plan calls for ('ring attention over ICI
via Pallas... beyond reference parity').

Design: q,k,v sharded over the 'sp' mesh axis along the sequence dim
inside shard_map.  Each of the P steps computes blockwise attention of
the local q shard against the currently-held k/v shard, merging with the
online-softmax (m, l, acc) recurrence, then rotates k/v around the ring
with ppermute — compute overlaps the ICI transfer since XLA pipelines
the collective-permute with the matmuls.  Per-device memory stays
O(seq/P); the full score matrix never exists.

The 'sp' axis is a sibling of the trainer mesh's named axes
(docs/parallelism.md): build a combined mesh with
``parallel.spmd.make_spmd_mesh``/``parallel.mesh.make_mesh`` and run
this kernel inside the step's shard_map; ``parallel.ulysses`` is the
all-to-all alternative for head-rich models.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_NEG_INF = -1e9


def _block_attend(q, k, v, scale, q_offset, k_offset, causal):
    """Scores of local q against one k/v shard, with global positions."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = q_offset + jnp.arange(sq)[:, None]
        k_pos = k_offset + jnp.arange(sk)[None, :]
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m_cur)
    l_cur = jnp.sum(p, axis=-1, keepdims=True)
    o_cur = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_cur, l_cur, o_cur


def ring_attention_sharded(q, k, v, axis_name, *, causal=False, scale=None):
    """Run INSIDE shard_map: q,k,v are per-device sequence shards
    (batch, heads, seq/P, d); returns the local output shard."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    p_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    sq = q.shape[2]

    m = jnp.full(q.shape[:3] + (1,), _NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)
    # mark the init carry as varying over the ring axis (shard_map vma
    # check: outputs of the loop body vary over 'sp')
    m, l, acc = jax.lax.pcast((m, l, acc), axis_name, to="varying")

    def step(i, carry):
        m_prev, l_prev, acc_prev, k_cur, v_cur = carry
        # with the j->j+1 rotation below, after i hops device j holds the
        # shard that originated on device (j - i) mod P
        src = (my_idx - i) % p_size
        m_cur, l_cur, o_cur = _block_attend(
            q, k_cur, v_cur, s, my_idx * sq, src * sq, causal)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha_p = jnp.exp(m_prev - m_new)
        alpha_c = jnp.exp(m_cur - m_new)
        l_new = alpha_p * l_prev + alpha_c * l_cur
        acc_new = acc_prev * alpha_p + o_cur * alpha_c
        # rotate k/v one hop around the ring (ICI neighbour exchange)
        perm = [(j, (j + 1) % p_size) for j in range(p_size)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return m_new, l_new, acc_new, k_next, v_next

    m, l, acc, _, _ = jax.lax.fori_loop(
        0, p_size, step, (m, l, acc, k, v))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def attention_spmd_jit(sharded_fn, mesh, axis, causal, scale):
    """Thin wrapper over mesh.spmd_jit for sequence-parallel attention
    (ring + ulysses share it): q,k,v rank-4 (B, H, S, D) sharded on the
    sequence dim over `axis`.  `scale` is coerced to a hashable float so
    array scalars work as cache keys."""
    from jax.sharding import PartitionSpec

    from . import mesh as mesh_mod

    spec = PartitionSpec(None, None, axis, None)
    return mesh_mod.spmd_jit(
        sharded_fn, mesh, (spec, spec, spec), spec,
        axis_name=axis, causal=causal,
        scale=float(scale) if scale is not None else None)


def ring_attention(q, k, v, mesh=None, axis="sp", causal=False, scale=None):
    """Host-level entry: shards (batch, heads, seq, d) over `axis` of the
    mesh and runs the ring. Accepts NDArray or jax arrays."""
    from ..ndarray.ndarray import NDArray, _wrap
    from . import mesh as mesh_mod

    unwrap = isinstance(q, NDArray)
    if unwrap:
        q, k, v = q._data, k._data, v._data
    if mesh is None:
        mesh = mesh_mod.make_mesh({axis: len(jax.devices())})
    out = attention_spmd_jit(
        ring_attention_sharded, mesh, axis, causal, scale)(q, k, v)
    return _wrap(out) if unwrap else out
