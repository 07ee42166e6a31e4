"""Flash attention Pallas kernels (forward + backward) for TPU.

Ref capability: the reference has NO fused attention op (SURVEY §2.2
"no fused attention op in this era") — transformers are composed from
batch_dot + softmax, materializing the (S,S) score matrix in HBM.  This
kernel is the capability upgrade the survey prescribes: online-softmax
blockwise attention that keeps scores in VMEM, MXU-aligned 128-tiles.

Both directions are Pallas kernels, three an attention.  Forward saves
the per-row log-sum-exp; backward recomputes P blockwise from (q, k,
lse) — the standard flash-attention-2 scheme: one kernel accumulates dQ
over k-blocks, a second accumulates dK/dV over q-blocks.

The RESIDENT kernels (K/V whole in VMEM) work on a GROUP of heads a
grid step: blocks (heads, 128, d) and (heads, seq, d) cut by the
BlockSpec from the same (b*h, seq, d) operands, a grid of
(b*h // heads, blocks), the same arithmetic head after head inside.
At a short sequence one head's tile is 40 ns of MXU work, and a grid
step a head is the price of starting 1536 steps and waiting on their
16 KB DMAs; `_heads_per_step` takes the largest divisor of the head
count whose blocks fit `_GROUP_VMEM_BYTES` — 12 at BERT-base's (128,
12, 128, 64) bf16, 6 at sequence 512, 1 where one head's K/V fill the
budget — from the operands' shapes and item size alone.  The row
statistics travel lane-dense, (b*h // heads, heads, seq) float32 with
the sequence along the lanes: lse from the forward, and delta =
rowsum(dO * O), which the dQ kernel makes from the O tile and hands
to the dK/dV kernel.  (A (b*h, seq, 1) column array is stored (8,
128)-tiled, one lane in 128: 100 MB an array at BERT's shape, and the
DMA of a 128 x 128 tile a head.)  Each kernel turns lanes into
sublanes or back with ONE transpose a grid step; the dK/dV kernel
works on the transposed scores, where lane-dense rows broadcast as
they are.  The STREAMED kernels (K/V swept by a third grid dimension,
past `MXTPU_FLASH_MAX_KV_VMEM_MB`) keep a head a step.

Every kernel built while a program is traced is counted
(`flash_attention_stats`, the profiler section `flashAttention`).

Falls back transparently when seq/head dims don't tile (caller guards).
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call

_NEG_INF = -1e9
_LANES = 128
# VMEM the blocks of one grid step of the resident kernels may take,
# the pipeline's second buffer of each included (_heads_per_step); a
# quarter of the 16 MB a kernel may use on a v5e-class core, which
# leaves the rest to the float32 intermediates of the unrolled heads
_GROUP_VMEM_BYTES = 4 * 2 ** 20


# every kernel built while a program was traced: (variant, kernel, (b, h,
# sq, sk, d), dtype, heads a grid step, grid) -> how many times.  Counted
# when a program is TRACED, so a step that runs costs nothing; the
# profiler section `flashAttention` reads it.  The keys say how the
# kernels engaged; the counts are traces (a primal trace that
# differentiation replaces counts, a layer whose jaxpr JAX reuses does
# not), not the instances in a compiled program.
_built = collections.Counter()


def _record_built(variant, kernel, q, sk, heads, grid):
    b, h, sq, d = q.shape
    _built[(variant, kernel, (b, h, sq, sk, d), q.dtype.name, heads,
            tuple(grid))] += 1


def flash_attention_stats():
    """The `flashAttention` profiler section: how the kernels engaged
    in the programs traced since the last reset.  `built` has a row for
    each distinct kernel, named by its variant (resident / streamed),
    which of the three it is, its shapes, the heads a grid step works
    on and the grid."""
    built = {}
    for (variant, kernel, shape, dtype, heads, grid), n in _built.items():
        dims = " ".join(f"{k}{v}" for k, v in zip(
            ("b", "h", "sq", "sk", "d"), shape))
        built[f"{variant} {kernel} {dims} {dtype} heads{heads} "
              f"grid{'x'.join(map(str, grid))}"] = n

    def count(variant):
        return sum(n for key, n in _built.items() if key[0] == variant)

    return {"kernels": sum(_built.values()),
            "resident": count("resident"), "streamed": count("streamed"),
            "built": built}


def reset_flash_attention_stats():
    _built.clear()


def _heads_per_step(h, sq, sk, d, itemsize, block=128):
    """How many heads one grid step of the resident kernels works on:
    the largest divisor of `h` whose blocks fit `_GROUP_VMEM_BYTES`.

    One head's blocks, each held twice by the pipeline: four of
    (block, d) -- q, dO, O, dQ in the dQ kernel; k, v, dK, dV in the
    dK/dV kernel -- and up to three of (seq, d) resident beside them
    (k, v; q, dO, O), the forward needing less than either.  A divisor
    of `h` keeps a group inside one batch row, so the key-padding
    mask's block is one row a step whatever the batch; where one
    head's blocks already fill the budget (long K/V, a large head) the
    answer is 1, a grid step a head."""
    per_head = 2 * itemsize * d * (4 * block + 3 * max(sq, sk))
    fit = min(max(_GROUP_VMEM_BYTES // per_head, 1), _LANES)
    return max(g for g in range(1, h + 1) if h % g == 0 and g <= fit)


def _lanes_to_rows(cols, heads):
    """(rows, 128) with head g's row statistic in lane g -> (heads,
    rows), the statistic along the lanes: one transpose a grid step."""
    return cols.T[:heads]


def _flash_fwd_kernel(*refs, block_k, causal, scale, seq_k, has_mask):
    # one grid step works on a GROUP of heads of one batch row: q / o
    # blocks are (heads, block_q, d), k / v (heads, seq_k, d); with
    # has_mask the batch row's additive key-padding row (1, 1, seq_k)
    # rides along, shared by the group.  The per-row log-sum-exp leaves
    # lane-dense, (1, heads, block_q) of a (b*h // heads, heads, sq)
    # array: a (block_q, 1) column a head would be stored (8, 128)-
    # tiled, one lane in 128, and cost the DMA of a 128 x 128 tile.
    # Each head's column goes into its lane of one (block_q, 128) value
    # and a single transpose a step turns lanes into rows.
    if has_mask:
        q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        km_ref = None
    heads, block_q, d = q_ref.shape
    qi = pl.program_id(1)  # q-block index
    num_kb = seq_k // block_k
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, _LANES), 1)
    lse_cols = jnp.zeros((block_q, _LANES), jnp.float32)

    for g in range(heads):
        q = q_ref[g] * scale
        m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc0 = jnp.zeros((block_q, d), jnp.float32)

        def body(kb, carry, g=g, q=q):
            m_prev, l_prev, acc = carry
            k = k_ref[g, pl.ds(kb * block_k, block_k), :]
            v = v_ref[g, pl.ds(kb * block_k, block_k), :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            if km_ref is not None:
                s = s + km_ref[0, 0, pl.ds(kb * block_k, block_k)][None, :]
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        if causal:
            # only k-blocks at or before this q-block contribute
            max_kb = jnp.minimum(
                ((qi + 1) * block_q + block_k - 1) // block_k, num_kb)
            m, l, acc = jax.lax.fori_loop(0, max_kb, body, (m0, l0, acc0))
        else:
            m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
        l = jnp.maximum(l, 1e-30)
        o_ref[g] = (acc / l).astype(o_ref.dtype)
        lse_cols = jnp.where(lane == g, m + jnp.log(l), lse_cols)
    lse_ref[0] = _lanes_to_rows(lse_cols, heads)


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _km_spec(h, heads, sk):
    """BlockSpec mapping the grid's (b*h // heads) dim onto the original
    (b, 1, sk) mask: a group of heads lies within one batch row, and no
    h-fold HBM copy of the mask is ever made."""
    groups = h // heads
    return _vmem((1, 1, sk), lambda i, j: (i // groups, 0, 0))


# ---------------------------------------------------------------------------
# streamed variant: K/V swept by a third grid dimension instead of
# resident in VMEM — the long-KV path past the _tiles_ok VMEM bound.
# Pallas TPU iterates the LAST grid dim innermost and sequentially and
# scratch persists across grid steps, so the online-softmax state
# (m, l, acc) carries across k-blocks; outputs are flushed on the
# final k-block (same scheme as jax's reference TPU flash kernels).
# ---------------------------------------------------------------------------


def _flash_fwd_stream_kernel(*refs, causal, scale, has_mask, num_kb):
    if has_mask:
        (q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        km_ref = None
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: a k-block strictly above the diagonal contributes nothing
    live = (kb * block_k <= qi * block_q + block_q - 1) if causal \
        else (kb >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0] * scale
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if km_ref is not None:
            s = s + km_ref[0, 0, :][None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[:]
        l_prev = l_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _flush():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _flash_forward_stream(q, k, v, *, causal, scale, kmask=None,
                          block_q=128, block_k=128):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)
    num_kb = sk // block_k

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q3, k3, v3]
    if kmask is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda i, j, kk: (i // h, 0, kk),
            memory_space=pltpu.VMEM))
        args.append(kmask.astype(jnp.float32).reshape(b, 1, sk))

    grid = (bh, sq // block_q, num_kb)
    _record_built("streamed", "fwd", q, sk, 1, grid)
    out, lse = pallas_call(
        functools.partial(_flash_fwd_stream_kernel, causal=causal,
                          scale=scale, has_mask=kmask is not None,
                          num_kb=num_kb),
        grid=grid,
        in_specs=in_specs,
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ),
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )(*args)
    return out.reshape(b, h, sq, d), lse


def _flash_forward(q, k, v, *, causal, scale, kmask=None,
                   block_q=128, block_k=128):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    heads = _heads_per_step(h, sq, sk, d, q.dtype.itemsize,
                            max(block_q, block_k))
    # the first operand stays the (b*h, sq, d) query: the group is cut
    # by the BlockSpec, not by a reshape in front of the call
    q3 = q.reshape(bh, sq, d)
    k3 = k.reshape(bh, sk, d)
    v3 = v.reshape(bh, sk, d)

    tile = _vmem((heads, block_q, d), lambda i, j: (i, j, 0))
    full = _vmem((heads, sk, d), lambda i, j: (i, 0, 0))
    in_specs = [tile, full, full]
    args = [q3, k3, v3]
    if kmask is not None:
        in_specs.append(_km_spec(h, heads, sk))
        args.append(kmask.astype(jnp.float32).reshape(b, 1, sk))

    grid = (bh // heads, sq // block_q)
    _record_built("resident", "fwd", q, sk, heads, grid)
    out, lse = pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k,
                          causal=causal, scale=scale, seq_k=sk,
                          has_mask=kmask is not None),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh // heads, heads, sq), jnp.float32),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(tile,
                   _vmem((1, heads, block_q), lambda i, j: (i, 0, j))),
    )(*args)
    return out.reshape(b, h, sq, d), lse


def _flash_dq_stream_kernel(*refs, causal, scale, has_mask, num_kb):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        km_ref = None
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (kb * block_k <= qi * block_q + block_q - 1) if causal \
        else (kb >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if km_ref is not None:
            s = s + km_ref[0, 0, :][None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _flush():
        dq_ref[0] = (scale * dq_scr[:]).astype(dq_ref.dtype)


def _flash_dkv_stream_kernel(*refs, causal, scale, has_mask, num_qb):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        km_ref = None
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    ki = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: q-blocks entirely before this k-block see none of it
    live = (qb * block_q + block_q - 1 >= ki * block_k) if causal \
        else (qb >= 0)

    @pl.when(live)
    def _step():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if km_ref is not None:
            s = s + km_ref[0, 0, :][None, :]
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[:] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qb == num_qb - 1)
    def _flush():
        dk_ref[0] = (scale * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward_stream(q, k, v, o, lse, do, *, causal, scale,
                           kmask=None, block_q=128, block_k=128):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    q3, k3, v3 = (t.reshape(bh, -1, d) for t in (q, k, v))
    o3 = o.reshape(bh, sq, d)
    do3 = do.reshape(bh, sq, d)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)
    num_kb = sk // block_k
    num_qb = sq // block_q
    has_mask = kmask is not None
    km3 = (kmask.astype(jnp.float32).reshape(b, 1, sk)
           if has_mask else None)

    def _km_blk(i, j, kk):
        return (i // h, 0, kk)

    q_blk = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM)
    k_blk = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, kk, 0),
                         memory_space=pltpu.VMEM)
    r_blk = pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, j, 0),
                         memory_space=pltpu.VMEM)

    dq_specs = [q_blk, k_blk, k_blk, q_blk, r_blk, r_blk]
    dq_args = [q3, k3, v3, do3, lse, delta]
    if has_mask:
        dq_specs.append(pl.BlockSpec((1, 1, block_k), _km_blk,
                                     memory_space=pltpu.VMEM))
        dq_args.append(km3)
    grid = (bh, num_qb, num_kb)
    _record_built("streamed", "dq", q, sk, 1, grid)
    dq = pallas_call(
        functools.partial(_flash_dq_stream_kernel, causal=causal,
                          scale=scale, has_mask=has_mask,
                          num_kb=num_kb),
        grid=grid,
        in_specs=dq_specs,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        out_specs=q_blk,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(*dq_args)

    # dkv grid: (bh, k_blocks, q_blocks) — q swept innermost
    qk_blk = pl.BlockSpec((1, block_q, d), lambda i, j, kk: (i, kk, 0),
                          memory_space=pltpu.VMEM)
    kk_blk = pl.BlockSpec((1, block_k, d), lambda i, j, kk: (i, j, 0),
                          memory_space=pltpu.VMEM)
    rr_blk = pl.BlockSpec((1, block_q, 1), lambda i, j, kk: (i, kk, 0),
                          memory_space=pltpu.VMEM)
    dkv_specs = [qk_blk, kk_blk, kk_blk, qk_blk, rr_blk, rr_blk]
    dkv_args = [q3, k3, v3, do3, lse, delta]
    if has_mask:
        dkv_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda i, j, kk: (i // h, 0, j),
            memory_space=pltpu.VMEM))
        dkv_args.append(km3)
    grid = (bh, num_kb, num_qb)
    _record_built("streamed", "dkv", q, sk, 1, grid)
    dk, dv = pallas_call(
        functools.partial(_flash_dkv_stream_kernel, causal=causal,
                          scale=scale, has_mask=has_mask,
                          num_qb=num_qb),
        grid=grid,
        in_specs=dkv_specs,
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ),
        out_specs=(kk_blk, kk_blk),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )(*dkv_args)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _flash_dq_kernel(*refs, block_k, causal, scale, seq_k, has_mask):
    # a group of heads a step, as the forward; lse arrives lane-dense
    # (1, heads, block_q) and one transpose a step gives every head its
    # (block_q, 1) column.  delta = rowsum(dO * O) is made here from the
    # O tile (no XLA pass writes it, no padded (b*h, sq, 1) array holds
    # it) and leaves lane-dense for the dK/dV kernel.
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, km_ref,
         dq_ref, delta_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, delta_ref) = refs
        km_ref = None
    heads, block_q, d = q_ref.shape
    qi = pl.program_id(1)
    num_kb = seq_k // block_k
    lse_cols = lse_ref[0].T           # (block_q, heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, _LANES), 1)
    delta_cols = jnp.zeros((block_q, _LANES), jnp.float32)

    for g in range(heads):
        q = q_ref[g].astype(jnp.float32)
        do = do_ref[g].astype(jnp.float32)
        lse = lse_cols[:, g:g + 1]    # (block_q, 1)
        delta = jnp.sum(do * o_ref[g].astype(jnp.float32), axis=-1,
                        keepdims=True)
        dq0 = jnp.zeros((block_q, d), jnp.float32)

        def body(kb, dq, g=g, q=q, do=do, lse=lse, delta=delta):
            k = k_ref[g, pl.ds(kb * block_k, block_k), :] \
                .astype(jnp.float32)
            v = v_ref[g, pl.ds(kb * block_k, block_k), :] \
                .astype(jnp.float32)
            s = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            if km_ref is not None:
                s = s + km_ref[0, 0, pl.ds(kb * block_k, block_k)][None, :]
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            p = jnp.exp(s - lse)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        if causal:
            max_kb = jnp.minimum(
                ((qi + 1) * block_q + block_k - 1) // block_k, num_kb)
            dq = jax.lax.fori_loop(0, max_kb, body, dq0)
        else:
            dq = jax.lax.fori_loop(0, num_kb, body, dq0)
        dq_ref[g] = (scale * dq).astype(dq_ref.dtype)
        delta_cols = jnp.where(lane == g, delta, delta_cols)
    delta_ref[0] = _lanes_to_rows(delta_cols, heads)


def _flash_dkv_kernel(*refs, block_q, causal, scale, seq_q, has_mask):
    # works on the TRANSPOSED scores, (block_k, block_q): the keys of
    # this tile down the sublanes, the queries along the lanes.  The
    # lane-dense lse and delta rows then broadcast down the sublanes as
    # they are, and dV = P^T dO, dK = dS^T Q are plain products of the
    # transposed P and dS; every dot contracts the same elements in
    # float32 as the (block_q, block_k) form did.
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, km_ref,
         dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref) = refs
        km_ref = None
    heads, block_k, d = k_ref.shape
    ki = pl.program_id(1)
    num_qb = seq_q // block_q
    nt = (((1,), (1,)), ((), ()))     # a @ b.T
    # this k-block's additive mask as a column: constant across
    # q-blocks and heads
    km_col = (km_ref[0, :, pl.ds(ki * block_k, block_k)].T
              if km_ref is not None else None)

    for g in range(heads):
        k = k_ref[g].astype(jnp.float32)
        v = v_ref[g].astype(jnp.float32)
        dk0 = jnp.zeros((block_k, d), jnp.float32)
        dv0 = jnp.zeros((block_k, d), jnp.float32)

        def body(qb, carry, g=g, k=k, v=v):
            dk, dv = carry
            rows = pl.ds(qb * block_q, block_q)
            q = q_ref[g, rows, :].astype(jnp.float32)
            do = do_ref[g, rows, :].astype(jnp.float32)
            lse = lse_ref[0, g:g + 1, rows]        # (1, block_q)
            delta = delta_ref[0, g:g + 1, rows]
            st = scale * jax.lax.dot_general(
                k, q, nt, preferred_element_type=jnp.float32)
            if km_col is not None:
                st = st + km_col
            if causal:
                k_pos = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                q_pos = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                st = jnp.where(q_pos >= k_pos, st, _NEG_INF)
            pt = jnp.exp(st - lse)
            dv = dv + jnp.dot(pt, do, preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v, do, nt, preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta)
            dk = dk + jnp.dot(dst, q, preferred_element_type=jnp.float32)
            return dk, dv

        if causal:
            # q-blocks strictly before this k-block see nothing
            min_qb = (ki * block_k) // block_q
            dk, dv = jax.lax.fori_loop(min_qb, num_qb, body, (dk0, dv0))
        else:
            dk, dv = jax.lax.fori_loop(0, num_qb, body, (dk0, dv0))
        dk_ref[g] = (scale * dk).astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, *, causal, scale, kmask=None,
                    block_q=128, block_k=128):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bh = b * h
    heads = lse.shape[1]              # as the forward grouped them
    q3, k3, v3 = (t.reshape(bh, -1, d) for t in (q, k, v))
    o3 = o.reshape(bh, sq, d)
    do3 = do.reshape(bh, sq, d)

    has_mask = kmask is not None
    km3 = (kmask.astype(jnp.float32).reshape(b, 1, sk)
           if has_mask else None)
    km_spec = _km_spec(h, heads, sk)
    rows = jax.ShapeDtypeStruct((bh // heads, heads, sq), jnp.float32)

    def tile(block):
        return _vmem((heads, block, d), lambda i, j: (i, j, 0))

    def full(seq):
        return _vmem((heads, seq, d), lambda i, j: (i, 0, 0))

    q_rows = _vmem((1, heads, block_q), lambda i, j: (i, 0, j))
    dq_specs = [tile(block_q), full(sk), full(sk), tile(block_q),
                tile(block_q), q_rows]
    dq_args = [q3, k3, v3, do3, o3, lse]
    if has_mask:
        dq_specs.append(km_spec)
        dq_args.append(km3)

    grid = (bh // heads, sq // block_q)
    _record_built("resident", "dq", q, sk, heads, grid)
    dq, delta = pallas_call(
        functools.partial(_flash_dq_kernel, block_k=block_k,
                          causal=causal, scale=scale, seq_k=sk,
                          has_mask=has_mask),
        out_shape=(jax.ShapeDtypeStruct((bh, sq, d), q.dtype), rows),
        grid=grid,
        in_specs=dq_specs,
        out_specs=(tile(block_q), q_rows),
    )(*dq_args)

    all_rows = _vmem((1, heads, sq), lambda i, j: (i, 0, 0))
    dkv_specs = [full(sq), tile(block_k), tile(block_k), full(sq),
                 all_rows, all_rows]
    dkv_args = [q3, k3, v3, do3, lse, delta]
    if has_mask:
        dkv_specs.append(km_spec)
        dkv_args.append(km3)

    grid = (bh // heads, sk // block_k)
    _record_built("resident", "dkv", q, sk, heads, grid)
    dk, dv = pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q,
                          causal=causal, scale=scale, seq_q=sq,
                          has_mask=has_mask),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ),
        grid=grid,
        in_specs=dkv_specs,
        out_specs=(tile(block_k), tile(block_k)),
    )(*dkv_args)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


def _tiles_ok(q, k, block_q=128, block_k=128):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    # head_dim 64 is the common transformer case (BERT/GPT heads) and
    # tiles onto the MXU fine (lane dim padded to 128);
    # tests/test_aot_tpu.py compiles it for the chip at BERT-base's
    # shape, so the rule is static — no compile probe at dispatch
    if d % 64 != 0:
        return False
    return (sq % block_q == 0 and sk % block_k == 0
            and sq >= block_q and sk >= block_k)


def _kv_resident(q, k):
    """Whether full K/V rows fit comfortably in VMEM (the fast
    resident kernels, blockspec (1, sk, d)).  Past ~half of a
    v5e-class core's ~16 MB VMEM the STREAMED kernels take over: K/V
    swept by a third grid dimension, online-softmax state in scratch —
    unbounded sequence length at a small extra DMA cost.
    MXTPU_FLASH_MAX_KV_VMEM_MB moves the crossover."""
    from ...base import getenv

    d = q.shape[3]
    sk = k.shape[2]
    itemsize = 2 if q.dtype in (jnp.bfloat16, jnp.float16) else 4
    kv_mb = 2 * sk * d * itemsize / 1e6
    return kv_mb <= getenv("FLASH_MAX_KV_VMEM_MB", 8.0, float)


def _fwd_dispatch(q, k):
    return _flash_forward if _kv_resident(q, k) else \
        _flash_forward_stream


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_sdpa(q, k, v, km, causal, scale):
    # km: additive (b, sk) key-padding mask or None (None is an empty
    # pytree to custom_vjp, so one definition covers both paths)
    fwd = _fwd_dispatch(q, k)
    out, _ = fwd(q, k, v, causal=causal, scale=scale, kmask=km)
    return out


def _flash_sdpa_fwd(q, k, v, km, causal, scale):
    fwd = _fwd_dispatch(q, k)
    out, lse = fwd(q, k, v, causal=causal, scale=scale, kmask=km)
    return out, (q, k, v, km, out, lse)


def _flash_sdpa_bwd(causal, scale, res, g):
    q, k, v, km, o, lse = res
    bwd = _flash_backward if _kv_resident(q, k) else \
        _flash_backward_stream
    dq, dk, dv = bwd(q, k, v, o, lse, g, causal=causal,
                     scale=scale, kmask=km)
    # mask is non-differentiable
    dkm = None if km is None else jnp.zeros_like(km)
    return dq, dk, dv, dkm


_flash_sdpa.defvjp(_flash_sdpa_fwd, _flash_sdpa_bwd)


def _as_key_padding_mask(mask, q, k):
    """Normalize a (b, 1, 1, sk)-broadcastable mask to an additive
    (b, sk) float row, or None when the mask is not that shape (full
    (sq, sk) score masks stay on the XLA fallback)."""
    if mask is None:
        return None
    b, sk = q.shape[0], k.shape[2]
    if mask.ndim != 4 or mask.shape != (b, 1, 1, sk):
        return None
    row = mask.reshape(b, sk)
    if row.dtype == jnp.bool_:
        return jnp.where(row, 0.0, _NEG_INF).astype(jnp.float32)
    return row.astype(jnp.float32)


def flash_attention(q, k, v, mask=None, scale=None, causal=False):
    """Fused attention; q,k,v: (batch, heads, seq, head_dim).

    Key-padding masks — additive or bool, shape (b, 1, 1, seq_k), the
    form BERT-style encoders build — ride inside the kernel; full
    per-score masks and unaligned shapes fall back to the XLA
    reference (the caller treats this function as best-effort)."""
    from ..attention import sdpa_reference

    if not _tiles_ok(q, k):
        return sdpa_reference(q, k, v, mask, scale=scale, causal=causal)
    if causal and q.shape[2] != k.shape[2]:
        # the kernels use the start-aligned q_pos >= k_pos convention;
        # the reference's causal mask for sq != sk is END-aligned
        # (tril offset sk-sq) — keep the oracle's semantics
        return sdpa_reference(q, k, v, mask, scale=scale, causal=causal)
    s = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    km = _as_key_padding_mask(mask, q, k)
    if mask is not None and km is None:  # full score mask: XLA fallback
        return sdpa_reference(q, k, v, mask, scale=scale, causal=causal)
    return _flash_sdpa(q, k, v, km, bool(causal), s)
