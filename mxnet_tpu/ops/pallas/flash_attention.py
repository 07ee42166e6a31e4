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

The GROUPED kernels serve K/V with fewer heads than Q (the query
heads that read one K/V head are one tile) and a sliding window
(k-blocks outside it are never touched); `window=None` with equal head
counts never reaches them.  They are described where they stand.

The fwd rules of both `custom_vjp`s NAME the two values the backward
kernels need beyond the layer's own q, k, v: the output (`flash_out`,
b*h*s*d in the compute dtype) and the row statistic (`flash_lse`,
float32 b*h*s); `RESIDUAL_NAMES` is the pair.  A name is an identity
outside `jax.checkpoint`; inside one whose policy is
`save_only_these_names(*RESIDUAL_NAMES)` (`DataParallelTrainer(remat=
True)`) the pair is kept and the backward pass recomputes the layer
WITHOUT running the forward kernel a second time.

Every kernel built while a program is traced is counted, and every
pair of residuals named (`flash_attention_stats`, the profiler section
`flashAttention`).

Falls back transparently when seq/head dims don't tile (caller guards).
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler
from .. import registry
from . import pallas_call

_NEG_INF = -1e9
_LANES = 128
# VMEM the blocks of one grid step of the resident kernels may take,
# the pipeline's second buffer of each included (_heads_per_step); a
# quarter of the 16 MB a kernel may use on a v5e-class core, which
# leaves the rest to the float32 intermediates of the unrolled heads
_GROUP_VMEM_BYTES = 4 * 2 ** 20
# the names the fwd rules give the kernel's output and its row statistic
# (`_name_residuals`); a `jax.checkpoint` policy that saves them keeps
# the forward kernel out of the recomputation
RESIDUAL_NAMES = registry.RESIDUAL_NAMES["flash_attention"]


# every kernel built while a program was traced: (variant, kernel, (b, h,
# sq, sk, d), dtype, heads a grid step, grid) -> how many times.  Counted
# when a program is TRACED, so a step that runs costs nothing; the
# profiler section `flashAttention` reads it.  The keys say how the
# kernels engaged; the counts are traces (a primal trace that
# differentiation replaces counts, a layer whose jaxpr JAX reuses does
# not), not the instances in a compiled program.
_built = collections.Counter()


def _record_built(variant, kernel, q, sk, heads, grid, kv_heads=None,
                  window=None):
    b, h, sq, d = q.shape
    _built[(variant, kernel, (b, h, sq, sk, d), q.dtype.name, heads,
            tuple(grid), kv_heads or h, window)] += 1


# every (out, lse) pair a fwd rule named while a program was traced:
# (variant, (b, h, sq, sk, d), dtype, kv heads, window) -> [pairs, bytes]
_named = collections.defaultdict(lambda: [0, 0])


def _name_residuals(variant, q, k, window, out, lse):
    """`out` and `lse` under `RESIDUAL_NAMES`, the pair and its bytes
    (by the avals) counted for the `flashAttention` section."""
    b, h, sq, d = q.shape
    row = _named[(variant, (b, h, sq, k.shape[2], d), q.dtype.name,
                  k.shape[1], window)]
    row[0] += 1
    row[1] += sum(x.size * x.dtype.itemsize for x in (out, lse))
    return tuple(checkpoint_name(x, name)
                 for x, name in zip((out, lse), RESIDUAL_NAMES))


def _dims(variant, shape, kv_heads, window):
    dims = " ".join(f"{k}{v}" for k, v in zip(
        ("b", "h", "sq", "sk", "d"), shape))
    if variant == "grouped":
        dims += f" kv{kv_heads} window{window or 0}"
    return dims


def flash_attention_stats():
    """The `flashAttention` profiler section: how the kernels engaged
    in the programs traced since the last reset.  `built` has a row for
    each distinct kernel, named by its variant (resident / streamed /
    grouped), which of the three it is, its shapes (a grouped kernel's
    with its K/V head count and its window, 0 for none), the heads a
    grid step works on and the grid.  `residual_pairs` and
    `residual_bytes` have a row for each variant and shape whose fwd
    rule named its (out, lse) pair under `RESIDUAL_NAMES`: how many
    pairs, and the bytes a `jax.checkpoint` that saves them keeps."""
    built = {}
    for (variant, kernel, shape, dtype, heads, grid, kv_heads,
         window), n in _built.items():
        built[f"{variant} {kernel} {_dims(variant, shape, kv_heads, window)}"
              f" {dtype} heads{heads} grid{'x'.join(map(str, grid))}"] = n
    pairs, nbytes = {}, {}
    for (variant, shape, dtype, kv_heads, window), (n, size) \
            in _named.items():
        row = f"{variant} {_dims(variant, shape, kv_heads, window)} {dtype}"
        pairs[row], nbytes[row] = n, size

    def count(variant):
        return sum(n for key, n in _built.items() if key[0] == variant)

    return {"kernels": sum(_built.values()),
            "resident": count("resident"), "streamed": count("streamed"),
            "grouped": count("grouped"), "built": built,
            "residuals_named": sum(pairs.values()),
            "residual_pairs": pairs, "residual_bytes": nbytes}


def reset_flash_attention_stats():
    _built.clear()
    _named.clear()


_stats_rows = profiler.rows_table(
    "Flash Attention (kernels built at trace time)",
    (("kernels", "kernels"),
     ("resident (K/V in VMEM)", "resident"),
     ("streamed (K/V swept by the grid)", "streamed"),
     ("grouped (shared K/V heads, window)", "grouped")))


def _stats_table(stats):
    out = _stats_rows(stats)
    for row in sorted(stats["built"]):
        out.append(f"  {row}  x{stats['built'][row]}")
    out.append(f"{'(out, lse) pairs named for remat':<40}"
               f"{stats['residuals_named']:>12}")
    for row in sorted(stats["residual_pairs"]):
        out.append(f"  named {row}  x{stats['residual_pairs'][row]}  "
                   f"{stats['residual_bytes'][row]} bytes")
    return out


profiler.register_section("flashAttention", flash_attention_stats,
                          reset_flash_attention_stats, _stats_table)


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


# ---------------------------------------------------------------------------
# grouped variant: K/V with fewer heads than Q (grouped-query attention)
# and / or a sliding window.  The `group` query heads that read one K/V
# head are ONE tile: q block (1, group, block_q, d) of the (b * kv_heads,
# group, sq, d) view, worked on as (group * block_q, d) rows against the
# shared K/V, so the group's products are one MXU product eight (or six)
# times as tall, and dK/dV's sum over the group is the contraction of
# that product.  Forward and dQ keep the K/V head resident (its block
# index does not move along the q-blocks, so it is fetched once a K/V
# head) and loop over the k-blocks a q-block can see: those above the
# diagonal and those wholly left of the window are never touched, and
# only the blocks the mask's edge crosses pay for the mask.  dK/dV
# sweeps the q-blocks a k-block is seen from by a third grid dimension,
# the tiles of all the group's heads arriving together; steps past the
# visible range keep the last block's index (nothing is fetched) and do
# nothing.  Products take the operands' dtype (bf16 on the chip) and
# accumulate in float32; the scale is applied to the float32 scores.
# Row statistics travel lane-dense as (b * kv_heads, q-blocks, 1,
# group * block_q): one row a tile, in the tile's own row order.
# ---------------------------------------------------------------------------

_GROUPED_BLOCK_Q = 128
_GROUPED_BLOCK_K = 128      # forward and dQ: the k-block of the loop
# the forward's k-block without a window: the online softmax's
# bookkeeping (running maximum, rescaling the accumulator, columns of
# one lane in 128) is paid a block, so wide blocks where the visible
# span is long; under a window the span is short and wide blocks would
# mostly be masked
_GROUPED_BLOCK_K_FULL = 512
_GROUPED_BLOCK_DKV = 256    # dK/dV: the k-tile a grid step owns
# what a grouped kernel may use of the chip's 128 MB of VMEM, and the
# share of it the resident K/V head may take, the pipeline's second
# buffer included: 8 MB at (8192, 128) bf16 and 16 MB in float32 (a
# trainer's eager float32 probe of the model), where the default 16 MB
# a kernel would not hold the tiles beside it
_GROUPED_VMEM_LIMIT = 48 * 2 ** 20
_GROUPED_KV_VMEM_BYTES = 32 * 2 ** 20


def _visible_k_blocks(qi, block_q, block_k, num_kb, causal, window):
    """(a, b, c, e): k-blocks [a, e) hold a visible pair for q-block
    `qi`; of them [b, c) are visible whole and need no mask."""
    if not causal:
        return 0, 0, num_kb, num_kb
    first_row, last_row = qi * block_q, qi * block_q + block_q - 1
    hi = jnp.minimum(last_row // block_k + 1, num_kb)
    whole_hi = (first_row + 1) // block_k
    if window is None:
        lo = whole_lo = 0
    else:
        lo = jnp.maximum(first_row - window + 1, 0) // block_k
        whole_lo = jnp.maximum(last_row - window + block_k, 0) // block_k
    b = jnp.clip(whole_lo, lo, hi)
    c = jnp.clip(whole_hi, b, hi)
    return lo, b, c, hi


_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def _tile_positions(qi, group, block_q, block_k):
    """(query position of every row of a (group * block_q, block_k)
    score tile, key offset of every column inside its k-block)."""
    rows = group * block_q
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (group, block_q, block_k), 1).reshape(rows, block_k)
    return q_pos, jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)


def _sweep_k_blocks(body, bounds, carry):
    """`body(kb, carry, masked=)` over the k-blocks [a, e) of
    `_visible_k_blocks`: masked on the edges, unmasked on [b, c)."""
    a, b, c, e = bounds
    edge = functools.partial(body, masked=True)
    carry = jax.lax.fori_loop(a, b, edge, carry)
    carry = jax.lax.fori_loop(b, c, functools.partial(body, masked=False),
                              carry)
    return jax.lax.fori_loop(c, e, edge, carry)


def _pair_mask(q_pos, k_pos, window):
    seen = q_pos >= k_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return seen


def _col_to_row(col):
    """(rows, 1) -> (1, rows): sublanes to lanes by one transpose."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _row_to_col(row):
    """(1, rows) -> (rows, 1)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _grouped_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                        causal, window, scale, seq_k):
    _, group, block_q, d = q_ref.shape
    rows = group * block_q
    qi = pl.program_id(1)
    # the scale goes onto the q tile once a grid step, not onto every
    # block of scores: the loop is bound by the VPU, not the MXU
    q = (q_ref[0].reshape(rows, d).astype(jnp.float32) * scale).astype(
        q_ref.dtype)
    q_pos, k_off = _tile_positions(qi, group, block_q, block_k)

    def body(kb, carry, masked):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_pair_mask(q_pos, kb * block_k + k_off, window),
                          s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m, l, acc = _sweep_k_blocks(
        body, _visible_k_blocks(qi, block_q, block_k, seq_k // block_k,
                                causal, window),
        (jnp.full((rows, 1), _NEG_INF, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, d), jnp.float32)))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype).reshape(group, block_q, d)
    lse_ref[0, 0] = _col_to_row(m + jnp.log(l))


def _grouped_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                       dq_ref, delta_ref, *, block_k, causal, window,
                       scale, seq_k):
    _, group, block_q, d = q_ref.shape
    rows = group * block_q
    qi = pl.program_id(1)
    q = q_ref[0].reshape(rows, d)
    do = do_ref[0].reshape(rows, d)
    delta = jnp.sum(do.astype(jnp.float32)
                    * o_ref[0].reshape(rows, d).astype(jnp.float32),
                    axis=-1, keepdims=True)
    lse = _row_to_col(lse_ref[0, 0])
    q_pos, k_off = _tile_positions(qi, group, block_q, block_k)

    def body(kb, dq, masked):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_pair_mask(q_pos, kb * block_k + k_off, window),
                          s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32)

    dq = _sweep_k_blocks(
        body, _visible_k_blocks(qi, block_q, block_k, seq_k // block_k,
                                causal, window),
        jnp.zeros((rows, d), jnp.float32))
    dq_ref[0] = (scale * dq).astype(dq_ref.dtype).reshape(group, block_q, d)
    delta_ref[0, 0] = _col_to_row(delta)


def _visible_q_blocks(ki, block_q, block_k, num_qb, causal, window):
    """q-blocks [lo, hi) hold a pair that sees k-tile `ki`."""
    if not causal:
        return 0, num_qb
    lo = (ki * block_k) // block_q
    if window is None:
        return lo, num_qb
    last_seen_from = ki * block_k + block_k - 2 + window
    return lo, jnp.minimum(last_seen_from // block_q + 1, num_qb)


def _grouped_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_scr, dv_scr, *, causal, window,
                        scale, num_qb, sweep):
    # transposed scores (block_k, group * block_q): the lane-dense lse
    # and delta rows broadcast down the sublanes as they are
    _, group, block_q, d = q_ref.shape
    block_k = k_ref.shape[1]
    rows = group * block_q
    ki = pl.program_id(1)
    t = pl.program_id(2)
    lo, hi = _visible_q_blocks(ki, block_q, block_k, num_qb, causal, window)
    qb = lo + t

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def step(masked):
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0].reshape(rows, d)
        do = do_ref[0].reshape(rows, d)
        st = scale * jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32)
        if masked:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, rows), 0)
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, group, block_q), 2).reshape(
                    block_k, rows)
            st = jnp.where(_pair_mask(q_pos, k_pos, window), st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0])
        dv_scr[:] += jnp.dot(pt.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_scr[:] += jnp.dot(dst.astype(q.dtype), q,
                             preferred_element_type=jnp.float32)

    if causal:
        live = qb < hi
        # whole: every query of the block at or after every key of the
        # tile, and the farthest pair still inside the window
        whole = qb * block_q >= ki * block_k + block_k - 1
        if window is not None:
            whole = whole & (qb * block_q + block_q - 1 - ki * block_k
                             < window)
        pl.when(live & whole)(functools.partial(step, False))
        pl.when(live & jnp.logical_not(whole))(
            functools.partial(step, True))
    else:
        step(False)

    @pl.when(t == sweep - 1)
    def _flush():
        dk_ref[0] = (scale * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _grouped_params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_GROUPED_VMEM_LIMIT)


def _grouped_views(q, k, v):
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    return (q.reshape(b * kvh, h // kvh, sq, d), k.reshape(b * kvh, sk, d),
            v.reshape(b * kvh, sk, d))


def _grouped_forward(q, k, v, *, causal, window, scale):
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group, block_q = h // kvh, _GROUPED_BLOCK_Q
    q4, k3, v3 = _grouped_views(q, k, v)
    tile = _vmem((1, group, block_q, d), lambda i, j: (i, 0, j, 0))
    full = _vmem((1, sk, d), lambda i, j: (i, 0, 0))
    stat = _vmem((1, 1, 1, group * block_q), lambda i, j: (i, j, 0, 0))
    grid = (b * kvh, sq // block_q)
    _record_built("grouped", "fwd", q, sk, group, grid, kvh, window)
    block_k = _GROUPED_BLOCK_K_FULL if window is None \
        and sk % _GROUPED_BLOCK_K_FULL == 0 else _GROUPED_BLOCK_K
    out, lse = pallas_call(
        functools.partial(_grouped_fwd_kernel, block_k=block_k,
                          causal=causal, window=window, scale=scale,
                          seq_k=sk),
        out_shape=(
            jax.ShapeDtypeStruct(q4.shape, q.dtype),
            jax.ShapeDtypeStruct((b * kvh, sq // block_q, 1,
                                  group * block_q), jnp.float32)),
        grid=grid, in_specs=[tile, full, full], out_specs=(tile, stat),
        compiler_params=_grouped_params(("parallel", "arbitrary")),
    )(q4, k3, v3)
    return out.reshape(b, h, sq, d), lse


def _grouped_backward(q, k, v, o, lse, do, *, causal, window, scale):
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    group, block_q = h // kvh, _GROUPED_BLOCK_Q
    block_k = _GROUPED_BLOCK_DKV if sk % _GROUPED_BLOCK_DKV == 0 \
        else _GROUPED_BLOCK_K
    num_qb = sq // block_q
    q4, k3, v3 = _grouped_views(q, k, v)
    o4, do4 = o.reshape(q4.shape), do.reshape(q4.shape)

    tile = _vmem((1, group, block_q, d), lambda i, j: (i, 0, j, 0))
    full = _vmem((1, sk, d), lambda i, j: (i, 0, 0))
    stat = _vmem((1, 1, 1, group * block_q), lambda i, j: (i, j, 0, 0))
    grid = (b * kvh, num_qb)
    _record_built("grouped", "dq", q, sk, group, grid, kvh, window)
    dq, delta = pallas_call(
        functools.partial(_grouped_dq_kernel, block_k=_GROUPED_BLOCK_K,
                          causal=causal, window=window, scale=scale,
                          seq_k=sk),
        out_shape=(jax.ShapeDtypeStruct(q4.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)),
        grid=grid, in_specs=[tile, full, full, tile, tile, stat],
        out_specs=(tile, stat),
        compiler_params=_grouped_params(("parallel", "arbitrary")),
    )(q4, k3, v3, do4, o4, lse)

    # the q-blocks one k-tile is seen from: all of them without a
    # window (the causal half of the steps does nothing), else those
    # that reach window - 1 rows past the tile
    sweep = num_qb if not causal or window is None else min(
        num_qb, (block_k + window - 2) // block_q + 1)

    def q_block(i, j, t):
        lo, hi = _visible_q_blocks(j, block_q, block_k, num_qb, causal,
                                   window)
        return jnp.minimum(lo + t, hi - 1)

    q_tile = _vmem((1, group, block_q, d),
                   lambda i, j, t: (i, 0, q_block(i, j, t), 0))
    q_stat = _vmem((1, 1, 1, group * block_q),
                   lambda i, j, t: (i, q_block(i, j, t), 0, 0))
    k_tile = _vmem((1, block_k, d), lambda i, j, t: (i, j, 0))
    grid = (b * kvh, sk // block_k, sweep)
    _record_built("grouped", "dkv", q, sk, group, grid, kvh, window)
    dk, dv = pallas_call(
        functools.partial(_grouped_dkv_kernel, causal=causal,
                          window=window, scale=scale, num_qb=num_qb,
                          sweep=sweep),
        out_shape=(jax.ShapeDtypeStruct(k3.shape, k.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v.dtype)),
        grid=grid,
        in_specs=[q_tile, k_tile, k_tile, q_tile, q_stat, q_stat],
        out_specs=(k_tile, k_tile),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_grouped_params(
            ("parallel", "parallel", "arbitrary")),
    )(q4, k3, v3, do4, lse, delta)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped_sdpa(q, k, v, causal, window, scale):
    return _grouped_forward(q, k, v, causal=causal, window=window,
                            scale=scale)[0]


def _grouped_sdpa_fwd(q, k, v, causal, window, scale):
    out, lse = _name_residuals("grouped", q, k, window, *_grouped_forward(
        q, k, v, causal=causal, window=window, scale=scale))
    return out, (q, k, v, out, lse)


def _grouped_sdpa_bwd(causal, window, scale, res, g):
    q, k, v, o, lse = res
    return _grouped_backward(q, k, v, o, lse, g, causal=causal,
                             window=window, scale=scale)


_grouped_sdpa.defvjp(_grouped_sdpa_fwd, _grouped_sdpa_bwd)


def _grouped_ok(q, k, mask):
    """Gate of the grouped variant: no mask operand, self-attention
    lengths, 128-tiles, and a K/V head that stays resident (32k tokens
    at head size 128 in bf16): a rule of the shapes alone."""
    kv_bytes = 2 * 2 * k.shape[2] * k.shape[3] * k.dtype.itemsize
    return (mask is None and q.shape[2] == k.shape[2]
            and _tiles_ok(q, k) and kv_bytes <= _GROUPED_KV_VMEM_BYTES)


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
    out, lse = _name_residuals(
        "resident" if fwd is _flash_forward else "streamed", q, k, None,
        *fwd(q, k, v, causal=causal, scale=scale, kmask=km))
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


def flash_attention(q, k, v, mask=None, scale=None, causal=False,
                    window=None):
    """Fused attention; q: (batch, heads, seq, head_dim), k and v the
    same or with fewer heads, a divisor of q's: query head j then reads
    K/V head j // (heads // kv_heads).  `window` (with `causal`) keeps
    the pairs with 0 <= i - j < window.

    Key-padding masks — additive or bool, shape (b, 1, 1, seq_k), the
    form BERT-style encoders build — ride inside the kernel; full
    per-score masks and unaligned shapes fall back to the XLA
    reference (the caller treats this function as best-effort).  With
    equal head counts and no window the kernels are the resident /
    streamed ones; shared K/V heads or a window take the grouped ones."""
    from ..attention import sdpa_reference

    if window is not None and not causal:
        raise ValueError("attention window needs causal=True")
    reference = functools.partial(sdpa_reference, q, k, v, mask,
                                  scale=scale, causal=causal, window=window)
    s = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if window is not None or k.shape[1] != q.shape[1]:
        if not _grouped_ok(q, k, mask):
            return reference()
        return _grouped_sdpa(q, k, v, bool(causal),
                             None if window is None else int(window), s)
    if not _tiles_ok(q, k):
        return reference()
    if causal and q.shape[2] != k.shape[2]:
        # the kernels use the start-aligned q_pos >= k_pos convention;
        # the reference's causal mask for sq != sk is END-aligned
        # (tril offset sk-sq) — keep the oracle's semantics
        return reference()
    km = _as_key_padding_mask(mask, q, k)
    if mask is not None and km is None:  # full score mask: XLA fallback
        return reference()
    return _flash_sdpa(q, k, v, km, bool(causal), s)
