"""The gated delta rule's intra-chunk ("WY") part as two Pallas TPU
kernels, and its chunk scan as two more (the last paragraph).
`wy(q, k, v, g, beta)` is ops/linear_attention.py's `_wy_xla` (the
mathematics, the roundings and the closed-form derivative are written
down there), with a `jax.custom_vjp` whose forward and backward are
one kernel each.

Chunks are independent here, so every grid axis is parallel: a step
takes one (sequence, value head) and a block of PAIRS of chunks (up to
`_MAX_PAIRS`, fewer where wide float32 heads' blocks would not fit), and
reads its key head's q and k through the BlockSpec (value head h reads
key head h // r; nothing is repeated in HBM).  Two chunks of 64 are
worked on as ONE 128 x 128 block-diagonal matrix: the products of
block-diagonal matrices are block-diagonal, so (I + A)^-1, the decays
and both triangular masks come out chunk by chunk while every array is
a whole (128, 128) tile of the MXU and of the vector unit, and no lane
is ever sliced at 64.  The only cross terms are k k^T and q k^T's
off-diagonal quarters, one bf16 pass each, masked away.

The inverse is a chain of ten dependent float32 products (six bf16
passes each); `_LOCKSTEP` pairs go through it side by side, as one
batched product a link, so that independent chains fill each other's
waits on the MXU: 2.94 ms a head group of the benchmark's cell for one
pair at a time, 1.91 for four (my chip runs, PR 35; unrolling the loop
over pairs instead gave 2.69).

A vector of a chunk (g, beta, their gradients) travels as a lane row
(1, 128) a pair; the kernels turn a row into a column, and back, by a
masked reduction over the identity (exact: one term a sum), and make
the running sum G the same way over the lower triangle.

What the kernels hand the chunk scan has the CHUNK axis first, (n, b,
h, 64, size), written there by the BlockSpec: `lax.scan` consumes it,
and hands back its cotangents, without a transposed copy.  The inverse
is kept for the backward lane-dense, a pair's two blocks side by side
(b, h, n / 2, 64, 128) float32; exp(G_C), a scalar a chunk, is left to
XLA.  dq and dk leave the backward kernel a VALUE head, float32, and
are summed over the r heads of a key head outside.

The chunk scan is a second kernel pair, `scan(u, w, within, q_in,
k_out, last)`: ops/linear_attention.py's `_scan_xla` (the recurrence
and its derivative are written down there), with a `jax.custom_vjp`.
The grid is (block of pairs, block of chunks), the chunk axis
"arbitrary": a block of up to `_SCAN_PAIRS` (sequence, value head)
pairs keeps its float32 states, dk x dv each, in a VMEM scratch buffer
from one grid step to the next (zeroed at the first), and a grid step
works through its chunks (up to `_SCAN_CHUNKS`) in a `lax.fori_loop`,
the pairs side by side as batched products, so that the chain of
dependent 64-row products of one pair does not wait alone on the MXU.
The forward writes o straight into the rule's (b, h, seq, dv) layout.
Its residuals are its inputs: the backward runs the forward again in a
mode that writes the float32 state at every chunk's start and no o
(under the rule's `jax.checkpoint` the pass's forward would write them
for nothing), then a kernel over the chunks in reverse that carries the
state's cotangent in VMEM, reads the kept states and writes the six
cotangents.  Roundings are `_scan_xla`'s and those of JAX's derivative
of it: a product's operands in the rule's dtype (the state and d
rounded to it as operands only), every sum, the state, the decay and
its cotangent float32; a float32 cotangent (the state's, and d's)
against bf16 operands goes into the product as three bf16 parts whose
sum it is exactly (`_product`: the float32 product that
`precision="highest"` gives, in three MXU passes, not six and not one),
and the cotangent of a bf16 value is rounded to bf16 where JAX rounds
it.  VMEM: at most `_SCAN_VMEM_LIMIT`; a grid step's blocks, twice for
the pipeline, at most half of it, the rest for the states, their
cotangents and a chunk's float32 temporaries (`_Scan` sizes the
blocks from the bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call

CHUNK = 64
_PAIR = 2 * CHUNK
_BLOCK = 16         # ops/linear_attention.py: _unit_lower_inverse
_MAX_PAIRS = 8      # pairs of chunks a grid step
_LOCKSTEP = 4       # of them side by side in one turn of a kernel's loop
# what a kernel may use of the chip's 128 MB of VMEM, and the half of
# it a grid step's blocks may take, the pipeline's second buffer
# included (7.8 MB in the backward at 8 pairs of 128-wide bf16 heads);
# the rest is for the float32 tiles of the pairs in lockstep
_VMEM_LIMIT = 32 * 2 ** 20
_BLOCK_BYTES = _VMEM_LIMIT // 2


def admits(q, k, v):
    """Whether the kernels take these shapes: an even number of chunks
    of 64, key and value sizes that fill whole lanes, bf16 or float32."""
    return (v.shape[2] % _PAIR == 0 and k.shape[-1] % 128 == 0
            and v.shape[-1] % 128 == 0
            and v.dtype in (jnp.bfloat16, jnp.float32)
            and q.dtype == k.dtype == v.dtype)


def _matmul(x, y, transposed=False, **how):
    """x y (or x y^T) for (pairs, rows, size) operands, a pair at a
    time, float32 out."""
    return jax.lax.dot_general(
        x, y, (((2,), (2 if transposed else 1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, **how)


def _exact(x, y):
    return _matmul(x, y, precision=jax.lax.Precision.HIGHEST)


def _dot(dtype):
    """Products whose operands are rounded to the inputs' dtype and
    accumulated in float32 (exact float32 products for float32)."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None

    def dot(x, y, transposed=False):
        return _matmul(x.astype(dtype), y.astype(dtype), transposed,
                       precision=precision)

    return dot


class _Pair:
    """Masks of a pair of chunks, made once a grid step."""

    def __init__(self):
        row = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, _PAIR), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (_PAIR, _PAIR), 1)
        self.eye = row == col
        self.chunk = (row // CHUNK) == (col // CHUNK)
        self.seen = self.chunk & (col <= row)
        self.strict = self.chunk & (col < row)
        self.block = (row // _BLOCK) == (col // _BLOCK)
        self.chunk_end = col == (row // CHUNK) * CHUNK + (CHUNK - 1)


def _over_lanes(mask, row):
    """(.., 1, 128) rows -> (.., 128, 1): sum_j mask[i, j] row[j]."""
    return jnp.sum(jnp.where(mask, row, 0.0), axis=-1, keepdims=True)


def _over_sublanes(mask, column):
    """(.., 128, 1) columns -> (.., 1, 128): sum_i mask[i, j] column[i]."""
    return jnp.sum(jnp.where(mask, column, 0.0), axis=-2, keepdims=True)


def _decays(pair, g_row, beta_row):
    """G as a column, beta as a column, decay[i, j] = exp(G_i - G_j)
    for i >= j of one chunk and 0 elsewhere, exp(G) and exp(G_C - G) as
    columns."""
    total = _over_lanes(pair.seen, g_row)
    total_row = _over_sublanes(pair.eye, total)
    beta = _over_lanes(pair.eye, beta_row)
    decay = jnp.where(pair.seen, jnp.exp(
        jnp.where(pair.seen, total - total_row, 0.0)), 0.0)
    at_end = _over_lanes(pair.chunk_end, total_row)
    return beta, decay, jnp.exp(total), jnp.exp(at_end - total)


def _unit_lower_inverse(pair, a):
    """ops/linear_attention.py's `_unit_lower_inverse` on pairs."""
    eye = pair.eye.astype(jnp.float32)

    def nilpotent_inverse(n, index):
        out, power, reach = eye + n, n, 2
        while reach < index:
            power = _exact(power, power)
            out = out + _exact(out, power)
            reach *= 2
        return out

    diagonal = nilpotent_inverse(-jnp.where(pair.block, a, 0.0), _BLOCK)
    below = _exact(diagonal, jnp.where(pair.block, 0.0, a))
    return _exact(nilpotent_inverse(-below, CHUNK // _BLOCK), diagonal)


def _transposed(x):
    return jnp.swapaxes(x, -1, -2)


def _lockstep(pairs):
    """Pairs worked on side by side in one turn of a kernel's loop:
    the inverse is a chain of ten dependent products, and the chains
    of independent pairs fill each other's waits."""
    return max(c for c in range(1, _LOCKSTEP + 1) if pairs % c == 0)


def _turns(pairs, one):
    """`one(first pair)` for every `_lockstep` pairs of a grid step."""
    side = _lockstep(pairs)
    if pairs == side:
        one(0)
    else:
        jax.lax.fori_loop(0, pairs // side, lambda t, _: one(t * side), None)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, u_ref, w_ref,
                    within_ref, q_in_ref, k_out_ref, inverse_ref, *, pairs):
    dtype, side = v_ref.dtype, _lockstep(pairs)
    dot, pair = _dot(dtype), _Pair()

    def one(first):
        rows = pl.ds(pl.multiple_of(first * _PAIR, _PAIR), side * _PAIR)
        q, k, v = (ref[rows, :].reshape(side, _PAIR, -1)
                   for ref in (q_ref, k_ref, v_ref))
        at = pl.ds(first, side)
        beta, decay, into, out = _decays(pair, g_ref[at], beta_ref[at])
        a = jnp.where(pair.strict, beta * decay * dot(k, k, True), 0.0)
        inverse = _unit_lower_inverse(pair, a)
        k32 = k.astype(jnp.float32)
        u = dot(inverse, v.astype(jnp.float32) * beta)
        w = dot(inverse, k32 * (beta * into))
        within = decay * dot(q, k, True)
        # the second chunk's block sits in lanes 64-127: turn it down
        within = jnp.concatenate(
            [within[:, :CHUNK], pltpu.roll(within[:, CHUNK:], CHUNK, 2)],
            axis=1)[:, :, :CHUNK]
        chunks = pl.ds(2 * first, 2 * side)
        for ref, x in ((u_ref, u), (w_ref, w), (within_ref, within),
                       (q_in_ref, q.astype(jnp.float32) * into),
                       (k_out_ref, k32 * out)):
            ref[chunks] = x.astype(ref.dtype).reshape((2 * side,)
                                                      + ref.shape[1:])
        # a pair's two blocks side by side: the other quarters are zeros
        inverse_ref[at] = inverse[:, :CHUNK] + inverse[:, CHUNK:]

    _turns(pairs, one)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inverse_ref,
                     du_ref, dw_ref, dwithin_ref, dq_in_ref, dk_out_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, pairs):
    dtype, side = v_ref.dtype, _lockstep(pairs)
    dot, pair = _dot(dtype), _Pair()
    # 0/1 products put a chunk's (64, 64) block into either half of
    # the lanes (exact in one pass: one term a sum)
    lane = jax.lax.broadcasted_iota(jnp.int32, (side, CHUNK, _PAIR), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (side, CHUNK, _PAIR), 1)
    place = [(lane == row + half * CHUNK).astype(dtype) for half in range(2)]

    def one(first):
        rows = pl.ds(pl.multiple_of(first * _PAIR, _PAIR), side * _PAIR)
        q, k, v = (ref[rows, :].reshape(side, _PAIR, -1)
                   for ref in (q_ref, k_ref, v_ref))
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        at, chunks = pl.ds(first, side), pl.ds(2 * first, 2 * side)
        beta, decay, into, out = _decays(pair, g_ref[at], beta_ref[at])
        m, n = dot(k, k, True), dot(q, k, True)
        inverse = inverse_ref[at]
        transposed = _transposed(jnp.where(pair.chunk, jnp.concatenate(
            [inverse, inverse], axis=1), 0.0))
        du, dw, dq_in, dk_out = (
            ref[chunks].reshape(side, _PAIR, -1)
            for ref in (du_ref, dw_ref, dq_in_ref, dk_out_ref))
        dq_in, dk_out = dq_in.astype(jnp.float32), dk_out.astype(jnp.float32)
        dwithin = dwithin_ref[chunks].reshape(side, 2, CHUNK, CHUNK)
        dwithin = jnp.concatenate(
            [dot(dwithin[:, half], place[half]) for half in range(2)], axis=1)
        # u = T (beta v), w = T (beta e^G k): T's cotangent, then A's
        d_t = dot(du, v32 * beta, True) + dot(dw, k32 * (beta * into), True)
        d_bv, d_bk = dot(transposed, du), dot(transposed, dw)
        d_a = -jnp.where(pair.strict, _exact(_exact(transposed, d_t),
                                             transposed), 0.0)
        d_m = d_a * beta * decay
        d_n = dwithin * decay
        through_decay = (d_a * beta * m + dwithin * n) * decay
        d_k = dot(d_m + _transposed(d_m), k) + dot(_transposed(d_n), q)
        d_q = dot(d_n, k)
        # the element-wise terms: beta v, beta e^G k, q e^G, k e^(G_C - G)
        bk_k = jnp.sum(d_bk * k32, axis=-1, keepdims=True)
        at_end = jnp.sum(dk_out * k32, axis=-1, keepdims=True) * out
        d_beta = jnp.sum(d_a * decay * m, axis=-1, keepdims=True) \
            + jnp.sum(d_bv * v32, axis=-1, keepdims=True) + into * bk_k
        d_total = jnp.sum(through_decay, axis=-1, keepdims=True) \
            - _over_lanes(pair.eye, jnp.sum(through_decay, axis=-2,
                                                keepdims=True)) \
            + into * (beta * bk_k + jnp.sum(dq_in * q32, axis=-1,
                                            keepdims=True)) - at_end
        for ref, x in ((dq_ref, d_q + dq_in * into),
                       (dk_ref, d_k + (beta * into) * d_bk + dk_out * out),
                       (dv_ref, beta * d_bv)):
            ref[rows, :] = x.astype(ref.dtype).reshape(side * _PAIR, -1)
        # g reaches G_i of every later token of its chunk, G_C included
        dg_ref[at] = _over_sublanes(pair.seen, d_total) \
            + _over_sublanes(pair.chunk, at_end)
        dbeta_ref[at] = _over_sublanes(pair.eye, d_beta)

    _turns(pairs, one)


class _Blocks:
    """The grid of both kernels, (sequence x value head, block of
    pairs), and how an array of each layout is cut for a grid step."""

    def __init__(self, k, v):
        self.b, self.h, seq, dv = v.shape
        self.r, self.n = self.h // k.shape[1], seq // CHUNK
        dk, item = k.shape[-1], v.dtype.itemsize
        # the backward's blocks of one pair: q, k, dw, dq_in, dk_out
        # and float32 dq, dk; v, dv and float32 du; dwithin and T
        pair_bytes = _PAIR * (dk * (5 * item + 8) + dv * (2 * item + 4)
                              + _PAIR * item + CHUNK * 4)
        self.pairs = max(c for c in range(1, _MAX_PAIRS + 1)
                         if (self.n // 2) % c == 0
                         and (c == 1 or 2 * c * pair_bytes <= _BLOCK_BYTES))
        self.grid = (self.b * self.h, self.n // 2 // self.pairs)

    def by_token(self, size, heads_to_one=1):
        """(b, heads, seq, size): the tokens of the step's pairs; q and
        k hold a head for every `r` value heads."""
        h = self.h
        return pl.BlockSpec(
            (None, None, self.pairs * _PAIR, size),
            lambda i, j: (i // h, (i % h) // heads_to_one, j, 0),
            memory_space=pltpu.VMEM)

    def by_chunk(self, size):
        """(n, b, h, 64, size), what the chunk scan consumes."""
        h = self.h
        return pl.BlockSpec((2 * self.pairs, None, None, CHUNK, size),
                            lambda i, j: (j, i // h, i % h, 0, 0),
                            memory_space=pltpu.VMEM)

    def by_pair(self, rows):
        """(b, h, n / 2, rows, 128): a lane row, or the inverse's two
        blocks side by side, a pair."""
        h = self.h
        return pl.BlockSpec((None, None, self.pairs, rows, _PAIR),
                            lambda i, j: (i // h, i % h, j, 0, 0),
                            memory_space=pltpu.VMEM)

    def chunked(self, size, dtype):
        return jax.ShapeDtypeStruct(
            (self.n, self.b, self.h, CHUNK, size), dtype)

    def paired(self, rows):
        return jax.ShapeDtypeStruct(
            (self.b, self.h, self.n // 2, rows, _PAIR), jnp.float32)

    def call(self, kernel, **specs):
        return pallas_call(
            functools.partial(kernel, pairs=self.pairs), grid=self.grid,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_VMEM_LIMIT), **specs)


def _rows(x):
    """(b, h, seq) -> (b, h, seq / 128, 1, 128) float32: a lane row a
    pair of chunks."""
    return x.astype(jnp.float32).reshape(x.shape[:2] + (-1, 1, _PAIR))


def _chunk_ends(g):
    """exp(G_C) of every chunk, (b, h, n): a scalar a chunk is XLA's."""
    g = g.astype(jnp.float32)
    return jnp.exp(g.reshape(g.shape[:2] + (-1, CHUNK)).sum(-1))


def _forward(q, k, v, g, beta):
    dk, dv, dtype, cut = k.shape[-1], v.shape[-1], v.dtype, _Blocks(k, v)
    inputs = [cut.by_token(dk, cut.r), cut.by_token(dk, cut.r),
              cut.by_token(dv), cut.by_pair(1), cut.by_pair(1)]
    *outs, inverse = cut.call(
        _forward_kernel, in_specs=inputs,
        out_shape=(cut.chunked(dv, jnp.float32), cut.chunked(dk, dtype),
                   cut.chunked(CHUNK, dtype), cut.chunked(dk, dtype),
                   cut.chunked(dk, dtype), cut.paired(CHUNK)),
        out_specs=(cut.by_chunk(dv), cut.by_chunk(dk), cut.by_chunk(CHUNK),
                   cut.by_chunk(dk), cut.by_chunk(dk), cut.by_pair(CHUNK)),
    )(q, k, v, _rows(g), _rows(beta))
    return (*outs, jnp.moveaxis(_chunk_ends(g), 2, 0)), inverse


def _backward(q, k, v, g, beta, inverse, cotangents):
    b, h, seq, dv = v.shape
    hk, dk, cut = k.shape[1], k.shape[-1], _Blocks(k, v)
    du, dw, dwithin, dq_in, dk_out, dlast = cotangents
    by_head = jax.ShapeDtypeStruct((b, h, seq, dk), jnp.float32)
    dq, dk_, dv_, dg, dbeta = cut.call(
        _backward_kernel,
        in_specs=[cut.by_token(dk, cut.r), cut.by_token(dk, cut.r),
                  cut.by_token(dv), cut.by_pair(1), cut.by_pair(1),
                  cut.by_pair(CHUNK), cut.by_chunk(dv), cut.by_chunk(dk),
                  cut.by_chunk(CHUNK), cut.by_chunk(dk), cut.by_chunk(dk)],
        out_shape=(by_head, by_head, jax.ShapeDtypeStruct(v.shape, v.dtype),
                   cut.paired(1), cut.paired(1)),
        out_specs=(cut.by_token(dk), cut.by_token(dk), cut.by_token(dv),
                   cut.by_pair(1), cut.by_pair(1)),
    )(q, k, v, _rows(g), _rows(beta), inverse, du, dw, dwithin, dq_in,
      dk_out)
    # exp(G_C)'s cotangent reaches every g of the chunk
    d_end = jnp.moveaxis(dlast, 0, 2) * _chunk_ends(g)
    dg = dg.reshape(b, h, -1, CHUNK) + d_end[..., None]
    dq, dk_ = (x.reshape(b, hk, h // hk, seq, dk).sum(2).astype(q.dtype)
               for x in (dq, dk_))
    return (dq, dk_, dv_, dg.reshape(g.shape).astype(g.dtype),
            dbeta.reshape(beta.shape).astype(beta.dtype))


@jax.custom_vjp
def wy(q, k, v, g, beta):
    """The intra-chunk part of the gated delta rule (module docstring)
    for shapes `admits` takes: q, k (b, key heads, seq, dk), v (b, h,
    seq, dv), g and beta (b, h, seq)."""
    return _forward(q, k, v, g, beta)[0]


def _wy_fwd(q, k, v, g, beta):
    outs, inverse = _forward(q, k, v, g, beta)
    return outs, (q, k, v, g, beta, inverse)


wy.defvjp(_wy_fwd, lambda kept, cotangents: _backward(*kept, cotangents))


# ---------------------------------------------------------------------------
# The chunk scan

_SCAN_PAIRS = 16        # (sequence, value head) pairs a grid step, side by side
_SCAN_CHUNKS = 8        # chunks a grid step at most
# what a scan kernel may use of VMEM; a grid step's blocks, twice, take
# at most half of it, and the state, its cotangent and the float32
# temporaries of a chunk's products the rest
_SCAN_VMEM_LIMIT = 64 * 2 ** 20
_SCAN_BLOCK_BYTES = _SCAN_VMEM_LIMIT // 2


def _parts(x, dtype):
    """`x` as a sum of values of `dtype`: itself where it is of that
    dtype or the dtype is float32, else (a float32 cotangent against
    bf16) three bf16 values whose sum is x exactly."""
    if x.dtype == dtype or dtype == jnp.float32:
        return [x.astype(dtype)]
    parts = []
    for _ in range(3):
        part = x.astype(dtype)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return parts


def _product(dtype, x, y, lhs=2, rhs=1):
    """sum over x's axis `lhs` and y's axis `rhs` of (pairs, ., .)
    operands, a pair at a time, float32 out.  Operands of `dtype` go in
    as they are; a float32 operand against bf16 ones goes in as three
    bf16 parts, so that the product is the float32 product of JAX's
    derivative (`precision="highest"`), not one bf16 pass."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    out = None
    for a in _parts(x, dtype):
        for b in _parts(y, dtype):
            p = jax.lax.dot_general(
                a, b, (((lhs,), (rhs,)), ((0,), (0,))),
                preferred_element_type=jnp.float32, precision=precision)
            out = p if out is None else out + p
    return out


def _rounded(x, dtype):
    """x rounded to `dtype` and back to float32: a cotangent of a value
    of `dtype`, as JAX's derivative rounds it."""
    return x.astype(dtype).astype(jnp.float32)


def _scan_forward_kernel(*refs, chunks, keep):
    """The chunks of a grid step in order, a block of pairs side by
    side.  `keep`: write the state at every chunk's start and no o (the
    backward's own forward); else write o."""
    if keep:
        u_ref, w_ref, k_out_ref, last_ref, states_ref, state_ref = refs
    else:
        (u_ref, w_ref, within_ref, q_in_ref, k_out_ref, last_ref, o_ref,
         state_ref) = refs
    dtype = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    def turn(t, carry):
        state = state_ref[...]
        if keep:
            states_ref[t] = state
        # the state goes into a product rounded to the operands' dtype
        rounded = state.astype(dtype)
        d = u_ref[t] - _product(dtype, w_ref[t], rounded)
        d = d.astype(dtype)
        if not keep:
            o = _product(dtype, q_in_ref[t], rounded) \
                + _product(dtype, within_ref[t], d)
            rows = pl.ds(pl.multiple_of(t * CHUNK, CHUNK), CHUNK)
            o_ref[:, rows, :] = o.astype(o_ref.dtype)
        state_ref[...] = state * last_ref[t] \
            + _product(dtype, k_out_ref[t], d, 1, 1)
        return carry

    jax.lax.fori_loop(0, chunks, turn, None)


def _scan_backward_kernel(states_ref, u_ref, w_ref, within_ref, q_in_ref,
                          k_out_ref, last_ref, do_ref, du_ref, dw_ref,
                          dwithin_ref, dq_in_ref, dk_out_ref, dlast_ref,
                          d_state_ref, *, chunks):
    """The chunks of a grid step in reverse, the state's cotangent
    carried in VMEM (module docstring)."""
    dtype = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state_ref[...] = jnp.zeros(d_state_ref.shape, jnp.float32)

    def turn(i, carry):
        t = chunks - 1 - i
        state, d_next = states_ref[t], d_state_ref[...]
        w, within, q_in, k_out = (ref[t] for ref in (w_ref, within_ref,
                                                     q_in_ref, k_out_ref))
        rows = pl.ds(pl.multiple_of(t * CHUNK, CHUNK), CHUNK)
        do = do_ref[:, rows, :]
        rounded = state.astype(dtype)
        d = (u_ref[t] - _product(dtype, w, rounded)).astype(dtype)
        # d reaches o through `within` and the next state through k_out
        dd = _rounded(_product(dtype, k_out, d_next, 2, 1), dtype) \
            + _rounded(_product(dtype, within, do, 1, 1), dtype)
        du_ref[t] = dd
        for ref, x in ((dwithin_ref, _product(dtype, do, d, 2, 2)),
                       (dk_out_ref, _product(dtype, d, d_next, 2, 2)),
                       (dq_in_ref, _product(dtype, do, rounded, 2, 2)),
                       (dw_ref, _product(dtype, -dd, rounded, 2, 2))):
            ref[t] = x.astype(ref.dtype)
        dlast = jnp.sum(jnp.sum(state * d_next, axis=2, keepdims=True),
                        axis=1, keepdims=True)
        dlast_ref[t] = jnp.broadcast_to(dlast, dlast_ref.shape[1:])
        d_state_ref[...] = d_next * last_ref[t] \
            + _rounded(_product(dtype, q_in, do, 1, 1), dtype) \
            + _rounded(_product(dtype, w, -dd, 1, 1), dtype)
        return carry

    jax.lax.fori_loop(0, chunks, turn, None)


class _Scan:
    """The grid of the scan kernels, (block of pairs, block of chunks),
    the chunk axis "arbitrary": a pair's state stays in VMEM from one
    grid step to the next.  Arrays come in the scan's layout flattened
    to pairs, (n, pairs, 64, size); o and its cotangent (pairs, seq,
    dv), the layout of the rule's output."""

    def __init__(self, u, w, kind):
        self.n, self.pairs, _, self.dv = u.shape
        self.dk, dk, dv, item = w.shape[-1], w.shape[-1], self.dv, \
            w.dtype.itemsize
        # VMEM bytes of a pair's blocks a chunk (a lane row takes 8
        # sublanes, `within` 128 lanes): u, w, k_out and the decay ...
        block = CHUNK * (dv * 4 + 2 * dk * item) + 8 * dv * 4
        if kind == "states":        # ... and the state written
            block += dk * dv * 4
        else:                       # ... q_in, within, o (do)
            block += CHUNK * (dk + 128 + dv) * item
        if kind == "backward":      # ... the state read, du, dw, dq_in,
            block += dk * dv * 4 + CHUNK * (dv * 4 + (3 * dk + 128) * item) \
                + 8 * 128 * 4       # dk_out, dwithin, dlast
        # a pair's state, its cotangent and a chunk's float32 temporaries
        held = 8 * dk * dv * 4
        self.side = max(c for c in range(1, _SCAN_PAIRS + 1)
                        if self.pairs % c == 0 and (c == 1 or (
                            2 * c * block <= _SCAN_BLOCK_BYTES and c * held
                            <= _SCAN_VMEM_LIMIT - _SCAN_BLOCK_BYTES)))
        self.chunks = max(c for c in range(1, _SCAN_CHUNKS + 1)
                          if self.n % c == 0 and (
                              c == 1 or 2 * c * self.side * block
                              <= _SCAN_BLOCK_BYTES))
        self.blocks = self.n // self.chunks
        self.reverse = kind == "backward"

    def _chunk(self, c):
        return self.blocks - 1 - c if self.reverse else c

    def by_chunk(self, rows, size):
        """(n, pairs, rows, size): a chunk's rows of a pair."""
        return pl.BlockSpec((self.chunks, self.side, rows, size),
                            lambda p, c: (self._chunk(c), p, 0, 0),
                            memory_space=pltpu.VMEM)

    def by_token(self):
        """(pairs, seq, dv): o and its cotangent."""
        return pl.BlockSpec((self.side, self.chunks * CHUNK, self.dv),
                            lambda p, c: (p, self._chunk(c), 0),
                            memory_space=pltpu.VMEM)

    def call(self, kernel, **specs):
        return pallas_call(
            functools.partial(kernel, chunks=self.chunks),
            grid=(self.pairs // self.side, self.blocks),
            scratch_shapes=[pltpu.VMEM((self.side, self.dk, self.dv),
                                       jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_SCAN_VMEM_LIMIT), **specs)


def _by_pair(x):
    """(n, b, h, ...) -> (n, b * h, ...): free, the pairs are major."""
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def _decay_rows(last, dv):
    """exp(G_C), (n, b, h), as a float32 lane row a pair and chunk."""
    last = _by_pair(last.astype(jnp.float32))
    return jnp.broadcast_to(last[..., None, None], last.shape + (1, dv))


def _scan_forward(u, w, within, q_in, k_out, last, keep):
    """o, (b, h, seq, dv) in w's dtype, or (keep) the float32 state at
    every chunk's start, (n, pairs, dk, dv)."""
    n, b, h, _, dv = u.shape
    dk, dtype = w.shape[-1], w.dtype
    u, w, within, q_in, k_out = (_by_pair(x) for x in
                                 (u, w, within, q_in, k_out))
    cut = _Scan(u, w, "states" if keep else "forward")
    rows = _decay_rows(last, dv)
    kernel = functools.partial(_scan_forward_kernel, keep=keep)
    if keep:
        return cut.call(
            kernel,
            in_specs=[cut.by_chunk(CHUNK, dv), cut.by_chunk(CHUNK, dk),
                      cut.by_chunk(CHUNK, dk), cut.by_chunk(1, dv)],
            out_shape=jax.ShapeDtypeStruct((n, b * h, dk, dv), jnp.float32),
            out_specs=cut.by_chunk(dk, dv),
        )(u, w, k_out, rows)
    o = cut.call(
        kernel,
        in_specs=[cut.by_chunk(CHUNK, dv), cut.by_chunk(CHUNK, dk),
                  cut.by_chunk(CHUNK, CHUNK), cut.by_chunk(CHUNK, dk),
                  cut.by_chunk(CHUNK, dk), cut.by_chunk(1, dv)],
        out_shape=jax.ShapeDtypeStruct((b * h, n * CHUNK, dv), dtype),
        out_specs=cut.by_token(),
    )(u, w, within, q_in, k_out, rows)
    return o.reshape(b, h, n * CHUNK, dv)


def _scan_backward(u, w, within, q_in, k_out, last, do):
    n, b, h, _, dv = u.shape
    dk, dtype = w.shape[-1], w.dtype
    states = _scan_forward(u, w, within, q_in, k_out, last, True)
    shapes = [(x.shape, x.dtype) for x in (u, w, within, q_in, k_out)]
    u, w, within, q_in, k_out = (_by_pair(x) for x in
                                 (u, w, within, q_in, k_out))
    cut = _Scan(u, w, "backward")

    def chunked(size, kind):
        return jax.ShapeDtypeStruct((n, b * h, CHUNK, size), kind)

    *grads, dlast = cut.call(
        _scan_backward_kernel,
        in_specs=[cut.by_chunk(dk, dv), cut.by_chunk(CHUNK, dv),
                  cut.by_chunk(CHUNK, dk), cut.by_chunk(CHUNK, CHUNK),
                  cut.by_chunk(CHUNK, dk), cut.by_chunk(CHUNK, dk),
                  cut.by_chunk(1, dv), cut.by_token()],
        out_shape=(chunked(dv, jnp.float32), chunked(dk, dtype),
                   chunked(CHUNK, dtype), chunked(dk, dtype),
                   chunked(dk, dtype),
                   jax.ShapeDtypeStruct((n, b * h, 1, 128), jnp.float32)),
        out_specs=(cut.by_chunk(CHUNK, dv), cut.by_chunk(CHUNK, dk),
                   cut.by_chunk(CHUNK, CHUNK), cut.by_chunk(CHUNK, dk),
                   cut.by_chunk(CHUNK, dk), cut.by_chunk(1, 128)),
    )(states, u, w, within, q_in, k_out, _decay_rows(last, dv),
      do.reshape(b * h, n * CHUNK, dv))
    return tuple(x.reshape(shape).astype(kind) for x, (shape, kind)
                 in zip(grads, shapes)) \
        + (dlast[:, :, 0, 0].reshape(last.shape).astype(last.dtype),)


@jax.custom_vjp
def scan(u, w, within, q_in, k_out, last):
    """The chunk scan of the gated delta rule (module docstring) over
    what `wy` hands it, the chunk axis first: u (n, b, h, 64, dv)
    float32, w, q_in, k_out (n, b, h, 64, dk) and within (n, b, h, 64,
    64) in the rule's dtype, last (n, b, h) float32.  Returns o, (b, h,
    seq, dv) in that dtype."""
    return _scan_forward(u, w, within, q_in, k_out, last, False)


def _scan_fwd(*xs):
    # the states are written by the backward's own forward: under a
    # `jax.checkpoint` the pass's forward would write them for nothing
    return _scan_forward(*xs, False), xs


scan.defvjp(_scan_fwd, lambda xs, do: _scan_backward(*xs, do))
