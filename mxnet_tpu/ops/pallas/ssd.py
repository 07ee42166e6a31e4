"""The Mamba-2 SSD scan as a pair of Pallas TPU kernels: ops/ssm.py's
`_ssd_chunked` (the mathematics, the roundings and the oracle are
written down there), with a `jax.custom_vjp` of its own.

`scan(x, dt, a, b, c, d, chunk)` takes the op's arguments.  The running
sum G of dt a inside each chunk, (batch, seq, heads) float32, is XLA's,
and so is its derivative; the kernels read G and dt BY HEAD, (batch, 1,
heads, seq): a head's values of a chunk are a lane-dense (1, chunk)
row, and d comes repeated onto each head's lanes.  The grid is (batch,
group, chunk), the chunk axis "arbitrary": a group's heads keep their
float32 states, transposed and side by side, (state size, heads x head
size) in a VMEM scratch buffer from one chunk to the next (zeroed at
the first).  A grid step reads the chunk's x of the group's heads,
which lie side by side in x (512 lanes at 8 heads of 64), its B and C,
and works through the group's heads one UNIT of 128 lanes at a time in
a `lax.fori_loop` (unrolled: `_UNROLLED`): two heads of 64, or one
head of a multiple of 128.
A head's row goes onto its lanes, and a sum over its lanes comes back
as a row, by a transpose (`_Unit`): no (chunk, 1) column is reduced
over its lanes.  C B^T is made once a grid step; the decays exp(G_i -
G_j) of each head (i >= j, float32) and their product with C B^T exist
in VMEM alone: nothing of size chunk x chunk reaches HBM.  A unit's
in-chunk product is ONE product: its two heads' decayed C B^T side by
side (chunk x 2 chunk) against their x dt stacked, each masked to its
own lanes; C S^T of the unit, and the state's update, take both heads'
lanes at once.  So every array is whole (8, 128) tiles and no lane is
sliced at 64.

The forward writes y alone.  Its residuals are its inputs: the
backward runs the forward again in a mode that writes the float32
state at every chunk's start and no y (no decays are made there), then
a kernel over the chunks in reverse that carries the state's cotangent
in VMEM, reads the kept state and writes dx, dB and dC (summed over
the group's heads inside), and by head and token the cotangents of dt
(through x dt), of G and the terms of d's, as lane-dense (group, head,
token) rows.  XLA adds G's cotangent up into dt's and a's.

Roundings are `_ssd_chunked`'s and those of JAX's derivative of it:
products take their operands in x's dtype (the state rounded to it as
an operand only) and accumulate in float32; the decays, the state,
every sum and every cotangent that is not of a value of x's dtype are
float32, and a cotangent of a value of x's dtype (x dt, the decayed
C B^T, the decayed x dt, the rounded state) is rounded to it where JAX
rounds it.  A float32 cotangent against operands of bf16 goes into the
product as two bf16 parts (`_parts`): the product is then within 2^-17
of the float32 product of JAX's derivative (`precision="highest"`),
under the rounding to bf16 that every such product's result meets.
Each output is rounded once, after its float32 sum.

Every call's FIRST operand is float32 G by head (the benchmark's
readers name kernels by their first operand's shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call

_LANES = 128
# what a kernel may use of VMEM: `admits` keeps a group's blocks as
# wide as its heads, twice for the pipeline, and the carried state or
# its cotangent under three quarters of it
_VMEM_LIMIT = 32 * 2 ** 20
# a grid step's units, up to this many, are one straight line (the
# loop unrolled by Mosaic; its body is traced once), so that one unit's
# chain of dependent products fills the waits of another's: a layer's
# gradient at the nemotron cell's shape (4 units) took 4.86 ms of
# device time on a TPU v5e as a loop, 2.84 unrolled
_UNROLLED = 8


def admits(x, b, chunk):
    """Whether the kernels take these shapes: x (batch, seq, heads, head
    size) with heads of 64 (two to a unit) or of a multiple of 128, a
    group's heads filling whole lanes, b (batch, seq, groups, state
    size) with a state of whole lanes, chunks of 128, bf16 or
    float32."""
    heads, p = x.shape[2], x.shape[3]
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    # the backward's blocks of a group as wide as its heads, twice for
    # the pipeline: the kept state, x, dy and dx; and the carried
    # state's cotangent
    group_bytes = 3 * n * r * p * 4 + 6 * chunk * r * p * x.dtype.itemsize
    return (heads % groups == 0 and (p == 64 or p % _LANES == 0)
            and (r * p) % _LANES == 0 and n % _LANES == 0
            and chunk == _LANES
            and group_bytes <= _VMEM_LIMIT * 3 // 4
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and b.dtype == x.dtype)


def _parts(x, dtype):
    """`x` as a sum of values of `dtype`: itself where it is of that
    dtype or the dtype is float32, else (a float32 cotangent against
    bf16) two bf16 values whose sum is x to 2^-17 of it."""
    if x.dtype == dtype or dtype == jnp.float32:
        return [x.astype(dtype)]
    high = x.astype(dtype)
    return [high, (x - high.astype(jnp.float32)).astype(dtype)]


def _product(dtype, x, y, lhs=1, rhs=0):
    """The sum over x's axis `lhs` and y's axis `rhs` of 2-D operands,
    float32 out: x y by default, (1, 1) x y^T, (0, 0) x^T y.  Operands
    go in as `dtype` (exact float32 products for float32), a float32
    operand against bf16 as its `_parts`."""
    precision = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None
    out = None
    for u in _parts(x, dtype):
        for v in _parts(y, dtype):
            p = jax.lax.dot_general(
                u, v, (((lhs,), (rhs,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision)
            out = p if out is None else out + p
    return out


def _rounded(x, dtype):
    """x rounded to `dtype` and back to float32: a cotangent of a value
    of `dtype`, as JAX's derivative rounds it."""
    return x.astype(dtype).astype(jnp.float32)


def _row(block, head):
    """Sublane `head` of a (heads, tokens) block as a (1, tokens) row."""
    sublane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(sublane == head, block, 0.0), axis=0,
                   keepdims=True)


class _Unit:
    """What a grid step knows of its units: `k` heads of `size` lanes
    in each unit of `width` lanes, and the chunk's lower triangle.  A
    head's value a token comes in as a (1, chunk) row; it goes onto the
    head's lanes, and a sum over them comes back as a row, by a
    transpose: no lane of a (chunk, 1) column is ever reduced."""

    def __init__(self, x_ref, size):
        self.chunk, lanes = x_ref.shape
        self.size, self.width = size, max(size, _LANES)
        self.k = self.width // size
        self.units = lanes // self.width
        self.unrolled = self.units <= _UNROLLED
        self.r = self.units * self.k
        shape = (self.chunk, self.chunk)
        self.seen = (jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                     <= jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        self.head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (1, self.width), 1) // size
        self.first = pl.program_id(1) * self.r

    def lanes(self, u):
        return pl.ds(pl.multiple_of(u * self.width, self.width), self.width)

    def rows(self, block, u):
        """The unit's heads' rows of a (heads, chunk) block."""
        return [_row(block, self.first + u * self.k + j)
                for j in range(self.k)]

    def on_lanes(self, rows):
        """(1, chunk) rows, a head each -> (chunk, width): a head's
        value a token on each of its lanes."""
        return jnp.concatenate(
            [jnp.broadcast_to(row, (self.size, self.chunk)) for row in rows],
            axis=0).T

    def head_rows(self, x):
        """(chunk, width) -> a (1, chunk) row a head: the sum over its
        lanes."""
        x = x.T
        return [jnp.sum(x[j * self.size:(j + 1) * self.size], axis=0,
                        keepdims=True) for j in range(self.k)]

    def head_sums(self, x):
        """(1, width) -> a (1, 1) sum a head over its lanes."""
        if self.k == 1:
            return [jnp.sum(x, axis=1, keepdims=True)]
        return [jnp.sum(jnp.where(self.head_of_lane == j, x, 0.0), axis=1,
                        keepdims=True) for j in range(self.k)]

    def masked(self, x):
        """(rows, width) -> (k rows, width): a head's lanes of x, the
        others zero, the unit's heads stacked (x itself for one)."""
        if self.k == 1:
            return x
        return jnp.concatenate([jnp.where(self.head_of_lane == j, x, 0.0)
                                for j in range(self.k)], axis=0)

    def decay(self, row):
        """exp(G_i - G_j) for i >= j, 0 above the diagonal, float32, from
        G as a row."""
        g = jnp.broadcast_to(row, (self.chunk, self.chunk))
        return jnp.where(self.seen, jnp.exp(
            jnp.where(self.seen, g.T - g, 0.0)), 0.0)


def _forward_kernel(g_ref, dt_ref, d_ref, x_ref, b_ref, c_ref, out_ref,
                    state_ref, *, size, keep):
    """A chunk of a group.  `keep`: write the state at the chunk's start
    and no y (the backward's own forward); else write y."""
    dtype, unit = x_ref.dtype, _Unit(x_ref, size)
    last = unit.chunk - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    if keep:
        out_ref[...] = state_ref[...]
    g, dt_all, c = g_ref[...], dt_ref[...], c_ref[...]
    # B^T once a grid step, for the state's update
    b_t = b_ref[...].astype(jnp.float32).T.astype(dtype)
    if not keep:
        cb = _product(dtype, c, b_ref[...], 1, 1)

    def one(u, carry):
        lanes, rows = unit.lanes(u), unit.rows(g, u)
        total = unit.on_lanes(rows)
        end = total[last:]
        x = x_ref[:, lanes].astype(jnp.float32)
        xdt = (x * unit.on_lanes(unit.rows(dt_all, u))).astype(dtype)
        state = state_ref[:, lanes]
        if not keep:
            within = jnp.concatenate([unit.decay(row) * cb for row in rows],
                                     axis=1)
            stacked = unit.masked(xdt.astype(jnp.float32)).astype(dtype)
            y = _product(dtype, within.astype(dtype), stacked) \
                + jnp.exp(total) * _product(dtype, c, state.astype(dtype)) \
                + d_ref[:, lanes] * x
            out_ref[:, lanes] = y.astype(out_ref.dtype)
        # the state the chunk leaves: its own, decayed to the chunk's end
        weighted = (xdt.astype(jnp.float32) * jnp.exp(end - total)) \
            .astype(dtype)
        state_ref[:, lanes] = jnp.exp(end) * state \
            + _product(dtype, b_t, weighted)
        return carry

    jax.lax.fori_loop(0, unit.units, one, None, unroll=unit.unrolled)


def _backward_kernel(g_ref, dt_ref, d_ref, x_ref, b_ref, c_ref, state_ref,
                     dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dg_ref, dd_ref,
                     d_state_ref, *, size):
    """A chunk of a group in reverse; `d_state_ref` carries the
    cotangent of the state the chunk leaves."""
    dtype, unit = x_ref.dtype, _Unit(x_ref, size)
    chunk, last, r = unit.chunk, unit.chunk - 1, unit.r
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros(d_state_ref.shape, f32)

    g, dt_all, b, c = g_ref[...], dt_ref[...], b_ref[...], c_ref[...]
    cb = _product(dtype, c, b, 1, 1)
    c_t = c.astype(f32).T.astype(dtype)
    n = b.shape[1]
    # a head's (1, chunk) rows go to sublane h of a block of the group's
    sublane = jax.lax.broadcasted_iota(jnp.int32, (-(-r // 8) * 8, 1), 0)
    token = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def one(u, carry):
        d_cb, d_b, d_c, rows_dt, rows_g, rows_d = carry
        lanes, rows = unit.lanes(u), unit.rows(g, u)
        total = unit.on_lanes(rows)
        end = total[last:]
        x = x_ref[:, lanes].astype(f32)
        dy = dy_ref[:, lanes].astype(f32)
        dt = unit.on_lanes(unit.rows(dt_all, u))
        xdt = (x * dt).astype(dtype)
        xdt32 = xdt.astype(f32)
        state = state_ref[:, lanes]
        rounded = state.astype(dtype)
        d_next = d_state_ref[:, lanes]
        # y += exp(G) C S^T
        d_cs = dy * jnp.exp(total)
        d_c = d_c + _product(dtype, d_cs, rounded, 1, 1)
        through_g = d_cs * _product(dtype, c, rounded)
        # y += (L o C B^T) (x dt), a head's block of the stacked product
        decays = [unit.decay(row) for row in rows]
        within = [(decay * cb).astype(dtype) for decay in decays]
        # dy is of x's dtype: its float32 form goes in whole
        stacked = unit.masked(dy).astype(dtype)
        d_within = _rounded(_product(dtype, stacked, xdt, 1, 1), dtype)
        d_xdt = _rounded(_product(dtype, jnp.concatenate(within, axis=0),
                                  stacked, 0, 0), dtype)
        from_decays = []
        for j, decay in enumerate(decays):
            dw = d_within[j * chunk:(j + 1) * chunk]
            d_cb = d_cb + dw * decay
            through = dw * cb * decay
            # G_i gains the row's sum, G_j loses the column's
            from_decays.append(jnp.sum(through.T, axis=0, keepdims=True)
                               - jnp.sum(through, axis=0, keepdims=True))
        # the state left: exp(G_T) S + (x dt exp(G_T - G))^T B
        to_end = jnp.exp(end - total)
        weighted = (xdt32 * to_end).astype(dtype)
        d_weighted = _rounded(_product(dtype, b, d_next), dtype)
        d_b = d_b + _product(dtype, weighted, d_next, 1, 1)
        d_xdt = _rounded(d_xdt + _rounded(d_weighted * to_end, dtype),
                         dtype)
        through_end = d_weighted * xdt32 * to_end
        # G_T: what reaches G of the chunk's last token through to_end
        # and through the state's decay exp(G_T)
        d_end = unit.head_sums(
            jnp.sum(through_end, axis=0, keepdims=True)
            + jnp.sum(d_next * state, axis=0, keepdims=True) * jnp.exp(end))
        d_state_ref[:, lanes] = jnp.exp(end) * d_next \
            + _rounded(_product(dtype, c_t, d_cs), dtype)
        # x dt, and the skip d x
        dx_ref[:, lanes] = (d_xdt * dt + d_ref[:, lanes] * dy) \
            .astype(dx_ref.dtype)
        by_dt = unit.head_rows(d_xdt * x)
        by_g = unit.head_rows(through_g - through_end)
        by_d = unit.head_rows(dy * x)
        for j in range(unit.k):
            at = sublane == u * unit.k + j
            d_g = by_g[j] + from_decays[j] + jnp.where(token == last,
                                                       d_end[j], 0.0)
            rows_dt = jnp.where(at, by_dt[j], rows_dt)
            rows_g = jnp.where(at, d_g, rows_g)
            rows_d = jnp.where(at, by_d[j], rows_d)
        return d_cb, d_b, d_c, rows_dt, rows_g, rows_d

    zeros = jnp.zeros((sublane.shape[0], chunk), f32)
    d_cb, d_b, d_c, rows_dt, rows_g, rows_d = jax.lax.fori_loop(
        0, unit.units, one,
        (jnp.zeros((chunk, chunk), f32), jnp.zeros((chunk, n), f32),
         jnp.zeros((chunk, n), f32), zeros, zeros, zeros),
        unroll=unit.unrolled)
    # C B^T, shared by the group's heads
    db_ref[...] = (d_b + _product(dtype, d_cb, c, 0, 0)).astype(db_ref.dtype)
    dc_ref[...] = (d_c + _product(dtype, d_cb, b)).astype(dc_ref.dtype)
    ddt_ref[...] = rows_dt[:r]
    dg_ref[...] = rows_g[:r]
    dd_ref[...] = rows_d[:r]


class _Grid:
    """The grid of the kernels, (batch, group, chunk), the chunk axis
    "arbitrary" (in reverse for the backward), and how each array is
    cut for a grid step."""

    def __init__(self, x, b, chunk, reverse=False):
        self.batch, seq, self.heads, self.size = x.shape
        self.groups, self.n = b.shape[2], b.shape[3]
        self.chunk, self.chunks = chunk, seq // chunk
        self.r = self.heads // self.groups
        self.reverse = reverse

    def _at(self, c):
        return self.chunks - 1 - c if self.reverse else c

    def spec(self, block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    def by_group(self, width):
        """(batch, seq, groups x width): a chunk of the group's columns
        (x of its heads, B, C)."""
        return self.spec((None, self.chunk, width),
                         lambda i, j, c: (i, self._at(c), j))

    def by_head(self, rows, shared=False):
        """(batch, groups, rows, seq): a chunk of the group's rows; or
        (`shared`: batch, 1, rows, seq) of every group's."""
        return self.spec((None, None, rows, self.chunk),
                         lambda i, j, c: (i, 0 if shared else j, 0,
                                          self._at(c)))

    def by_state(self):
        """(batch, groups, chunks, n, r x size): a chunk's start state."""
        return self.spec((None, None, None, self.n, self.r * self.size),
                         lambda i, j, c: (i, j, self._at(c), 0, 0))

    def call(self, kernel, **specs):
        return pallas_call(
            functools.partial(kernel, size=self.size),
            grid=(self.batch, self.groups, self.chunks),
            scratch_shapes=[pltpu.VMEM((self.n, self.r * self.size),
                                       jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT), **specs)

    def inputs(self):
        """G and dt by head, d on its lanes, x, B, C."""
        width = self.r * self.size
        return [self.by_head(self.heads, True),
                self.by_head(self.heads, True),
                self.spec((1, width), lambda i, j, c: (0, j)),
                self.by_group(width), self.by_group(self.n),
                self.by_group(self.n)]


def _running(dt, a, chunk):
    """G: the running sum of dt a inside each chunk, (batch, seq, heads)
    float32."""
    batch, seq, heads = dt.shape
    total = dt.astype(jnp.float32) * a.astype(jnp.float32)
    return jnp.cumsum(total.reshape(batch, seq // chunk, chunk, heads),
                      axis=2).reshape(batch, seq, heads)


def _operands(x, dt, a, b, c, d, chunk):
    """G and dt by head (batch, 1, heads, seq), d on each head's lanes
    (1, heads x size), and x, B, C with their last two axes as one."""
    batch, seq, _, size = x.shape

    def by_head(v):
        return jnp.swapaxes(v, 1, 2)[:, None]

    return (by_head(_running(dt, a, chunk)), by_head(dt.astype(jnp.float32)),
            jnp.repeat(d.astype(jnp.float32), size)[None],
            x.reshape(batch, seq, -1), b.reshape(batch, seq, -1),
            c.reshape(batch, seq, -1))


def _forward(x, dt, a, b, c, d, chunk, keep):
    """y (batch, seq, heads, size) in x's dtype, or (keep) the float32
    state at every chunk's start, (batch, groups, chunks, n, r x size)."""
    grid = _Grid(x, b, chunk)
    kernel = functools.partial(_forward_kernel, keep=keep)
    if keep:
        out = jax.ShapeDtypeStruct(
            (grid.batch, grid.groups, grid.chunks, grid.n,
             grid.r * grid.size), jnp.float32)
        return grid.call(kernel, in_specs=grid.inputs(), out_shape=out,
                         out_specs=grid.by_state())(
            *_operands(x, dt, a, b, c, d, chunk))
    y = grid.call(kernel, in_specs=grid.inputs(),
                  out_shape=jax.ShapeDtypeStruct(
                      x.shape[:2] + (x.shape[2] * x.shape[3],), x.dtype),
                  out_specs=grid.by_group(grid.r * grid.size))(
        *_operands(x, dt, a, b, c, d, chunk))
    return y.reshape(x.shape)


def _backward(x, dt, a, b, c, d, chunk, dy):
    batch, seq, heads, size = x.shape
    states = _forward(x, dt, a, b, c, d, chunk, True)
    grid = _Grid(x, b, chunk, reverse=True)
    by_head = jax.ShapeDtypeStruct((batch, grid.groups, grid.r, seq),
                                   jnp.float32)
    flat = (batch, seq, grid.groups * grid.n)
    dx, db, dc, ddt, dg, dd = grid.call(
        _backward_kernel,
        in_specs=grid.inputs() + [grid.by_state(),
                                  grid.by_group(grid.r * size)],
        out_shape=(jax.ShapeDtypeStruct((batch, seq, heads * size), x.dtype),
                   jax.ShapeDtypeStruct(flat, b.dtype),
                   jax.ShapeDtypeStruct(flat, c.dtype), by_head, by_head,
                   by_head),
        out_specs=(grid.by_group(grid.r * size), grid.by_group(grid.n),
                   grid.by_group(grid.n), grid.by_head(grid.r),
                   grid.by_head(grid.r), grid.by_head(grid.r)),
    )(*_operands(x, dt, a, b, c, d, chunk), states,
      dy.reshape(batch, seq, heads * size))

    def by_token(v):
        """(batch, groups, r, seq) -> (batch, seq, heads)."""
        return jnp.moveaxis(v, 3, 1).reshape(batch, seq, heads)

    # G's cotangent reaches every dt a of its chunk up to the token
    dg = jax.lax.cumsum(by_token(dg).reshape(batch, -1, chunk, heads),
                        axis=2, reverse=True).reshape(batch, seq, heads)
    dt32, a32 = dt.astype(jnp.float32), a.astype(jnp.float32)
    return (dx.reshape(x.shape),
            (by_token(ddt) + dg * a32).astype(dt.dtype),
            jnp.sum(dg * dt32, axis=(0, 1)).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            jnp.sum(by_token(dd), axis=(0, 1)).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan(x, dt, a, b, c, d, chunk):
    """The SSD scan (ops/ssm.py) for shapes `admits` takes, over a
    sequence of whole chunks: x (batch, seq, heads, size), dt (batch,
    seq, heads), a and d (heads,), b and c (batch, seq, groups, n).
    Returns y, (batch, seq, heads, size) in x's dtype."""
    return _forward(x, dt, a, b, c, d, chunk, False)


def _scan_fwd(x, dt, a, b, c, d, chunk):
    # the states are written by the backward's own forward: the pass's
    # forward would write them for nothing where the layer is recomputed
    return _forward(x, dt, a, b, c, d, chunk, False), (x, dt, a, b, c, d)


scan.defvjp(_scan_fwd,
            lambda chunk, kept, dy: _backward(*kept, chunk, dy))
