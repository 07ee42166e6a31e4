"""The short causal convolution of a Gated DeltaNet mixer as two Pallas
TPU kernels: ops/linear_attention.py's `causal_conv1d` (the
mathematics, the roundings, the XLA form and the oracle), with a
`jax.custom_vjp` whose forward and backward are one kernel each.

`conv(weight, x)`: x is a projection's output (batch, seq, width) whose
first `channels` columns are convolved (the `[q | k | v]` of a packed
`[q | k | v | z]`: the BlockSpec reads them where they lie, so no
sliced copy exists); weight is (1, taps, channels) float32, tap j on
x_(t - (taps - 1) + j).  `conv_biased(weight, x)`: the same with a bias
added before SiLU, the weight (1, taps + 1, channels) with the bias as
its last row (a Mamba-2 mixer's convolution).  The grid is (batch,
channel block, row block).  A grid step reads `rows` tokens of `lanes`
channels, and the `_HALO` rows before them through a second BlockSpec
on the same operand (zero before the sequence's start), and works
through its channels 128 lanes at a time: the rows go to a float32 VMEM buffer
behind the halo, each tap is a read of that buffer shifted by its
offset, the taps are multiplied and added in float32 in tap order,
SiLU in float32, one rounding to x's dtype.

The backward reads x and the output's cotangent dy once, and the
`_HALO` rows AFTER the block of both (zero past the sequence's end):
it recomputes the pre-activation y of the block's rows and of `_TAIL`
more, g = dy * silu'(y) in float32, dx_t = sum_j w_j g_(t + taps - 1
- j) summed in float32 and rounded once, and dw_j = sum of x_(t - taps
+ 1 + j) g_t in float32: (8, 128) partial sums a tap that stay in the
output block across the row blocks (the row axis "arbitrary") and are
added up outside over the batch and the eight rows; the bias's
gradient, where there is one, is the sum of g, kept the same way.
Nothing the size of x is kept or written but dx: the residuals are the
inputs.  Every call's FIRST operand is the float32 (1, taps, channels)
weight, (1, taps + 1, channels) with a bias (the benchmark's readers
name kernels by their first operand's shape).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_call

_LANES = 128
_HALO = 16          # rows read before (and after) a block: a bf16 tile
_TAIL = 8           # rows past a block whose y the backward needs
_MAX_TAPS = _TAIL
# a block: on a v5e any of 256-1024 rows by 512-2048 lanes runs a
# layer's pass in the same time: the work a lane group binds, not
# the blocks' transfers
_MAX_ROWS = 512
_MAX_BLOCK_LANES = 1024
# the backward's blocks, twice for the pipeline: 13 MB at float32
_VMEM_LIMIT = 32 * 2 ** 20


def admits(shape, weight_shape, dtype):
    """Whether the kernels take a (batch, seq, width) input of `dtype`
    under a (channels, taps) weight: channels of whole lanes inside the
    width, at most `_MAX_TAPS` taps, a sequence of whole halo tiles,
    bf16 or float32."""
    (_, seq, width), (channels, taps) = shape, weight_shape
    return (channels % _LANES == 0 and channels <= width
            and 1 <= taps <= _MAX_TAPS and seq % _HALO == 0
            and dtype in (jnp.bfloat16, jnp.float32))


def _largest(limit, floor, divides):
    size = limit
    while size > floor and divides % size:
        size //= 2
    return size


def _lanes(c):
    """Lane group c of a block; c is a loop's counter."""
    return pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)


def _rows_valid(shape, first, seq):
    """Of `shape[0]` rows from global row `first` on, those inside the
    sequence."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + first
    return (row >= 0) & (row < seq)


def _taps(ext_ref, w, rows):
    """sum_j w_j x_(t - taps + 1 + j) for the first `rows` rows of a
    buffer that holds `_HALO` rows before the block's first, tap by tap
    in float32."""
    taps = w.shape[0]
    y = None
    for j in range(taps):
        term = w[j:j + 1] * ext_ref[pl.ds(_HALO - taps + 1 + j, rows)]
        y = term if y is None else y + term
    return y


def _silu_grad(y):
    s = jax.nn.sigmoid(y)
    return s * (1.0 + y * (1.0 - s))


def _forward_kernel(w_ref, x_ref, before_ref, o_ref, ext_ref, *, seq,
                    biased=False):
    rows = x_ref.shape[0]
    first = pl.program_id(2) * rows

    def one(c, _):
        lanes = _lanes(c)
        before = before_ref[:, lanes].astype(jnp.float32)
        ext_ref[:_HALO] = jnp.where(
            _rows_valid(before.shape, first - _HALO, seq), before, 0.0)
        ext_ref[_HALO:] = x_ref[:, lanes].astype(jnp.float32)
        if biased:
            w = w_ref[:, lanes]
            y = _taps(ext_ref, w[:-1], rows) + w[-1:]
        else:
            y = _taps(ext_ref, w_ref[:, lanes], rows)
        o_ref[:, lanes] = (y * jax.nn.sigmoid(y)).astype(o_ref.dtype)

    jax.lax.fori_loop(0, x_ref.shape[1] // _LANES, one, None)


def _backward_kernel(w_ref, x_ref, before_ref, after_ref, dy_ref,
                     dy_after_ref, dx_ref, dw_ref, ext_ref, g_ref, *, seq,
                     biased=False):
    rows = x_ref.shape[0]
    r = pl.program_id(2)
    first = r * rows

    @pl.when(r == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def one(c, _):
        lanes = _lanes(c)
        w = w_ref[:, lanes]
        if biased:
            w, bias = w[:-1], w[-1:]
        taps = w.shape[0]
        before = before_ref[:, lanes].astype(jnp.float32)
        after = after_ref[:, lanes].astype(jnp.float32)
        ext_ref[:_HALO] = jnp.where(
            _rows_valid(before.shape, first - _HALO, seq), before, 0.0)
        ext_ref[_HALO:_HALO + rows] = x_ref[:, lanes].astype(jnp.float32)
        ext_ref[_HALO + rows:] = jnp.where(
            _rows_valid(after.shape, first + rows, seq), after, 0.0)
        # y of the block's rows and of the `_TAIL` after them
        y = _taps(ext_ref, w, rows + _TAIL)
        if biased:
            y = y + bias
        d = _silu_grad(y)
        g = dy_ref[:, lanes].astype(jnp.float32) * d[:rows]
        g_ref[:rows] = g
        tail = dy_after_ref[:, lanes].astype(jnp.float32)[:_TAIL]
        g_ref[rows:] = jnp.where(
            _rows_valid(tail.shape, first + rows, seq), tail * d[rows:], 0.0)
        dx = None
        for j in range(taps):
            term = w[j:j + 1] * g_ref[pl.ds(taps - 1 - j, rows)]
            dx = term if dx is None else dx + term
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        for j in range(taps):
            x_j = ext_ref[pl.ds(_HALO - taps + 1 + j, rows)]
            dw_ref[j, :, lanes] += (x_j * g).reshape(-1, 8, _LANES).sum(0)
        if biased:
            dw_ref[taps, :, lanes] += g.reshape(-1, 8, _LANES).sum(0)

    jax.lax.fori_loop(0, x_ref.shape[1] // _LANES, one, None)


def _shapes(weight, x):
    """`taps`: the weight's rows, the bias's among them where there is
    one."""
    b, seq, _ = x.shape
    _, taps, channels = weight.shape
    rows = _largest(_MAX_ROWS, _HALO, seq)
    lanes = _largest(_MAX_BLOCK_LANES, _LANES, channels)
    return b, seq, taps, channels, rows, lanes


def _specs(seq, rows, lanes):
    """The block, the `_HALO` rows before it and those after it (their
    indices clamped into the sequence: the kernels mask them), of a
    (batch, seq, width) array; grid (batch, channel block, row
    block)."""
    per, last = rows // _HALO, seq // _HALO - 1

    def spec(size, index):
        return pl.BlockSpec((None, size, lanes), index,
                            memory_space=pltpu.VMEM)

    return (spec(rows, lambda i, c, r: (i, r, c)),
            spec(_HALO, lambda i, c, r: (i, jnp.maximum(r * per - 1, 0), c)),
            spec(_HALO, lambda i, c, r: (
                i, jnp.minimum((r + 1) * per, last), c)))


def _weight_spec(taps, lanes):
    return pl.BlockSpec((None, taps, lanes), lambda i, c, r: (0, 0, c),
                        memory_space=pltpu.VMEM)


def _forward(weight, x, biased=False):
    b, seq, taps, channels, rows, lanes = _shapes(weight, x)
    block, before, _ = _specs(seq, rows, lanes)
    return pallas_call(
        functools.partial(_forward_kernel, seq=seq, biased=biased),
        grid=(b, channels // lanes, seq // rows),
        in_specs=[_weight_spec(taps, lanes), block, before],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, seq, channels), x.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT))(weight, x, x)


def _backward(weight, x, dy, biased=False):
    b, seq, taps, channels, rows, lanes = _shapes(weight, x)
    block, before, after = _specs(seq, rows, lanes)
    dx, dw = pallas_call(
        functools.partial(_backward_kernel, seq=seq, biased=biased),
        grid=(b, channels // lanes, seq // rows),
        in_specs=[_weight_spec(taps, lanes), block, before, after, block,
                  after],
        out_specs=[block, pl.BlockSpec(
            (None, taps, 8, lanes), lambda i, c, r: (i, 0, 0, c),
            memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((b, seq, channels), x.dtype),
                   jax.ShapeDtypeStruct((b, taps, 8, channels), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((2 * _HALO + rows, _LANES), jnp.float32),
            pltpu.VMEM((rows + _TAIL, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT))(weight, x, x, x, dy, dy)
    dw = dw.sum((0, 2))[None]
    after_columns = x.shape[-1] - channels
    if after_columns:
        dx = jnp.pad(dx, ((0, 0), (0, 0), (0, after_columns)))
    return dw, dx


@jax.custom_vjp
def conv(weight, x):
    """SiLU of the causal depthwise convolution of x's first `channels`
    columns (module docstring): weight (1, taps, channels) float32, x
    (batch, seq, width); returns (batch, seq, channels) in x's dtype."""
    return _forward(weight, x)


conv.defvjp(lambda weight, x: (_forward(weight, x), (weight, x)),
            lambda kept, dy: _backward(*kept, dy))


@jax.custom_vjp
def conv_biased(weight, x):
    """`conv` with a bias added before SiLU: weight (1, taps + 1,
    channels) float32, the bias its last row."""
    return _forward(weight, x, biased=True)


conv_biased.defvjp(
    lambda weight, x: (_forward(weight, x, biased=True), (weight, x)),
    lambda kept, dy: _backward(*kept, dy, biased=True))
