"""Rounding for the controls: a reference computed one precision below
the configuration's.  int8 is the precision below bfloat16 that the v5e
has (393 TOP/s): every operand of a matrix product or convolution, in
the forward AND the backward pass, is rounded to int8 under a per-tensor
scale, and the products accumulate in float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def int8(x):
    """Round to int8 under a per-tensor scale (absmax / 127)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def operand(x):
    """The value rounded to int8; the gradient passes straight through."""
    return x + jax.lax.stop_gradient(int8(x) - x)


@jax.custom_vjp
def cotangent(y):
    """Identity whose backward pass rounds the cotangent to int8: with
    `operand` on both operands of a product, the backward pass's two
    products take int8 operands too."""
    return y


cotangent.defvjp(lambda y: (y, None), lambda _, g: (int8(g),))


def rounding(precision):
    """(rounding of a product's operands, rounding of its result's
    cotangent) for a reference precision."""
    if precision == "float32":
        return (lambda x: x), (lambda y: y)
    if precision == "int8":
        return operand, cotangent
    raise ValueError(f"unknown reference precision {precision!r}")
