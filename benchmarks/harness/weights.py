"""Weights from the seed, made on the device in one jitted call.  The
program's parameters and the reference's are both filled from here, so
neither takes anything the other has made."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _spec_key(specs):
    return tuple((tuple(shape), kind, float(scale))
                 for shape, kind, scale in specs)


@functools.lru_cache(maxsize=4)
def _generator(spec_key):
    def gen(key):
        keys = jax.random.split(key, len(spec_key))
        out = []
        for k, (shape, kind, scale) in zip(keys, spec_key):
            if kind == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            elif kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif kind == "normal":
                out.append(scale * jax.random.normal(k, shape, jnp.float32))
            elif kind == "trunc_normal":
                out.append(scale * jax.random.truncated_normal(
                    k, -2.0, 2.0, shape, jnp.float32))
            else:
                raise ValueError(f"unknown initialiser kind {kind!r}")
        return tuple(out)

    return jax.jit(gen)


def make(seed, specs):
    """Tuple of float32 arrays for `specs` = [(shape, kind, scale)];
    the same seed gives the same arrays."""
    return _generator(_spec_key(specs))(jax.random.PRNGKey(int(seed)))
