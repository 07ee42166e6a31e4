"""What the benchmark takes from a Gluon block under
`parallel.DataParallelTrainer`: filling its parameters, and reading the
step object's state between steps.  The one file of the harness that
imports mxnet_tpu; shared by every configuration that trains through
that entry point."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# norm of the first gradient from the norm of the optimizer's first
# state leaf after one step from zero state
GRADIENT_FROM_STATE = {
    # m1 = (1 - beta1) * g1
    "adamw": lambda opt: 1.0 / (1.0 - opt["beta1"]),
    # m1 = -lr * (g1 + wd * w0): the gradient with its decay
    "sgd": lambda opt: 1.0 / opt["learning_rate"],
}


def fill(block, arrays, ctx):
    """Set the block's parameters, in `_ordered_params()` order, to
    `arrays` (device arrays made from the seed)."""
    from mxnet_tpu.ndarray.ndarray import NDArray

    named = block._ordered_params()
    if len(named) != len(arrays):
        raise ValueError(f"the program's block has {len(named)} parameters, "
                         f"the reference lists {len(arrays)}")
    for (name, p), arr in zip(named, arrays):
        known = p.shape or ()
        if len(known) == len(arr.shape) and any(
                k not in (0, a) for k, a in zip(known, arr.shape)):
            raise ValueError(f"parameter {name} has shape {known}, the "
                             f"reference's leaf at its place {arr.shape}")
        p.set_data(NDArray(arr, ctx=ctx))


def _split(leaves, parts):
    """Each leaf cut along its first axis into its `parts` equal parts:
    a leaf that packs several of the model's tensors (BERT's q, k, v
    projections) is compared tensor by tensor."""
    return [chunk for x, k in zip(leaves, parts)
            for chunk in (jnp.split(x, k, axis=0) if k > 1 else [x])]


@functools.partial(jax.jit, static_argnums=1)
def _norms(leaves, parts):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in _split(leaves, parts)])


@functools.partial(jax.jit, static_argnums=2)
def _change_norms(now, before, parts):
    return _norms([a - b for a, b in zip(now, before)], parts)


def first_gradient_norms(trainer, optimizer, parts):
    """Norm, part by part, of the first gradient as the optimizer got
    it, from the trainer's state after its first step; 0 where a leaf
    has none."""
    scale = GRADIENT_FROM_STATE[optimizer["name"]](optimizer)
    firsts = [st[0] if isinstance(st, tuple) else st
              for st in trainer._states]
    held = [(st, k) for st, k in zip(firsts, parts) if st is not None]
    norms = iter(np.asarray(_norms([st for st, _ in held],
                                   tuple(k for _, k in held))) * scale)
    return np.array([0.0 if st is None else next(norms)
                     for st, k in zip(firsts, parts) for _ in range(k)])


def change_norms(trainer, params0, parts):
    """Norm, part by part, of (the trainer's parameters now - params0)."""
    return np.asarray(_change_norms(list(trainer._params), list(params0),
                                    tuple(parts)))


def trainable_flags(trainer, parts):
    return [tr for tr, k in zip(trainer._trainable, parts) for _ in range(k)]


def step_program_text(trainer, x, y):
    """Text of the compiled single-step program (a second
    `.lower().compile()`: a cache load after the step has run)."""
    from mxnet_tpu import random as mx_random

    xj = tuple(jnp.asarray(v) for v in x) if isinstance(x, (tuple, list)) \
        else jnp.asarray(x)
    return trainer._step_fn.lower(
        trainer._params, trainer._states, xj, jnp.asarray(y),
        mx_random.next_key(), jnp.asarray(trainer._lr, jnp.float32),
        jnp.asarray(1.0, jnp.float32)).compile().as_text()
