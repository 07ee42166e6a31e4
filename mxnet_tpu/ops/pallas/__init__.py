"""Pallas TPU kernels (the custom-call tier; ref: the reference's
hand-CUDA/cuDNN kernels, re-expressed compiler-first)."""
import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, out_shape, **kwargs):
    """``pl.pallas_call`` for kernels that may run inside a
    ``shard_map`` (the multi-replica whole step traces the model there):
    under its varying-axes check every output must say which manual
    mesh axes it varies over, and a kernel's outputs vary over whatever
    its operands do.  Outside a ``shard_map`` that set is empty and
    this is ``pl.pallas_call`` as written."""
    def call(*operands):
        vma = frozenset().union(
            *(jax.typeof(o).vma for o in operands))
        operands = tuple(
            jax.lax.pcast(o, tuple(vma - jax.typeof(o).vma),
                          to="varying") for o in operands)
        typed = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma),
            out_shape)
        return pl.pallas_call(kernel, out_shape=typed, **kwargs)(*operands)

    return call
