"""Device mesh helpers for SPMD parallelism.

Ref: the reference has no mesh concept — its parallelism is explicit
per-device replicas + kvstore comm (SURVEY §2.3).  The TPU-native
replacement: a ``jax.sharding.Mesh`` whose axes name the parallelism
dimensions (dp = data, tp = tensor, pp = pipeline, sp = sequence), with
XLA inserting ICI collectives from sharding annotations.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import numpy as np

from ..base import MXNetError


def make_mesh(axis_shapes=None, devices=None):
    """THE canonical mesh constructor: every named mesh in the package
    is built here, whatever the axis count.

    ``axis_shapes``: a dict ``axis -> size``, a spec string like
    ``'dp=4,mp=2'`` (validated against the canonical axis alphabet by
    ``parallel.spmd.mesh.parse_mesh_shape``), or None for a one-axis
    all-'dp' mesh over ``devices`` (default: all local devices).  The
    axis product must equal the device count — a mismatch is a loud
    error, never a truncated mesh."""
    import jax
    from jax.sharding import Mesh

    if isinstance(axis_shapes, str):
        from .spmd.mesh import parse_mesh_shape

        axis_shapes = parse_mesh_shape(axis_shapes)
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if axis_shapes is None:
        axis_shapes = {"dp": n}
    names = tuple(axis_shapes)
    sizes = tuple(int(s) for s in axis_shapes.values())
    if int(np.prod(sizes)) != n:
        raise MXNetError(
            f"mesh {axis_shapes} needs {int(np.prod(sizes))} devices, "
            f"have {n}")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, names)


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def replica_mesh(devices, axis="dp"):
    """DEPRECATED alias: a one-axis mesh over an explicit replica
    device list.  Kept for callers of the original single-axis
    whole-step API; new code should call :func:`make_mesh` (which this
    delegates to) — it is the one constructor that also understands
    multi-axis shapes and spec strings."""
    devices = list(devices)
    return make_mesh({axis: len(devices)}, devices)


def data_axes(mesh):
    """The mesh axes the batch dim shards over.  A mesh axis named
    'dcn' is the cross-slice/process data axis (ref: ps-lite workers ×
    multi-GPU per worker, SURVEY §3.4); it composes OUTSIDE 'dp' so the
    gradient reduction is hierarchical — reduce over ICI within the
    slice, then over DCN across slices — exactly the pod shape."""
    return tuple(a for a in ("dcn", "dp") if a in mesh.axis_names)


def batch_sharding(mesh, axis=None):
    """Shard dim 0 over the data axis/axes (split_and_load, SPMD form).
    Default: ('dcn','dp') when a 'dcn' axis exists, else 'dp'."""
    from jax.sharding import NamedSharding, PartitionSpec

    if axis is None:
        axes = data_axes(mesh)
        axis = axes if len(axes) > 1 else (axes[0] if axes else "dp")
    return NamedSharding(mesh, PartitionSpec(axis))


# the mesh of the jit-with-shardings step being traced, if any
_partitioned_mesh = contextvars.ContextVar("mxtpu_partitioned_mesh",
                                           default=None)


@contextlib.contextmanager
def auto_partitioned(mesh):
    """Declare that the code traced inside runs under ``jax.jit`` with
    shardings on ``mesh`` — i.e. the compiler partitions it
    automatically.  Ops the compiler cannot partition (Mosaic kernels)
    read it through :func:`per_batch_shard`."""
    token = _partitioned_mesh.set(mesh)
    try:
        yield
    finally:
        _partitioned_mesh.reset(token)


def per_batch_shard(fn, *arrays):
    """``fn(*arrays)``, computed shard by shard over the leading (batch)
    dim when the surrounding step is automatically partitioned over
    more than one data shard.  The compiler refuses to partition a
    Mosaic kernel ("cannot be automatically partitioned"), so a kernel
    whose math is independent per example is wrapped in a ``shard_map``
    over the batch axes here, where it is called.  ``None`` entries of
    ``arrays`` are passed through; every other entry carries the batch
    on dim 0 (or 1 there, to broadcast over it), and so does the
    result.  Mesh axes that do not shard the batch see the operands
    replicated."""
    import jax
    from jax.sharding import PartitionSpec

    mesh = _partitioned_mesh.get()
    axes = data_axes(mesh) if mesh is not None else ()
    n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if n == 1:
        return fn(*arrays)
    spec = PartitionSpec(axes)
    live = [i for i, a in enumerate(arrays) if a is not None]
    in_specs = []
    for i in live:
        b = arrays[i].shape[0]
        if b != 1 and b % n:
            raise MXNetError(
                f"batch {b} does not divide over the {n} data shards "
                f"of mesh {dict(mesh.shape)}")
        in_specs.append(PartitionSpec() if b == 1 else spec)

    def body(*present):
        full = [None] * len(arrays)
        for i, a in zip(live, present):
            full[i] = a
        return fn(*full)

    # check_vma=False: the body is one kernel per shard with no
    # collective in it — there is nothing for the replication check to
    # find, and Pallas's interpreter (the CPU tests) trips over it
    return jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=spec,
        check_vma=False)(*(arrays[i] for i in live))


def global_put(value, sharding):
    """device_put that also works on multi-process meshes.

    Single process: plain jax.device_put.  Multi-process (the sharding
    spans non-addressable devices): every process holds the same global
    host value, and each places ONLY its addressable shards via
    make_array_from_callback — no cross-host transfer needed (the DCN
    data path stays inside compiled steps, where it belongs)."""
    import jax

    if jax.process_count() <= 1 or not hasattr(sharding, "mesh"):
        return jax.device_put(value, sharding)
    host = np.asarray(value)
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx])


def shard_param_spec(shape, mesh, tp_axis="tp"):
    """Megatron-ish default: shard the largest dim of >=2D params over
    the tensor axis when divisible; replicate otherwise."""
    from jax.sharding import PartitionSpec

    if tp_axis not in mesh.axis_names or len(shape) < 2:
        return PartitionSpec()
    tp = mesh.shape[tp_axis]
    if tp <= 1:
        return PartitionSpec()
    dims = [None] * len(shape)
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % tp == 0 and shape[i] >= tp * 2:
            dims[i] = tp_axis
            break
    return PartitionSpec(*dims)


def spmd_jit(sharded_fn, mesh, in_specs, out_specs, **kwargs):
    """Cached jit(shard_map(partial(fn, **kwargs))) — a fresh jax.jit per
    call would recompile every step (jit caches by function identity).
    kwargs values must be hashable (they become cache-key items)."""
    return _spmd_jit(sharded_fn, mesh, in_specs, out_specs,
                     tuple(sorted(kwargs.items())))


@functools.lru_cache(maxsize=64)
def _spmd_jit(sharded_fn, mesh, in_specs, out_specs, kwargs_items):
    import jax

    return jax.jit(jax.shard_map(
        functools.partial(sharded_fn, **dict(kwargs_items)),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs))


