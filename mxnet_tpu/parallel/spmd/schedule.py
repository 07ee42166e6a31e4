"""Pipelined microbatch schedule over the 'pp' mesh axis.

Canonical home of the machinery that started as
``parallel/pipeline.py`` (which now re-exports from here), promoted
under the spmd plan API so pipeline parallelism composes with the
multi-axis mesh instead of living as an orphaned fragment.

Ref capability: ABSENT in the reference (SURVEY §2.3 'PP: ABSENT —
closest: group2ctx manual staging, no microbatching'); this is a
capability upgrade alongside TP/SP.

TPU-native design: stage parameters are STACKED on a leading axis of
size P and sharded over the 'pp' mesh axis, so each device holds one
stage.  Inside shard_map, a fori_loop runs the rotating microbatch
schedule: at tick t, device 0 feeds microbatch t, every device applies
its stage to its current activation, and activations rotate one hop
along the pipeline with ppermute (ICI neighbour exchange).  After P-1
warmup ticks the pipe is full; outputs stream off the last device and
are broadcast with a masked psum.  Backward is jax autodiff through
the whole schedule — ppermute transposes to the reverse rotation,
giving the mirrored fill/drain automatically, with the forward of
later microbatches overlapping the drain of earlier ones inside the
one program (XLA schedules the interleave; no host round-trips between
microbatches).

Constraints (the standard stacked-pipeline contract): all stages share
one jittable ``stage_fn(params_slice, x) -> y`` with x and y of the
same shape, and the number of microbatches must be >= 1 (default: the
``MXTPU_PP_MICROBATCHES`` knob, else P).  Wall-clock efficiency is
n_micro / (n_micro + P - 1) (the pipeline bubble).

:class:`PipelineTrainStep` closes the loop ROADMAP item 1 asks for:
forward schedule, loss, backward (the transposed schedule), a 'dp'
gradient psum, and an SGD-momentum update of the stacked stage params
— ONE pjit'd executable per training step on a ('dp','pp') mesh, lr
riding as a traced scalar so schedules never retrace.

``stage_partition`` maps a layer count onto P stages (the loud
``pp stages > layers`` error lives there); a generic
``Trainer(mesh_shape="...,pp=N")`` is rejected at construction with a
pointer here — an arbitrary HybridBlock cannot be auto-staged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...base import MXNetError, getenv


def default_microbatches(n_stages):
    """Microbatch count: ``MXTPU_PP_MICROBATCHES`` when set, else the
    stage count (one microbatch in flight per stage — the smallest
    full-pipe schedule)."""
    n = getenv("PP_MICROBATCHES", 0, int)
    return int(n) if n and n > 0 else int(n_stages)


def stage_partition(n_layers, n_stages):
    """Partition ``n_layers`` sequential layers onto ``n_stages``
    pipeline stages: returns a tuple of ``(start, stop)`` layer ranges,
    balanced to within one layer (earlier stages take the remainder).

    Loud errors: a non-positive stage count, or MORE stages than layers
    — an empty stage would sit in the rotate schedule doing identity
    work while costing a full pipeline-bubble slot."""
    n_layers, n_stages = int(n_layers), int(n_stages)
    if n_stages < 1:
        raise MXNetError(f"pp stage count must be >= 1, got {n_stages}")
    if n_stages > n_layers:
        raise MXNetError(
            f"pp={n_stages} pipeline stages > {n_layers} layers — an "
            "empty stage wastes a bubble slot; shrink the 'pp' axis in "
            "MXTPU_MESH_SHAPE or deepen the model")
    base, rem = divmod(n_layers, n_stages)
    out, start = [], 0
    for s in range(n_stages):
        stop = start + base + (1 if s < rem else 0)
        out.append((start, stop))
        start = stop
    return tuple(out)


def _pipeline_sharded(params, xs_local, *, stage_fn, axis_name, n_micro,
                      P):
    """Runs INSIDE shard_map: params leaves are the local (1, ...)
    stage slice; xs_local is the replicated (n_micro, mb, ...) batch."""
    idx = jax.lax.axis_index(axis_name)
    local = jax.tree.map(lambda p: p[0], params)
    T = n_micro + P - 1
    # carries vary across the 'pp' axis (per-device state) — mark them
    # so shard_map's vma check accepts the fori_loop carry
    acts, outs = jax.lax.pcast(
        (jnp.zeros_like(xs_local[0]), jnp.zeros_like(xs_local)),
        axis_name, to="varying")

    def tick(t, carry):
        acts, outs = carry
        # device 0 ingests microbatch t (zeros once drained)
        feed = jnp.where(t < n_micro, xs_local[jnp.minimum(
            t, n_micro - 1)], jnp.zeros_like(acts))
        inp = jnp.where(idx == 0, feed, acts)
        out = stage_fn(local, inp)
        # last device emits microbatch t-(P-1) at tick t
        emit_t = t - (P - 1)
        outs = jnp.where(
            (idx == P - 1) & (emit_t >= 0),
            outs.at[jnp.maximum(emit_t, 0)].set(out), outs)
        # rotate activations one hop down the pipe
        acts = jax.lax.ppermute(
            out, axis_name, [(j, (j + 1) % P) for j in range(P)])
        return acts, outs

    _, outs = jax.lax.fori_loop(0, T, tick, (acts, outs))
    # broadcast the last device's outputs to every device
    mask = (idx == P - 1).astype(outs.dtype)
    return jax.lax.psum(outs * mask, axis_name)


def pipeline_apply(stage_fn, stacked_params, x, mesh, axis="pp",
                   n_micro=None):
    """Run x through P pipelined stages.

    stage_fn: (params_slice, x_mb) -> y_mb, same shape in/out.
    stacked_params: pytree whose leaves have leading dim P (one slice
      per stage) — shard leading dim over `axis` for real PP.
    x: (B, ...) with B divisible by n_micro (n_micro >= 1; default
      ``MXTPU_PP_MICROBATCHES``, else P).
    Returns (B, ...) outputs (the composition of all stages).
    """
    from jax.sharding import PartitionSpec

    from .. import mesh as mesh_mod

    P = mesh.shape[axis]
    n_micro = default_microbatches(P) if n_micro is None else int(n_micro)
    if n_micro < 1:
        raise MXNetError(f"n_micro must be >= 1, got {n_micro}")
    B = x.shape[0]
    if B % n_micro:
        raise MXNetError(f"batch {B} must divide into n_micro={n_micro}")
    mb = B // n_micro
    xs = x.reshape((n_micro, mb) + x.shape[1:])

    pspec = jax.tree.map(lambda _: PartitionSpec(axis), stacked_params)
    in_specs = (pspec, PartitionSpec())
    try:
        # cached jit(shard_map) keyed on (stage_fn, mesh, specs, attrs)
        # — a fresh closure per call would retrace every training step
        fn = mesh_mod.spmd_jit(
            _pipeline_sharded, mesh, in_specs, PartitionSpec(),
            stage_fn=stage_fn, axis_name=axis, n_micro=n_micro, P=P)
    except TypeError:
        # unhashable param pytree (dict specs): uncached fallback
        import functools

        fn = jax.jit(jax.shard_map(
            functools.partial(_pipeline_sharded, stage_fn=stage_fn,
                              axis_name=axis, n_micro=n_micro, P=P),
            mesh=mesh, in_specs=in_specs, out_specs=PartitionSpec()))
    out = fn(stacked_params, xs)
    return out.reshape((B,) + x.shape[1:])


# -- compiled pipelined training step ---------------------------------------


def _pp_train_sharded(params, states, xs_local, y_local, lr, *,
                      stage_fn, loss_fn, pp_axis, dp_axis, n_micro, P,
                      momentum):
    """One training step inside shard_map on a ('dp','pp') mesh: the
    rotate-schedule forward, loss over this dp-shard's batch, autodiff
    backward through the schedule (transposed ppermute rotation), a
    psum of loss+grads over 'dp' (params replicate across dp), and the
    SGD-momentum update of the stacked stage params."""
    def _loss(params_):
        out = _pipeline_sharded(params_, xs_local, stage_fn=stage_fn,
                                axis_name=pp_axis, n_micro=n_micro, P=P)
        return jnp.sum(loss_fn(out, y_local))

    loss, grads = jax.value_and_grad(_loss)(params)
    if dp_axis is not None:
        loss = jax.lax.psum(loss, dp_axis)
        grads = jax.tree.map(lambda g: jax.lax.psum(g, dp_axis), grads)
    new_states = jax.tree.map(lambda s, g: momentum * s + g, states,
                              grads)
    new_params = jax.tree.map(lambda w, s: w - lr * s, params,
                              new_states)
    return loss, new_params, new_states


class PipelineTrainStep:
    """A compiled training step for a stack of P uniform stages on a
    ('dp','pp') mesh: ONE pjit'd executable per step.

    >>> step = PipelineTrainStep(stage_fn, mesh, momentum=0.9)
    >>> loss, params, states = step(params, states, x, y, lr=0.1)

    ``params`` is a pytree with leading dim P on every leaf (one slice
    per stage, sharded over 'pp'); ``states`` the momentum buffers of
    the same structure (``init_states`` builds zeros).  ``x``/``y``
    shard over 'dp'; ``lr`` is traced, so schedules never retrace.  The
    executable is cached per (mesh, shapes) — repeat calls at one shape
    are zero-compile, one dispatch (``_imperative.count_dispatch``)."""

    def __init__(self, stage_fn, mesh, loss_fn=None, pp_axis="pp",
                 dp_axis="dp", n_micro=None, momentum=0.9):
        if pp_axis not in mesh.axis_names:
            raise MXNetError(
                f"mesh has no {pp_axis!r} axis (axes: "
                f"{tuple(mesh.axis_names)}) — add pp=N to the mesh "
                "shape to pipeline")
        for a in mesh.axis_names:
            if a not in (pp_axis, dp_axis):
                raise MXNetError(
                    f"PipelineTrainStep runs on ('dp','pp') meshes; "
                    f"axis {a!r} is unsupported here — tensor-parallel "
                    "('mp') composition rides the Trainer whole-step "
                    "path (docs/parallelism.md)")
        self.stage_fn = stage_fn
        self.mesh = mesh
        self.loss_fn = loss_fn or (lambda out, y: (out - y) ** 2)
        self.pp_axis = pp_axis
        self.dp_axis = dp_axis if dp_axis in mesh.axis_names else None
        self.P = int(mesh.shape[pp_axis])
        self.n_micro = (default_microbatches(self.P) if n_micro is None
                        else int(n_micro))
        self.momentum = float(momentum)
        self._fn = None

    def init_states(self, params):
        """Zero momentum buffers matching ``params``."""
        return jax.tree.map(jnp.zeros_like, params)

    def _build(self, params):
        import functools

        from jax.sharding import PartitionSpec as PS

        pspec = jax.tree.map(lambda _: PS(self.pp_axis), params)
        # batch arrives microbatch-major (n_micro, mb, ...): dim 1 — the
        # per-microbatch batch — shards over 'dp'; the microbatch dim is
        # the schedule's loop index and stays whole on every device
        data = PS(None, self.dp_axis) if self.dp_axis else PS()
        body = functools.partial(
            _pp_train_sharded, stage_fn=self.stage_fn,
            loss_fn=self.loss_fn, pp_axis=self.pp_axis,
            dp_axis=self.dp_axis, n_micro=self.n_micro, P=self.P,
            momentum=self.momentum)
        return jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(pspec, pspec, data, data, PS()),
            out_specs=(PS(), pspec, pspec)))

    def __call__(self, params, states, x, y, lr):
        from ... import _imperative

        mb_total = self.n_micro
        B = int(x.shape[0])
        if B % mb_total:
            raise MXNetError(
                f"batch {B} must divide into n_micro={mb_total}")
        dp = (int(self.mesh.shape[self.dp_axis])
              if self.dp_axis else 1)
        if B % (mb_total * dp):
            raise MXNetError(
                f"batch {B} must divide across dp={dp} shards x "
                f"n_micro={mb_total} microbatches")
        xs = x.reshape((mb_total, B // mb_total) + tuple(x.shape[1:]))
        ys = y.reshape((mb_total, B // mb_total) + tuple(y.shape[1:]))
        if self._fn is None:
            self._fn = self._build(params)
        lr = jnp.asarray(lr, jnp.float32)
        _imperative.count_dispatch()
        loss, new_params, new_states = self._fn(params, states, xs, ys,
                                                lr)
        return loss, new_params, new_states
