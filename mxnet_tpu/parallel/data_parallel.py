"""SPMD data-parallel training: the whole-step compiled path.

Ref: §3.3 of SURVEY.md — Trainer.step's kvstore push/pull pair becomes a
psum INSIDE the compiled step ("TPU translation: push+pull → psum over
ICI mesh axis inside the step computation; update_on_kvstore → sharded
optimizer state").  This module is that north-star path: ONE jitted XLA
computation per training step containing forward, backward, gradient
all-reduce (inserted by GSPMD from shardings) and the optimizer update,
with parameter donation for in-place update.

Works with any HybridBlock + gluon Loss + optimizer name.  The eager
Trainer (gluon/trainer.py) stays for MXNet-parity semantics; this class
is the performance path the bench uses.

What the host does in a step is always on record: `step()` reads the
clock at the four boundaries of its three phases (the put of the batch,
the key and the two scalars, the enqueue of the compiled step) and
appends one tuple to a bounded log, `step_log()`, with what jax spent
making programs inside the call (`profiler.compile_account()`: zeros
for a warm step).  The profiler section `dataParallelStep` is summed
from that log; `build()` keeps its own phases in `build_record`.  The
same phases are `profiler.op_scope` spans (`dp.build`,
`dp.build.params`, `dp.step`, `dp.step.put`, ...), so a
`jax.profiler` trace holds them on its host plane, and the compiled
step's instructions carry `forward` / `optimizer` in their `op_name`
(backward is `transpose(jvp(forward))`), so a device trace splits by
phase (docs/observability.md, "Device trace").
"""
from __future__ import annotations

import collections
import functools
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from .. import autograd
from .. import profiler as _profiler
from .. import random as _random
from ..base import MXNetError
from ..ndarray.ndarray import NDArray, _wrap
from . import mesh as mesh_mod

# one record a `step()` call, oldest first: (trainer serial, t, begin_ns,
# put_ns, args_ns, enqueue_ns, put_bytes, trace_ns, lower_ns,
# compile_ns, cache_loads).  `begin_ns` is time.perf_counter_ns() when
# the put began; the three after it are the phases' nanoseconds; the
# last four what jax spent making programs inside the call's `dp.step`
# (profiler.compile_account(): the union of each kind, a load inside
# its compile).  Always on: the cost is four clock reads, a tuple and an
# append, and the readers run where nothing was armed beforehand.
_step_log = collections.deque(maxlen=4096)

# what `build()` did, in the clock and units of the step log: begin_ns,
# then nanoseconds of its two phases and of the whole, then the compile
# account booked inside `dp.build`
BuildRecord = collections.namedtuple(
    "BuildRecord", "serial begin_ns params_ns opt_state_ns total_ns "
    "trace_ns lower_ns compile_ns cache_loads")
_built = 0          # trainers built so far; the newest one's serial
# [children wrapped in a jax.checkpoint, trainers built with remat=True]
_remat_built = [0, 0]
# the clock, `_built` and `_remat_built` when the section's window opened
_window = (0, 0, (0, 0))
_live = weakref.WeakValueDictionary()   # serial -> trainer, while it lives


def step_log(last=None):
    """The newest `last` records of the step log (all that are kept,
    at most 4096, by default), oldest first."""
    log = list(_step_log)
    return log if last is None else log[max(len(log) - last, 0):]


def live_trainers():
    """The trainers built and still alive, oldest first: where a reader
    of a model's non-trainable state starts (`aux_params()`), as
    `step_log()` is where a reader of the spans does."""
    return [_live[serial] for serial in sorted(_live)]


def data_parallel_step_stats():
    """The `dataParallelStep` profiler section: the step log's records
    since the window opened, summed.  A window of more steps than the
    log keeps reports the newest 4096 of them.  `remat_children` counts
    the child blocks the trainers built in the window wrapped in a
    `jax.checkpoint`; `remat_saves` names what that checkpoint's policy
    keeps, each name with the number of those trainers."""
    opened_ns, built_then, (children_then, remat_then) = _window
    records = [r for r in step_log() if r[2] >= opened_ns]

    def ms(field):
        return round(sum(r[field] for r in records) / 1e6, 3)

    trainers = _remat_built[1] - remat_then
    return {"steps": len(records), "builds": _built - built_then,
            "put_ms": ms(3), "args_ms": ms(4), "enqueue_ms": ms(5),
            "put_bytes": sum(r[6] for r in records),
            "remat_children": _remat_built[0] - children_then,
            "remat_saves": dict.fromkeys(_remat_saves(), trainers)
            if trainers else {}}


def reset_data_parallel_step_stats():
    global _window
    _window = (time.perf_counter_ns(), _built, tuple(_remat_built))


_step_rows = _profiler.rows_table(
    "Data-Parallel Step (host side)",
    (("steps", "steps"),
     ("trainers built", "builds"),
     ("batch put (ms)", "put_ms"),
     ("key and scalars (ms)", "args_ms"),
     ("step enqueue (ms)", "enqueue_ms"),
     ("bytes put", "put_bytes"),
     ("remat: children checkpointed", "remat_children")))


def _step_table(stats):
    out = _step_rows(stats)
    for name in sorted(stats["remat_saves"]):
        out.append(f"{'remat keeps[' + name + '] (trainers)':<40}"
                   f"{stats['remat_saves'][name]:>12}")
    return out


_profiler.register_section(
    "dataParallelStep", data_parallel_step_stats,
    reset_data_parallel_step_stats, _step_table)


def _remat_saves():
    """The names `remat=True` keeps across a checkpoint: every output
    an op names for it (`ops.registry.RESIDUAL_NAMES`: the flash
    kernels' output and row statistic, the gated delta rule's output,
    the SSD scan's output)."""
    from ..ops import registry

    return tuple(name for names in registry.RESIDUAL_NAMES.values()
                 for name in names)


@functools.cache
def _remat_policy():
    """ONE policy object a process: JAX caches a checkpoint's partial
    evaluation by the policy's identity, and `save_only_these_names`
    makes a new closure a call.  A policy a layer would give every
    layer jaxprs of its own: nothing shared in the lowered module, and
    the TPU compiler then names the grouped products' kernels without
    the phase prefix the device trace's readers go by."""
    return jax.checkpoint_policies.save_only_these_names(*_remat_saves())


class DataParallelTrainer:
    """Compiled SPMD train step over a device mesh.

    batch axis sharded on 'dp'; params replicated (or tp-sharded via
    shard_params=True); grads psum'ed by GSPMD; optimizer fused in-step.
    """

    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, shard_params=False, donate=True,
                 shard_opt_states=False, compute_dtype=None, remat=False,
                 param_spec_fn=None, accum_steps=1):
        self.block = block
        self.loss_fn = loss_fn
        self.mesh = mesh if mesh is not None else mesh_mod.make_mesh()
        # gradient accumulation (ref: grad_req='add' + Trainer.step on
        # the accumulated batch): the global batch is split into
        # `accum_steps` micro-batches scanned INSIDE the compiled step —
        # activation memory scales with batch/accum_steps while the
        # optimizer sees the exact full-batch mean gradient.  TPU-first
        # form of the reference's python-loop accumulation: one XLA
        # computation, no per-micro-batch dispatch.
        self._accum = int(accum_steps)
        if self._accum < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        # multi-precision training (ref: MXNet fp16 + fp32 master weights,
        # optimizer_op multi_mp_sgd; TPU-first: bf16 feeds the MXU at full
        # rate, fp32 feeds it at ~1/4): master params + optimizer states
        # stay fp32, forward/backward run in `compute_dtype`
        self._compute_dtype = jnp.dtype(compute_dtype) \
            if compute_dtype is not None else None
        opt_params = dict(optimizer_params or {})
        self._lr = float(opt_params.pop("learning_rate", 0.01))
        self._opt_name = optimizer
        self._opt_params = opt_params
        self._shard_params = shard_params
        # optional (name, shape) -> PartitionSpec-or-None override: the
        # hook for non-tp layouts (e.g. expert parallelism: shard
        # MoEFFN's expert-stacked params over an 'ep' axis — see
        # parallel/moe.gluon_moe_param_spec_fn); None falls through to
        # the default rule
        self._param_spec_fn = param_spec_fn
        self._donate = donate
        # ZeRO-style: optimizer state sharded over 'dp'; XLA inserts the
        # gather/scatter collectives (ref: kvstore_dist_server.h
        # server-side sharded update, SURVEY §3.3 "update_on_kvstore →
        # sharded optimizer state")
        self._shard_opt_states = shard_opt_states
        # rematerialization (jax.checkpoint): don't store forward
        # activations across checkpoint boundaries — recompute them
        # during backward.  Applied PER DIRECT CHILD BLOCK of the model
        # (a single outer checkpoint would recompute everything and
        # still materialize every residual at once — no peak-HBM win);
        # children holding aux-mutating params (BatchNorm moving stats)
        # stay exact.  RECOMPUTED: everything of a child but what the
        # flash attention kernels name (projections, rotary, gates,
        # norms, feed-forward and expert layers; the kernels' q, k, v
        # come from the recomputed projections).  KEPT, besides the
        # child's inputs: a flash kernel's output and its row statistic
        # (`flash_attention.RESIDUAL_NAMES`), b*s*h*d x itemsize +
        # 4*b*h*s bytes an attention: the backward kernels read both
        # whether kept or recomputed, so keeping them costs capacity
        # and no traffic, and the forward kernel runs once; and the
        # gated delta rule's output (`linear_attention.RESIDUAL_NAMES`),
        # so the rule is not run again for what follows it.  Lowered for
        # a CPU the names sit in the dispatch's dropped TPU branch, and
        # the XLA form of attention is recomputed whole.
        # Trades ~1/3 more FLOPs, less the attention forward's, for
        # ~O(depth) less HBM (the reference's closest analogue is
        # mirror/memonger).
        self._remat = bool(remat)
        self._step_fn = None
        self._many_fns = {}
        self._n_inputs = 1
        self._named = None      # [(name, Parameter)]
        self._params = None     # list of raw jax arrays (device, sharded)
        self._states = None     # optimizer state pytree per param
        self._t = 0
        self._build_record = None

    # -- param plumbing ------------------------------------------------------

    def _gather_params(self, sample_x):
        if self.block._active is False:
            self.block.hybridize()
        # one eager probe to finish deferred init, where any is pending
        # (a model whose shapes are all stated needs none: at 16k tokens
        # an eager float32 forward is a second program to compile and
        # gigabytes of intermediates for nothing)
        if any(p._deferred_init is not None
               for p in self.block.collect_params().values()):
            if isinstance(sample_x, tuple):
                probe = self.block(*sample_x)
            else:
                probe = self.block(sample_x)
            if isinstance(probe, (list, tuple)):
                for p in probe:
                    p.wait_to_read()
        self._named = self.block._ordered_params()
        from jax.sharding import NamedSharding

        params = []
        self._param_shardings = []
        self._custom_spec = []  # which params param_spec_fn placed
        for name, p in self._named:
            raw = p.data()._data
            from jax.sharding import PartitionSpec

            spec = None
            custom = False
            if self._param_spec_fn is not None:
                spec = self._param_spec_fn(name, raw.shape)
                custom = spec is not None
            if spec is None:
                if self._shard_params:
                    spec = mesh_mod.shard_param_spec(raw.shape, self.mesh)
                else:
                    spec = PartitionSpec()
            self._custom_spec.append(custom)
            sh = NamedSharding(self.mesh, spec)
            # explicit copy: device_put may alias `raw` (same device), and
            # the step donates its param inputs — donating an aliased
            # buffer would delete the block's own weights out from under
            # eager use (`Buffer has been deleted or donated`)
            params.append(mesh_mod.global_put(jnp.array(raw, copy=True),
                                              sh))
            self._param_shardings.append(sh)
        self._params = tuple(params)
        if self._param_spec_fn is not None and not any(self._custom_spec):
            # an explicitly-passed spec fn that placed NOTHING is a
            # misconfiguration (e.g. a custom block prefix the matcher
            # doesn't see) — training would silently replicate what the
            # user asked to shard
            raise MXNetError(
                "param_spec_fn matched no parameters; check the "
                "parameter names it filters on (e.g. "
                "gluon_moe_param_spec_fn expects the default 'moeffn' "
                "prefix)")
        self._trainable = [p.grad_req != "null" for _, p in self._named]

    def _opt_state_sharding(self, shape):
        """dp-sharded NamedSharding for one optimizer-state tensor:
        shard the largest dp-divisible axis; replicate if none."""
        from jax.sharding import NamedSharding, PartitionSpec

        dp = self.mesh.shape.get("dp", 1)
        dims = [None] * len(shape)
        if self._shard_opt_states and dp > 1:
            for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
                if shape[i] % dp == 0 and shape[i] >= dp:
                    dims[i] = "dp"
                    break
        return NamedSharding(self.mesh, PartitionSpec(*dims))

    def _place_state(self, raw, param_sharding=None, custom=False):
        z = jnp.zeros_like(raw)
        # a param placed by param_spec_fn (e.g. experts over 'ep')
        # keeps its optimizer state under the SAME sharding — a
        # replicated Adam state for an ep-sharded weight would cost
        # ep x the memory the sharding saved.  Default tp-sharded
        # params (shard_params=True) keep the ZeRO dp placement.
        if custom:
            spec = getattr(param_sharding, "spec", None)
            if spec is not None and any(s is not None for s in spec):
                return mesh_mod.global_put(z, param_sharding)
        return mesh_mod.global_put(z, self._opt_state_sharding(z.shape))

    def _init_opt_states(self):
        name = self._opt_name
        states = []
        # built below; stored as a tuple to keep jit pytree structure stable
        for raw, sh, custom, trainable in zip(self._params,
                                              self._param_shardings,
                                              self._custom_spec,
                                              self._trainable):
            if not trainable:
                states.append(None)
            elif name == "sgd" and self._opt_params.get("momentum", 0):
                states.append(self._place_state(raw, sh, custom))
            elif name in ("adam", "adamw", "lamb"):
                states.append((self._place_state(raw, sh, custom),
                               self._place_state(raw, sh, custom)))
            elif name == "sgd":
                states.append(None)
            else:
                raise MXNetError(
                    f"DataParallelTrainer supports sgd/adam/adamw/lamb, "
                    f"got {name!r}")
        self._states = tuple(states)

    # -- the compiled step --------------------------------------------------

    def _build_step(self):
        from jax.sharding import NamedSharding, PartitionSpec

        block, loss_block = self.block, self.loss_fn
        named = self._named
        trainable = self._trainable
        opt_name = self._opt_name
        op = dict(self._opt_params)
        momentum = float(op.get("momentum", 0.0))
        wd = float(op.get("wd", 0.0))
        beta1 = float(op.get("beta1", 0.9))
        beta2 = float(op.get("beta2", 0.999))
        eps = float(op.get("epsilon", 1e-8))
        clip = op.get("clip_gradient")

        from ..gluon.block import _tracing

        cdt = self._compute_dtype

        def _to_compute(r):
            if cdt is not None and jnp.issubdtype(r.dtype, jnp.floating):
                return r.astype(cdt)
            return r

        # `forward` and `optimizer` below name the program's phases in
        # every instruction's op_name: jit(step)/jvp(forward)/...,
        # .../transpose(jvp(forward))/..., .../optimizer/...  Metadata
        # only; a reader maps device events to phases through them
        @jax.named_scope("forward")
        def forward_loss(param_raws, x_raw, y_raw, key):
            orig_dtypes = [r.dtype for r in param_raws]
            if cdt is not None:
                # trainable params only: non-trainables (BN moving
                # stats) must stay fp32 so their EMA isn't quantized to
                # bf16 every step — the BN kernel does its stats math
                # in fp32 regardless
                param_raws = tuple(
                    _to_compute(r) if tr else r
                    for r, tr in zip(param_raws, trainable))
                if isinstance(x_raw, tuple):
                    x_raw = tuple(_to_compute(r) for r in x_raw)
                else:
                    x_raw = _to_compute(x_raw)
            params = [p for _, p in named]
            old = [p._traced_value for p in params]
            prev = getattr(_tracing, "active", False)
            _tracing.active = True
            tok = _random.push_trace_key(key)
            wrappers = [_wrap(r) for r in param_raws]
            try:
                for p, w in zip(params, wrappers):
                    p._traced_value = w
                with autograd.pause(train_mode=True):
                    if isinstance(x_raw, tuple):
                        out = block.forward(*(_wrap(r) for r in x_raw))
                    else:
                        out = block.forward(_wrap(x_raw))
                    loss = loss_block(out, _wrap(y_raw))
            finally:
                _random.pop_trace_key(tok)
                _tracing.active = prev
                for p, o in zip(params, old):
                    p._traced_value = o
            # aux side effects (BatchNorm moving stats): wrappers mutated
            # in place during forward; surface as aux outputs (cast back
            # to the master dtype so bf16 never leaks into master params)
            aux = tuple(w._data.astype(d) for w, d in
                        zip(wrappers, orig_dtypes))
            return jnp.mean(loss._data.astype(jnp.float32)), aux

        def apply_opt(raw, g, state, lr, t):
            if clip is not None:
                g = jnp.clip(g, -clip, clip)
            if opt_name == "sgd":
                g = g + wd * raw
                if momentum:
                    new_m = momentum * state - lr * g
                    return raw + new_m, new_m
                return raw - lr * g, None
            m, v = state
            if opt_name != "adamw":
                g = g + wd * raw
            nm = beta1 * m + (1 - beta1) * g
            nv = beta2 * v + (1 - beta2) * jnp.square(g)
            mhat = nm / (1 - beta1 ** t)
            vhat = nv / (1 - beta2 ** t)
            upd = mhat / (jnp.sqrt(vhat) + eps)
            if opt_name == "adamw":
                upd = upd + wd * raw
            if opt_name == "lamb":
                wn = jnp.linalg.norm(raw)
                un = jnp.linalg.norm(upd)
                ratio = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
                upd = ratio * upd
            return raw - lr * upd, (nm, nv)

        loss_fn_for_grad = forward_loss
        if self._remat:
            _remat_built[1] += 1
            if not self._apply_child_remat():
                # no wrappable children (flat model): checkpoint the
                # whole forward — full recompute but for what the
                # policy keeps, saves only the head residuals
                loss_fn_for_grad = jax.checkpoint(forward_loss,
                                                  policy=_remat_policy())

        accum = self._accum

        def _grads_once(params, x, y, key):
            return jax.value_and_grad(
                loss_fn_for_grad, has_aux=True)(params, x, y, key)

        def _grads_accum(params, x, y, key):
            """Micro-batch scan: split the leading batch axis into
            (accum, B/accum), accumulate f32 grads, average.  Equal
            micro sizes make mean-of-means == full-batch mean, so the
            result is bitwise the same contract as _grads_once."""
            def split(a):
                b = a.shape[0]
                if b % accum:
                    raise ValueError(
                        f"batch {b} not divisible by accum_steps {accum}")
                return a.reshape((accum, b // accum) + a.shape[1:])

            xs = tuple(split(v) for v in x) if isinstance(x, tuple) \
                else split(x)
            ys = split(y)
            keys = jax.random.split(key, accum)

            def body(carry, inp):
                gsum, loss_sum = carry
                xi, yi, ki = inp
                (loss, aux), g = _grads_once(params, xi, yi, ki)
                gsum = jax.tree.map(
                    lambda s, gi: s + gi.astype(jnp.float32), gsum, g)
                return (gsum, loss_sum + loss), aux

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, loss_sum), auxs = jax.lax.scan(
                body, (g0, jnp.float32(0)), (xs, ys, keys))
            grads = jax.tree.map(
                lambda s, p: (s / accum).astype(p.dtype), gsum, params)
            # aux (BN moving stats): the last micro-batch's update —
            # the same value a sequential grad_req='add' loop leaves
            aux = jax.tree.map(lambda a: a[-1], auxs)
            return (loss_sum / accum, aux), grads

        mesh = self.mesh

        # Named `step_phases`, not `step`, on purpose: JAX leaves metadata
        # out of the persistent compile cache's key, so under the old name
        # a cache filled by a build without the scopes above (the same
        # arithmetic) would be LOADED in this one's place, and the
        # compiled text would name no phase.  The program's name is in
        # the key.  Whoever renames a scope without touching the
        # arithmetic has to rename this too.  (Keying by metadata instead
        # puts source paths and the tracing call stack into the key: one
        # program, a key per checkout and per caller; PERF.md, PR 27.)
        def step_phases(params, states, x, y, key, lr, t):
            with mesh_mod.auto_partitioned(mesh):
                (loss, aux), grads = (
                    _grads_accum if accum > 1 else _grads_once)(
                        params, x, y, key)
            new_params, new_states = [], []
            with jax.named_scope("optimizer"):
                for raw, g, st, tr, new_raw in zip(params, grads, states,
                                                   trainable, aux):
                    if not tr:
                        # non-trainable: the aux-updated value (BN stats)
                        new_params.append(new_raw)
                        new_states.append(st)
                    else:
                        nw, ns = apply_opt(raw, g, st, lr, t)
                        new_params.append(nw)
                        new_states.append(ns)
            return loss, tuple(new_params), tuple(new_states)

        data_sh = mesh_mod.batch_sharding(self.mesh)
        repl = NamedSharding(self.mesh, PartitionSpec())
        x_sh = tuple(data_sh for _ in range(self._n_inputs)) \
            if self._n_inputs > 1 else data_sh
        # optimizer states keep their (possibly dp-sharded / ZeRO)
        # placement in and out of the step
        state_sh = jax.tree.map(lambda s: s.sharding, self._states)
        in_shardings = (tuple(self._param_shardings),
                        state_sh, x_sh, data_sh, repl, repl, repl)
        # pin param output shardings to the input layout, else GSPMD may
        # pick a different layout for returned params and the next call's
        # in_shardings check rejects them
        out_shardings = (repl, tuple(self._param_shardings), state_sh)
        donate = (0, 1) if self._donate else ()
        self._data_sh = data_sh
        self._step_core = step_phases
        self._in_shardings = in_shardings
        self._out_shardings = out_shardings
        self._step_fn = jax.jit(step_phases, in_shardings=in_shardings,
                                out_shardings=out_shardings,
                                donate_argnums=donate)
        self._many_fns = {}

    def _build_step_many(self, n_steps, stacked):
        """Jit a lax.scan over `n_steps` applications of the step body —
        the bulk-execution path (ref: MXNET_EXEC_BULK_EXEC_TRAIN pushes
        whole graph segments to the engine in one go; here the whole
        K-step TRAINING RUN is one XLA computation, so per-dispatch
        latency is paid once per K steps instead of every step).

        `stacked`: True → x/y carry a leading (K,) axis with one
        minibatch per step; False → the same device-resident batch is
        reused every step (synthetic benchmark semantics).
        """
        step = self._step_core
        (param_sh, state_sh, x_sh, y_sh, repl, _, _) = self._in_shardings

        def many(params, states, x, y, keys, lr, t0):
            def body(carry, inp):
                params, states, t = carry
                if stacked:
                    key, xi, yi = inp
                else:
                    key = inp
                    xi, yi = x, y
                loss, params, states = step(params, states, xi, yi,
                                            key, lr, t)
                return (params, states, t + 1.0), loss
            xs = (keys, x, y) if stacked else keys
            (params, states, _), losses = jax.lax.scan(
                body, (params, states, t0), xs)
            return losses, params, states

        if stacked:
            from jax.sharding import NamedSharding, PartitionSpec

            def _stack_sh(sh):
                return NamedSharding(self.mesh,
                                     PartitionSpec(None, *sh.spec))
            x_in = jax.tree.map(_stack_sh, x_sh)
            y_in = _stack_sh(y_sh)
        else:
            x_in, y_in = x_sh, y_sh
        fn = jax.jit(
            many,
            in_shardings=(param_sh, state_sh, x_in, y_in, repl, repl, repl),
            out_shardings=(repl, param_sh, state_sh),
            donate_argnums=(0, 1) if self._donate else ())
        self._many_fns[(n_steps, stacked)] = fn
        return fn

    def _apply_child_remat(self):
        """Wrap each eligible direct child block's forward in
        jax.checkpoint so backward recomputes that child instead of
        storing its activations.  Returns the number of children
        wrapped.  Eligible: HybridBlock children whose params all carry
        gradients (aux-mutating children — BatchNorm moving stats —
        must stay exact: their in-place wrapper updates would leak
        checkpointed tracers).  Idempotent per trainer."""
        if getattr(self, "_remat_applied", False):
            return self._remat_count
        self._remat_applied = True
        self._remat_count = 0
        children = getattr(self.block, "_children", None) or {}
        for name, child in list(children.items()):
            params = child.collect_params()
            if any(p.grad_req == "null" for p in params.values()):
                continue
            child.forward = self._make_remat_forward(child.forward)
            self._remat_count += 1
        _remat_built[0] += self._remat_count
        return self._remat_count

    @staticmethod
    def _make_remat_forward(orig):
        def fwd(*args):
            if not args or not all(isinstance(a, NDArray) for a in args):
                return orig(*args)  # non-array calling pattern: exact

            def pure(*raws):
                outs = orig(*[_wrap(r) for r in raws])
                if isinstance(outs, (tuple, list)):
                    return tuple(o._data for o in outs)
                return (outs._data,)

            outs = jax.checkpoint(pure, policy=_remat_policy())(
                *[a._data for a in args])
            wrapped = [_wrap(o) for o in outs]
            return wrapped[0] if len(wrapped) == 1 else tuple(wrapped)

        return fwd

    # -- public api ---------------------------------------------------------

    def build(self, x):
        """Place the parameters and the optimizer state and make the
        step's `jit` for example input(s) `x`, without running a step
        (needed before `load_states` on a fresh trainer); the first
        `step()` traces and compiles it.  Idempotent."""
        global _built
        if self._step_fn is not None:
            return
        multi = isinstance(x, (tuple, list))
        if multi:
            x = tuple(v._data if isinstance(v, NDArray) else v for v in x)
            self._n_inputs = len(x)
            probe = tuple(_wrap(jnp.asarray(v[:2])) for v in x)
        else:
            if isinstance(x, NDArray):
                x = x._data
            self._n_inputs = 1
            probe = _wrap(jnp.asarray(x[:2]))
        now = time.perf_counter_ns
        with _profiler.op_scope("dp.build", "trainer") as scope:
            begin = now()
            with _profiler.op_scope("dp.build.params", "trainer"):
                self._gather_params(probe)
            params_end = now()
            with _profiler.op_scope("dp.build.opt_state", "trainer"):
                self._init_opt_states()
            states_end = now()
            self._build_step()
            scope.note(params=len(self._named))
            compiled = _profiler.compile_account()
            end = now()
        _built += 1
        self._serial = _built
        _live[_built] = self
        self._build_record = BuildRecord(
            _built, begin, params_end - begin, states_end - params_end,
            end - begin, compiled.trace_ns, compiled.lower_ns,
            compiled.compile_ns, compiled.cache_loads)

    @property
    def build_record(self):
        """The `BuildRecord` of this trainer's `build()`, None before
        it: where it began, how long the parameters' placement
        (`dp.build.params`), the optimizer state (`dp.build.opt_state`)
        and the whole took, and what jax spent making programs inside
        it."""
        return self._build_record

    def step(self, x, y):
        """One compiled SPMD step; returns scalar loss NDArray.

        `x` may be a single array or a tuple/list of arrays for
        multi-input blocks (BERT: tokens/types/targets/...); every
        input is batch-sharded on the 'dp' mesh axis.
        """
        now = time.perf_counter_ns
        t = self._t + 1
        multi = isinstance(x, (tuple, list))
        if multi:
            x = tuple(v._data if isinstance(v, NDArray) else v for v in x)
        elif isinstance(x, NDArray):
            x = x._data
        if isinstance(y, NDArray):
            y = y._data
        # before the step's span, never inside it: `dp.build` and the
        # `dp.step`s are disjoint, and so are their compile accounts
        self.build(x)
        with _profiler.op_scope("dp.step", "trainer", t=t, step_num=t, _r=1):
            begin = now()
            with _profiler.op_scope("dp.step.put", "trainer") as scope:
                data_sh = self._data_sh
                if multi:
                    x = tuple(mesh_mod.global_put(jnp.asarray(v), data_sh)
                              for v in x)
                    put_bytes = sum(v.nbytes for v in x)
                else:
                    x = mesh_mod.global_put(jnp.asarray(x), data_sh)
                    put_bytes = x.nbytes
                y = mesh_mod.global_put(jnp.asarray(y), data_sh)
                put_bytes += y.nbytes
                scope.note(bytes=put_bytes)
            put_end = now()
            with _profiler.op_scope("dp.step.args", "trainer"):
                self._t = t
                key = _random.next_key()
                lr = jnp.asarray(self._lr, jnp.float32)
                t_arg = jnp.asarray(float(t), jnp.float32)
            args_end = now()
            with _profiler.op_scope("dp.step.enqueue", "trainer"):
                loss, self._params, self._states = self._step_fn(
                    self._params, self._states, x, y, key, lr, t_arg)
            enqueue_end = now()
            compiled = _profiler.compile_account()
            _step_log.append((self._serial, t, begin, put_end - begin,
                              args_end - put_end, enqueue_end - args_end,
                              put_bytes, compiled.trace_ns,
                              compiled.lower_ns, compiled.compile_ns,
                              compiled.cache_loads))
        return _wrap(loss)

    def step_many(self, x, y, n_steps=None):
        """Run K training steps as ONE compiled XLA computation
        (lax.scan over the step body); returns the per-step losses as a
        (K,) NDArray.

        Two calling modes:
        - ``step_many(xs, ys, n_steps=None)`` where ``xs``/``ys`` carry
          a leading (K,) axis: one minibatch per scanned step (bulk
          training over K pre-staged batches).
        - ``step_many(x, y, n_steps=K)`` with plain batch shapes: the
          same batch is re-used K times (synthetic-benchmark semantics,
          ref: benchmark_score.py --benchmark 1).

        Numerically identical to K ``step()`` calls — the same PRNG key
        sequence is consumed — but per-dispatch latency is paid once.
        """
        multi = isinstance(x, (tuple, list))
        if multi:
            x = tuple(v._data if isinstance(v, NDArray) else v for v in x)
        elif isinstance(x, NDArray):
            x = x._data
        if isinstance(y, NDArray):
            y = y._data
        stacked = n_steps is None
        if stacked:
            n_steps = int((x[0] if multi else x).shape[0])
        if n_steps < 1:
            raise MXNetError(f"step_many needs n_steps >= 1, got {n_steps}")
        with _profiler.op_scope("dp.step_many", "trainer", n_steps=n_steps):
            # build the single-step path first (shapes from ONE minibatch)
            probe = tuple(v[0] for v in x) if (stacked and multi) else \
                (x[0] if stacked else x)
            self.build(probe)
            fn = self._many_fns.get((n_steps, stacked)) or \
                self._build_step_many(n_steps, stacked)
            data_sh = self._data_sh
            from jax.sharding import NamedSharding, PartitionSpec

            if stacked:
                put_sh = NamedSharding(self.mesh,
                                       PartitionSpec(None, *data_sh.spec))
            else:
                put_sh = data_sh
            with _profiler.op_scope("dp.step_many.put", "trainer"):
                if multi:
                    x = tuple(mesh_mod.global_put(jnp.asarray(v), put_sh)
                              for v in x)
                else:
                    x = mesh_mod.global_put(jnp.asarray(x), put_sh)
                y = mesh_mod.global_put(jnp.asarray(y), put_sh)
            # consume the SAME key sequence n individual step() calls would
            keys = jnp.stack([_random.next_key() for _ in range(n_steps)])
            t0 = jnp.asarray(float(self._t + 1), jnp.float32)
            lr = jnp.asarray(self._lr, jnp.float32)
            self._t += n_steps
            with _profiler.op_scope("dp.step_many.enqueue", "trainer"):
                losses, self._params, self._states = fn(
                    self._params, self._states, x, y, keys, lr, t0)
        return _wrap(losses)

    @property
    def learning_rate(self):
        return self._lr

    def set_learning_rate(self, lr):
        self._lr = float(lr)

    # -- sharded checkpoint/resume ------------------------------------------

    @staticmethod
    def _shard_id(index, shape):
        """Stable on-disk id of one shard: 'start:stop/...' per dim.
        This string is the checkpoint contract — used by both save and
        load."""
        return "/".join(
            f"{sl.start or 0}:{sl.stop if sl.stop is not None else dim}"
            for sl, dim in zip(index, shape)) or "full"

    def _ckpt_tensors(self):
        """Flat {key: jax.Array} over params + optimizer states."""
        out = {}
        for (name, _), raw in zip(self._named, self._params):
            out[f"param::{name}"] = raw
        for i, st in enumerate(self._states):
            if st is None:
                continue
            leaves = st if isinstance(st, tuple) else (st,)
            for j, leaf in enumerate(leaves):
                out[f"state::{i}::{j}"] = leaf
        return out

    def save_states(self, prefix, async_save=False):
        """Sharded SPMD checkpoint (ref: trainer.save_states + Module
        do_checkpoint, SURVEY §5 checkpoint mechanisms).

        Each process writes ONLY its addressable shards — no cross-host
        gather (the round-1 gap: sync_to_block was a full gather and
        optimizer state wasn't saved at all). Layout:
        ``{prefix}-meta.npz`` (step counter, lr, mesh shape) +
        ``{prefix}-shards-p{rank}.npz`` per process.

        ``async_save=True`` snapshots device shards to host memory
        synchronously (cheap; must happen before the next donated step
        invalidates the buffers) and pushes the file write onto the
        engine's host pool so training overlaps the disk IO (orbax-style
        async checkpointing). Returns a future — call ``.result()``
        before relying on the files (it also re-raises any write error).
        """
        if self._step_fn is None:
            raise MXNetError("save_states before the first step: nothing "
                             "to checkpoint yet")
        proc = jax.process_index()
        # D2H snapshot happens NOW in both modes: the step donates param
        # buffers, so device refs must not outlive the next step()
        shard_arrays = {}
        for key, arr in self._ckpt_tensors().items():
            for s in arr.addressable_shards:
                if s.replica_id != 0:
                    continue  # one copy per distinct shard
                sid = self._shard_id(s.index, arr.shape)
                # copy=True: on CPU backends __array__ can be zero-copy,
                # and an aliased view would be clobbered by the next
                # donated step while the async write is in flight
                shard_arrays[f"{key}@@{sid}"] = np.array(s.data,
                                                         copy=True)
        meta = dict(t=np.int64(self._t), lr=np.float64(self._lr),
                    mesh_shape=np.array(
                        [self.mesh.shape[a] for a in self.mesh.axis_names],
                        np.int64),
                    mesh_axes=np.array(list(self.mesh.axis_names)))

        def _write():
            np.savez(f"{prefix}-shards-p{proc}.npz", **shard_arrays)
            if proc == 0:
                np.savez(f"{prefix}-meta.npz", **meta)

        if async_save:
            from .. import engine as _engine

            return _engine.push_host(_write)
        _write()
        return None

    def load_states(self, prefix):
        """Restore a sharded checkpoint onto the SAME mesh topology.

        Each process reads only the shard files covering its addressable
        devices; arrays are rebuilt with
        ``make_array_from_single_device_arrays`` (no host broadcast).
        """
        import glob as _glob

        if self._step_fn is None:
            raise MXNetError("load_states requires a built trainer: call "
                             "trainer.build(example_x) first")
        meta = np.load(f"{prefix}-meta.npz", allow_pickle=False)
        self._t = int(meta["t"])
        self._lr = float(meta["lr"])
        saved_axes = [str(a) for a in meta["mesh_axes"]]
        saved_shape = [int(v) for v in meta["mesh_shape"]]
        cur = [(a, self.mesh.shape[a]) for a in self.mesh.axis_names]
        if list(zip(saved_axes, saved_shape)) != cur:
            raise MXNetError(
                f"checkpoint mesh {list(zip(saved_axes, saved_shape))} != "
                f"current mesh {cur}; resharding on load isn't supported")
        # index shard KEYS across all visible files, but extract payloads
        # LAZILY — each process materializes only the shards covering its
        # own addressable devices (npz members decompress on access)
        files = [np.load(f, allow_pickle=False)
                 for f in sorted(_glob.glob(f"{prefix}-shards-p*.npz"))]
        where = {k: z for z in files for k in z.files}

        def rebuild(key, like):
            pieces = []
            for dev in like.sharding.addressable_devices:
                idx = like.sharding.addressable_devices_indices_map(
                    like.shape)[dev]
                sid = self._shard_id(idx, like.shape)
                z = where.get(f"{key}@@{sid}")
                if z is None:
                    raise MXNetError(
                        f"checkpoint {prefix} missing shard {sid} of {key}")
                pieces.append(jax.device_put(
                    jnp.asarray(z[f"{key}@@{sid}"], like.dtype), dev))
            return jax.make_array_from_single_device_arrays(
                like.shape, like.sharding, pieces)

        new_params = [rebuild(f"param::{name}", raw)
                      for (name, _), raw in zip(self._named, self._params)]
        new_states = []
        for i, st in enumerate(self._states):
            if st is None:
                new_states.append(None)
            elif isinstance(st, tuple):
                new_states.append(tuple(
                    rebuild(f"state::{i}::{j}", leaf)
                    for j, leaf in enumerate(st)))
            else:
                new_states.append(rebuild(f"state::{i}::0", st))
        for z in files:
            z.close()
        self._params = tuple(new_params)
        self._states = tuple(new_states)

    def aux_params(self):
        """{name: numpy array} of the non-trainable parameters as the
        newest step left them (BatchNorm's running statistics, a
        decoder's routing log): the step's aux outputs, read on
        demand.  The block's own Parameters are stale once the trainer
        has taken them; these are the live values."""
        if self._named is None:
            return {}
        return {name: np.asarray(jax.device_get(raw))
                for (name, _), raw, tr in zip(self._named, self._params,
                                              self._trainable) if not tr}

    def sync_to_block(self):
        """Write the trained params back into the block's Parameters."""
        if self._named is None:
            return
        for (name, p), raw in zip(self._named, self._params):
            gathered = jax.device_get(raw)
            from ..ndarray import ndarray as _nd

            p.set_data(_nd.array(np.asarray(gathered)))
