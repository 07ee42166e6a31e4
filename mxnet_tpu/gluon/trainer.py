"""gluon.Trainer (ref: python/mxnet/gluon/trainer.py).

Applies an Optimizer to a set of Parameters with gradient aggregation
through a KVStore.  API-identical to the reference; the aggregation is
XLA collectives (see kvstore.py) so the same user loop scales from one
chip to a pod (the north-star contract: "gluon.Trainer scales across a
pod unchanged").
"""
from __future__ import annotations

from .. import engine as _engine
from .. import kvstore as _kvstore
from .. import optimizer as _opt
from .. import profiler as _profiler
from ..base import MXNetError
from ..telemetry import health as _health
from .parameter import Parameter, ParameterDict

# ---------------------------------------------------------------------------
# step-fusion window counters (surfaced as the "trainerStep" section of
# profiler.dumps(), window-scoped under reset=True like cachedGraph)

_step_stats = {"steps": 0, "params_fused": 0, "buckets_built": 0,
               "dispatches": 0, "whole_step_steps": 0,
               "whole_step_compiles": 0, "whole_step_fallbacks": 0,
               "zero_steps": 0, "zero_fallbacks": 0, "spmd_steps": 0}


def trainer_step_stats():
    """Aggregate Trainer.step() fusion counters since the last reset:
    steps, params_fused (params that rode a multi-tensor update call),
    buckets_built (flat allreduce buckets), dispatches (device
    submissions: update kernels + collectives + replica transfers; a
    compiled whole step counts as ONE), the derived dispatches_per_step,
    and the whole-step path's own counters — whole_step_steps (steps
    that ran as one compiled executable), whole_step_compiles (fresh
    executable signatures; stable after warmup is the no-recompile
    gate), whole_step_fallbacks (whole_step() calls that bypassed to
    the eager fused path), and the ZeRO-1 counters — zero_steps (steps
    whose weight update ran cross-replica-sharded) and zero_fallbacks
    (zero_shard steps that ran unsharded for an ineligible
    configuration) — plus spmd_steps (whole steps that ran on a
    multi-axis mesh via the GSPMD compiler, ``mesh_shape=...``)."""
    s = dict(_step_stats)
    s["dispatches_per_step"] = (round(s["dispatches"] / s["steps"], 2)
                                if s["steps"] else 0.0)
    return s


def reset_trainer_step_stats():
    for k in _step_stats:
        _step_stats[k] = 0


_profiler.register_section(
    "trainerStep", trainer_step_stats, reset_trainer_step_stats,
    _profiler.rows_table(
        "Trainer Step Fusion",
        (("steps", "steps"),
         ("params fused", "params_fused"),
         ("allreduce buckets built", "buckets_built"),
         ("dispatches per step", "dispatches_per_step"),
         ("whole-step compiled steps", "whole_step_steps"),
         ("whole-step compiles", "whole_step_compiles"),
         ("whole-step fallbacks", "whole_step_fallbacks"),
         ("zero-sharded steps", "zero_steps"),
         ("zero-shard fallbacks", "zero_fallbacks"),
         ("spmd mesh steps", "spmd_steps"))))


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None, whole_step=None,
                 zero_shard=None, mesh_shape=None, sharding_plan=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be a ParameterDict or list")
        self._all_params = list(params)
        self._params = [p for p in params if p.grad_req != "null"]
        self._param2idx = {p.name: i for i, p in enumerate(self._params)}
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._optimizer = _opt.create(
            optimizer, param_dict={i: p for i, p in enumerate(self._params)},
            **optimizer_params)
        self._kv_type = kvstore
        self._kvstore = None
        self._update_on_kvstore = update_on_kvstore
        self._compression_params = compression_params
        self._states = [None] * len(self._params)
        self._kv_initialized = False
        self._contexts = None
        # whole-step compilation (ROADMAP item 4): opt-in via the ctor
        # arg or MXTPU_WHOLE_STEP; None defers to the env knob so a
        # deployment can flip the path without code changes
        whole_step_default = whole_step is None
        if whole_step is None:
            from ..base import getenv

            whole_step = getenv("WHOLE_STEP", False, bool)
        self._whole_step = bool(whole_step)
        self._whole_step_compiler = None
        # ZeRO-1 cross-replica weight-update sharding (arXiv 2004.13336):
        # reduce-scatter grads, update only this rank's shard, allgather
        # weights — optimizer state shrinks to 1/world_size per replica.
        # Opt-in via the ctor arg or MXTPU_ZERO_SHARD; None defers to
        # the env knob like whole_step
        if zero_shard is None:
            from ..base import getenv

            zero_shard = getenv("ZERO_SHARD", False, bool)
        self._zero_shard = bool(zero_shard)
        # multi-axis spmd mesh (ROADMAP item 1): a mesh-shape spec
        # ('dp=4,mp=2' / dict) routes whole_step() through the GSPMD
        # SpmdStepCompiler — params shard over 'mp', batch over 'dp',
        # ZeRO state over both — still ONE executable per step.  None
        # defers to MXTPU_MESH_SHAPE; setting a shape implies the
        # whole-step path (the eager pipeline has no multi-axis form).
        if mesh_shape is None:
            from ..parallel.spmd import mesh as _spmd_mesh

            self._mesh_shape = _spmd_mesh.mesh_shape_from_env()
        else:
            from ..parallel.spmd import mesh as _spmd_mesh

            self._mesh_shape = _spmd_mesh.parse_mesh_shape(mesh_shape)
        self._sharding_plan = sharding_plan
        if self._mesh_shape is not None and whole_step_default:
            self._whole_step = True
        if sharding_plan is not None and self._mesh_shape is None:
            raise MXNetError(
                "sharding_plan given but no mesh_shape — pass "
                "mesh_shape='dp=...,mp=...' (or set MXTPU_MESH_SHAPE) "
                "to route steps onto the multi-axis mesh the plan "
                "shards over")
        self._zero_states = {}   # chunk pos -> {rank: tuple(shard NDArrays)}
        self._zero_layout = None  # (per-chunk layout tuple, world)
        self._zero_warned = set()
        # per-step fusion accounting (published into _step_stats by step)
        self._dispatches = 0
        self._buckets = 0
        self._params_fused = 0

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @property
    def optimizer(self):
        return self._optimizer

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def _init_kvstore(self):
        if self._kv_initialized:
            return
        ctxs = self._params[0].list_ctx() if self._params else []
        self._contexts = ctxs
        multi_device = len(ctxs) > 1
        if self._kv_type is None or (not multi_device and
                                     not str(self._kv_type).startswith("dist")):
            self._kvstore = None
        else:
            self._kvstore = _kvstore.create(self._kv_type)
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            if self._update_on_kvstore is None:
                self._update_on_kvstore = bool(self._kvstore._is_dist())
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
            for i, p in enumerate(self._params):
                self._kvstore.init(i, p.list_data()[0:1])
        self._kv_initialized = True

    # -- stepping -----------------------------------------------------------

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce grads + optimizer update (ref: Trainer.step §3.3)."""
        self._init_kvstore()
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None and scaler.enabled and self._update_on_kvstore:
            # server-side optimizer is a pickle snapshot that never sees
            # rescale_grad updates or the overflow skip — applying 2^16-
            # scaled grads there would silently diverge (ref: amp is a
            # local-trainer feature in the reference too)
            raise MXNetError(
                "dynamic loss scaling (amp.scale_loss) is not supported "
                "with update_on_kvstore; use update_on_kvstore=False")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._dispatches = self._buckets = self._params_fused = 0
        ran_zero = False
        with _profiler.op_scope("trainer.step", cat="trainer"):
            if self._zero_shard:
                ran_zero = self._try_zero_step()
            if not ran_zero:
                self._allreduce_grads()
                self._update(ignore_stale_grad)
        _step_stats["steps"] += 1
        _step_stats["dispatches"] += self._dispatches
        _step_stats["buckets_built"] += self._buckets
        _step_stats["params_fused"] += self._params_fused
        if ran_zero:
            _step_stats["zero_steps"] += 1

    def _fusion_enabled(self):
        """The fused step is ON by default; aggregate_num=1 (or
        MXNET_OPTIMIZER_AGGREGATION_SIZE=1) restores the sequential
        one-dispatch-per-parameter behavior exactly."""
        return getattr(self._optimizer, "aggregate_num", 1) > 1

    # -- ZeRO-1 sharded weight update (eager tier) --------------------------

    def _zero_fallback(self, reason):
        """Loud, once-per-reason notice that a zero_shard step ran the
        unsharded path; returns False for the _try_zero_step caller."""
        if reason not in self._zero_warned:
            self._zero_warned.add(reason)
            from ..log import get_logger

            get_logger("mxnet_tpu.trainer").warning(
                "ZeRO-1 sharded update bypassed -> unsharded path: %s",
                reason)
        _step_stats["zero_fallbacks"] += 1
        return False

    def _zero_ineligible_reason(self, ctxs):
        """The eager sharded step's bypass matrix (checked BEFORE the
        plan ticks anything) — every case the fused step already
        recognizes, plus the eager-tier-only dist restriction."""
        if not self._fusion_enabled():
            return "aggregate_num == 1 (sequential step requested)"
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None and scaler.enabled:
            return "amp dynamic loss scaling (the overflow skip is a " \
                "host-side decision)"
        if self._update_on_kvstore and self._kvstore is not None:
            return "update_on_kvstore=True (server-side optimizer)"
        if self._kvstore is None:
            return "no kvstore to reduce over"
        if self._kvstore._compression is not None:
            return "gradient compression (per-key error feedback)"
        if self._kvstore._is_dist():
            return "dist kvstore (the eager sharded step is single-" \
                "process; multi-process ZeRO rides the whole-step path)"
        ctxs0 = tuple(ctxs)
        for p in self._params:
            if getattr(p, "grad_stype", "default") != "default":
                return f"sparse-grad parameter {p.name}"
            if getattr(p, "stype", "default") != "default":
                return f"sparse parameter {p.name}"
            if p.grad_req == "add":
                return f"grad_req='add' on {p.name}"
            if tuple(p.list_ctx()) != ctxs0:
                return "parameters span different context sets"
        return None

    def _try_zero_step(self):
        """Run one ZeRO-1 sharded eager step; returns True when the
        sharded path engaged (False = run the unsharded step instead —
        with a single replica sharding is the identity, silently)."""
        ctxs = list(self._contexts or [])
        if len(ctxs) <= 1:
            return False  # world size 1: nothing to shard
        reason = self._zero_ineligible_reason(ctxs)
        if reason is not None:
            return self._zero_fallback(reason)
        ctx0 = ctxs[0]
        plan, svals, reason = self._optimizer.whole_step_plan(
            list(range(len(self._params))),
            [p.data(ctx0) for p in self._params],
            [None] * len(self._params), zero_world=len(ctxs))
        if reason is not None:
            return self._zero_fallback(reason)
        self._ensure_zero_states(plan, len(ctxs),
                                 dict(enumerate(ctxs)))
        self._zero_eager_run(plan, svals, ctxs)
        return True

    def _zero_eager_run(self, plan, svals, ctxs):
        """reduce-scatter -> shard update -> weight allgather, eagerly:
        the bit-identical sharded twin of _allreduce_grads + _update +
        _broadcast_updated (same pairwise-tree reduce order, same
        ``_fk_*`` kernels over the same flat concatenation — only the
        slice each replica updates, and therefore the optimizer state
        each replica holds, shrinks to 1/world)."""
        from .. import engine as _eng

        n = len(ctxs)
        devs = [c.jax_device() for c in ctxs]
        stats = {"buckets": 0, "dispatches": 0}
        g_shards, w_shards = [], []
        with _profiler.op_scope("reduce_scatter", cat="trainer"):
            for (_k, _s, _n_st, _dt, idxs, _total, padded) in plan:
                vlists = [self._params[j].list_grad() for j in idxs]
                g_shards.append(self._kvstore.zero_reduce_scatter(
                    vlists, padded, devs, stats))
                shard_n = padded // n
                per_rank = []
                for r, ctx in enumerate(ctxs):
                    wflat = _eng.flatten_pad(
                        [self._params[j].data(ctx)._data for j in idxs],
                        padded)
                    per_rank.append(_eng.slice_flat(
                        wflat, r * shard_n, shard_n))
                    stats["dispatches"] += 2
                w_shards.append(per_rank)
        new_w_shards = []
        with _profiler.op_scope("fused_update", cat="trainer"):
            for c, chunk in enumerate(plan):
                per_rank = []
                for r in range(n):
                    new_w = self._optimizer.zero_fused_update(
                        (chunk,), (svals[c],), [w_shards[c][r]],
                        [g_shards[c][r]],
                        [self._zero_states[c][r]])[0]
                    per_rank.append(new_w)
                    stats["dispatches"] += 1
                new_w_shards.append(per_rank)
        with _profiler.op_scope("allgather", cat="trainer"):
            for c, (_k, _s, _n_st, _dt, idxs, _total, _padded) in \
                    enumerate(plan):
                shapes = [tuple(self._params[j].data(ctxs[0]).shape)
                          for j in idxs]
                outs = self._kvstore.zero_allgather(
                    new_w_shards[c], shapes, devs, stats)
                for r, ctx in enumerate(ctxs):
                    for jj, j in enumerate(idxs):
                        self._params[j]._data[ctx]._data = outs[r][jj]
        self._dispatches += stats["dispatches"]
        self._buckets += stats["buckets"]
        # params_fused double-counts per rank above; normalize to the
        # fused path's per-step meaning (each param fused once)
        self._params_fused = len(self._params)

    # -- ZeRO-1 state management (shared by eager and whole-step) -----------

    def _zero_layout_of(self, plan, world):
        return (tuple((c[2], c[3], c[4], c[5], c[6]) for c in plan),
                int(world))

    def _ensure_zero_states(self, plan, world, rank_ctx):
        """Allocate (or adopt from full per-param states) the shard-
        sized optimizer state for every plan chunk on every rank in
        ``rank_ctx`` (rank -> context).  Existing full states (an
        unsharded restart, or a load_states_dict) are flattened, zero-
        padded and sliced — bit-identical adoption — then released, so
        per-replica state memory drops to ~1/world."""
        from ..ndarray import ndarray as _nd_mod
        from ..ndarray.ndarray import NDArray as _ND

        layout = self._zero_layout_of(plan, world)
        if self._zero_layout is not None and self._zero_layout != layout \
                and self._zero_states:
            raise MXNetError(
                "ZeRO-1 shard layout changed mid-run (params, "
                "aggregate_num, MXTPU_KVSTORE_BUCKET_MB, hyperparameter "
                "grouping or world size changed since the shards were "
                "allocated); snapshot with states_dict() and reload "
                "into a fresh Trainer")
        self._zero_layout = layout
        for c, (_k, _s, n_states, dt, idxs, total, padded) in \
                enumerate(plan):
            entry = dict(self._zero_states.get(c) or {})
            missing = [r for r in rank_ctx if r not in entry]
            if not missing:
                continue
            shard_n = padded // world
            full_slots = None
            if n_states and any(self._states[j] for j in idxs):
                import numpy as _np

                full_slots = []
                for slot in range(n_states):
                    parts = []
                    for j in idxs:
                        st = next(iter(self._states[j].values())) \
                            if self._states[j] else None
                        w = self._params[j]
                        if st is None:
                            parts.append(_np.zeros(
                                int(_np.prod(w.shape)), dtype=dt))
                            continue
                        nd_ = st if isinstance(st, _ND) else st[slot]
                        parts.append(nd_.asnumpy().reshape(-1))
                    flat = _np.concatenate(parts) if parts else \
                        _np.zeros(0, dtype=dt)
                    pad = padded - flat.shape[0]
                    if pad:
                        flat = _np.concatenate(
                            [flat, _np.zeros(pad, dtype=flat.dtype)])
                    full_slots.append(flat)
            for r in missing:
                ctx = rank_ctx[r]
                slots = []
                for slot in range(n_states):
                    if full_slots is None:
                        slots.append(_nd_mod.zeros(
                            (shard_n,), dtype=dt, ctx=ctx))
                    else:
                        slots.append(_nd_mod.array(
                            full_slots[slot][r * shard_n:
                                             (r + 1) * shard_n],
                            dtype=dt, ctx=ctx))
                entry[r] = tuple(slots)
            self._zero_states[c] = entry
            for j in idxs:
                self._states[j] = None  # release the full copies

    def _unshard_zero_states(self):
        """Inverse of the :meth:`_ensure_zero_states` adoption: gather
        the live shard state back into canonical per-param ``_states``
        (pure reshaping — bit-exact) and drop the shards, so an
        unsharded update path engaging after sharded steps continues
        the SAME optimizer trajectory instead of silently recreating
        zeroed state.  Raises when this process does not hold every
        rank's shards (a multi-process 'world' job cannot fall back
        unsharded mid-run)."""
        if not self._zero_states:
            return
        # adopt=False: the unshard MUST materialize canonical per-param
        # states — direct shard adoption would hand the shards straight
        # back and leave the unsharded update with nothing to read
        self._load_zero_states(
            self._zero_snapshot(),
            source="<live ZeRO-1 shards: an unsharded update "
            "path engaged after sharded steps>", adopt=False)

    def _zero_snapshot(self):
        """The ZeRO state-snapshot dict (world / chunks / per-rank
        shards) — the ONE builder behind both ``states_dict()`` and the
        unshard fallback, so the layout the checkpoint path writes and
        the layout ``_load_zero_states`` gathers can never drift."""
        layout, world = self._zero_layout
        return {
            "world": world,
            "chunks": [
                {"indices": list(idxs), "n_states": n_states,
                 "dtype": str(dt), "total": total, "padded": padded,
                 "shapes": [[int(d) for d in self._params[j].shape]
                            for j in idxs]}
                for (n_states, dt, idxs, total, padded) in layout],
            "shards": {r: {c: list(entry[r])
                           for c, entry in
                           sorted(self._zero_states.items())
                           if r in entry}
                       for r in sorted({rr for e in
                                        self._zero_states.values()
                                        for rr in e})},
        }

    def optimizer_state_bytes(self):
        """Measured optimizer-state footprint: ``{"per_replica": max
        bytes any one replica holds, "total": bytes across replicas}``.
        Sharded (ZeRO-1) runs report ~1/world per replica; unsharded
        runs report the full state on every replica."""
        if self._zero_states:
            per_rank = {}
            for entry in self._zero_states.values():
                for r, slots in entry.items():
                    per_rank[r] = per_rank.get(r, 0) + sum(
                        int(s._data.nbytes) for s in slots)
            vals = list(per_rank.values()) or [0]
            return {"per_replica": max(vals), "total": sum(vals)}
        total = 0

        def _acc(s):
            nonlocal total
            if s is None:
                return
            if isinstance(s, tuple):
                for x in s:
                    _acc(x)
                return
            total += int(s._data.nbytes)

        for st in self._states:
            for s in (st or {}).values():
                _acc(s)
        return {"per_replica": total, "total": total}

    # -- whole-step compilation (ROADMAP item 4) ----------------------------

    @property
    def whole_step_enabled(self):
        return self._whole_step

    def whole_step(self, block, loss_fn, x, y=None, batch_size=None):
        """One FULL training step — forward, loss, backward, gradient
        allreduce, optimizer update, weight rebind — for the given
        hybridizable ``block``.

        With whole-step compilation enabled (``Trainer(...,
        whole_step=True)`` or ``MXTPU_WHOLE_STEP=1``) the entire step
        runs as ONE compiled XLA executable with donated weight/state
        buffers (~1 device dispatch per post-warmup step, allreduce
        overlapped with backward by XLA); disabled — or for any bypass
        configuration the PR-3 fusion already recognizes (sparse, AMP
        overflow handling, ``update_on_kvstore``, compression,
        ``dist_async``) — the same call runs the eager
        forward/backward + fused ``step()`` pipeline, bit-identically.
        Bypasses under an enabled knob are LOUD (one warning per
        reason + the ``whole_step_fallbacks`` counter).

        ``loss_fn(out, y)`` (or ``loss_fn(out)`` when ``y`` is None)
        maps the block output to a loss NDArray of any shape; gradients
        are those of its SUM (exactly ``loss.backward()``'s all-ones
        seed) and the summed scalar loss is returned.  ``x`` may be one
        array or a tuple for multi-input blocks; with multiple replica
        contexts the leading batch axis is split contiguously across
        them (compiled: the SPMD mesh shard; eager: per-context
        slices).  Pass STABLE ``block``/``loss_fn`` objects — the
        compiled executable is cached per identity, so a fresh lambda
        per call retraces every step.  ``batch_size`` defaults to the
        leading dim of ``x`` and feeds ``rescale_grad`` exactly like
        ``step()``.

        With ``Trainer(..., zero_shard=True)`` (or
        ``MXTPU_ZERO_SHARD=1``) the compiled step's gradient reduction
        becomes an in-program reduce-scatter, each replica updates only
        its 1/world flat shard (optimizer state allocated at ~1/world
        per replica), and updated weight shards allgather back —
        bit-identical to the unsharded compiled step (see
        docs/performance.md, "ZeRO-1")."""
        inputs = tuple(x) if isinstance(x, (list, tuple)) else (x,)
        if batch_size is None:
            batch_size = int(inputs[0].shape[0])
        self._init_kvstore()
        if self._whole_step:
            from . import whole_step as _ws

            if self._whole_step_compiler is None:
                if self._mesh_shape is not None:
                    from ..parallel.spmd import SpmdStepCompiler

                    self._whole_step_compiler = \
                        SpmdStepCompiler.from_shape(
                            self, self._mesh_shape, self._sharding_plan)
                else:
                    self._whole_step_compiler = _ws.WholeStepCompiler(self)
            self._optimizer.rescale_grad = self._scale / batch_size
            try:
                with _profiler.op_scope("whole_step", cat="trainer"):
                    loss, wstats = self._whole_step_compiler.step(
                        block, loss_fn, inputs, y)
            except _ws.Bypass as b:
                self._whole_step_compiler.warn_fallback(b.reason)
                _step_stats["whole_step_fallbacks"] += 1
            else:
                _step_stats["steps"] += 1
                _step_stats["dispatches"] += 1
                _step_stats["params_fused"] += len(self._params)
                _step_stats["buckets_built"] += wstats["buckets"]
                _step_stats["whole_step_steps"] += 1
                _step_stats["whole_step_compiles"] += wstats["compiles"]
                if wstats.get("zero"):
                    _step_stats["zero_steps"] += 1
                if wstats.get("spmd"):
                    _step_stats["spmd_steps"] += 1
                # health-monitor FLOP geometry (batch size + param
                # elements -> the analytic MFU fallback); disarmed
                # this is the module no-op
                _health.note_whole_step(self, batch_size)
                return loss
        return self._eager_whole_step(block, loss_fn, inputs, y,
                                      batch_size)

    def _eager_whole_step(self, block, loss_fn, inputs, y, batch_size):
        """The uncompiled twin of :meth:`whole_step`: eager forward +
        autograd backward + the PR-3 fused ``step()``.  Splits the
        global batch across the parameter replicas' contexts exactly
        like the compiled path's mesh sharding (contiguous equal dim-0
        chunks in context order), so the two paths see the same
        per-replica batches."""
        from .. import autograd as _autograd
        from ..ndarray import ndarray as _nd_mod
        from ..ndarray.ndarray import NDArray

        ctxs = (self._params[0].list_ctx() if self._params
                else [inputs[0].context if isinstance(inputs[0], NDArray)
                      else None])

        def _as_ctx(v, ctx):
            if isinstance(v, NDArray):
                return v.as_in_context(ctx) if ctx is not None else v
            return _nd_mod.array(v, ctx=ctx)

        losses = []
        if len(ctxs) > 1:
            n = len(ctxs)
            b = int(inputs[0].shape[0])
            if b % n:
                raise MXNetError(
                    f"whole_step batch {b} is not divisible across "
                    f"{n} replica contexts")
            shard = b // n
            with _autograd.record():
                for r, ctx in enumerate(ctxs):
                    sl = slice(r * shard, (r + 1) * shard)
                    xs = tuple(_as_ctx(v[sl], ctx) for v in inputs)
                    out = block(*xs)
                    l = loss_fn(out, _as_ctx(y[sl], ctx)) \
                        if y is not None else loss_fn(out)
                    losses.append(l.sum())
            _autograd.backward(losses)
        else:
            ctx = ctxs[0]
            with _autograd.record():
                out = block(*(_as_ctx(v, ctx) for v in inputs))
                l = loss_fn(out, _as_ctx(y, ctx)) if y is not None \
                    else loss_fn(out)
                losses.append(l.sum())
            losses[0].backward()
        self.step(batch_size)
        total = losses[0]
        for l in losses[1:]:
            total = total + l.as_in_context(total.context)
        return total

    def allreduce_grads(self):
        self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError("allreduce_grads() is illegal with "
                             "update_on_kvstore=True")
        self._allreduce_grads()

    def _allreduce_grads(self):
        if self._kvstore is None:
            return
        if self._update_on_kvstore:
            for i, p in enumerate(self._params):
                grads = p.list_grad()
                # push grads; server applies optimizer; pull new weights
                self._kvstore.push(i, grads)
                self._kvstore.pull(i, out=p.list_data())
                # one server-side optimizer update + a reduce add and a
                # pull transfer per EXTRA replica (single-replica rebinds
                # are free)
                self._dispatches += 2 * len(grads) - 1
            return
        if self._fusion_enabled() and len(self._params) > 1:
            # fused path: submit EVERY param in one multi-key pushpull;
            # the kvstore packs same-dtype grads into flat buckets and
            # runs one allreduce per bucket
            grads_per_key = [p.list_grad() for p in self._params]
            with _profiler.op_scope("allreduce", cat="trainer"):
                kvs = self._kvstore.pushpull(
                    list(range(len(self._params))), grads_per_key,
                    out=grads_per_key)
            if kvs:
                self._dispatches += kvs["dispatches"]
                self._buckets += kvs["buckets"]
            for p, grads in zip(self._params, grads_per_key):
                for ctx, g in zip(p.list_ctx(), grads):
                    p._data[ctx]._grad = g
            return
        for i, p in enumerate(self._params):
            grads = p.list_grad()
            with _profiler.op_scope("allreduce", cat="trainer"):
                self._kvstore.pushpull(i, grads, out=grads)
            # a reduce add + a pull transfer per EXTRA replica; the
            # single-replica case rebinds without any device work
            self._dispatches += 2 * (len(grads) - 1)
            # write reduced grad back into each replica's holder
            for ctx, g in zip(p.list_ctx(), grads):
                p._data[ctx]._grad = g

    def update(self, batch_size, ignore_stale_grad=False):
        self._init_kvstore()
        if self._update_on_kvstore:
            raise MXNetError("update() is illegal with "
                             "update_on_kvstore=True")
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._update_on_kvstore and self._kvstore is not None:
            return  # already updated during push
        # live ZeRO shards + an unsharded update (a bypass fallback, a
        # direct step() on one replica, the world-mesh local rank):
        # gather the shards back into canonical states first — the SAME
        # trajectory continues bit-exactly instead of a silently
        # re-zeroed momentum (multi-process raises: a lone rank cannot
        # gather its peers' shards)
        self._unshard_zero_states()
        scaler = getattr(self, "_amp_loss_scaler", None)
        if scaler is not None and scaler.enabled:
            # dynamic loss scaling: on non-finite grads skip the update
            # and shrink the scale (ref: amp trainer overflow handling);
            # `enabled` (not the current scale value) gates this so the
            # dynamics keep running after the scale decays to 1
            grads = [p.grad(p.list_ctx()[0]) for p in self._params]
            skip = scaler.update(scaler.has_overflow(grads))
            self._scale = self._amp_original_scale / scaler.loss_scale
            if skip:
                return
        # grads are identical after allreduce: update ONCE on the first
        # context and broadcast — keeps optimizer num_update correct
        # (one tick per step, not per device) and optimizer state
        # un-replicated, matching the reference's update_on_kvstore
        # single-update semantics
        from ..ndarray.sparse import BaseSparseNDArray

        use_fused = self._fusion_enabled()
        fused = []      # (index, weight, grad, state)
        seq = []        # (index, weight, grad, state, is_row_sparse)
        for i, p in enumerate(self._params):
            ctx0 = p.list_ctx()[0]
            w = p.data(ctx0)
            g = p.grad(ctx0)
            sparse = (getattr(p, "grad_stype", "default") == "row_sparse"
                      and getattr(self._optimizer, "supports_sparse",
                                  False))
            if self._states[i] is None:
                self._states[i] = {}
            if ctx0 not in self._states[i]:
                self._states[i][ctx0] = \
                    self._optimizer.create_state_multi_precision(i, w)
            st = self._states[i][ctx0]
            if (use_fused and not sparse
                    and not isinstance(g, BaseSparseNDArray)
                    and not isinstance(w, BaseSparseNDArray)):
                fused.append((i, w, g, st))
            else:
                seq.append((i, w, g, st, sparse))
        if fused:
            # one multi-tensor kernel call per (dtype, rule, hyperparam)
            # group — the optimizer may still bounce ineligible params
            # back to its sequential update (counted as seq_updates)
            with _profiler.op_scope("fused_update", cat="trainer"):
                fstats = self._optimizer.fused_update(
                    [f[0] for f in fused], [f[1] for f in fused],
                    [f[2] for f in fused], [f[3] for f in fused])
            self._dispatches += fstats["fused_calls"] + \
                fstats["seq_updates"]
            self._params_fused += fstats["params_fused"]
        for i, w, g, st, sparse in seq:
            if sparse:
                # sparse_grad embeddings: route through the lazy row-wise
                # optimizer kernels (ref: trainer.py _row_sparse_pull
                # path); optimizers without a sparse path keep the dense
                # grad
                from ..ndarray import sparse as _sparse

                g = _sparse.cast_storage(g, "row_sparse")
            self._optimizer.update_multi_precision(i, w, g, st)
            self._dispatches += 1
        self._broadcast_updated()

    def _broadcast_updated(self):
        """Refresh every replica with ONE batched device transfer per
        extra context (both the fused and the sequential fallback path —
        previously one as_in_context per parameter per context)."""
        per_ctx = {}
        for p in self._params:
            ctxs = p.list_ctx()
            if len(ctxs) <= 1:
                continue
            src = p.data(ctxs[0])
            for ctx in ctxs[1:]:
                per_ctx.setdefault(ctx, []).append((p, ctx, src))
        for ctx, entries in per_ctx.items():
            with _profiler.op_scope("broadcast", cat="trainer"):
                outs = _engine.batched_put(
                    [s._data for _, _, s in entries], ctx.jax_device())
                for (p, c, _), new in zip(entries, outs):
                    p._data[c]._data = new
            self._dispatches += 1

    # -- state io (ref: trainer.save_states/load_states) --------------------

    # Pickle-blob layout version.  v1 wraps the round-0 bare dict in
    # {"version": 1, ...}; load_states rejects unversioned or newer
    # blobs with an actionable error instead of a KeyError.
    STATES_FORMAT_VERSION = 1

    def states_dict(self):
        """Versioned optimizer-state snapshot with device-resident
        (NDArray) leaves — no host copy happens here, so the checkpoint
        subsystem can capture buffer references synchronously and
        schedule the readback on the engine's d2h lane.  The
        update_on_kvstore path snapshots the server-side updater as an
        opaque blob instead."""
        self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            if self._kvstore._updater is None:
                raise MXNetError(
                    "cannot snapshot optimizer states: this kvstore "
                    "updates server-side with no local updater (async "
                    "PS); checkpoint from rank 0 via "
                    "kvstore.save_optimizer_states instead")
            # the updater blob holds only the moment arrays — carry the
            # shared optimizer's step counters too, else a resumed Adam
            # re-applies its t=1 bias-correction warmup
            return {"version": self.STATES_FORMAT_VERSION,
                    "kvstore": self._kvstore._updater.get_states(),
                    "num_update": self._optimizer.num_update,
                    "index_update_count":
                        dict(self._optimizer._index_update_count)}
        blob = {i: {str(c): s for c, s in (st or {}).items()}
                for i, st in enumerate(self._states)}
        out = {"version": self.STATES_FORMAT_VERSION, "states": blob,
               "num_update": self._optimizer.num_update,
               "index_update_count":
                   dict(self._optimizer._index_update_count)}
        if self._mesh_shape is not None:
            # metadata only: spmd state leaves are GLOBAL arrays (the
            # d2h readback gathers full values), so the snapshot itself
            # is mesh-agnostic; recording the shape lets a restore at a
            # different MXTPU_MESH_SHAPE be validated/logged
            # (checkpoint.reshard.check_mesh_change) instead of silent
            from ..parallel.spmd.mesh import format_mesh_shape

            out["mesh_shape"] = format_mesh_shape(self._mesh_shape)
        if self._zero_states:
            # ZeRO-1: the live optimizer state is per-rank flat shards
            # (1/world each); snapshot THEM (device-resident leaves —
            # the async checkpoint capture/readback applies unchanged)
            # plus the layout needed to gather them back into canonical
            # per-param states on load.  A multi-process job holds only
            # its own rank's shards here; CheckpointManager merges the
            # per-rank blobs on restore.
            out["zero"] = self._zero_snapshot()
        return out

    def load_states_dict(self, blob, source="<states blob>"):
        """Inverse of ``states_dict`` (leaves may be NDArray or numpy)."""
        self._init_kvstore()
        if isinstance(blob, dict) and "version" not in blob and set(
                blob) == {"states", "num_update", "index_update_count"}:
            # the round-0 layout is exactly v1 minus the version key —
            # loading it is lossless, so don't strand old checkpoints
            blob = dict(blob, version=self.STATES_FORMAT_VERSION)
        if not isinstance(blob, dict) or "version" not in blob:
            raise MXNetError(
                f"{source}: unversioned Trainer states blob with an "
                "unrecognized layout — not written by any "
                "save_states; if it predates state versioning, load "
                "the parameters alone and let the optimizer restart.")
        if blob["version"] != self.STATES_FORMAT_VERSION:
            raise MXNetError(
                f"{source}: Trainer states format v{blob['version']} "
                f"does not match this build's "
                f"v{self.STATES_FORMAT_VERSION}; save and load with "
                "matching mxnet_tpu versions.")
        if "kvstore" in blob:
            if (not (self._update_on_kvstore and self._kvstore is not None)
                    or self._kvstore._updater is None):
                raise MXNetError(
                    f"{source}: states were saved from a kvstore-side "
                    "updater but this Trainer has none (local updates, "
                    "or an async PS that updates server-side); recreate "
                    "it with a matching update_on_kvstore setup")
            self._kvstore._updater.set_states(blob["kvstore"])
            if "num_update" in blob:  # updater wraps this same object
                self._optimizer.num_update = blob["num_update"]
                self._optimizer._index_update_count = dict(
                    blob["index_update_count"])
            return
        if self._update_on_kvstore and self._kvstore is not None:
            raise MXNetError(
                f"{source}: states were saved from a local-update "
                "Trainer but this Trainer updates on the kvstore — "
                "loading would silently leave the kvstore updater's "
                "optimizer at step 0; recreate the Trainer with "
                "update_on_kvstore=False to resume these states")
        from ..optimizer import _states_from_np

        if blob.get("mesh_shape"):
            from ..checkpoint.reshard import check_mesh_change

            check_mesh_change(blob["mesh_shape"], self._mesh_shape,
                              source=source)
        self._optimizer.num_update = blob["num_update"]
        self._optimizer._index_update_count = dict(
            blob["index_update_count"])
        if blob.get("zero"):
            # sharded snapshot: gather the flat shards back into
            # canonical per-param states (pure reshaping — bit-exact),
            # so a sharded run restarts unsharded and vice versa; a
            # zero_shard target re-shards lazily on its first step
            self._load_zero_states(blob["zero"], source)
            return
        # an UNSHARDED snapshot supersedes any live shards too — stale
        # shard entries would otherwise win the next _ensure_zero_states
        # check and the loaded states would sit unused
        self._zero_states = {}
        self._zero_layout = None
        for i, p in enumerate(self._params):
            saved = blob["states"].get(i, {})
            if not saved:
                continue
            self._states[i] = {}
            vals = list(saved.values())
            for j, ctx in enumerate(p.list_ctx()):
                v = vals[j] if j < len(vals) else vals[0]
                self._states[i][ctx] = _states_from_np(v)

    def _zero_plan_probe(self, world):
        """Build the zero plan this trainer WOULD run at ``world``
        replicas, with the step-counter ticks the build performs
        contained (saved and restored) — a layout probe, not a step.
        Returns the plan tuple, or None when the configuration has no
        fused/sharded form."""
        opt = self._optimizer
        saved = (opt.num_update, dict(opt._index_update_count))
        try:
            ctx0 = self._params[0].list_ctx()[0]
            plan, _svals, reason = opt.whole_step_plan(
                list(range(len(self._params))),
                [p.data(ctx0) for p in self._params],
                [None] * len(self._params), zero_world=world)
        except Exception:  # uninitialized params etc: no probe
            plan, reason = None, "probe failed"
        finally:
            opt.num_update = saved[0]
            opt._index_update_count = saved[1]
        return None if reason is not None else plan

    def _try_adopt_zero_snapshot(self, zero):
        """Elastic fast path: when the snapshot's shard world equals
        this trainer's replica world AND its chunk layout matches the
        plan this trainer would build, install the flat shards
        DIRECTLY as the live per-rank optimizer state — bit-identical
        to gather-then-lazy-reshard (both are pure reshaping of the
        same bytes) without materializing full per-param states on the
        resume path.  Returns True on adoption; False falls back to
        the gather path."""
        from ..checkpoint.reshard import _chunk_of, _shard_np
        from ..ndarray import ndarray as _nd_mod

        if not self._zero_shard or not self._params:
            return False
        ctxs = self._params[0].list_ctx()
        world = int(zero["world"])
        if world <= 1 or len(ctxs) != world:
            return False
        try:
            shards = {int(r): v for r, v in zero["shards"].items()}
        except (TypeError, ValueError):
            return False
        if set(shards) != set(range(world)):
            return False
        plan = self._zero_plan_probe(world)
        if plan is None or len(plan) != len(zero["chunks"]):
            return False
        for chunk, (_k, _s, n_states, dt, idxs, total, padded) in \
                zip(zero["chunks"], plan):
            if (int(chunk["n_states"]) != n_states
                    or str(chunk["dtype"]) != str(dt)
                    or [int(j) for j in chunk["indices"]] != list(idxs)
                    or int(chunk["total"]) != total
                    or int(chunk["padded"]) != padded):
                return False
        new_states = {}
        for c, (_k, _s, n_states, dt, idxs, _total, padded) in \
                enumerate(plan):
            shard_n = padded // world
            entry = {}
            for r, ctx in enumerate(ctxs):
                try:
                    sh = _chunk_of(shards[r], c)
                    arrs = [_shard_np(sh[slot])
                            for slot in range(n_states)]
                except (KeyError, IndexError, TypeError):
                    # truncated/partial snapshot: the gather path's
                    # missing-shard diagnosis beats a bare KeyError
                    return False
                slots = []
                for arr in arrs:
                    if arr.shape != (shard_n,):
                        return False
                    slots.append(_nd_mod.array(arr, dtype=dt, ctx=ctx))
                entry[r] = tuple(slots)
            new_states[c] = entry
        self._zero_states = new_states
        self._zero_layout = self._zero_layout_of(plan, world)
        for (_k, _s, _n, _dt, idxs, _t, _p) in plan:
            for j in idxs:
                self._states[j] = None
        return True

    def _load_zero_states(self, zero, source, adopt=True):
        """Gather a ZeRO-1 state snapshot (per-rank flat shards) into
        canonical per-param optimizer states at ctx0 — the gather-on-
        restore path: concatenate the rank shards of every chunk, drop
        the zero pad, and unflatten along the chunk's param layout.
        Requires every rank's shards (a multi-process restore goes
        through CheckpointManager, which merges the per-rank blobs).

        With ``adopt=True`` (the restore path) a snapshot whose shard
        world and chunk layout already match this sharded trainer is
        installed directly as live shards instead — the elastic resume
        fast path (``CheckpointManager`` re-slices a foreign-world
        snapshot onto this world first, see checkpoint/reshard.py)."""
        import numpy as np

        from ..ndarray import ndarray as _nd_mod
        from ..ndarray.ndarray import NDArray as _ND

        if adopt and self._try_adopt_zero_snapshot(zero):
            return
        world = int(zero["world"])
        have = {int(r) for r in zero["shards"]}
        if have != set(range(world)):
            raise MXNetError(
                f"{source}: ZeRO-1 optimizer-state snapshot was sharded "
                f"across {world} rank(s) but only rank(s) "
                f"{sorted(have)} are present in this blob — restore "
                "through CheckpointManager, which gathers every rank's "
                "trainer-shard<r>.states from the checkpoint directory "
                "(see docs/checkpointing.md)")
        shards = {int(r): v for r, v in zero["shards"].items()}
        ctx0 = self._params[0].list_ctx()[0] if self._params else None
        for c, chunk in enumerate(zero["chunks"]):
            n_states = int(chunk["n_states"])
            idxs = [int(j) for j in chunk["indices"]]
            shapes = [tuple(int(d) for d in s) for s in chunk["shapes"]]
            if not n_states:
                for j in idxs:
                    self._states[j] = None
                continue
            slot_fulls = []
            for slot in range(n_states):
                parts = []
                for r in range(world):
                    rank_chunks = shards[r]
                    sh = rank_chunks[c] if c in rank_chunks \
                        else rank_chunks[str(c)]
                    s = sh[slot]
                    parts.append(s.asnumpy() if isinstance(s, _ND)
                                 else np.asarray(s))
                slot_fulls.append(
                    np.concatenate(parts)[:int(chunk["total"])])
            for jj, j in enumerate(idxs):
                off = sum(int(np.prod(s)) for s in shapes[:jj])
                n = int(np.prod(shapes[jj]))
                per_slot = tuple(
                    _nd_mod.array(
                        slot_fulls[slot][off:off + n].reshape(
                            shapes[jj]),
                        dtype=chunk["dtype"], ctx=ctx0)
                    for slot in range(n_states))
                self._states[j] = {
                    ctx0: per_slot[0] if n_states == 1 else per_slot}
        # any live shards are superseded by the loaded snapshot
        self._zero_states = {}
        self._zero_layout = None

    def save_states(self, fname):
        self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.save_optimizer_states(fname)
            return
        import pickle

        from ..optimizer import _states_to_np

        from ..checkpoint import atomic_file

        payload = self.states_dict()
        payload["states"] = {
            i: {c: _states_to_np(s) for c, s in st.items()}
            for i, st in payload["states"].items()}
        if payload.get("zero"):
            payload["zero"]["shards"] = {
                r: {c: [s.asnumpy() for s in slots]
                    for c, slots in chunks.items()}
                for r, chunks in payload["zero"]["shards"].items()}
        # atomic commit: a kill mid-dump must not truncate the previous
        # good states file under the published name
        with atomic_file(fname) as tmp:
            with open(tmp, "wb") as f:
                pickle.dump(payload, f)

    def load_states(self, fname):
        self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
            return
        import pickle

        with open(fname, "rb") as f:
            blob = pickle.load(f)
        self.load_states_dict(blob, source=fname)


