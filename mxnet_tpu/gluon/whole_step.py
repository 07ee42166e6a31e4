"""Whole-step SPMD compilation for ``gluon.Trainer`` (ROADMAP item 4).

One training step — forward, loss, backward, cross-replica gradient
reduction, grouped optimizer update, weight rebind — compiled into ONE
XLA executable, in the spirit of "Automatic Full Compilation of Julia
Programs and ML Models to Cloud TPUs" (arXiv 1810.09868) and TVM's
end-to-end compilation (arXiv 1802.04799).  PR 3's fused step cut the
dispatch count 50x but still stitches several dispatches per step from
Python (forward/backward CachedOp, one allreduce per bucket, per-group
``fused_update`` calls, the batched broadcast); giving XLA the whole
dataflow lets it schedule the allreduce against the backward for free
and drops host dispatch to ~one program submission per step.

The pieces are the SAME single-source implementations the eager tiers
use, re-entered under the trace:

- forward/loss: ``gluon.block.traced_apply`` — the capture body shared
  with the CachedOp graph fn;
- gradient reduction: ``kvstore.traced_pushpull`` — the flat-bucket
  pushpull lowered to in-program ``psum`` collectives over the replica
  ('dp') or cross-process ('world') mesh axis;
- optimizer update: ``optimizer.whole_step_plan`` +
  ``apply_whole_step_plan`` — the ``_fk_*`` fused kernels over the same
  flat-buffer grouping ``fused_update`` dispatches, with lr/t/wd/rescale
  riding as traced scalars so LR schedules never retrace.

Entered via ``Trainer(..., whole_step=True)`` or ``MXTPU_WHOLE_STEP=1``
through ``Trainer.whole_step(...)``; every configuration the PR-3
fusion already bypasses (sparse grads, AMP dynamic scaling,
``update_on_kvstore``, gradient compression, ``dist_async``) raises
:class:`Bypass` and falls back LOUDLY to the eager fused path, which
stays bit-identical.  An active checkpoint donation hold does not leave
the compiled path — like the fused tier, the step switches to its
pre-warmed non-donating twin executable (see docs/performance.md).
"""
from __future__ import annotations

import numpy as np

from .. import _imperative
from .. import engine as _engine
from .. import kvstore as _kvstore_mod
from .. import optimizer as _opt
from .. import random as _random
from ..base import MXNetError
from ..log import get_logger
from ..ndarray.ndarray import NDArray, _wrap
from ..telemetry import health as _health
from . import block as _block_mod

_log = get_logger("mxnet_tpu.whole_step")


class Bypass(Exception):
    """This configuration must take the eager fused path instead.

    Raised only BEFORE the step has any side effect (no optimizer tick,
    no dispatch), so the caller can run the eager step for the same
    batch without double-applying anything."""

    def __init__(self, reason):
        super().__init__(reason)
        self.reason = reason


class WholeStepCompiler:
    """Per-Trainer builder + executable cache for the whole-step path.

    Holds the traced closures (one per update-plan structure), the
    donation warmup bookkeeping (mirroring ``optimizer._fused_apply``:
    the first call per signature runs the non-donating twin so a later
    checkpoint hold switches executables without a mid-step compile),
    and — on the multi-replica mesh path — the cached replicated global
    arrays the parameters/states live in between steps.
    """

    def __init__(self, trainer):
        self.trainer = trainer
        self._closures = {}       # structure key -> (fn, meta)
        self._seen_sigs = set()   # compile-counter signatures
        self._nondonate_warmed = set()
        self._warned = set()
        self._probe_cache = {}    # (structure key) -> aux names tuple
        # mesh path: cached replicated global arrays + the exact shard
        # views installed into the eager holders (staleness detection)
        self._mesh_key = None
        self._gparams = None      # [garr] per trainer param
        self._gparam_views = None  # [{ctx: raw}] per trainer param
        self._gstates = None      # [tuple(garr)] per trainer param
        self._gstate_views = None  # [tuple(raw)] per trainer param
        self._gothers = None      # [garr] per non-trainer block param
        self._gother_views = None  # [{ctx: raw}]
        # ZeRO path: per-chunk state globals, sharded over the replica
        # axis (chunk pos -> [garr per slot] / [{rank: raw} per slot])
        self._zgstates = {}
        self._zgstate_views = {}
        self._zero_rank_devs = {}  # rank -> jax device of its shards

    # -- public entry -------------------------------------------------------

    def warn_fallback(self, reason):
        """Loud, once-per-reason notice that a whole-step call ran the
        eager fused path instead."""
        if reason not in self._warned:
            self._warned.add(reason)
            _log.warning(
                "whole-step compilation bypassed -> eager fused path: %s",
                reason)

    def step(self, block, loss_fn, inputs, y):
        """Run one compiled whole step.  Returns ``(loss, stats)`` with
        ``stats = {"compiles": fresh-signature count, "buckets":
        traced allreduce buckets}``; raises :class:`Bypass` (before any
        side effect) when the configuration must take the eager path."""
        t = self.trainer
        self._check_bypass(block)
        mesh_info = self._mesh_info()
        # ZeRO-1 (arXiv 2004.13336) engages on any real replica mesh;
        # with a single replica and no cross-process reduction the
        # sharding is the identity, so the unsharded program runs
        zero_world = None
        if t._zero_shard and mesh_info is not None:
            zero_world = len(list(mesh_info[0].devices.flat))
        named = block._ordered_params()
        order = self._order_params(named)
        train_block_pos, other_params, other_block_pos = order
        if zero_world is None:
            self._ensure_states()
        ctx0 = t._params[0].list_ctx()[0]

        # input signature / structure key (before ticking anything)
        x_sig = tuple(
            (tuple(int(d) for d in v.shape), str(getattr(v, "dtype", "")))
            for v in (tuple(inputs) + ((y,) if y is not None else ())))
        has_y = y is not None

        # the mesh path cannot carry aux-mutating forwards (BatchNorm
        # moving stats are per-replica state in the eager model; one
        # replicated parameter cannot hold N diverging values) — probe
        # abstractly BEFORE the plan ticks anything
        if mesh_info is not None:
            self._probe_mesh_aux(block, loss_fn, inputs, y, order,
                                 mesh_info, ctx0)

        plan, svals, reason = t._optimizer.whole_step_plan(
            list(range(len(t._params))),
            [p.data(ctx0) for p in t._params],
            ([None] * len(t._params) if zero_world is not None else
             [self._state_entry(i) for i in range(len(t._params))]),
            zero_world=zero_world)
        if reason is not None:
            raise Bypass(reason)
        if zero_world is not None:
            t._ensure_zero_states(plan, zero_world,
                                  self._zero_rank_ctx(mesh_info))

        skey = (id(block), id(loss_fn), plan, has_y, len(inputs),
                self._mesh_struct_key(mesh_info), zero_world)
        fn, meta = self._closures.get(skey, (None, None))
        if fn is None:
            fn, meta = self._build_closure(block, loss_fn, plan, order,
                                           mesh_info, has_y, zero_world)
            self._closures[skey] = (fn, meta)
            self._evict_stale_closures()

        # argument assembly
        key_raw = _random.next_key()
        sval_raws = tuple(self._sval_array(plan[c], svals[c])
                          for c in range(len(plan)))
        if mesh_info is None:
            args = self._single_args(block, inputs, y, other_params, ctx0)
        else:
            args = self._mesh_args(block, inputs, y, other_params,
                                   mesh_info,
                                   zero_plan=(plan if zero_world
                                              else None))
        train_ws, sts, other_ws, xs, y_raw = args

        # donation twin selection + compile accounting
        with _engine.donation_dispatch_guard() as held:
            donate = None
            if _opt._fused_donate_ok() and not held:
                # warm key covers the INPUT signature too (like
                # _fused_apply's shape-bearing sig): every shape the
                # step runs at must warm its own non-donating twin,
                # else a hold during a later-shape step would compile
                # mid-step inside this guard
                warm_key = (skey, x_sig)
                if warm_key in self._nondonate_warmed:
                    donate = (1, 2)
                else:
                    # warm the non-donating twin first: a checkpoint
                    # hold arriving later switches executables without
                    # a mid-step XLA compile
                    self._nondonate_warmed.add(warm_key)
            sig = (skey, x_sig, donate is not None)
            compiles = 0
            if sig not in self._seen_sigs:
                self._seen_sigs.add(sig)
                compiles = 1
            jitted = _imperative.get_jitted(fn, {}, donate_argnums=donate)
            _imperative.count_dispatch()
            loss_raw, new_ws, new_sts, aux_raws = jitted(
                key_raw, train_ws, sts, other_ws, xs, y_raw, sval_raws)
            # rebind INSIDE the guard: a checkpoint capture on another
            # thread must never observe holders pointing at
            # just-donated buffers
            if mesh_info is None:
                self._rebind_single(new_ws, new_sts, aux_raws,
                                    meta, named, ctx0)
                loss_out = loss_raw
            else:
                loss_out = self._rebind_mesh(
                    new_ws, new_sts, other_params, loss_raw,
                    zero=zero_world is not None)
        _engine.track(loss_out)
        if compiles and donate is None:
            # fresh NON-donating executable (the warmup call, so the
            # buffers in `args` are still live): let an armed health
            # monitor read the whole-step FLOP count from the lowered
            # cost analysis — disarmed this is the module no-op
            _health.note_whole_step_compiled(
                jitted, (key_raw, train_ws, sts, other_ws, xs, y_raw,
                         sval_raws))
        stats = {"compiles": compiles,
                 "buckets": meta.get("buckets", 0),
                 "zero": zero_world is not None}
        return _wrap(loss_out), stats

    # Closure-cache bound: each entry pins a compiled executable (and
    # strongly references its block/loss_fn), so unstable identities —
    # e.g. a fresh lambda per call — would otherwise leak one
    # executable per step until host OOM, not just retrace.
    MAX_CLOSURES = 8

    def _evict_stale_closures(self):
        while len(self._closures) > self.MAX_CLOSURES:
            old_key = next(iter(self._closures))  # dict FIFO = oldest
            old_fn, _meta = self._closures.pop(old_key)
            _imperative.evict(old_fn)
            self._seen_sigs = {s for s in self._seen_sigs
                               if s[0] != old_key}
            self._nondonate_warmed = {w for w in self._nondonate_warmed
                                      if w[0] != old_key}
            if "closure-cache-overflow" not in self._warned:
                self._warned.add("closure-cache-overflow")
                _log.warning(
                    "whole-step executable cache overflow (evicting "
                    "oldest) — pass STABLE block/loss_fn objects; a "
                    "fresh lambda per call retraces (and would "
                    "otherwise leak an executable) every step")

    # -- bypass / topology --------------------------------------------------

    def _check_bypass(self, block):
        t = self.trainer
        if not t._params:
            raise Bypass("no trainable parameters")
        scaler = getattr(t, "_amp_loss_scaler", None)
        if scaler is not None and scaler.enabled:
            raise Bypass("amp dynamic loss scaling (the overflow skip "
                         "is a host-side decision)")
        if t._update_on_kvstore and t._kvstore is not None:
            raise Bypass("update_on_kvstore=True (server-side optimizer)")
        if t._kvstore is not None and t._kvstore._compression is not None:
            raise Bypass("gradient compression (per-key error feedback)")
        if t._kvstore is not None and t._kvstore._is_async():
            raise Bypass("dist_async (per-push PS transport)")
        ctxs0 = None
        for p in t._params:
            if getattr(p, "grad_stype", "default") != "default":
                raise Bypass(f"sparse-grad parameter {p.name}")
            if getattr(p, "stype", "default") != "default":
                raise Bypass(f"sparse parameter {p.name}")
            if p.grad_req == "add":
                raise Bypass(f"grad_req='add' on {p.name} (gradient "
                             "accumulation across calls)")
            ctxs = tuple(p.list_ctx())
            if ctxs0 is None:
                ctxs0 = ctxs
            elif ctxs != ctxs0:
                raise Bypass("parameters span different context sets "
                             "(model-parallel placement)")
        block_ids = {id(p) for _, p in block._ordered_params()}
        for p in t._params:
            if id(p) not in block_ids:
                raise Bypass(f"trainer parameter {p.name} is not a "
                             "parameter of the stepped block")

    def _mesh_info(self):
        """(mesh, axis_name) for the replica topology, or None when one
        local replica and no cross-process reduction is in play."""
        t = self.trainer
        ctxs = t._params[0].list_ctx()
        from ..parallel import dist as _dist

        multiproc = (t._kvstore is not None and t._kvstore._is_dist()
                     and _dist.is_multiprocess())
        if len(ctxs) > 1:
            if multiproc:
                raise Bypass("multi-process job with multiple local "
                             "replica contexts (hierarchical dcn+dp "
                             "whole-step mesh not supported yet)")
            from ..parallel import mesh as _mesh_mod

            return (_mesh_mod.make_mesh(
                {"dp": len(ctxs)},
                [c.jax_device() for c in ctxs]), "dp")
        if multiproc:
            return (_dist.world_mesh(), "world")
        return None

    def _mesh_struct_key(self, mesh_info):
        if mesh_info is None:
            return None
        mesh, axis = mesh_info
        return (axis, tuple(str(d) for d in mesh.devices.flat))

    def _order_params(self, named):
        """Map block capture order <-> trainer update order.

        Returns ``(train_block_pos, other_params, other_block_pos)``:
        ``train_block_pos[i]`` is the block slot of trainer param ``i``;
        the ``other_*`` lists cover every block param that is NOT a
        trainer trainable (frozen params, BatchNorm moving stats, and
        any trainable the user excluded from the Trainer — those update
        on neither path, keeping compiled/eager weights consistent)."""
        t = self.trainer
        trainer_pos = {id(p): i for i, p in enumerate(t._params)}
        train_block_pos = [None] * len(t._params)
        other_params, other_block_pos = [], []
        for pos, (_name, p) in enumerate(named):
            i = trainer_pos.get(id(p))
            if i is not None:
                train_block_pos[i] = pos
            else:
                other_params.append(p)
                other_block_pos.append(pos)
        return tuple(train_block_pos), other_params, tuple(other_block_pos)

    def _ensure_states(self):
        """Create missing optimizer states exactly like the eager
        ``Trainer._update`` (same ctx0 placement, same constructor)."""
        t = self.trainer
        for i, p in enumerate(t._params):
            ctx0 = p.list_ctx()[0]
            if t._states[i] is None:
                t._states[i] = {}
            if ctx0 not in t._states[i]:
                t._states[i][ctx0] = \
                    t._optimizer.create_state_multi_precision(
                        i, p.data(ctx0))

    def _state_entry(self, i):
        t = self.trainer
        ctx0 = t._params[i].list_ctx()[0]
        return t._states[i][ctx0]

    def _state_nds(self, i):
        """The state NDArray holders of param i as a flat tuple."""
        st = self._state_entry(i)
        if st is None:
            return ()
        return (st,) if isinstance(st, NDArray) else tuple(st)

    # -- closure ------------------------------------------------------------

    def _build_closure(self, block, loss_fn, plan, order, mesh_info,
                       has_y, zero_world=None):
        train_block_pos, _other_params, other_block_pos = order
        n_block = len(block._ordered_params())
        axis_name = mesh_info[1] if mesh_info is not None else None
        kvstore = self.trainer._kvstore
        meta = {}

        def _whole_step_fn(key, train_ws, sts, other_ws, xs, y, svals):
            import jax
            import jax.numpy as jnp

            def _loss(train_ws_):
                all_raws = [None] * n_block
                for pos, r in zip(train_block_pos, train_ws_):
                    all_raws[pos] = r
                for pos, r in zip(other_block_pos, other_ws):
                    all_raws[pos] = r
                out, aux = _block_mod.traced_apply(block, all_raws,
                                                   list(xs), key,
                                                   train=True)
                loss_nd = loss_fn(out, _wrap(y)) if has_y else \
                    loss_fn(out)
                if not isinstance(loss_nd, NDArray):
                    raise MXNetError(
                        "whole-step loss_fn must return an NDArray")
                # summing before the vjp seeds the same all-ones
                # cotangent loss.backward() uses on the unreduced loss
                return jnp.sum(loss_nd._data), aux

            ws_in = list(train_ws)
            if axis_name is not None:
                # the weights arrive replicated; typed varying over the
                # replica axis, their gradients stay per-replica, so the
                # explicit bucketed collectives below are THE reduction
                # (left replicated, autodiff would psum each gradient
                # on its own and the buckets would sum a second time)
                ws_in = jax.lax.pcast(ws_in, axis_name, to="varying")
            (loss, aux), grads = jax.value_and_grad(
                _loss, has_aux=True)(ws_in)
            if zero_world is not None:
                # ZeRO-1: no full allreduce — the per-chunk reduce-
                # scatter inside apply_zero_step_plan IS the gradient
                # reduction (kvstore.traced_reduce_scatter_flat), each
                # rank updates only its 1/world flat shard, and the
                # updated weight shards allgather back — all inside
                # this one program
                loss = jax.lax.psum(loss, axis_name)
                new_ws, new_sts = _opt.apply_zero_step_plan(
                    plan, list(train_ws), grads,
                    [list(s) for s in sts], list(svals),
                    zero_world, axis_name)
            else:
                if axis_name is not None:
                    loss = jax.lax.psum(loss, axis_name)
                    if kvstore is not None:
                        grads = kvstore.traced_pushpull(grads, axis_name)
                    else:
                        grads = _kvstore_mod.traced_bucket_allreduce(
                            grads, axis_name)
                new_ws, new_sts = _opt.apply_whole_step_plan(
                    plan, list(train_ws), grads,
                    [list(s) for s in sts], list(svals))
            meta.setdefault("aux_names", tuple(n for n, _ in aux))
            return (loss, tuple(new_ws),
                    tuple(tuple(s) for s in new_sts),
                    tuple(r for _, r in aux))

        if mesh_info is not None:
            meta["buckets"] = (len(plan) if zero_world is not None
                               else self._count_buckets(plan))
            import jax
            from jax.sharding import PartitionSpec as P

            mesh, axis = mesh_info
            data = P(axis)
            # zero: optimizer-state shards ride SHARDED over the
            # replica axis (in and out), so each device allocates only
            # its 1/world slice — the ZeRO-1 memory contract
            sts_spec = P(axis) if zero_world is not None else P()
            fn = jax.shard_map(
                _whole_step_fn, mesh=mesh,
                in_specs=(P(), P(), sts_spec, P(), data,
                          data if has_y else P(), P()),
                out_specs=(P(), P(), sts_spec, P()))
            return fn, meta
        return _whole_step_fn, meta

    def _count_buckets(self, plan):
        """Static count of traced allreduce buckets for the stats
        (mirrors ``traced_bucket_allreduce``'s grouping)."""
        from ..base import getenv

        t = self.trainer
        cap = max(int(getenv("KVSTORE_BUCKET_MB", 32.0, float)
                      * (1 << 20)), 1)
        groups = {}
        ctx0 = t._params[0].list_ctx()[0]
        for p in t._params:
            w = p.data(ctx0)
            groups.setdefault(str(w.dtype), []).append(
                int(w.size) * int(np.dtype(w.dtype).itemsize))
        buckets = 0
        for sizes in groups.values():
            cur, n = 0, 0
            for s in sizes:
                if n and cur + s > cap:
                    buckets += 1
                    cur, n = 0, 0
                cur += s
                n += 1
            if n:
                buckets += 1
        return buckets

    def _probe_mesh_aux(self, block, loss_fn, inputs, y, order,
                        mesh_info, ctx0):
        """Abstractly trace the per-shard forward (jax.eval_shape — no
        compile, no execution) to learn whether it mutates aux state;
        aux-mutating forwards (BatchNorm moving stats) bypass the mesh
        path, because eager replicas keep N diverging per-context
        copies that one replicated parameter cannot represent."""
        import jax

        skey = ("auxprobe", id(block), id(loss_fn),
                tuple((tuple(int(d) for d in v.shape),
                       str(getattr(v, "dtype", ""))) for v in inputs))
        cached = self._probe_cache.get(skey)
        if cached is None:
            train_block_pos, other_params, other_block_pos = order
            t = self.trainer
            mesh, _axis = mesh_info
            nshards = len(list(mesh.devices.flat))
            n_block = len(block._ordered_params())
            box = {}

            def _probe(key, all_ws, xs):
                import jax.numpy as jnp

                _out, aux = _block_mod.traced_apply(block, list(all_ws),
                                                    list(xs), key,
                                                    train=True)
                box["aux"] = tuple(n for n, _ in aux)
                return jnp.zeros(())

            def _sds(arr):
                return jax.ShapeDtypeStruct(tuple(arr.shape), arr.dtype)

            all_ws = [None] * n_block
            for pos, p in zip(train_block_pos, t._params):
                all_ws[pos] = _sds(p.data(ctx0)._data)
            for pos, p in zip(other_block_pos, other_params):
                all_ws[pos] = _sds(p.data(ctx0)._data
                                   if ctx0 in (p._data or {})
                                   else p.data()._data)
            _m, axis = mesh_info
            xs = []
            for v in inputs:
                shape = tuple(int(d) for d in v.shape)
                if axis == "world":
                    # the world path shards PER PROCESS: each rank's
                    # shard is its full local batch (_stage_sharded
                    # builds the (P*b, ...) global from the local
                    # array), so the per-shard probe shape is the
                    # local shape unchanged
                    local = shape
                else:
                    if shape[0] % nshards:
                        raise Bypass(
                            f"batch {shape[0]} not divisible by the "
                            f"{nshards}-replica mesh")
                    local = (shape[0] // nshards,) + shape[1:]
                xs.append(jax.ShapeDtypeStruct(
                    local, np.dtype(getattr(v, "dtype", np.float32))))
            probe_key = _random.next_key()
            key_sds = jax.ShapeDtypeStruct(tuple(probe_key.shape),
                                           probe_key.dtype)
            try:
                jax.eval_shape(_probe, key_sds, tuple(all_ws), tuple(xs))
            except Bypass:
                raise
            except Exception:
                # probe trouble is not a verdict; the real trace will
                # surface any actual error with full context
                box.setdefault("aux", ())
            cached = box.get("aux", ())
            self._probe_cache[skey] = cached
        if cached:
            raise Bypass(
                "forward mutates aux state (%s) — per-replica moving "
                "stats cannot ride one replicated whole-step parameter"
                % ", ".join(cached))

    # -- argument assembly / rebind ----------------------------------------

    @staticmethod
    def _sval_array(chunk, svals):
        """One 1-D device array per plan chunk, pre-cast on host to the
        chunk dtype with the same numpy casting ``fused_update``'s
        ``jnp.asarray(v, dtype)`` applies — bit-identical scalars."""
        import jax.numpy as jnp

        dt = chunk[3]  # (kernel, static, n_states, dt, idxs[, total, padded])
        return jnp.asarray(np.asarray(svals, dtype=np.dtype(dt)))

    def _single_args(self, block, inputs, y, other_params, ctx0):
        t = self.trainer
        dev = ctx0.jax_device()
        train_ws = tuple(p.data(ctx0)._data for p in t._params)
        sts = tuple(tuple(s._data for s in self._state_nds(i))
                    for i in range(len(t._params)))
        other_ws = tuple(
            (p.data(ctx0) if ctx0 in (p._data or {}) else p.data())._data
            for p in other_params)
        xs = tuple(self._stage(v, dev) for v in inputs)
        y_raw = self._stage(y, dev) if y is not None else None
        return train_ws, sts, other_ws, xs, y_raw

    @staticmethod
    def _stage(v, dev):
        import jax
        import jax.numpy as jnp

        raw = v._data if isinstance(v, NDArray) else jnp.asarray(v)
        if next(iter(raw.devices())) != dev:
            raw = jax.device_put(raw, dev)
        return raw

    def _rebind_single(self, new_ws, new_sts, aux_raws, meta, named,
                       ctx0):
        t = self.trainer
        for i, p in enumerate(t._params):
            p._data[ctx0]._data = _engine.track(new_ws[i])
            for slot, st_nd in enumerate(self._state_nds(i)):
                st_nd._data = _engine.track(new_sts[i][slot])
        aux_names = meta.get("aux_names", ())
        if aux_names:
            pdict = dict(named)
            for name, raw in zip(aux_names, aux_raws):
                p = pdict[name]
                target = p.data(ctx0) if ctx0 in (p._data or {}) \
                    else p.data()
                target._data = _engine.track(raw)

    # -- mesh path ----------------------------------------------------------

    def _zero_rank_ctx(self, mesh_info):
        """rank -> context map for the zero-state shards: on the 'dp'
        mesh every replica context is a local rank (in mesh order); on
        the 'world' mesh only this process's rank is local."""
        t = self.trainer
        _mesh, axis = mesh_info
        ctxs = t._params[0].list_ctx()
        if axis == "world":
            from ..parallel import dist as _dist

            return {_dist.rank(): ctxs[0]}
        return dict(enumerate(ctxs))

    def _mesh_args(self, block, inputs, y, other_params, mesh_info,
                   zero_plan=None):
        from ..parallel import mesh as _mesh_mod

        mesh, axis = mesh_info
        t = self.trainer
        mkey = self._mesh_struct_key(mesh_info)
        if self._mesh_key != mkey or self._gparams is None:
            self._mesh_key = mkey
            self._gparams = [None] * len(t._params)
            self._gparam_views = [None] * len(t._params)
            self._gstates = [None] * len(t._params)
            self._gstate_views = [None] * len(t._params)
            self._gothers = [None] * len(other_params)
            self._gother_views = [None] * len(other_params)
            self._zgstates = {}
            self._zgstate_views = {}
        repl = _mesh_mod.replicated(mesh)

        def _fresh_param(p):
            ctx0 = p.list_ctx()[0]
            return _mesh_mod.global_put(p.data(ctx0)._data, repl)

        for i, p in enumerate(t._params):
            views = self._gparam_views[i]
            stale = views is None or any(
                p._data[c]._data is not views.get(c)
                for c in p.list_ctx())
            if stale:
                self._gparams[i] = _fresh_param(p)
                self._bind_param_views(p, i)
            if zero_plan is not None:
                continue  # state lives in per-chunk shard globals
            st_nds = self._state_nds(i)
            sviews = self._gstate_views[i]
            sstale = sviews is None or len(sviews) != len(st_nds) or any(
                nd_._data is not v for nd_, v in zip(st_nds, sviews))
            if sstale:
                self._gstates[i] = tuple(
                    _mesh_mod.global_put(nd_._data, repl)
                    for nd_ in st_nds)
                self._bind_state_views(i)
        if len(other_params) != len(self._gothers):
            self._gothers = [None] * len(other_params)
            self._gother_views = [None] * len(other_params)
        for j, p in enumerate(other_params):
            views = self._gother_views[j]
            stale = views is None or any(
                p._data[c]._data is not views.get(c)
                for c in p.list_ctx())
            if stale:
                self._gothers[j] = _fresh_param(p)
                per_dev = {s.device: s.data
                           for s in self._gothers[j].addressable_shards}
                self._gother_views[j] = {}
                for c in p.list_ctx():
                    view = per_dev.get(c.jax_device())
                    if view is not None:
                        p._data[c]._data = view
                        self._gother_views[j][c] = view

        data_sh = _mesh_mod.batch_sharding(mesh, axis=axis)
        xs = tuple(self._stage_sharded(v, data_sh, mesh, axis)
                   for v in inputs)
        y_raw = self._stage_sharded(y, data_sh, mesh, axis) \
            if y is not None else None
        train_ws = tuple(self._gparams)
        sts = (self._zero_mesh_states(mesh_info, zero_plan)
               if zero_plan is not None else tuple(self._gstates))
        other_ws = tuple(self._gothers)
        return train_ws, sts, other_ws, xs, y_raw

    def _zero_mesh_states(self, mesh_info, plan):
        """Per-chunk global state arrays for the ZeRO path: each slot is
        ONE (padded,) array sharded over the replica axis, assembled
        from the per-rank shard NDArrays in ``trainer._zero_states`` —
        so every device materializes only its 1/world slice.  Cached
        with identity-checked shard views like the param globals
        (load_states_dict or a fresh allocation rebuilds them)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = self.trainer
        mesh, axis = mesh_info
        sh = NamedSharding(mesh, P(axis))
        # rank -> device for the rebind after the step, which must not
        # ask the (by then donated) shard holders where they live
        self._zero_rank_devs = {
            r: c.jax_device()
            for r, c in self._zero_rank_ctx(mesh_info).items()}
        out = []
        for c, (_k, _s, n_states, _dt, _idxs, _total, padded) in \
                enumerate(plan):
            entry = t._zero_states[c]
            ranks = sorted(entry)
            cached_views = self._zgstate_views.get(c)
            stale = cached_views is None or len(cached_views) != \
                n_states or any(
                    entry[r][slot]._data is not cached_views[slot].get(r)
                    for slot in range(n_states) for r in ranks)
            if stale:
                garrs, views = [], []
                for slot in range(n_states):
                    shards = [entry[r][slot]._data for r in ranks]
                    garrs.append(
                        jax.make_array_from_single_device_arrays(
                            (padded,), sh, shards))
                    views.append({r: entry[r][slot]._data
                                  for r in ranks})
                self._zgstates[c] = garrs
                self._zgstate_views[c] = views
            out.append(tuple(self._zgstates[c]))
        return tuple(out)

    def _rebind_zero_states(self, new_sts):
        """Inverse of :meth:`_zero_mesh_states`: rebind every local
        rank's shard holder to its slice of the updated global state
        arrays (inside the donation guard, like every other rebind)."""
        t = self.trainer
        for c, chunk_sts in enumerate(new_sts):
            entry = t._zero_states[c]
            garrs, views = [], []
            for slot, garr in enumerate(chunk_sts):
                garr = _engine.track(garr)
                per_dev = {s.device: s.data
                           for s in garr.addressable_shards}
                vmap = {}
                for r in sorted(entry):
                    data = per_dev.get(self._zero_rank_devs[r])
                    if data is not None:
                        entry[r][slot]._data = data
                        vmap[r] = data
                garrs.append(garr)
                views.append(vmap)
            self._zgstates[c] = garrs
            self._zgstate_views[c] = views

    def _stage_sharded(self, v, data_sh, mesh, axis):
        import jax
        import jax.numpy as jnp

        from ..parallel import mesh as _mesh_mod

        raw = v._data if isinstance(v, NDArray) else jnp.asarray(v)
        if axis == "world" and jax.process_count() > 1:
            # each process contributes ITS local batch as one shard of
            # the (P*b, ...) global batch (the eager dist model: every
            # worker steps on its own data, grads summed over 'world')
            P = jax.process_count()
            gshape = (P * raw.shape[0],) + tuple(raw.shape[1:])
            my_dev = mesh.devices.flat[jax.process_index()]
            return jax.make_array_from_single_device_arrays(
                gshape, data_sh, [jax.device_put(raw, my_dev)])
        return _mesh_mod.global_put(raw, data_sh)

    def _bind_param_views(self, p, i):
        per_dev = {s.device: s.data
                   for s in self._gparams[i].addressable_shards}
        self._gparam_views[i] = {}
        for c in p.list_ctx():
            view = per_dev.get(c.jax_device())
            if view is not None:
                p._data[c]._data = view
                self._gparam_views[i][c] = view

    def _bind_state_views(self, i):
        st_nds = self._state_nds(i)
        views = []
        # the holder's device comes from the parameter's context, never
        # from the holder's array: after a donating step that array IS
        # the donated buffer, and a real chip has deleted it (the CPU
        # backend ignores donation, so only a chip shows this)
        dev = self.trainer._params[i].list_ctx()[0].jax_device()
        for nd_, garr in zip(st_nds, self._gstates[i]):
            view = {s.device: s.data
                    for s in garr.addressable_shards}.get(dev)
            if view is None:  # ctx0 device not in mesh: keep ctx0 copy
                view = nd_._data
            else:
                nd_._data = view
            views.append(view)
        self._gstate_views[i] = tuple(views)

    def _rebind_mesh(self, new_ws, new_sts, other_params, loss_raw,
                     zero=False):
        t = self.trainer
        for i, p in enumerate(t._params):
            self._gparams[i] = _engine.track(new_ws[i])
            self._bind_param_views(p, i)
            if zero:
                continue  # state shards rebind per chunk below
            self._gstates[i] = tuple(_engine.track(s)
                                     for s in new_sts[i])
            self._bind_state_views(i)
        if zero:
            self._rebind_zero_states(new_sts)
        # loss: the replicated scalar's local shard (eager-friendly
        # single-device value)
        shard = loss_raw.addressable_shards[0]
        return shard.data
