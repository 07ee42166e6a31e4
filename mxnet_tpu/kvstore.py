"""KVStore: string-keyed parameter/gradient store.

Ref: src/kvstore/ (kvstore_local.h, comm.h, kvstore_nccl.h,
kvstore_dist.h) + python/mxnet/kvstore.py.

TPU-native design (BASELINE north star): every type maps to XLA
collectives instead of device-copy trees / NCCL / ps-lite —
- 'local'/'device'/'nccl': single-process multi-device aggregation.
  Eager path reduces across the per-device replicas with XLA add (the
  CommDevice equivalent); inside a compiled step the same push+pull pair
  becomes an in-graph psum over the ICI mesh axis (see parallel/).
- 'dist_sync'/'dist_async'/'dist_device_sync': multi-process path over
  jax.distributed (DCN collectives); single-process fallback degrades to
  'device' so the nightly-style local-launcher tests run anywhere.
Server-side optimizer (`update_on_kvstore`) runs the Updater on the
reduced gradient once, then broadcasts — semantically identical to the
reference's KVStoreDistServer sync-mode update.
"""
from __future__ import annotations

import jax
from jax._src.lax.parallel import all_gather_invariant

from .base import MXNetError
from .ndarray.ndarray import NDArray, _wrap
from .ndarray import ndarray as _nd
from . import optimizer as _opt


class KVStore:
    """Ref: include/mxnet/kvstore.h KVStore::Create."""

    def __init__(self, kv_type="local"):
        self._type = kv_type
        self._store = {}          # key -> canonical NDArray (merged value)
        self._updater = None
        self._optimizer = None
        self._compression = None  # GradientCompression when enabled
        self._ps = None           # PSClient for the dist_async transport

    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        from .parallel import dist

        return dist.rank()

    @property
    def num_workers(self):
        from .parallel import dist

        return dist.num_workers()

    # -- init ---------------------------------------------------------------

    def init(self, key, value):
        keys, values = _normalize(key, value)
        from .ndarray.sparse import BaseSparseNDArray

        for k, vlist in zip(keys, values):
            if k in self._store:
                raise MXNetError(f"key {k} already initialized")
            v = vlist[0]
            # canonical stored value is dense: every pull/push path reads
            # ._data (sparse stays sparse only on the wire, ref: comm.h)
            self._store[k] = (v.todense() if isinstance(v, BaseSparseNDArray)
                              else v.copy())
            if self._is_async():
                # set-if-absent on the server: every worker sends, first
                # one wins (ref: KVStoreDist::InitImpl push to servers)
                self._ps_client().init(str(k), self._store[k].asnumpy())

    # -- push / pull --------------------------------------------------------

    def push(self, key, value, priority=0):
        """Aggregate values (sum over devices, ref: CommDevice reduce; and
        over workers for dist_*)."""
        keys, values = _normalize(key, value)
        for k, vlist in zip(keys, values):
            if k not in self._store:
                raise MXNetError(f"key {k} has not been initialized")
            if self._compression is not None:
                vlist = [self._compression.compress(k, slot, v)
                         for slot, v in enumerate(vlist)]
            reduced = _reduce_sum(vlist, self._store[k].context)
            if self._is_async():
                # no barrier, no cross-worker reduce: the server merges
                # (or optimizer-updates) THIS worker's push immediately
                self._ps_client().push(str(k), reduced.asnumpy())
                continue
            if self._is_dist():
                reduced = self._dist_allreduce(k, reduced)
            if self._updater is not None:
                # server-side optimizer (update_on_kvstore=True)
                self._updater(_key_index(k), reduced, self._store[k])
            else:
                self._store[k]._data = reduced._data

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        from .ndarray.sparse import BaseSparseNDArray

        keys, outs = _normalize(key, out)
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} has not been initialized")
            if self._is_async():
                # fetch the server's CURRENT value — may not yet include
                # other workers' in-flight pushes (async semantics)
                import jax.numpy as jnp

                self._store[k]._data = jnp.asarray(
                    self._ps_client().pull(str(k)))
            src = self._store[k]
            for o in olist:
                if isinstance(o, BaseSparseNDArray):
                    # ref: KVStoreLocal::PullImpl only serves dense outs;
                    # sparse outs must go through row_sparse_pull
                    raise MXNetError(
                        "pull with a sparse out is not supported; use "
                        "row_sparse_pull(key, out, row_ids=...)")
                o._data = src.as_in_context(o.context)._data

    def pushpull(self, key, value, out=None, priority=0):
        """push+pull in one call.  The multi-key form takes the fused
        path: dense same-dtype values are packed into size-capped flat
        buckets (``MXTPU_KVSTORE_BUCKET_MB``, default 32), each bucket is
        reduced/allreduced as ONE flat buffer, and the results are
        unpacked into the existing out holders — one collective per
        bucket instead of one per key (ref: the reference's fused
        aggregate pushes; "Memory-efficient array redistribution"
        motivates the many-small→few-large collective rewrite).
        Bit-compatible with the sequential per-key path: the pairwise
        reduce order over device slots is identical, and every remaining
        op is elementwise.  Sparse values, gradient compression, the
        server-side-optimizer and dist_async paths all fall through to
        the sequential form unchanged."""
        from . import engine as _engine

        _engine.fault_point("kvstore.pushpull")
        if isinstance(key, (list, tuple)) and len(key) > 1 \
                and self._fusion_eligible():
            keys, values = _normalize(key, value)
            outs = _normalize(key, out)[1] if out is not None else values
            fused, rest = self._split_fusable(keys, values, outs)
            stats = {"buckets": 0, "dispatches": 0}
            if fused:
                self._pushpull_fused(fused, stats)
            for k, vlist, olist in rest:
                self.push(k, vlist, priority)
                self.pull(k, olist, priority)
                stats["dispatches"] += 2 * len(vlist)
            return stats
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)
        return None

    def _fusion_eligible(self):
        # compression quantizes per (key, slot) with error feedback;
        # update_on_kvstore applies the optimizer inside push; dist_async
        # pushes per key to the PS — none of these compose with packing.
        return (self._updater is None and self._compression is None
                and not self._is_async())

    def _split_fusable(self, keys, values, outs):
        from .ndarray.sparse import BaseSparseNDArray

        fused, rest = [], []
        for k, vlist, olist in zip(keys, values, outs):
            ok = (k in self._store and len(vlist) == len(olist) > 0
                  and all(isinstance(v, NDArray)
                          and not isinstance(v, BaseSparseNDArray)
                          for v in vlist)
                  and all(isinstance(o, NDArray)
                          and not isinstance(o, BaseSparseNDArray)
                          for o in olist)
                  and len({str(v.dtype) for v in vlist}) == 1)
            (fused if ok else rest).append((k, vlist, olist))
        return fused, rest

    def _pushpull_fused(self, items, stats):
        import jax.numpy as jnp

        from . import engine
        from .base import getenv

        cap = max(int(getenv("KVSTORE_BUCKET_MB", 32.0, float) * (1 << 20)),
                  1)
        if not self._is_dist():
            # single replica + no cross-worker reduce: there is nothing
            # to sum, so packing would be pure overhead — mirror
            # push+pull's rebind exactly (zero device work when value,
            # store and outs share one device)
            multi = []
            for k, vlist, olist in items:
                if len(vlist) > 1:
                    multi.append((k, vlist, olist))
                    continue
                store = self._store[k]
                if vlist[0].context != store.context:
                    stats["dispatches"] += 1
                store._data = vlist[0].as_in_context(store.context)._data
                for o in olist:
                    if o.context != store.context:
                        stats["dispatches"] += 1
                    o._data = store.as_in_context(o.context)._data
            items = multi
            if not items:
                return
        # one bucket stream per (dtype, slot-count, slot-device layout);
        # the fingerprint covers the VALUE slots — those are what gets
        # packed into one flatten call, so every bucket member's slot s
        # must live on the same device (outs may land anywhere: the
        # unpack side transfers per destination device)
        groups = {}
        for item in items:
            _, vlist, _olist = item
            fp = (str(vlist[0].dtype), len(vlist),
                  tuple(str(next(iter(v._data.devices()))) for v in vlist))
            groups.setdefault(fp, []).append(item)
        for members in groups.values():
            bucket, size = [], 0
            for item in members:
                nbytes = item[1][0].size * item[1][0].dtype.itemsize
                if bucket and size + nbytes > cap:
                    self._reduce_bucket(bucket, stats, jnp, engine)
                    bucket, size = [], 0
                bucket.append(item)
                size += nbytes
            if bucket:
                self._reduce_bucket(bucket, stats, jnp, engine)

    def _reduce_bucket(self, bucket, stats, jnp, engine):
        """ONE flat allreduce for every key in `bucket`; results land in
        the canonical store and every out holder."""
        ks = [b[0] for b in bucket]
        shapes = [tuple(b[1][0].shape) for b in bucket]
        n_slots = len(bucket[0][1])
        single = len(bucket) == 1
        if single:
            # a lone key (e.g. one tensor bigger than the bucket cap)
            # gains nothing from pack/unpack: reduce it directly
            flats = [bucket[0][1][s]._data for s in range(n_slots)]
        else:
            # pack: one flat buffer per device slot
            flats = [engine.flatten_arrays([b[1][s]._data for b in bucket])
                     for s in range(n_slots)]
            stats["dispatches"] += n_slots
        # pairwise tree reduce across slots — same pair order as
        # _reduce_sum, so the per-element sum order (and therefore the
        # bits) match the sequential per-key path exactly
        reduced = _pairwise_tree_reduce(flats, stats, jnp, engine)
        target_dev = self._store[ks[0]].context.jax_device()
        if next(iter(reduced.devices())) != target_dev:
            reduced = engine.track(jax.device_put(reduced, target_dev))
            stats["dispatches"] += 1
        if self._is_dist():
            from .parallel import dist

            reduced = dist.allreduce(_wrap(reduced))._data
            stats["dispatches"] += 1
        # unpack once per distinct destination device
        per_dev = {}

        def pieces_for(dev):
            got = per_dev.get(dev)
            if got is None:
                flat = reduced
                if next(iter(reduced.devices())) != dev:
                    flat = engine.track(jax.device_put(reduced, dev))
                    stats["dispatches"] += 1
                if single:
                    got = per_dev[dev] = [flat]
                else:
                    got = per_dev[dev] = engine.unflatten_array(flat,
                                                                shapes)
                    stats["dispatches"] += 1
            return got

        for i, (k, _vlist, olist) in enumerate(bucket):
            # each key's canonical buffer stays on ITS OWN store
            # context (keys in one bucket may live on different
            # devices), matching the sequential per-key path — a write
            # to ks[0]'s device would stick and relocate every later
            # per-key reduce for that key
            self._store[k]._data = pieces_for(
                self._store[k].context.jax_device())[i]
            for o in olist:
                o._data = pieces_for(next(iter(o._data.devices())))[i]
        stats["buckets"] += 1

    # -- whole-step (traced) form ------------------------------------------

    def traced_pushpull(self, g_raws, axis_name):
        """The multi-key ``pushpull`` lowered INTO a compiled step
        (ROADMAP item 4): called while tracing the whole-step closure,
        it returns the cross-replica-summed gradients as traced buffers
        with the reduction expressed as in-program collectives, so XLA
        schedules it (overlapped with backward) instead of Python
        stitching eager collectives between dispatches.

        Fusion-ineligible stores (compression, server-side optimizer,
        dist_async) must not reach here — the whole-step compiler
        bypasses to the eager path first, mirroring
        ``_fusion_eligible``."""
        if not self._fusion_eligible():
            raise MXNetError(
                "traced_pushpull on a fusion-ineligible kvstore "
                "(compression / update_on_kvstore / dist_async); the "
                "whole-step compiler must bypass to the eager path")
        return traced_bucket_allreduce(g_raws, axis_name)

    # -- ZeRO-1 eager multi-key forms (fused-but-not-whole-step tier) ------

    def zero_reduce_scatter(self, vlists, padded, devices, stats):
        """Eager reduce-scatter of one flat bucket (ZeRO-1, arXiv
        2004.13336): ``vlists`` is a list of per-key NDArray slot lists
        (one slot per replica device, same dtype), packed per slot into
        ONE zero-padded flat buffer of ``padded`` elements; each rank
        ``r`` then receives the cross-slot sum of flat chunk ``r`` on
        ``devices[r]``.  The per-element add order is the same pairwise
        tree ``_reduce_bucket`` uses, so a sharded eager step stays
        bit-identical to the unsharded eager step.  Returns one raw
        shard buffer per rank."""
        import jax.numpy as jnp

        from . import engine

        if not self._fusion_eligible() or self._is_dist():
            raise MXNetError(
                "zero_reduce_scatter on an ineligible kvstore "
                "(compression / update_on_kvstore / dist); the trainer "
                "must bypass to the unsharded path")
        n = len(devices)
        shard_n = int(padded) // n
        flats = [engine.flatten_pad([v[s]._data for v in vlists], padded)
                 for s in range(n)]
        pieces = [engine.unflatten_array(f, [(shard_n,)] * n)
                  for f in flats]
        stats["dispatches"] += 2 * n
        shards = []
        for r, dev in enumerate(devices):
            parts = [pieces[s][r] for s in range(n)]
            # the shared tree keeps the exact _reduce_bucket pair
            # order, elementwise, so bits match the unsharded reduce
            shard = _pairwise_tree_reduce(parts, stats, jnp, engine)
            if next(iter(shard.devices())) != dev:
                shard = engine.track(jax.device_put(shard, dev))
                stats["dispatches"] += 1
            shards.append(shard)
        stats["buckets"] += 1
        return shards

    def zero_allgather(self, shard_raws, shapes, devices, stats):
        """Eager allgather: every rank's updated weight shard lands on
        every device, re-concatenated and unpacked into per-tensor
        buffers of ``shapes`` (the zero pad tail is never read).
        Returns ``{rank: [tensor raws]}``."""
        from . import engine

        out = {}
        for r, dev in enumerate(devices):
            moved = []
            for s in shard_raws:
                if next(iter(s.devices())) != dev:
                    s = engine.track(jax.device_put(s, dev))
                    stats["dispatches"] += 1
                moved.append(s)
            flat = engine.flatten_arrays(moved)
            out[r] = engine.unflatten_array(flat, shapes)
            stats["dispatches"] += 2
        return out

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows (ref: KVStoreLocal::PullRowSparse).

        `out` row_sparse → filled with the selected rows; dense out gets
        the full value (rows outside row_ids zeroed)."""
        if row_ids is None:
            # ref: kvstore.py asserts row_ids is not None
            raise MXNetError("row_sparse_pull requires row_ids")
        import numpy as np
        import jax.numpy as jnp

        from .ndarray.sparse import RowSparseNDArray

        keys, outs = _normalize(key, out)
        rids = list(row_ids) if isinstance(row_ids, (list, tuple)) \
            else [row_ids]
        # row_ids align with outs the same way the reference's
        # kvstore.py zips them: either one rid per flattened out, one rid
        # per key (broadcast over that key's outs), or a single rid for
        # everything. (Round-1 bug: `rids * len(olist)` restarted at
        # rids[0] for every key, silently pulling key 0's rows.)
        n_flat = sum(len(olist) for olist in outs)
        if len(rids) == n_flat:
            per_key, off = [], 0
            for olist in outs:
                per_key.append(rids[off:off + len(olist)])
                off += len(olist)
        elif len(rids) == len(keys):
            per_key = [[r] * len(olist) for r, olist in zip(rids, outs)]
        elif len(rids) == 1:
            per_key = [rids * len(olist) for olist in outs]
        else:
            raise MXNetError(
                f"row_ids length {len(rids)} matches neither the number "
                f"of outs ({n_flat}) nor the number of keys ({len(keys)})")
        for k, olist, krids in zip(keys, outs, per_key):
            if k not in self._store:
                raise MXNetError(f"key {k} has not been initialized")
            src = self._store[k]
            for o, rid in zip(olist, krids):
                ids = np.unique(np.asarray(
                    rid.asnumpy() if isinstance(rid, NDArray) else rid
                ).astype(np.int64))
                if ids.size and (ids[0] < 0 or ids[-1] >= src.shape[0]):
                    raise MXNetError(
                        f"row_ids out of range for key {k}: "
                        f"[{ids[0]}, {ids[-1]}] vs {src.shape[0]} rows")
                rows = src._data[jnp.asarray(ids)]
                if isinstance(o, RowSparseNDArray):
                    o._values, o._indices = rows, jnp.asarray(ids)
                else:
                    dense = jnp.zeros(src.shape, src._data.dtype)
                    o._data = dense.at[jnp.asarray(ids)].set(rows)

    # -- broadcast (newer API parity) --------------------------------------

    def broadcast(self, key, value, out, priority=0):
        self.init(key, value)
        self.pull(key, out=out, priority=priority)

    # -- optimizer ----------------------------------------------------------

    def set_optimizer(self, optimizer):
        """Run the optimizer on the (reduced) push'ed grads —
        ref: kvstore_dist_server.h set_optimizer."""
        self._optimizer = optimizer
        if self._is_async():
            # serialized to the server; updates happen per-push there.
            # Only rank 0 sends (ref: python/mxnet/kvstore.py — a late
            # worker re-sending would wipe server-side Adam state
            # accrued from earlier pushes)
            from .parallel import dist

            if dist.rank() == 0:
                self._ps_client().set_optimizer(optimizer)
            return
        self._updater = _opt.get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """Enable 2-bit gradient compression with error feedback
        (ref: src/kvstore/gradient_compression.cc Quantize2BitImpl).

        On TPU the ICI all-reduce needs no compression — this matters for
        the DCN (cross-slice) path, and is kept semantically faithful:
        each pushed gradient is quantized to {-t, 0, +t} with the
        quantization error accumulated into a per-(key, slot) residual
        added to the next push."""
        params = dict(compression_params or {})
        ctype = params.get("type", "2bit")
        if ctype == "none":
            self._compression = None
            return
        if ctype != "2bit":
            raise MXNetError(f"unsupported compression type {ctype!r}")
        self._compression = GradientCompression(
            threshold=float(params.get("threshold", 0.5)))

    # -- dist ---------------------------------------------------------------

    def _is_dist(self):
        return self._type.startswith("dist")

    def _is_async(self):
        """dist_async rides the PS transport: per-push server update, no
        barrier (ref: kvstore_dist_server.h sync_mode_=false)."""
        from .parallel import dist

        return self._type == "dist_async" and dist.is_multiprocess()

    def _ps_client(self):
        if self._ps is None:
            import os
            import time

            from .parallel import dist, ps

            if dist.rank() == 0 and "DMLC_PS_SERVER_PORT" not in os.environ:
                ps.ensure_local_server()
            endpoints = ps.server_endpoints()
            last = None
            for _ in range(60):  # servers may still be starting
                try:
                    self._ps = ps.PSClient(endpoints)
                    break
                except OSError as e:
                    last = e
                    time.sleep(0.25)
            else:
                raise MXNetError(
                    f"cannot reach parameter servers {endpoints}: {last}")
        return self._ps

    def _dist_allreduce(self, key, value):
        from .parallel import dist

        return dist.allreduce(value)

    def barrier(self):
        if self._is_dist():
            from .parallel import dist

            dist.barrier()

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no optimizer set on kvstore")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())


def _pairwise_tree_reduce(parts, stats, jnp, engine):
    """Pairwise tree reduce over device slots IN SLOT ORDER — the ONE
    definition of the eager reduction order.  Both the unsharded
    flat-bucket allreduce (``_reduce_bucket``) and the ZeRO-1 eager
    reduce-scatter (``zero_reduce_scatter``) run THIS loop, so their
    per-element sum order (and therefore sharded/unsharded bit parity)
    can never drift apart.  Operands are moved to the left operand's
    device; every transfer and add is booked in ``stats``."""
    while len(parts) > 1:
        nxt = []
        for i in range(0, len(parts) - 1, 2):
            a, b = parts[i], parts[i + 1]
            dev_a = next(iter(a.devices()))
            if next(iter(b.devices())) != dev_a:
                b = jax.device_put(b, dev_a)
                stats["dispatches"] += 1
            nxt.append(engine.track(jnp.add(a, b)))
            stats["dispatches"] += 1
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    return parts[0]


def _key_index(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


def _normalize(key, value):
    if isinstance(key, (list, tuple)):
        out_v = []
        for v in value:
            out_v.append(list(v) if isinstance(v, (list, tuple)) else [v])
        return list(key), out_v
    return [key], [list(value) if isinstance(value, (list, tuple))
                   else [value]]


def _reduce_sum(vlist, target_ctx):
    """Sum NDArrays living on (possibly) different devices.

    Eager CommDevice equivalent: gather to the target device and add —
    XLA handles the transfers; inside jit this is a psum.
    """
    from .ndarray.sparse import BaseSparseNDArray, RowSparseNDArray
    from .ndarray import sparse as _sparse

    if all(isinstance(v, RowSparseNDArray) for v in vlist):
        # row_sparse aggregation stays sparse (ref: comm.h ReduceRowSparse);
        # align every shard on the target device first — sparse add
        # concatenates indices/values and jax rejects mixed-device inputs
        acc = vlist[0].as_in_context(target_ctx)
        for v in vlist[1:]:
            acc = _sparse.add(acc, v.as_in_context(target_ctx))
        return acc.todense()
    vlist = [v.todense() if isinstance(v, BaseSparseNDArray) else v
             for v in vlist]
    if len(vlist) == 1:
        return vlist[0].as_in_context(target_ctx)
    # pairwise tree reduce (ref: comm_tree.h CommDeviceTree): log2(N)
    # dependency depth instead of a serial N-add chain, so independent
    # partial sums overlap across devices under the async dispatcher
    while len(vlist) > 1:
        nxt = []
        for i in range(0, len(vlist) - 1, 2):
            nxt.append(vlist[i] + vlist[i + 1].as_in_context(
                vlist[i].context))
        if len(vlist) % 2:
            nxt.append(vlist[-1])
        vlist = nxt
    return vlist[0].as_in_context(target_ctx)


_VALID = ("local", "device", "nccl", "dist", "dist_sync", "dist_async",
          "dist_device_sync", "dist_device_async", "horovod", "teststore")


def create(name="local"):
    """Ref: mx.kv.create — all single-process types share the XLA
    collective path; dist types add the multi-process DCN allreduce."""
    if isinstance(name, KVStore):
        return name
    if name not in _VALID:
        raise MXNetError(f"unknown kvstore type {name!r}; valid: {_VALID}")
    return KVStore(name)


# ---------------------------------------------------------------------------
# Whole-step (traced) gradient reduction — the in-program twin of the
# eager flat-bucket pushpull above.


def traced_bucket_allreduce(g_raws, axis_name):
    """In-program twin of the eager flat-bucket reduction
    (``_pushpull_fused``): pack same-dtype gradients into size-capped
    flat buckets (``MXTPU_KVSTORE_BUCKET_MB``, the same knob), one
    ``lax.psum`` over ``axis_name`` per bucket, unpack into per-tensor
    views.  Runs only under a trace (shard_map over the replica/world
    mesh); with ``axis_name=None`` (single replica, nothing to sum) it
    is the identity, mirroring the eager path's rebind-only case.

    The pack/unpack kernels are the engine's shared flat-buffer staging
    kernels (``_k_flatten``/``_k_unflatten``), so the comm-fusion tier
    has one implementation eager and traced."""
    if axis_name is None:
        return list(g_raws)
    from . import engine
    from .base import getenv

    cap = max(int(getenv("KVSTORE_BUCKET_MB", 32.0, float) * (1 << 20)), 1)
    # one bucket stream per dtype, members in arrival order (the same
    # grouping fingerprint the eager path uses, minus the slot layout —
    # inside SPMD there is exactly one slot per shard)
    groups = {}
    order = []  # (group_key, index within group) per input position
    for g in g_raws:
        k = str(g.dtype)
        groups.setdefault(k, []).append(g)
        order.append((k, len(groups[k]) - 1))
    reduced = {}
    for k, members in groups.items():
        outs, bucket, size = [], [], 0
        for g in members:
            nbytes = g.size * g.dtype.itemsize
            if bucket and size + nbytes > cap:
                outs.extend(_psum_bucket(bucket, axis_name, engine))
                bucket, size = [], 0
            bucket.append(g)
            size += nbytes
        if bucket:
            outs.extend(_psum_bucket(bucket, axis_name, engine))
        reduced[k] = outs
    return [reduced[k][i] for k, i in order]


def _psum_bucket(bucket, axis_name, engine):
    """ONE in-program collective for every gradient in ``bucket``."""
    shapes = [tuple(int(d) for d in g.shape) for g in bucket]
    if len(bucket) == 1:
        # a lone tensor (e.g. bigger than the cap) gains nothing from
        # pack/unpack — reduce it directly, like the eager single case
        return [jax.lax.psum(bucket[0], axis_name)]
    flat = engine._k_flatten(list(bucket))
    red = jax.lax.psum(flat, axis_name)
    return list(engine._k_unflatten(red, shapes=tuple(shapes)))


# ---------------------------------------------------------------------------
# ZeRO-1 traced collectives (arXiv 2004.13336 "Automatic Cross-Replica
# Sharding of Weight Update in Data-Parallel Training"): the allreduce
# above rewritten as reduce-scatter (each rank receives the sum of ONE
# 1/world slice of the flat bucket) + allgather (updated slices
# broadcast back) — equal collective bandwidth, but the optimizer
# update and its state now touch only shard-sized buffers.  The
# portable psum_scatter/all_gather idioms follow arXiv 2112.01075.


def zero_padded_size(total, world):
    """Flat-bucket element count rounded up to a multiple of ``world``
    so every rank's shard is equal-sized.  The padding is part of the
    bucket fingerprint (plan tuples / closure keys carry it), so two
    layouts that differ only in pad never share an executable."""
    world = max(int(world), 1)
    return ((int(total) + world - 1) // world) * world


def traced_reduce_scatter_flat(ts, padded, axis_name):
    """ONE in-program collective: pack ``ts`` (same dtype) into a flat
    buffer zero-padded to ``padded`` elements and ``lax.psum_scatter``
    it over ``axis_name`` — this rank's equal-sized shard of the
    cross-replica sum.  Bit-identical per element to ``lax.psum`` of
    the same flat bucket (same reduction order over the axis)."""
    from . import engine

    flat = engine._k_flatten_pad(list(ts), padded=int(padded))
    return jax.lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                tiled=True)


def traced_shard_slice(ts, padded, world, axis_name):
    """This rank's shard of the flat concatenation of ``ts`` (the
    weight-side twin of :func:`traced_reduce_scatter_flat`: weights are
    replicated, so the shard is a local dynamic slice at
    ``axis_index``, no collective)."""
    from . import engine

    flat = engine._k_flatten_pad(list(ts), padded=int(padded))
    shard_n = int(padded) // int(world)
    r = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice(flat, (r * shard_n,), (shard_n,))


def traced_allgather_flat(shard, shapes, axis_name):
    """ONE in-program collective: gather every rank's shard back into
    the full flat bucket and unpack into per-tensor views of
    ``shapes`` (the zero-pad tail is never read)."""
    from . import engine

    # the gathered bucket is the same on every rank, and must be TYPED
    # so for a replicated out_spec to accept it; jax 0.9.0 keeps the
    # varying -> invariant form of all_gather out of jax.lax
    full = all_gather_invariant(shard, axis_name, axis=0, tiled=True)
    return list(engine._k_unflatten(
        full, shapes=tuple(tuple(int(d) for d in s) for s in shapes)))


def traced_bucket_reduce_scatter(g_raws, axis_name, world):
    """In-program ZeRO twin of :func:`traced_bucket_allreduce`: pack
    same-dtype gradients into size-capped flat buckets
    (``MXTPU_KVSTORE_BUCKET_MB``, the same knob), pad each bucket to a
    multiple of ``world`` (padding rides in the returned meta — the
    bucket fingerprint), one ``lax.psum_scatter`` per bucket.  Returns
    ``(shards, metas)`` with ``metas[i] = (positions, shapes, total,
    padded)`` mapping bucket ``i`` back to the input order; feed the
    updated shards to :func:`traced_bucket_allgather` to recover
    per-tensor arrays."""
    from .base import getenv

    cap = max(int(getenv("KVSTORE_BUCKET_MB", 32.0, float) * (1 << 20)), 1)
    groups = {}
    for pos, g in enumerate(g_raws):
        groups.setdefault(str(g.dtype), []).append((pos, g))
    shards, metas = [], []
    for members in groups.values():
        bucket, size = [], 0
        for pos, g in members:
            nbytes = g.size * g.dtype.itemsize
            if bucket and size + nbytes > cap:
                shards.append(_scatter_bucket(bucket, axis_name, world,
                                              metas))
                bucket, size = [], 0
            bucket.append((pos, g))
            size += nbytes
        if bucket:
            shards.append(_scatter_bucket(bucket, axis_name, world,
                                          metas))
    return shards, metas


def _scatter_bucket(bucket, axis_name, world, metas):
    positions = tuple(p for p, _g in bucket)
    shapes = tuple(tuple(int(d) for d in g.shape) for _p, g in bucket)
    total = sum(int(g.size) for _p, g in bucket)
    padded = zero_padded_size(total, world)
    metas.append((positions, shapes, total, padded))
    return traced_reduce_scatter_flat([g for _p, g in bucket], padded,
                                      axis_name)


def traced_bucket_allgather(shards, metas, axis_name):
    """Inverse of :func:`traced_bucket_reduce_scatter`: one
    ``lax.all_gather`` per bucket, results returned in the original
    input order."""
    out = {}
    for shard, (positions, shapes, _total, _padded) in zip(shards, metas):
        for pos, arr in zip(positions,
                            traced_allgather_flat(shard, shapes,
                                                  axis_name)):
            out[pos] = arr
    return [out[i] for i in range(len(out))]


# the issue-facing alias: "allgather" pairs with "reduce_scatter" in
# the public companion API
traced_allgather = traced_bucket_allgather


# ---------------------------------------------------------------------------
# 2-bit gradient compression (ref: src/kvstore/gradient_compression.{cc,h})


class GradientCompression:
    """Threshold quantization to {-t, 0, +t} with error-feedback residual
    (ref: GradientCompression::Quantize2BitImpl + dequantize — here the
    quantize/dequantize pair is fused since the wire format on TPU is the
    already-dequantized ternary tensor; what matters semantically is the
    information loss + residual accumulation, which match the reference
    exactly)."""

    def __init__(self, threshold=0.5):
        if threshold <= 0:
            raise MXNetError("compression threshold must be positive")
        self.threshold = threshold
        self._residuals = {}  # (key, slot) -> raw residual array

    def get_params(self):
        return {"type": "2bit", "threshold": self.threshold}

    def compress(self, key, slot, grad):
        import jax.numpy as jnp

        from .ndarray.sparse import BaseSparseNDArray

        if isinstance(grad, BaseSparseNDArray):
            grad = grad.todense()
        t = jnp.asarray(self.threshold, grad._data.dtype)
        resid = self._residuals.get((key, slot))
        g = grad._data if resid is None else grad._data + resid
        q = jnp.where(g >= t, t, jnp.where(g <= -t, -t,
                                           jnp.zeros_like(g)))
        self._residuals[(key, slot)] = g - q
        return _wrap(q)
