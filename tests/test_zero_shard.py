"""ZeRO-1 cross-replica weight-update sharding (arXiv 2004.13336).

The contract under test: with ``Trainer(..., zero_shard=True)`` (or
``MXTPU_ZERO_SHARD=1``) the gradient reduction becomes a reduce-scatter,
each replica runs the ``_fk_*`` update kernels only over its 1/world
flat shard, and updated weight shards allgather back — optimizer state
shrinks to ~1/world_size per replica at equal collective bandwidth,
BIT-identical within each tier (sharded whole-step ≡ unsharded
whole-step; sharded eager ≡ unsharded eager), with zero post-warmup
recompiles under LR decay, loud fallback for every ineligible
configuration, and state snapshots that round-trip sharded↔unsharded
through ``states_dict`` and ``CheckpointManager``.
"""
import json
import os
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _imperative, gluon, nd, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import trainer as trainer_mod

X = np.random.RandomState(1).rand(8, 16).astype(np.float32)
Y = np.random.RandomState(2).rand(8, 4).astype(np.float32)

WORLD = 8
CTXS = [mx.xla(i) for i in range(WORLD)]


def loss_fn(out, y):
    return (out - y) ** 2


def build(zero, whole_step=True, opt="sgd", opt_args=None, ctx=None,
          layers=2, aggregate_num=None, **tkw):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    units = 16
    for _ in range(layers):
        # 13 units: every flat bucket is deliberately NOT a multiple of
        # the 8-rank world, so the zero-pad path is always exercised
        net.add(nn.Dense(13, in_units=units, activation="relu"))
        units = 13
    net.add(nn.Dense(4, in_units=units))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    kwargs = dict(opt_args or {"learning_rate": 0.05, "momentum": 0.9,
                               "wd": 0.01})
    if aggregate_num is not None:
        kwargs["aggregate_num"] = aggregate_num
    tr = gluon.Trainer(net.collect_params(), opt, kwargs,
                       whole_step=whole_step, zero_shard=zero, **tkw)
    return net, tr


def weights(net, ctx=None):
    return [p.data(ctx).asnumpy() if ctx is not None
            else p.data().asnumpy()
            for p in net.collect_params().values()]


@pytest.mark.parametrize("opt,opt_args", [
    ("sgd", {"learning_rate": 0.05, "wd": 0.01}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01, "wd": 0.01}),
])
def test_whole_step_zero_bit_parity_vs_unsharded(opt, opt_args):
    """Sharded whole-step == unsharded whole-step, bit for bit, on the
    virtual 8-device mesh (psum_scatter shares psum's per-element
    reduction order; the update kernels are elementwise on the same
    flat bucket), with every replica context consistent after."""
    net_u, tr_u = build(False, opt=opt, opt_args=opt_args, ctx=CTXS)
    net_z, tr_z = build(True, opt=opt, opt_args=opt_args, ctx=CTXS)
    for _ in range(5):
        lu = tr_u.whole_step(net_u, loss_fn, X, Y)
        lz = tr_z.whole_step(net_z, loss_fn, X, Y)
    np.testing.assert_array_equal(lu.asnumpy(), lz.asnumpy())
    for a, b in zip(weights(net_u, CTXS[0]), weights(net_z, CTXS[0])):
        np.testing.assert_array_equal(a, b)
    for p in net_z.collect_params().values():
        ref = p.data(CTXS[0]).asnumpy()
        for c in CTXS[1:]:
            np.testing.assert_array_equal(p.data(c).asnumpy(), ref)
    assert tr_z.optimizer.num_update == tr_u.optimizer.num_update


def test_eager_zero_bit_parity_vs_eager_unsharded():
    """Sharded eager step == unsharded eager fused step, bit for bit
    (the per-shard pairwise reduce tree keeps the eager slot order)."""
    net_u, tr_u = build(False, whole_step=False, ctx=CTXS)
    net_z, tr_z = build(True, whole_step=False, ctx=CTXS)
    for _ in range(4):
        tr_u.whole_step(net_u, loss_fn, X, Y)
        tr_z.whole_step(net_z, loss_fn, X, Y)
    for a, b in zip(weights(net_u, CTXS[0]), weights(net_z, CTXS[0])):
        np.testing.assert_array_equal(a, b)
    for p in net_z.collect_params().values():
        ref = p.data(CTXS[0]).asnumpy()
        for c in CTXS[1:]:
            np.testing.assert_array_equal(p.data(c).asnumpy(), ref)
    stats = trainer_mod.trainer_step_stats()
    assert stats["zero_fallbacks"] == 0


def test_per_replica_state_bytes_shrink_about_world_size():
    net_u, tr_u = build(False, opt="adam",
                        opt_args={"learning_rate": 0.01}, ctx=CTXS)
    net_z, tr_z = build(True, opt="adam",
                        opt_args={"learning_rate": 0.01}, ctx=CTXS)
    tr_u.whole_step(net_u, loss_fn, X, Y)
    tr_z.whole_step(net_z, loss_fn, X, Y)
    full = tr_u.optimizer_state_bytes()["per_replica"]
    shard = tr_z.optimizer_state_bytes()["per_replica"]
    assert full > 0
    # 1/world plus per-chunk padding: comfortably under half, and
    # within 2x of the ideal 1/8
    assert shard < full / 2
    assert shard <= 2 * (full // WORLD + 64)


def test_zero_no_recompile_one_dispatch_under_lr_decay():
    from mxnet_tpu import lr_scheduler

    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(4):
        net.add(nn.Dense(16, in_units=16))
    net.initialize(mx.init.Xavier(), ctx=CTXS)
    sched = lr_scheduler.FactorScheduler(step=3, factor=0.9, base_lr=0.1)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.1, "lr_scheduler": sched},
                       whole_step=True, zero_shard=True)
    y16 = np.random.RandomState(3).rand(8, 16).astype(np.float32)
    for _ in range(3):
        tr.whole_step(net, loss_fn, X, y16)
    nd.waitall()
    lr0 = tr.learning_rate
    trainer_mod.reset_trainer_step_stats()
    c0 = _imperative.compiled_executable_count()
    d0 = _imperative.device_dispatch_count()
    for _ in range(12):
        tr.whole_step(net, loss_fn, X, y16)
    nd.waitall()
    stats = trainer_mod.trainer_step_stats()
    assert _imperative.compiled_executable_count() == c0
    assert _imperative.device_dispatch_count() - d0 == 12
    assert stats["zero_steps"] == 12
    assert stats["whole_step_steps"] == 12
    assert stats["zero_fallbacks"] == 0
    assert stats["dispatches_per_step"] == 1.0
    assert tr.learning_rate < lr0


def test_traced_bucket_reduce_scatter_allgather_roundtrip(monkeypatch):
    """The kvstore companion pair vs traced_bucket_allreduce, bit for
    bit, over uneven tensor sizes AND a tiny bucket cap that forces
    multi-bucket packing with per-bucket zero padding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import kvstore as kv
    from mxnet_tpu.parallel import mesh as mesh_mod

    monkeypatch.setenv("MXTPU_KVSTORE_BUCKET_MB", "0.0001")  # 104 bytes
    devs = jax.devices()[:WORLD]
    mesh = mesh_mod.make_mesh({"dp": len(devs)}, devs)
    shapes = [(13,), (7, 5), (3,), (11,)]
    rng = np.random.RandomState(0)
    per_rank = [[rng.randn(*s).astype(np.float32) for s in shapes]
                for _ in range(WORLD)]

    def rs_ag(*gs):
        shards, metas = kv.traced_bucket_reduce_scatter(
            list(gs), "dp", WORLD)
        assert len(metas) > 1  # the tiny cap split the bucket stream
        for _pos, _shp, total, padded in metas:
            assert padded % WORLD == 0 and padded >= total
        return tuple(kv.traced_allgather(shards, metas, "dp"))

    def ar(*gs):
        return tuple(kv.traced_bucket_allreduce(list(gs), "dp"))

    sharding = NamedSharding(mesh, P("dp"))
    gargs = [
        jax.make_array_from_single_device_arrays(
            (WORLD,) + s, sharding,
            [jax.device_put(per_rank[r][i][None], devs[r])
             for r in range(WORLD)])
        for i, s in enumerate(shapes)]

    sm = jax.shard_map
    f1 = jax.jit(sm(lambda gs: rs_ag(*[g[0] for g in gs]), mesh=mesh,
                    in_specs=(P("dp"),), out_specs=P()))
    f2 = jax.jit(sm(lambda gs: ar(*[g[0] for g in gs]), mesh=mesh,
                    in_specs=(P("dp"),), out_specs=P()))
    r1 = f1(tuple(gargs))
    r2 = f2(tuple(gargs))
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero_chunk_splitting_under_tiny_bucket_cap(monkeypatch):
    """A tiny MXTPU_KVSTORE_BUCKET_MB splits the zero plan into many
    single-collective chunks — parity must hold regardless."""
    monkeypatch.setenv("MXTPU_KVSTORE_BUCKET_MB", "0.0005")
    net_u, tr_u = build(False, ctx=CTXS, layers=3)
    net_z, tr_z = build(True, ctx=CTXS, layers=3)
    for _ in range(3):
        tr_u.whole_step(net_u, loss_fn, X, Y)
        tr_z.whole_step(net_z, loss_fn, X, Y)
    for a, b in zip(weights(net_u, CTXS[0]), weights(net_z, CTXS[0])):
        np.testing.assert_array_equal(a, b)
    assert len(tr_z._zero_states) > len(net_z.collect_params()) // 4


def test_single_replica_zero_is_silent_identity():
    """World size 1: sharding is the identity — the unsharded program
    runs, bit-identical, with NO fallback counted (not a bypass)."""
    net_u, tr_u = build(False)
    net_z, tr_z = build(True)
    trainer_mod.reset_trainer_step_stats()
    for _ in range(3):
        tr_u.whole_step(net_u, loss_fn, X, Y)
        tr_z.whole_step(net_z, loss_fn, X, Y)
    for a, b in zip(weights(net_u), weights(net_z)):
        np.testing.assert_array_equal(a, b)
    stats = trainer_mod.trainer_step_stats()
    assert stats["zero_steps"] == 0
    assert stats["zero_fallbacks"] == 0


@pytest.mark.parametrize("case", ["amp", "no_fused_kernel",
                                  "compression", "grad_add",
                                  "dist_eager", "sparse_grad",
                                  "sequential"])
def test_zero_bypass_matrix_falls_back_loudly(case):
    """Every ineligible configuration runs the unsharded path for that
    step, books zero_fallbacks, and still trains."""
    tkw = {}
    opt = "lamb" if case == "no_fused_kernel" else "sgd"
    agg = 1 if case == "sequential" else None
    if case == "compression":
        tkw = dict(compression_params={"type": "2bit"})
    if case == "dist_eager":
        tkw = dict(kvstore="dist_sync", update_on_kvstore=False)
    net, tr = build(True, whole_step=False, opt=opt, ctx=CTXS[:4],
                    layers=1, aggregate_num=agg,
                    opt_args={"learning_rate": 0.01}, **tkw)
    if case == "amp":
        from mxnet_tpu.amp import LossScaler

        tr._amp_loss_scaler = LossScaler(init_scale=2.0)
        tr._amp_original_scale = tr._scale
    if case == "grad_add":
        for p in net.collect_params().values():
            p.grad_req = "add"
    if case == "sparse_grad":
        next(iter(net.collect_params().values())).grad_stype = \
            "row_sparse"
    before = weights(net, CTXS[0])
    trainer_mod.reset_trainer_step_stats()
    tr.whole_step(net, loss_fn, X, Y)
    stats = trainer_mod.trainer_step_stats()
    assert stats["zero_steps"] == 0
    assert stats["zero_fallbacks"] >= 1
    after = weights(net, CTXS[0])
    assert any(not np.array_equal(a, b)
               for a, b in zip(before, after))


def test_states_dict_roundtrip_zero_to_unsharded_and_back():
    opt_args = {"learning_rate": 0.01, "wd": 0.01}

    def build_adam(zero):
        return build(zero, opt="adam", opt_args=opt_args, ctx=CTXS)

    cont_net, cont_tr = build_adam(True)
    for _ in range(5):
        cont_tr.whole_step(cont_net, loss_fn, X, Y)
    # zero 3 steps -> snapshot -> restart UNSHARDED for 2 more
    a_net, a_tr = build_adam(True)
    for _ in range(3):
        a_tr.whole_step(a_net, loss_fn, X, Y)
    blob = a_tr.states_dict()
    assert blob["zero"]["world"] == WORLD
    b_net, b_tr = build_adam(False)
    for src, dst in zip(a_net.collect_params().values(),
                        b_net.collect_params().values()):
        dst.set_data(src.data(CTXS[0]))
    b_tr.load_states_dict(blob)
    for _ in range(2):
        b_tr.whole_step(b_net, loss_fn, X, Y)
    for a, b in zip(weights(cont_net, CTXS[0]),
                    weights(b_net, CTXS[0])):
        np.testing.assert_array_equal(a, b)
    # and back: unsharded snapshot resumed SHARDED
    blob2 = b_tr.states_dict()
    assert "zero" not in blob2
    c_net, c_tr = build_adam(True)
    for src, dst in zip(b_net.collect_params().values(),
                        c_net.collect_params().values()):
        dst.set_data(src.data(CTXS[0]))
    c_tr.load_states_dict(blob2)
    for _ in range(2):
        c_tr.whole_step(c_net, loss_fn, X, Y)
    cont2_net, cont2_tr = build_adam(True)
    for _ in range(7):
        cont2_tr.whole_step(cont2_net, loss_fn, X, Y)
    for a, b in zip(weights(cont2_net, CTXS[0]),
                    weights(c_net, CTXS[0])):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_manager_roundtrips_sharded_and_unsharded(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager

    opt_args = {"learning_rate": 0.01}
    cont_net, cont_tr = build(True, opt="adam", opt_args=opt_args,
                              ctx=CTXS)
    for _ in range(5):
        cont_tr.whole_step(cont_net, loss_fn, X, Y)
    # sharded save -> unsharded restore
    a_net, a_tr = build(True, opt="adam", opt_args=opt_args, ctx=CTXS)
    for _ in range(3):
        a_tr.whole_step(a_net, loss_fn, X, Y)
    d1 = str(tmp_path / "z2u")
    CheckpointManager(d1, keep_n=2).save(3, params=a_net, trainer=a_tr,
                                         sync=True)
    b_net, b_tr = build(False, opt="adam", opt_args=opt_args, ctx=CTXS)
    meta = CheckpointManager(d1, keep_n=2).restore(params=b_net,
                                                   trainer=b_tr)
    assert meta["step"] == 3
    for _ in range(2):
        b_tr.whole_step(b_net, loss_fn, X, Y)
    for a, b in zip(weights(cont_net, CTXS[0]),
                    weights(b_net, CTXS[0])):
        np.testing.assert_array_equal(a, b)
    # unsharded save -> sharded restore
    c_net, c_tr = build(False, opt="adam", opt_args=opt_args, ctx=CTXS)
    for _ in range(3):
        c_tr.whole_step(c_net, loss_fn, X, Y)
    d2 = str(tmp_path / "u2z")
    CheckpointManager(d2, keep_n=2).save(3, params=c_net, trainer=c_tr,
                                         sync=True)
    d_net, d_tr = build(True, opt="adam", opt_args=opt_args, ctx=CTXS)
    CheckpointManager(d2, keep_n=2).restore(params=d_net, trainer=d_tr)
    for _ in range(2):
        d_tr.whole_step(d_net, loss_fn, X, Y)
    for a, b in zip(weights(cont_net, CTXS[0]),
                    weights(d_net, CTXS[0])):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_gathers_shards_across_rank_files(tmp_path):
    """The gather-on-restore path: ZeRO shards split across multiple
    trainer-shard<r>.states files (the multi-process layout) are merged
    back before the load."""
    from mxnet_tpu.checkpoint import CheckpointManager

    a_net, a_tr = build(True, opt="adam",
                        opt_args={"learning_rate": 0.01}, ctx=CTXS)
    for _ in range(3):
        a_tr.whole_step(a_net, loss_fn, X, Y)
    d = str(tmp_path)
    CheckpointManager(d, keep_n=2).save(3, params=a_net, trainer=a_tr,
                                        sync=True)
    ckpt = os.path.join(d, "ckpt-00000003")
    tfile = os.path.join(ckpt, "trainer-shard0.states")
    with open(tfile, "rb") as f:
        blob = pickle.load(f)
    shards = blob["zero"]["shards"]
    low = {r: v for r, v in shards.items() if int(r) < WORLD // 2}
    high = {r: v for r, v in shards.items() if int(r) >= WORLD // 2}
    blob["zero"]["shards"] = low
    with open(tfile, "wb") as f:
        pickle.dump(blob, f)
    peer = dict(blob)
    peer["zero"] = dict(blob["zero"], shards=high)
    with open(os.path.join(ckpt, "trainer-shard1.states"), "wb") as f:
        pickle.dump(peer, f)
    b_net, b_tr = build(False, opt="adam",
                        opt_args={"learning_rate": 0.01}, ctx=CTXS)
    CheckpointManager(d, keep_n=2).restore(params=b_net, trainer=b_tr)
    # continue and compare against the uninterrupted sharded run
    for _ in range(2):
        b_tr.whole_step(b_net, loss_fn, X, Y)
    cont_net, cont_tr = build(True, opt="adam",
                              opt_args={"learning_rate": 0.01},
                              ctx=CTXS)
    for _ in range(5):
        cont_tr.whole_step(cont_net, loss_fn, X, Y)
    for a, b in zip(weights(cont_net, CTXS[0]),
                    weights(b_net, CTXS[0])):
        np.testing.assert_array_equal(a, b)


def test_partial_shard_blob_raises_actionable_error():
    net, tr = build(True, opt="adam",
                    opt_args={"learning_rate": 0.01}, ctx=CTXS)
    for _ in range(2):
        tr.whole_step(net, loss_fn, X, Y)
    blob = tr.states_dict()
    blob["zero"]["shards"] = {0: blob["zero"]["shards"][0]}
    net2, tr2 = build(False, opt="adam",
                      opt_args={"learning_rate": 0.01}, ctx=CTXS)
    with pytest.raises(mx.MXNetError, match="CheckpointManager"):
        tr2.load_states_dict(blob)


def test_world_size_mismatch_strict_topology_names_sizes(tmp_path):
    """A world-size mismatch RESHARDS by default now (elastic restore);
    strict_topology=True restores the loud rejection, naming both
    sizes and the escape hatch."""
    from mxnet_tpu.checkpoint import CheckpointManager

    net, tr = build(True, ctx=CTXS)
    tr.whole_step(net, loss_fn, X, Y)
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    mgr.save(1, params=net, trainer=tr, sync=True)
    mpath = os.path.join(str(tmp_path), "ckpt-00000001",
                         "MANIFEST.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["num_processes"] = 16
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    net2, tr2 = build(True, ctx=CTXS)
    with pytest.raises(mx.MXNetError) as ei:
        CheckpointManager(str(tmp_path), keep_n=2).restore(
            step=1, params=net2, trainer=tr2, strict_topology=True)
    msg = str(ei.value)
    assert "16-process" in msg or "by a 16" in msg
    assert "1 process" in msg
    assert "strict_topology" in msg
    # default: the SAME restore reshards instead of raising (rank 0
    # reads saved shard 0 — the rank-replicated remap)
    net3, tr3 = build(True, ctx=CTXS)
    meta = CheckpointManager(str(tmp_path), keep_n=2).restore(
        step=1, params=net3, trainer=tr3)
    assert meta["step"] == 1
    for a, b in zip(weights(net, CTXS[0]), weights(net3, CTXS[0])):
        np.testing.assert_array_equal(a, b)


def test_unsharded_snapshot_supersedes_live_shards():
    """Loading an UNSHARDED states blob into a trainer with live ZeRO
    shards must drop the shards (review finding): the loaded snapshot,
    not the stale shard momentum, drives the next steps."""
    opt_args = {"learning_rate": 0.01, "wd": 0.01}
    src_net, src_tr = build(False, opt="adam", opt_args=opt_args,
                            ctx=CTXS)
    src_tr.whole_step(src_net, loss_fn, X, Y)
    blob = src_tr.states_dict()
    tgt_net, tgt_tr = build(True, opt="adam", opt_args=opt_args,
                            ctx=CTXS)
    for _ in range(3):
        tgt_tr.whole_step(tgt_net, loss_fn, X, Y)
    assert tgt_tr._zero_states
    for src, dst in zip(src_net.collect_params().values(),
                        tgt_net.collect_params().values()):
        dst.set_data(src.data(CTXS[0]))
    tgt_tr.load_states_dict(blob)
    assert not tgt_tr._zero_states  # stale shards dropped
    for _ in range(2):
        tgt_tr.whole_step(tgt_net, loss_fn, X, Y)
    ref_net, ref_tr = build(False, opt="adam", opt_args=opt_args,
                            ctx=CTXS)
    for _ in range(3):
        ref_tr.whole_step(ref_net, loss_fn, X, Y)
    for a, b in zip(weights(ref_net, CTXS[0]),
                    weights(tgt_net, CTXS[0])):
        np.testing.assert_array_equal(a, b)


def test_unsharded_fallback_after_sharded_steps_unshards_state():
    """When an unsharded update path engages after sharded steps (a
    bypass mid-run), the live shards are gathered back into canonical
    states — the SAME trajectory continues bit-exactly instead of a
    silently re-zeroed momentum (review finding)."""
    net_z, tr_z = build(True, opt="adam",
                        opt_args={"learning_rate": 0.01}, ctx=CTXS)
    net_u, tr_u = build(False, opt="adam",
                        opt_args={"learning_rate": 0.01}, ctx=CTXS)
    for _ in range(3):
        tr_z.whole_step(net_z, loss_fn, X, Y)
        tr_u.whole_step(net_u, loss_fn, X, Y)
    assert tr_z._zero_states
    # force the unsharded eager path mid-run on the sharded trainer
    tr_z._zero_shard = False
    tr_z._whole_step = False
    tr_u._zero_shard = False
    tr_u._whole_step = False
    for _ in range(2):
        tr_z.whole_step(net_z, loss_fn, X, Y)
        tr_u.whole_step(net_u, loss_fn, X, Y)
    assert not tr_z._zero_states  # gathered back, not duplicated
    for a, b in zip(weights(net_u, CTXS[0]), weights(net_z, CTXS[0])):
        np.testing.assert_array_equal(a, b)


def test_env_knob_precedence(monkeypatch):
    monkeypatch.setenv("MXTPU_ZERO_SHARD", "1")
    _, tr = build(None)
    assert tr._zero_shard
    monkeypatch.setenv("MXTPU_ZERO_SHARD", "0")
    _, tr2 = build(None)
    assert not tr2._zero_shard
    monkeypatch.setenv("MXTPU_ZERO_SHARD", "1")
    _, tr3 = build(False)
    assert not tr3._zero_shard  # explicit ctor arg beats env


def test_profiler_zero_counters_window_scoped():
    trainer_mod.reset_trainer_step_stats()
    net, tr = build(True, ctx=CTXS)
    tr.whole_step(net, loss_fn, X, Y)
    tr.whole_step(net, loss_fn, X, Y)
    out = json.loads(profiler.dumps(reset=True))
    ts = out["trainerStep"]
    assert ts["zero_steps"] == 2
    assert ts["zero_fallbacks"] == 0
    again = json.loads(profiler.dumps(reset=True))["trainerStep"]
    assert again["zero_steps"] == 0


def test_zero_whole_step_survives_real_donation(donation_on):
    """The ZeRO twin of test_whole_step_survives_real_donation: the
    per-rank shard holders' arrays are donated with the step."""
    net, tr = build(True, ctx=CTXS)
    losses = [float(tr.whole_step(net, loss_fn, X, Y).asnumpy())
              for _ in range(5)]
    assert losses[-1] < losses[0]
    net_u, tr_u = build(False, ctx=CTXS)
    ref = [float(tr_u.whole_step(net_u, loss_fn, X, Y).asnumpy())
           for _ in range(5)]
    np.testing.assert_allclose(losses, ref, rtol=1e-5)
