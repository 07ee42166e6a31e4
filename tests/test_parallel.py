"""SPMD parallelism tests on the virtual 8-device CPU mesh
(ref: tests/python/gpu/test_kvstore_gpu.py + nightly dist tests — the
modern analogue per SURVEY §4)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import data_parallel, mesh as mesh_mod


def test_make_mesh():
    m = mesh_mod.make_mesh()
    assert m.shape["dp"] == 8
    m2 = mesh_mod.make_mesh({"dp": 4, "tp": 2})
    assert m2.shape == {"dp": 4, "tp": 2}


def test_trainer_two_level_dcn_mesh_matches_flat_dp():
    """A {'dcn': 2, 'dp': 4} two-level mesh (the pod shape: DCN outer,
    ICI inner) must reproduce the flat {'dp': 8} losses step for step —
    the single-process half of VERDICT r3 #5 (the 2-process form runs
    in tests/test_dist_nightly.py::test_dist_hierarchical_dcn_x_ici)."""
    rng = np.random.RandomState(0)
    X = rng.rand(16, 20).astype(np.float32)
    Y = rng.randint(0, 10, 16).astype(np.float32)

    def run(mesh_shape):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
        net.initialize(mx.init.Xavier())
        tr = data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1},
            mesh=mesh_mod.make_mesh(mesh_shape))
        return [float(tr.step(X, Y).asnumpy()) for _ in range(4)]

    flat = run({"dp": 8})
    hier = run({"dcn": 2, "dp": 4})
    assert np.allclose(flat, hier, atol=1e-5), (flat, hier)
    assert flat[-1] < flat[0]  # actually training


def test_spmd_trainer_converges():
    np.random.seed(3)
    mx.random.seed(3)
    n, d = 512, 16
    X = np.random.rand(n, d).astype(np.float32)
    w_true = np.random.rand(d, 1).astype(np.float32)
    Y = (X @ w_true > w_true.sum() / 2).astype(np.float32).ravel()

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01})

    losses = []
    bs = 64
    for epoch in range(30):
        for i in range(0, n, bs):
            loss = trainer.step(X[i:i + bs], Y[i:i + bs])
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0] * 0.5, losses[:3] + losses[-3:]

    # sync back and check eager predictions agree with training
    trainer.sync_to_block()
    pred = net(nd.array(X)).asnumpy().argmax(1)
    assert (pred == Y).mean() > 0.9


def test_spmd_matches_single_device_math():
    """DP over 8 devices must equal single-device SGD step (allreduce
    correctness — the dist_sync_kvstore.py N-worker assertion)."""
    np.random.seed(0)
    X = np.random.rand(16, 4).astype(np.float32)
    Y = np.random.randint(0, 2, 16).astype(np.float32)

    def make_net(seed):
        np.random.seed(seed)
        net = nn.Dense(2, in_units=4)
        net.initialize(mx.init.Xavier())
        return net

    net_a = make_net(7)
    w0 = net_a.weight.data().asnumpy().copy()
    b0 = net_a.bias.data().asnumpy().copy()

    tr = data_parallel.DataParallelTrainer(
        net_a, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.5})
    tr.step(X, Y)
    tr.sync_to_block()
    w_spmd = net_a.weight.data().asnumpy()

    # reference: eager single-device on same initial weights
    net_b = nn.Dense(2, in_units=4)
    net_b.initialize()
    net_b.weight.set_data(nd.array(w0))
    net_b.bias.set_data(nd.array(b0))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer_b = gluon.Trainer(net_b.collect_params(), "sgd",
                              {"learning_rate": 0.5})
    with autograd.record():
        loss = loss_fn(net_b(nd.array(X)), nd.array(Y))
        # DataParallelTrainer optimizes mean loss; Trainer.step(bs)
        # rescales sum-of-grads by 1/bs — same thing for mean loss with
        # batch_size = number of rows when loss already averages:
        total = loss.mean()
    total.backward()
    trainer_b.step(1)
    w_eager = net_b.weight.data().asnumpy()
    assert np.allclose(w_spmd, w_eager, atol=1e-4), (w_spmd, w_eager)


def test_spmd_batchnorm_stats_update():
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.BatchNorm(), nn.Dense(2))
    net.initialize()
    tr = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1})
    X = np.random.rand(32, 4).astype(np.float32) + 3.0
    Y = np.random.randint(0, 2, 32).astype(np.float32)
    for _ in range(3):
        tr.step(X, Y)
    tr.sync_to_block()
    bn = net[1]
    assert not np.allclose(bn.running_mean.data().asnumpy(), 0.0), \
        "BN moving stats must update through the compiled SPMD step"


def test_spmd_tp_sharded_params():
    m = mesh_mod.make_mesh({"dp": 4, "tp": 2})
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(2))
    net.initialize()
    tr = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=m, shard_params=True)
    X = np.random.rand(16, 8).astype(np.float32)
    Y = np.random.randint(0, 2, 16).astype(np.float32)
    l0 = float(tr.step(X, Y).asscalar())
    l1 = float(tr.step(X, Y).asscalar())
    assert np.isfinite(l0) and np.isfinite(l1)
    # the big Dense weight must actually be sharded over tp
    big = [r for r in tr._params if r.shape == (64, 8)][0]
    assert len(big.sharding.device_set) >= 2


def test_spmd_zero_sharded_opt_states():
    """shard_opt_states=True: Adam m/v live dp-sharded (ZeRO-1) and the
    loss trajectory matches the replicated-state trainer exactly."""
    def run(shard):
        np.random.seed(5)
        mx.random.seed(5)
        net = nn.HybridSequential()
        net.add(nn.Dense(64, activation="relu"), nn.Dense(2))
        net.initialize(mx.init.Xavier())
        trainer = data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.05}, shard_opt_states=shard)
        X = np.random.RandomState(0).rand(64, 16).astype(np.float32)
        Y = (X.sum(1) > 8).astype(np.float32)
        losses = [float(trainer.step(X, Y).asscalar()) for _ in range(8)]
        return trainer, losses

    t_sharded, l_sharded = run(True)
    t_repl, l_repl = run(False)
    np.testing.assert_allclose(l_sharded, l_repl, rtol=1e-4)

    # the big states must actually be partitioned over dp
    dp = t_sharded.mesh.shape["dp"]
    assert dp > 1
    found_sharded = False
    for st in t_sharded._states:
        if st is None:
            continue
        m, v = st
        if any(d % dp == 0 and d >= dp for d in m.shape):
            assert "dp" in tuple(m.sharding.spec), m.sharding
            nshards = len({s.device for s in m.addressable_shards})
            assert nshards == dp
            found_sharded = True
    assert found_sharded
    for st in t_repl._states:
        if st is not None:
            assert tuple(st[0].sharding.spec) in ((), (None,), (None, None))


def test_spmd_checkpoint_resume(tmp_path):
    """Kill-and-resume: save sharded params+opt state mid-training,
    rebuild a fresh trainer, load, and reproduce the exact loss
    trajectory of uninterrupted training (VERDICT §Next 6)."""
    X = np.random.RandomState(7).rand(64, 16).astype(np.float32)
    Y = (X.sum(1) > 8).astype(np.float32)

    def fresh():
        from mxnet_tpu.gluon.block import _BlockScope

        # a resumed PROCESS restarts auto-prefix counters at zero; do the
        # same here so checkpoint param names line up across instances
        _BlockScope._counters.clear()
        np.random.seed(9)
        mx.random.seed(9)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
        net.initialize(mx.init.Xavier())
        return data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.05}, shard_opt_states=True)

    # uninterrupted run: 8 steps
    t0 = fresh()
    ref = [float(t0.step(X, Y).asscalar()) for _ in range(8)]

    # interrupted run: 5 steps, checkpoint, "crash", resume, 3 steps
    t1 = fresh()
    part1 = [float(t1.step(X, Y).asscalar()) for _ in range(5)]
    prefix = str(tmp_path / "ckpt")
    t1.save_states(prefix)
    del t1

    t2 = fresh()           # new process stand-in: fresh params
    t2.build(X)
    t2.load_states(prefix)
    assert t2._t == 5
    part2 = [float(t2.step(X, Y).asscalar()) for _ in range(3)]
    np.testing.assert_allclose(part1 + part2, ref, rtol=1e-5)

    # opt-state sharding survives the round trip
    for st in t2._states:
        if st is not None and any(d % 8 == 0 and d >= 8
                                  for d in st[0].shape):
            assert "dp" in tuple(st[0].sharding.spec)

    # mesh-mismatch guard
    import jax as _jax
    from mxnet_tpu.parallel import mesh as mesh_mod

    small = mesh_mod.make_mesh({"dp": 2}, devices=_jax.devices()[:2])
    t3 = data_parallel.DataParallelTrainer(
        fresh().block, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.05}, mesh=small)
    t3.build(X)
    with pytest.raises(mx.MXNetError):
        t3.load_states(prefix)


def test_gradient_compression_2bit():
    """2-bit threshold quantization with error feedback
    (ref: tests/nightly/dist_sync_kvstore.py --gc-type 2bit)."""
    import numpy as np

    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", nd.zeros((4,)))
    g = nd.array(np.array([0.3, 0.7, -0.9, 0.0], np.float32))
    kv.push("w", [g])
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), [0, 0.5, -0.5, 0])
    # error feedback: accumulated residual pushes 0.3+0.3 over threshold
    kv.push("w", [g])
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), [0.5, 0.5, -0.5, 0])
    # per-slot residuals are independent
    assert len(kv._compression._residuals) == 1
    assert kv._compression.get_params()["threshold"] == 0.5


def test_gradient_compression_validation():
    kv = mx.kv.create("device")
    with pytest.raises(mx.MXNetError):
        kv.set_gradient_compression({"type": "1bit"})
    with pytest.raises(mx.MXNetError):
        kv.set_gradient_compression({"type": "2bit", "threshold": -1})
    kv.set_gradient_compression({"type": "none"})
    assert kv._compression is None


def _dense_case():
    from mxnet_tpu import gluon

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(4))
    rng = np.random.RandomState(0)
    return (net, rng.rand(16, 10).astype(np.float32),
            rng.randint(0, 4, 16).astype(np.float32))


def _attention_case():
    """Two encoder layers (attention at a shape the flash kernels
    take: sequence 128, two heads of 64) between two projections."""
    from mxnet_tpu import gluon
    from mxnet_tpu.models.bert import BERTEncoderLayer

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, flatten=False),
            BERTEncoderLayer(128, 256, 2, dropout=0.0),
            BERTEncoderLayer(128, 256, 2, dropout=0.0),
            gluon.nn.Dense(4, flatten=False))
    rng = np.random.RandomState(0)
    return (net, rng.rand(4, 128, 10).astype(np.float32),
            rng.randint(0, 4, (4, 128)).astype(np.float32))


@pytest.mark.parametrize("case", ["dense", "attention", "flash_kernels"])
def test_spmd_remat_matches_exact(case, monkeypatch, request):
    """remat=True must change only the memory/FLOP schedule, not the
    math: identical loss trajectory and final params vs remat=False
    (to an ulp or two: XLA fuses a recomputed pass otherwise than a
    stored one).  On the flash kernels the output and row statistic
    that remat keeps are the values a second run of the forward kernel
    would produce: BIT FOR BIT the bare `jax.checkpoint`'s result, as
    on the XLA form of attention (what a CPU lowers), where the names
    sit in the branch the lowering drops."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.parallel import data_parallel, mesh as mesh_mod

    if case == "flash_kernels":
        # the TPU branch of the attention dispatch, its kernels run by
        # Pallas's interpreter: the residual names are live, as on a chip
        request.getfixturevalue("interpret_pallas")
        monkeypatch.setattr(jax.lax, "platform_dependent",
                            lambda *args, tpu, default: tpu(*args))
    jax.clear_caches()      # the section counts traces: start from none
    profiler.sections(reset=True)

    def run(remat):
        mx.random.seed(11)
        net, x, y = _dense_case() if case == "dense" else _attention_case()
        net.initialize(mx.init.Xavier())
        mesh = mesh_mod.make_mesh({"dp": 2}, devices=jax.devices()[:2])
        tr = data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1 if case == "dense" else 0.01},
            mesh=mesh, remat=remat)
        losses = [float(tr.step(x, y).asscalar()) for _ in range(5)]
        return losses, [np.asarray(p) for p in tr._params]

    stored, kept = run(False), run(True)
    assert np.allclose(stored[0], kept[0], atol=1e-6), (stored[0], kept[0])
    for a, b in zip(stored[1], kept[1]):
        assert np.allclose(a, b, atol=1e-6)
    assert kept[0][-1] < kept[0][0]
    if case == "dense":
        return
    # one trace of the fwd rule serves both layers and both trainers
    # (the dispatch traces its TPU branch whatever the platform): the
    # pair it named is the (b, h, s, d) output and the (b, h, s) lse
    stats = profiler.sections()["flashAttention"]
    assert stats["residuals_named"] == 1 and stats["residual_bytes"] == {
        "resident b2 h2 sq128 sk128 d64 float32": 4 * 2 * 2 * 128 * (64 + 1)}
    monkeypatch.setattr(data_parallel, "_remat_policy", lambda: None)
    bare = run(True)
    assert bare[0] == kept[0]
    for a, b in zip(bare[1], kept[1]):
        np.testing.assert_array_equal(a, b)


def test_step_many_matches_stepwise():
    """step_many(K) is ONE XLA computation (lax.scan bulk execution,
    ref: MXNET_EXEC_BULK_EXEC_TRAIN) and must reproduce K individual
    step() calls exactly — same PRNG key sequence, same optimizer-state
    trajectory."""

    def build():
        mx.random.seed(11)
        np.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
        net.initialize(mx.init.Xavier())
        return data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.01})

    rng = np.random.RandomState(5)
    K, bs, d = 4, 16, 8
    xs = rng.rand(K, bs, d).astype(np.float32)
    ys = rng.randint(0, 3, (K, bs)).astype(np.float32)

    tr_a = build()
    losses_a = [float(tr_a.step(xs[i], ys[i]).asscalar())
                for i in range(K)]

    # stacked mode: one minibatch per scanned step
    tr_b = build()
    losses_b = tr_b.step_many(xs, ys).asnumpy()
    assert np.allclose(losses_a, losses_b, atol=1e-6), (losses_a, losses_b)
    for a, b in zip(tr_a._params, tr_b._params):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert tr_a._t == tr_b._t == K

    # repeat mode: same batch K times == K step() calls on that batch
    tr_c = build()
    losses_c1 = [float(tr_c.step(xs[0], ys[0]).asscalar())
                 for i in range(K)]
    tr_d = build()
    losses_c2 = tr_d.step_many(xs[0], ys[0], n_steps=K).asnumpy()
    assert np.allclose(losses_c1, losses_c2, atol=1e-6)

    # interleaving with step() continues the same trajectory
    more_a = float(tr_a.step(xs[0], ys[0]).asscalar())
    more_b = float(tr_b.step(xs[0], ys[0]).asscalar())
    assert np.allclose(more_a, more_b, atol=1e-6)


def test_async_sharded_checkpoint(tmp_path):
    """async_save=True: the snapshot is immune to later donated steps
    (device buffers are invalidated) and the write completes on the
    host pool; the restored trajectory matches the synchronous save."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import _BlockScope
    from mxnet_tpu.parallel import data_parallel

    rng = np.random.RandomState(0)
    X = rng.rand(16, 6).astype(np.float32)
    Y = (X.sum(axis=1) > 3).astype(np.float32)

    def fresh():
        _BlockScope._counters.clear()
        np.random.seed(4)
        mx.random.seed(4)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
        net.initialize(mx.init.Xavier())
        return data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.05})

    t1 = fresh()
    for _ in range(3):
        t1.step(X, Y)
    fut = t1.save_states(str(tmp_path / "async"), async_save=True)
    # keep training WHILE the write is in flight: donation must not
    # corrupt the snapshot
    after = [float(t1.step(X, Y).asscalar()) for _ in range(3)]
    fut.result()

    t2 = fresh()
    t2.build(X)
    t2.load_states(str(tmp_path / "async"))
    assert t2._t == 3
    resumed = [float(t2.step(X, Y).asscalar()) for _ in range(3)]
    np.testing.assert_allclose(resumed, after, rtol=1e-5)


def test_param_spec_fn_matched_nothing_raises():
    """An explicitly-passed param_spec_fn that places nothing is a
    misconfiguration (e.g. custom block prefix): loud error, not
    silent replication."""
    import pytest as _pytest

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import data_parallel, mesh as mesh_mod

    net = nn.Dense(4, in_units=4)
    net.initialize(mx.init.Xavier())
    mesh = mesh_mod.make_mesh({"dp": 2}, devices=__import__("jax")
                              .devices()[:2])
    tr = data_parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1},
        mesh=mesh, param_spec_fn=lambda name, shape: None)
    with _pytest.raises(mx.MXNetError, match="matched no parameters"):
        tr.step(np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))


def test_zero_opt_states_stay_dp_sharded_with_tp_params():
    """shard_params=True (tp) + shard_opt_states=True (ZeRO): optimizer
    state keeps the dp placement — only param_spec_fn-placed params
    carry their own sharding into the state (review r3 find: the
    custom-spec override must not disable ZeRO for tp params)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import data_parallel, mesh as mesh_mod

    net = nn.Dense(32, in_units=64, use_bias=False)
    net.initialize(mx.init.Xavier())
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2},
                              devices=jax.devices()[:4])
    tr = data_parallel.DataParallelTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 1e-3},
        mesh=mesh, shard_params=True, shard_opt_states=True)
    x = np.random.RandomState(0).rand(8, 64).astype(np.float32)
    tr.step(x, np.zeros((8, 32), np.float32))
    (m, v), = [s for s in tr._states if s is not None]
    mspec = str(m.sharding.spec)
    assert "dp" in mspec and "tp" not in mspec, mspec


def test_accum_steps_matches_full_batch():
    """Gradient accumulation (ref: grad_req='add' + Trainer.step on the
    accumulated batch): accum_steps=K scanning K micro-batches inside
    the compiled step must reproduce the full-batch trajectory exactly
    (equal micro sizes: mean-of-means == full mean)."""
    rng = np.random.RandomState(3)
    X = rng.rand(32, 12).astype(np.float32)
    Y = rng.randint(0, 4, 32).astype(np.float32)

    def run(accum):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        tr = data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9},
            accum_steps=accum)
        losses = [float(tr.step(X, Y).asnumpy()) for _ in range(4)]
        flat = np.concatenate([p.data().asnumpy().ravel()
                               for p in net.collect_params().values()])
        return losses, flat

    l1, p1 = run(1)
    l2, p2 = run(2)
    l4, p4 = run(4)
    assert np.allclose(l1, l2, atol=1e-5), (l1, l2)
    assert np.allclose(l1, l4, atol=1e-5), (l1, l4)
    assert np.allclose(p1, p2, atol=1e-5)
    assert np.allclose(p1, p4, atol=1e-5)
    assert l1[-1] < l1[0]


def test_accum_steps_indivisible_batch_raises():
    net = nn.Dense(4)
    net.initialize(mx.init.Xavier())
    tr = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, accum_steps=3)
    X = np.random.rand(8, 6).astype(np.float32)
    Y = np.zeros((8,), np.float32)
    with pytest.raises(ValueError, match="divisible"):
        tr.step(X, Y)
