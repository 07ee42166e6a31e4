"""Pallas conv1x1+BN+ReLU epilogue-fusion kernels and ops (interpret
mode on CPU).  Ref: the cuDNN fused-op pattern
(CUDNN_FUSED_SCALE_BIAS_ACTIVATION_CONV_BNSTATS) rebuilt tpu-style —
see ops/pallas/conv_fused.py."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd


@pytest.fixture(autouse=True)
def _force_fused_kernels(monkeypatch):
    """Off-TPU the kernels gate themselves off (lowering would fail);
    the interpret_pallas fixture makes them runnable here, so force
    the pallas route for every test in this module."""
    monkeypatch.setenv("MXTPU_CONV_FUSED_INTERPRET", "1")


def _jnp():
    import jax.numpy as jnp

    return jnp


def test_matmul_bn_stats_parity(interpret_pallas):
    import jax

    from mxnet_tpu.ops.pallas import conv_fused as cf

    jnp = _jnp()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(256, 128).astype(np.float32) - 0.5)
    w = jnp.asarray(rng.rand(128, 128).astype(np.float32) - 0.5)
    y, s, q = cf.matmul_bn_stats(x, w)
    ry, rs, rq = cf._mm_stats_ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(q), np.asarray(rq), rtol=1e-5)

    # grads (custom VJP) against autodiff of the reference
    def lp(x, w):
        y, s, q = cf.matmul_bn_stats(x, w)
        return y.sum() + (2 * s).sum() + (0.5 * q).sum()

    def lr(x, w):
        y, s, q = cf._mm_stats_ref(x, w)
        return y.sum() + (2 * s).sum() + (0.5 * q).sum()

    gp = jax.grad(lp, (0, 1))(x, w)
    gr = jax.grad(lr, (0, 1))(x, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def test_bn_act_matmul_parity(interpret_pallas):
    import jax

    from mxnet_tpu.ops.pallas import conv_fused as cf

    jnp = _jnp()
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.rand(128, 64).astype(np.float32) - 0.5)
    w = jnp.asarray(rng.rand(64, 128).astype(np.float32) - 0.5)
    sc = jnp.asarray(rng.rand(1, 64).astype(np.float32) + 0.5)
    sh = jnp.asarray(rng.rand(1, 64).astype(np.float32) - 0.5)
    z = cf.bn_act_matmul(x, sc, sh, w)
    rz = jnp.dot(cf._bn_act_ref(x, sc, sh, True), w)
    np.testing.assert_allclose(np.asarray(z), np.asarray(rz), atol=1e-5)

    def lp(x, sc, sh, w):
        return (cf.bn_act_matmul(x, sc, sh, w) ** 2).sum()

    def lr(x, sc, sh, w):
        return (jnp.dot(cf._bn_act_ref(x, sc, sh, True), w) ** 2).sum()

    gp = jax.grad(lp, (0, 1, 2, 3))(x, sc, sh, w)
    gr = jax.grad(lr, (0, 1, 2, 3))(x, sc, sh, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_bn_act_matmul_stats_parity(interpret_pallas):
    from mxnet_tpu.ops.pallas import conv_fused as cf

    jnp = _jnp()
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.rand(128, 128).astype(np.float32) - 0.5)
    w = jnp.asarray(rng.rand(128, 64).astype(np.float32) - 0.5)
    sc = jnp.asarray(rng.rand(1, 128).astype(np.float32) + 0.5)
    sh = jnp.asarray(rng.rand(1, 128).astype(np.float32) - 0.5)
    y, s, q = cf.bn_act_matmul_stats(x, sc, sh, w)
    h = cf._bn_act_ref(x, sc, sh, True)
    ry, rs, rq = cf._mm_stats_ref(h, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(q), np.asarray(rq), rtol=1e-5)


def test_nontiling_shapes_fall_back():
    """Shapes that don't tile run the jnp reference transparently (no
    pallas_call, works off-TPU without interpret mode)."""
    from mxnet_tpu.ops.pallas import conv_fused as cf

    jnp = _jnp()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.rand(100, 48).astype(np.float32))  # no tiling
    w = jnp.asarray(rng.rand(48, 24).astype(np.float32))
    y, s, q = cf.matmul_bn_stats(x, w)
    ry, rs, rq = cf._mm_stats_ref(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-5)
    np.testing.assert_allclose(np.asarray(q), np.asarray(rq), rtol=1e-5)


def _make_bottleneck(fuse, seed=3, monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setenv("MXTPU_CONV_EPILOGUE",
                           "pallas" if fuse else "")
    from mxnet_tpu.gluon.model_zoo.vision import resnet as rn

    mx.random.seed(seed)
    np.random.seed(seed)
    blk = rn.BottleneckV1(64, 2, downsample=True, in_channels=32,
                          layout="NHWC")
    blk.initialize(mx.init.Xavier())
    return blk


def _sync_params(src, dst):
    # pair by structural (insertion) order: the global name counters
    # differ between the two builds and sort lexicographically
    # ("batchnorm10" < "batchnorm9"), which would misalign roles
    for p1, p2 in zip(src.collect_params().values(),
                      dst.collect_params().values()):
        p2.set_data(p1.data())
    for blk in (src, dst):
        for k, p in blk.collect_params().items():
            if "running_mean" in k:
                p.set_data(nd.zeros(p.shape))
            if "running_var" in k:
                p.set_data(nd.ones(p.shape))


def test_fused_bottleneck_matches_standard(interpret_pallas, monkeypatch):
    """The MXTPU_CONV_EPILOGUE=pallas BottleneckV1 path must match the
    standard conv/BN/ReLU composition bit-for-nearly-bit: forward
    (train+eval), parameter gradients, and running-stat updates."""
    x = nd.random.uniform(shape=(2, 8, 8, 32))
    blk_a = _make_bottleneck(False, monkeypatch=monkeypatch)
    blk_b = _make_bottleneck(True, monkeypatch=monkeypatch)
    assert blk_b._fuse and not blk_a._fuse
    blk_a(x)
    blk_b(x)  # resolve deferred shapes
    _sync_params(blk_a, blk_b)

    with autograd.record():
        ya = blk_a(x)
    ya.sum().backward()
    with autograd.record():
        yb = blk_b(x)
    yb.sum().backward()
    np.testing.assert_allclose(ya.asnumpy(), yb.asnumpy(), atol=1e-5)
    for (k, pa), pb in zip(blk_a.collect_params().items(),
                           blk_b.collect_params().values()):
        if pa.grad_req == "write":
            np.testing.assert_allclose(pa.grad().asnumpy(),
                                       pb.grad().asnumpy(),
                                       atol=1e-4, err_msg=k)
    # aux updates went through the fused ops' mutate_aux
    np.testing.assert_allclose(
        blk_a.body[1].running_mean.data().asnumpy(),
        blk_b.body[1].running_mean.data().asnumpy(), atol=1e-6)
    np.testing.assert_allclose(
        blk_a.body[4].running_var.data().asnumpy(),
        blk_b.body[4].running_var.data().asnumpy(), atol=1e-6)
    # eval mode (moving stats path, no stats epilogue)
    np.testing.assert_allclose(blk_a(x).asnumpy(), blk_b(x).asnumpy(),
                               atol=1e-5)


def test_fused_bottleneck_hybridized(interpret_pallas, monkeypatch):
    """The fused path must survive CachedOp capture (one XLA graph) and
    keep updating running stats through the trace."""
    x = nd.random.uniform(shape=(2, 8, 8, 32))
    blk_a = _make_bottleneck(False, monkeypatch=monkeypatch)
    blk_b = _make_bottleneck(True, monkeypatch=monkeypatch)
    blk_a(x)
    blk_b(x)
    blk_b.hybridize()
    blk_b(x)  # build the CachedOp in eval mode: the deferred-init
    # eager probe inside the first hybridized call would otherwise
    # apply BN's momentum update once more than the eager baseline
    _sync_params(blk_a, blk_b)
    with autograd.record():
        ya = blk_a(x)
        yb = blk_b(x)
    np.testing.assert_allclose(ya.asnumpy(), yb.asnumpy(), atol=1e-5)
    np.testing.assert_allclose(
        blk_a.body[7].running_mean.data().asnumpy(),
        blk_b.body[7].running_mean.data().asnumpy(), atol=1e-6)
    np.testing.assert_allclose(blk_a(x).asnumpy(), blk_b(x).asnumpy(),
                               atol=1e-5)


@pytest.mark.slow
def test_fused_resnet50_step_matches_standard(interpret_pallas,
                                              monkeypatch):
    """resnet50_v1(NHWC) under MXTPU_CONV_EPILOGUE=pallas: a full
    DataParallelTrainer step (jit + donation + SPMD) produces the same
    loss as the standard path with identical params/data."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import data_parallel

    x = np.random.RandomState(0).rand(8, 32, 32, 3).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, 8).astype(np.float32)

    losses = {}
    for mode in ("", "pallas"):
        monkeypatch.setenv("MXTPU_CONV_EPILOGUE", mode)
        mx.random.seed(0)
        np.random.seed(0)
        net = vision.resnet50_v1(layout="NHWC", classes=10)
        net.initialize(mx.init.Xavier())
        tr = data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9})
        losses[mode] = [float(tr.step(x, y).asnumpy()) for _ in range(2)]
    assert np.isfinite(losses["pallas"]).all()
    # step 1 is exact-path parity; step 2 has gone through one update
    # whose 1e-5-level numeric differences amplify through BN rsqrt
    np.testing.assert_allclose(losses["pallas"][0], losses[""][0],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(losses["pallas"][1], losses[""][1],
                               rtol=0.05)


def test_fused_flag_on_plain_cpu_falls_back(monkeypatch):
    """MXTPU_CONV_EPILOGUE=pallas on a CPU backend WITHOUT interpret
    mode must run the jnp reference forms, not die in pallas lowering
    (pallas on CPU is interpret-only, and the failure surfaces at
    compile time — past any trace-time try/except)."""
    monkeypatch.setenv("MXTPU_CONV_EPILOGUE", "pallas")
    monkeypatch.delenv("MXTPU_CONV_FUSED_INTERPRET", raising=False)
    from mxnet_tpu.gluon.model_zoo.vision import resnet as rn

    blk = rn.BottleneckV1(64, 1, layout="NHWC")
    blk.initialize(mx.init.Xavier())
    x = nd.random.uniform(shape=(2, 8, 8, 64))
    blk(x)
    with autograd.record():
        y = blk(x)
    y.sum().backward()
    assert y.shape == (2, 8, 8, 64)
    assert np.isfinite(y.asnumpy()).all()
