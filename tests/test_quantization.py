"""INT8 quantization tests.

Ref test strategy: tests/python/quantization/test_quantization.py —
quantize/dequantize roundtrips, quantized op vs fp32 reference within
tolerance, calibration, and whole-model quantization.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.contrib import quantization as qz


def test_quantize_dequantize_roundtrip_int8():
    x = np.random.RandomState(0).randn(16, 32).astype(np.float32) * 4
    q, mn, mx_ = nd.contrib.quantize_v2(nd.array(x))
    assert q.dtype == np.int8
    back = nd.contrib.dequantize(q, mn, mx_).asnumpy()
    step = float(mx_.asscalar()) / 127
    assert np.abs(back - x).max() <= step / 2 + 1e-6


def test_quantize_uint8_affine():
    x = np.random.RandomState(1).rand(8, 8).astype(np.float32) * 10 - 2
    q, mn, mx_ = nd.contrib.quantize(
        nd.array(x), nd.array(np.float32(x.min()).reshape(())),
        nd.array(np.float32(x.max()).reshape(())), out_type="uint8")
    assert q.dtype == np.uint8
    back = nd.contrib.dequantize(q, mn, mx_).asnumpy()
    step = (x.max() - x.min()) / 255
    assert np.abs(back - x).max() <= step / 2 + 1e-6


def test_quantize_calibrated_clips():
    x = np.array([-10.0, -1.0, 0.5, 1.0, 10.0], np.float32)
    q, mn, mx_ = nd.contrib.quantize_v2(nd.array(x), min_calib_range=-1.0,
                                        max_calib_range=1.0)
    qn = q.asnumpy()
    assert qn[0] == -127 and qn[-1] == 127  # outliers clip to the range
    assert float(mx_.asscalar()) == pytest.approx(1.0)


def test_quantized_fc_matches_fp32():
    rs = np.random.RandomState(2)
    x = rs.randn(10, 24).astype(np.float32)
    w = rs.randn(6, 24).astype(np.float32)
    b = rs.randn(6).astype(np.float32)
    ref = nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b),
                            num_hidden=6).asnumpy()
    qx, xmn, xmx = nd.contrib.quantize_v2(nd.array(x))
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(w))
    qb, bmn, bmx = nd.contrib.quantize_v2(nd.array(b))
    out, omn, omx = nd.contrib.quantized_fully_connected(
        qx, qw, qb, xmn, xmx, wmn, wmx, bmn, bmx, num_hidden=6)
    assert out.dtype == np.int32
    got = nd.contrib.dequantize(out, omn, omx).asnumpy()
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.03, rel


def test_quantized_conv_matches_fp32():
    rs = np.random.RandomState(3)
    x = rs.randn(2, 3, 10, 10).astype(np.float32)
    w = rs.randn(8, 3, 3, 3).astype(np.float32)
    ref = nd.Convolution(nd.array(x), nd.array(w), no_bias=True,
                         kernel=(3, 3), num_filter=8).asnumpy()
    qx, xmn, xmx = nd.contrib.quantize_v2(nd.array(x))
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(w))
    out, omn, omx = nd.contrib.quantized_conv(
        qx, qw, None, xmn, xmx, wmn, wmx, kernel=(3, 3), num_filter=8,
        no_bias=True)
    got = nd.contrib.dequantize(out, omn, omx).asnumpy()
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.03, rel


def test_quantized_pooling_preserves_scale():
    rs = np.random.RandomState(4)
    x = rs.randn(2, 3, 8, 8).astype(np.float32)
    qx, mn, mx_ = nd.contrib.quantize_v2(nd.array(x))
    qp, pmn, pmx = nd.contrib.quantized_pooling(qx, mn, mx_, kernel=(2, 2),
                                                stride=(2, 2))
    ref = nd.Pooling(nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="max").asnumpy()
    got = nd.contrib.dequantize(qp, pmn, pmx).asnumpy()
    assert np.abs(got - ref).max() < float(mx_.asscalar()) / 127 + 1e-6


def test_requantize_to_calibrated_int8():
    rs = np.random.RandomState(5)
    x = rs.randn(4, 16).astype(np.float32)
    w = rs.randn(4, 16).astype(np.float32)
    qx, xmn, xmx = nd.contrib.quantize_v2(nd.array(x))
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(w))
    out, omn, omx = nd.contrib.quantized_fully_connected(
        qx, qw, None, xmn, xmx, wmn, wmx, num_hidden=4, no_bias=True)
    ref = x.reshape(4, -1) @ w.T
    amax = float(np.abs(ref).max())
    q8, rmn, rmx = nd.contrib.requantize(out, omn, omx,
                                         min_calib_range=-amax,
                                         max_calib_range=amax)
    assert q8.dtype == np.int8
    got = nd.contrib.dequantize(q8, rmn, rmx).asnumpy()
    rel = np.abs(got - ref).max() / amax
    assert rel < 0.05, rel


def test_kl_threshold_clips_outliers():
    rs = np.random.RandomState(6)
    arr = rs.randn(20000).astype(np.float32)
    arr[0] = 1000.0  # single extreme outlier
    t = qz._get_optimal_threshold(arr)
    assert t < 100.0, "entropy calibration should clip the outlier"
    assert t > 1.0


def test_quantize_model_symbolic():
    import mxnet_tpu.symbol as sym

    rs = np.random.RandomState(7)
    data = sym.var("data")
    h = sym.FullyConnected(data, num_hidden=16, name="fc1")
    h = sym.Activation(h, act_type="relu")
    out = sym.FullyConnected(h, num_hidden=4, name="fc2")

    arg_params = {
        "fc1_weight": nd.array(rs.randn(16, 8).astype(np.float32) * 0.3),
        "fc1_bias": nd.array(rs.randn(16).astype(np.float32) * 0.1),
        "fc2_weight": nd.array(rs.randn(4, 16).astype(np.float32) * 0.3),
        "fc2_bias": nd.array(rs.randn(4).astype(np.float32) * 0.1),
    }
    x = rs.randn(32, 8).astype(np.float32)
    ex = out.bind(mx.current_context(),
                  dict(arg_params, data=nd.array(x)), grad_req="null")
    ref = ex.forward()[0].asnumpy()

    qsym, qargs, qaux = qz.quantize_model(out, arg_params,
                                          calib_mode="none")
    assert any(n.endswith("_quantize") for n in qargs), list(qargs)
    qex = qsym.bind(mx.current_context(),
                    dict(qargs, data=nd.array(x)), grad_req="null")
    got = qex.forward()[0].asnumpy()
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.06, rel


def test_quantize_model_symbolic_conv_no_bias():
    """Bias-less Convolution (the resnet pattern: conv->BN carries no
    conv bias) through the SYMBOLIC quantize pass: the rewritten graph
    wires 6 positional inputs (no bias slot) and the int8 kernels must
    parse that arity (regression: the no_bias graph used to shift
    min/max into the bias slot and fail at eval)."""
    import mxnet_tpu.symbol as sym

    rs = np.random.RandomState(9)
    data = sym.var("data")
    out = sym.Convolution(data, kernel=(3, 3), num_filter=8,
                          no_bias=True, name="convq")
    arg_params = {
        "convq_weight": nd.array(
            rs.randn(8, 3, 3, 3).astype(np.float32) * 0.2),
    }
    x = rs.randn(4, 3, 16, 16).astype(np.float32)
    ex = out.bind(mx.current_context(),
                  dict(arg_params, data=nd.array(x)), grad_req="null")
    ref = ex.forward()[0].asnumpy()

    qsym, qargs, _ = qz.quantize_model(out, arg_params,
                                       calib_mode="none")
    qex = qsym.bind(mx.current_context(),
                    dict(qargs, data=nd.array(x)), grad_req="null")
    got = qex.forward()[0].asnumpy()
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.06, rel


def test_quantize_model_full_cnn_end_to_end(tmp_path):
    """A whole model-zoo CNN (export -> symbol -> quantize -> bind ->
    forward)."""
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.symbol import load as sym_load

    mx.random.seed(0)
    net = vision.lenet()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = np.random.RandomState(0).rand(4, 1, 28, 28).astype(np.float32)
    ref = net(nd.array(x)).asnumpy()
    prefix = str(tmp_path / "qnet")
    net.export(prefix)
    symbol = sym_load(prefix + "-symbol.json")
    payload = nd.load(prefix + "-0000.params")
    arg_params = {k[4:]: v for k, v in payload.items()
                  if k.startswith("arg:")}
    aux_params = {k[4:]: v for k, v in payload.items()
                  if k.startswith("aux:")}
    qsym, qargs, qaux = qz.quantize_model(
        symbol, arg_params, aux_params, calib_mode="naive",
        calib_data=x)
    qex = qsym.bind(mx.current_context(),
                    dict(qargs, data=nd.array(x)), grad_req="null",
                    aux_states=dict(qaux))
    got = qex.forward()[0].asnumpy()
    # int8 end-to-end on a real conv stack: logits stay close enough
    # to preserve the prediction ordering
    assert np.isfinite(got).all()
    assert (got.argmax(1) == ref.argmax(1)).mean() >= 0.75


def test_quantize_model_calibrated():
    import mxnet_tpu.symbol as sym

    rs = np.random.RandomState(8)
    data = sym.var("data")
    out = sym.FullyConnected(data, num_hidden=8, name="fcq")
    arg_params = {
        "fcq_weight": nd.array(rs.randn(8, 12).astype(np.float32) * 0.5),
        "fcq_bias": nd.array(rs.randn(8).astype(np.float32) * 0.1),
    }
    calib = rs.randn(64, 12).astype(np.float32)
    qsym, qargs, _ = qz.quantize_model(
        out, arg_params, calib_mode="naive", calib_data=calib)
    # calibrated graph bakes requantize with fixed ranges
    assert "_requantize" in qsym.tojson()
    # evaluate on calibration-representative data: calibrated ranges
    # legitimately clip inputs outside what calibration saw
    x = calib[:16]
    ex = out.bind(mx.current_context(),
                  dict(arg_params, data=nd.array(x)), grad_req="null")
    ref = ex.forward()[0].asnumpy()
    qex = qsym.bind(mx.current_context(),
                    dict(qargs, data=nd.array(x)), grad_req="null")
    got = qex.forward()[0].asnumpy()
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.08, rel


def test_quantize_net_gluon():
    from mxnet_tpu.gluon import nn

    rs = np.random.RandomState(9)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    x = rs.randn(16, 20).astype(np.float32)
    ref = net(nd.array(x)).asnumpy()

    calib = rs.randn(64, 20).astype(np.float32)
    qnet = qz.quantize_net(net, calib_data=calib, calib_mode="naive")
    # forward path must actually run the int8 wrappers, not stale fp32
    assert all(type(l).__name__.startswith("Quantized")
               for l in qnet._layers), [type(l).__name__
                                        for l in qnet._layers]
    got = qnet(nd.array(x)).asnumpy()
    err = np.abs(got - ref).max()
    assert err > 0, "quantized output bit-identical to fp32 — no-op?"
    rel = err / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.08, rel


def test_quantize_net_conv_no_bias():
    """Eager int8 conv WITHOUT a bias (the resnet conv->BN pattern):
    the explicit-None bias slot must parse (same arity rule as the
    symbolic path's regression above)."""
    from mxnet_tpu.gluon import nn

    rs = np.random.RandomState(11)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, kernel_size=3, use_bias=False))
    net.add(nn.Conv2D(4, kernel_size=1, use_bias=True))
    net.initialize(mx.init.Xavier())
    x = rs.rand(2, 3, 12, 12).astype(np.float32)
    ref = net(nd.array(x)).asnumpy()
    qnet = qz.quantize_net(net, calib_data=x, calib_mode="naive")
    got = qnet(nd.array(x)).asnumpy()
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.08, rel


def test_quantize_net_hybridized_drops_stale_cache():
    from mxnet_tpu.gluon import nn

    rs = np.random.RandomState(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="sigmoid"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = rs.randn(8, 12).astype(np.float32)
    ref = net(nd.array(x)).asnumpy()  # builds the fp32 CachedOp
    qz.quantize_net(net)
    got = net(nd.array(x)).asnumpy()
    assert np.abs(got - ref).max() > 0, "stale fp32 CachedOp still used"
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6)
    assert rel < 0.08, rel


def test_quantize_net_excluded_layer():
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    d1, d2 = nn.Dense(16, activation="relu"), nn.Dense(4)
    net.add(d1, d2)
    net.initialize()
    x = np.random.RandomState(10).randn(4, 8).astype(np.float32)
    net(nd.array(x))
    qz.quantize_net(net, exclude_layers=[d2.name])
    kinds = [type(c).__name__ for c in net._children.values()]
    assert kinds[0] == "QuantizedDense" and kinds[1] == "Dense", kinds


# ---------------------------------------------------------------------------
# compile-native quantization: the quantized math contract


def _mlp(seed=0, in_units=20, hidden=32, out=10, act="relu"):
    from mxnet_tpu.gluon import nn

    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation=act, in_units=in_units,
                     flatten=False),
            nn.Dense(hidden, activation=act, in_units=hidden,
                     flatten=False),
            nn.Dense(out, in_units=hidden, flatten=False))
    net.initialize(mx.init.Xavier())
    return net


def test_quantized_net_hybridizes_bit_identical():
    """Compiled-vs-eager bit parity: the whole calibrated int8 chain is
    integer matmuls + elementwise fp32 scaling, so one fused XLA
    executable must produce EXACTLY the per-op eager bytes."""
    rs = np.random.RandomState(0)
    net = _mlp(seed=0)
    calib = rs.randn(64, 20).astype(np.float32)
    qnet = qz.quantize_net(net, calib_data=calib, calib_mode="naive")
    x = rs.randn(8, 20).astype(np.float32)
    eager = qnet(nd.array(x)).asnumpy()
    qnet.hybridize()
    compiled = qnet(nd.array(x)).asnumpy()
    assert np.array_equal(eager, compiled)
    # and the compiled graph is REAL int8: the hidden boundary between
    # folded layers carries int8, not fp32
    assert qnet._layers[0]._out_int8 and qnet._layers[1]._out_int8
    assert qnet._layers[0](nd.array(x)).dtype == np.int8


def test_per_channel_beats_per_tensor():
    """Per-output-channel weight scales must beat per-tensor scaling on
    a weight matrix whose rows live at wildly different magnitudes (the
    exact failure mode per-tensor symmetric scaling has)."""
    from mxnet_tpu.gluon import nn

    rs = np.random.RandomState(3)
    x = rs.randn(64, 24).astype(np.float32)

    def build():
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, in_units=24, flatten=False))
        net.initialize(mx.init.Xavier())
        # scale each output row differently: rows 0..3 are ~100x rows
        # 12..15
        w = net[0].weight.data().asnumpy() \
            * np.logspace(2, -2, 16)[:, None].astype(np.float32)
        net[0].weight.set_data(nd.array(w))
        return net

    ref = build()(nd.array(x)).asnumpy()
    # dynamic (uncalibrated) mode isolates the WEIGHT scaling choice:
    # both arms quantize the input identically and neither requantizes
    # the output (a calibrated per-TENSOR output range would crush the
    # small rows either way, masking the comparison)
    q_pc = qz.quantize_net(build(),
                           per_channel=True)(nd.array(x)).asnumpy()
    q_pt = qz.quantize_net(build(),
                           per_channel=False)(nd.array(x)).asnumpy()
    # normalize per row so the big rows don't dominate the comparison
    scale = np.abs(ref).max(axis=0) + 1e-9
    err_pc = (np.abs(q_pc - ref) / scale).max()
    err_pt = (np.abs(q_pt - ref) / scale).max()
    assert err_pc < err_pt / 4, (err_pc, err_pt)


def test_requantize_fold_equivalence():
    """The fold pass (dequantize → quantize collapsed into one
    requantize at the producer's calibrated range) must match the
    unfolded chain within quantization tolerance — the boundary ranges
    are identical, so the removed round trip was ~the identity."""
    rs = np.random.RandomState(5)
    calib = rs.randn(128, 20).astype(np.float32)
    x = rs.randn(16, 20).astype(np.float32)

    folded = qz.quantize_net(_mlp(seed=11), calib_data=calib,
                             calib_mode="naive", fold=True)
    unfolded = qz.quantize_net(_mlp(seed=11), calib_data=calib,
                               calib_mode="naive", fold=False)
    assert folded._layers[0]._out_int8
    assert not unfolded._layers[0]._out_int8
    y_f = folded(nd.array(x)).asnumpy()
    y_u = unfolded(nd.array(x)).asnumpy()
    # tolerance: one int8 step at the final layer's output range
    step = np.abs(y_u).max() / 127.0
    assert np.abs(y_f - y_u).max() <= step + 1e-6


def test_entropy_beats_naive_on_skewed_activations():
    """KL/entropy calibration must beat naive min/max when the
    activation distribution has a thin far tail: naive burns the whole
    int8 range on outliers, entropy clips them."""
    from mxnet_tpu.gluon import nn

    rs = np.random.RandomState(2)

    def build():
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=16, flatten=False,
                         activation="relu"),
                nn.Dense(8, in_units=32, flatten=False))
        net.initialize(mx.init.Xavier())
        return net

    # calibration inputs: bulk N(0,1) plus a few extreme outlier rows
    calib = rs.randn(256, 16).astype(np.float32)
    calib[:3] *= 60.0
    # held-out eval from the BULK distribution (what serving traffic
    # looks like)
    x = rs.randn(64, 16).astype(np.float32)
    ref = build()(nd.array(x)).asnumpy()
    y_naive = qz.quantize_net(build(), calib_data=calib,
                              calib_mode="naive")(nd.array(x)).asnumpy()
    y_ent = qz.quantize_net(build(), calib_data=calib,
                            calib_mode="entropy")(nd.array(x)).asnumpy()
    mse_naive = float(((y_naive - ref) ** 2).mean())
    mse_ent = float(((y_ent - ref) ** 2).mean())
    assert mse_ent < mse_naive, (mse_ent, mse_naive)


def _trained_classifier(steps=150, seed=0):
    """A briefly-trained 10-class MLP + its data distribution: the
    quality gate is defined on a net with real decision margins (an
    untrained net's iid-Gaussian logits sit arbitrarily close together,
    so ANY perturbation flips argmaxes — nothing to do with int8)."""
    from mxnet_tpu import autograd, gluon

    rs = np.random.RandomState(seed)
    centers = rs.randn(10, 32).astype(np.float32) * 2.0

    def sample(n, rng):
        y = rng.randint(0, 10, n)
        x = centers[y] + rng.randn(n, 32).astype(np.float32)
        return x.astype(np.float32), y.astype(np.int32)

    net = _mlp(seed=21, in_units=32, hidden=64, out=10)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    for _ in range(steps):
        x, y = sample(64, rs)
        with autograd.record():
            loss = loss_fn(net(nd.array(x)), nd.array(y))
        loss.backward()
        trainer.step(64)
    return net, sample


def test_quality_gate_argmax_agreement():
    """The serving quality band: a calibrated per-channel int8 net must
    agree with fp32 on >= 99% of held-out argmax decisions."""
    net, sample = _trained_classifier()
    rs = np.random.RandomState(3)
    calib, _ = sample(256, rs)
    x, _ = sample(500, np.random.RandomState(42))
    ref = net(nd.array(x)).asnumpy()
    qnet = qz.quantize_net(net, calib_data=calib, calib_mode="entropy")
    qnet.hybridize()
    got = qnet(nd.array(x)).asnumpy()
    agree = float((got.argmax(1) == ref.argmax(1)).mean())
    assert agree >= 0.99, agree


def test_dynamic_mode_compiles_without_calibration():
    """calib_mode='none' / no calib data: ranges are computed inside
    the compiled graph per batch — still one executable, no host
    syncs."""
    rs = np.random.RandomState(8)
    net = _mlp(seed=31)
    ref = net(nd.array(rs.randn(4, 20).astype(np.float32)))
    qnet = qz.quantize_net(net)
    qnet.hybridize()
    x = rs.randn(4, 20).astype(np.float32)
    y1 = qnet(nd.array(x)).asnumpy()
    from mxnet_tpu.gluon.block import cached_graph_stats

    before = cached_graph_stats()["compiles"]
    y2 = qnet(nd.array(x)).asnumpy()
    assert cached_graph_stats()["compiles"] == before  # reuse, not compile
    assert np.array_equal(y1, y2)


def test_quantized_net_save_load_roundtrip(tmp_path):
    """Serialization satellite: a quantized net persists qweights +
    scales + calibrated ranges through the versioned .params container
    and restores bit-identically into a twin."""
    rs = np.random.RandomState(4)
    calib = rs.randn(64, 20).astype(np.float32)
    qnet = qz.quantize_net(_mlp(seed=41), calib_data=calib,
                           calib_mode="naive")
    x = rs.randn(8, 20).astype(np.float32)
    ref = qnet(nd.array(x)).asnumpy()
    f = str(tmp_path / "qnet.params")
    qnet.save_parameters(f)

    # the restore recipe: rebuild the same architecture, quantize with
    # the same config (any representative calibration), then load — the
    # checkpointed scales/ranges overwrite the placeholder calibration
    twin = qz.quantize_net(_mlp(seed=99), calib_data=calib * 0.3,
                           calib_mode="naive")
    assert not np.array_equal(twin(nd.array(x)).asnumpy(), ref)
    twin.load_parameters(f)
    got = twin(nd.array(x)).asnumpy()
    assert np.array_equal(got, ref)


def test_fp32_int8_container_mismatch_is_loud(tmp_path):
    """Loading an fp32 .params file into a quantized net (or vice
    versa) must fail with the container-mismatch diagnosis, not load
    nothing / raise a generic missing-parameter error."""
    rs = np.random.RandomState(6)
    fp32 = _mlp(seed=51)
    f32file = str(tmp_path / "fp32.params")
    fp32.save_parameters(f32file)

    calib = rs.randn(32, 20).astype(np.float32)
    qnet = qz.quantize_net(_mlp(seed=52), calib_data=calib,
                           calib_mode="naive")
    qfile = str(tmp_path / "int8.params")
    qnet.save_parameters(qfile)

    with pytest.raises(mx.MXNetError, match="INT8-quantized"):
        qnet.load_parameters(f32file)
    with pytest.raises(mx.MXNetError, match="INT8-quantized param"):
        _mlp(seed=53).load_parameters(qfile)


def test_apply_fp32_params_requantizes_against_stored_scales():
    """The hot-reload primitive: fresh fp32 weights land as re-quantized
    int8 against the STORED per-channel scales; calibrated activation
    ranges are untouched."""
    rs = np.random.RandomState(7)
    calib = rs.randn(64, 20).astype(np.float32)
    src = _mlp(seed=61)
    qnet = qz.quantize_net(_mlp(seed=62), calib_data=calib,
                           calib_mode="naive")
    scales_before = qnet._layers[0].wscale.data().asnumpy().copy()
    in_range_before = float(qnet._layers[0].in_max.data().asscalar())
    qz.apply_fp32_params(qnet, {k: p.data() for k, p in
                                src._collect_params_with_prefix()
                                .items()})
    assert np.array_equal(qnet._layers[0].wscale.data().asnumpy(),
                          scales_before)
    assert float(qnet._layers[0].in_max.data().asscalar()) \
        == in_range_before
    # and the quantized weights now track the NEW fp32 weights
    w = src._layers[0].weight.data().asnumpy()
    expect = np.clip(np.round(w * (127.0 / scales_before[:, None])),
                     -127, 127).astype(np.int8)
    assert np.array_equal(qnet._layers[0].qweight.data().asnumpy(),
                          expect)


def test_calibration_is_device_side():
    """The calibration hooks must not host-sync per batch: the only
    .asnumpy()-equivalent transfers happen at finalize, one per
    tensor."""
    rs = np.random.RandomState(9)
    net = _mlp(seed=71)
    calls = {"n": 0}
    stats_cls = qz._Stats
    orig = stats_cls.finalize

    def counting_finalize(self):
        if self._dev:
            calls["n"] += 1
        return orig(self)

    stats_cls.finalize = counting_finalize
    try:
        calib = rs.randn(160, 20).astype(np.float32)
        # 5 batches of 32 via an iterator
        batches = [calib[i:i + 32] for i in range(0, 160, 32)]
        qz.quantize_net(net, calib_data=iter(batches),
                        calib_mode="entropy")
    finally:
        stats_cls.finalize = orig
    # 3 layers x (input, output) = 6 tensors -> 6 single-sync finalizes
    assert calls["n"] == 6, calls
    st = qz.quantize_stats()
    assert st["calib_batches"] >= 5
    assert st["calib_ms"] > 0


def test_calibration_must_cover_every_quantized_layer():
    """A calibration forward that never exercises a quantizable layer
    must fail LOUDLY — silently installing (inf, -inf) as calibrated
    ranges would serve NaNs with no error."""
    from mxnet_tpu.gluon import nn

    class TwoBranch(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.head = nn.Dense(8, in_units=16, flatten=False)
            self.tail = nn.Dense(4, in_units=16, flatten=False)

        def hybrid_forward(self, F, x):
            return self.head(x) + 0 * self.tail(x)

    mx.random.seed(0)
    net = TwoBranch()
    net.initialize(mx.init.Xavier())
    calib = np.random.RandomState(0).randn(16, 16).astype(np.float32)
    with pytest.raises(mx.MXNetError, match="never exercised"):
        # calib_forward only drives the head branch
        qz.quantize_net(net, calib_data=calib, calib_mode="naive",
                        calib_forward=lambda m, x: m.head(x))


def test_int8_input_into_uncalibrated_layer_is_loud():
    """Feeding a folded layer's int8 output into an UNCALIBRATED
    quantized layer cannot be interpreted (no boundary range) and must
    raise a diagnosis, not an opaque kernel error."""
    rs = np.random.RandomState(12)
    calibrated = qz.quantize_net(_mlp(seed=91),
                                 calib_data=rs.randn(32, 20)
                                 .astype(np.float32),
                                 calib_mode="naive")
    q8 = calibrated._layers[0](nd.array(rs.randn(4, 20)
                                        .astype(np.float32)))
    assert q8.dtype == np.int8
    dynamic = qz.quantize_net(_mlp(seed=92))  # no calibration
    with pytest.raises(mx.MXNetError, match="calibrated ranges"):
        dynamic._layers[1](q8)


def test_quantize_profiler_section_window_scoped():
    """`quantize` rides the profiler section registry: visible in
    dumps(), window-scoped under reset=True like every section."""
    from mxnet_tpu import profiler

    rs = np.random.RandomState(10)
    qz.reset_quantize_stats()
    qz.quantize_net(_mlp(seed=81),
                    calib_data=rs.randn(32, 20).astype(np.float32),
                    calib_mode="naive")
    data = profiler.sections()
    assert "quantize" in data
    assert data["quantize"]["layers_quantized"] == 3
    assert data["quantize"]["requant_folds"] == 2
    profiler.sections(reset=True)
    after = profiler.sections()
    assert after["quantize"]["layers_quantized"] == 0
    assert after["quantize"]["calib_ms"] == 0
