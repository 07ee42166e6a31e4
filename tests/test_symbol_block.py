"""SymbolBlock + new contrib blocks (SyncBatchNorm, PixelShuffle,
conv RNN cells, LSTMPCell).

Ref: tests/python/unittest/test_gluon.py (test_symbol_block,
test_sync_batchnorm) and test_contrib_* — oracle checks against plain
numpy / the non-contrib equivalents.
"""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def _small_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.BatchNorm(), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return net


def test_symbol_block_imports_roundtrip(tmp_path):
    mx.random.seed(0)
    net = _small_net()
    x = nd.array(np.random.RandomState(0).rand(5, 8).astype("float32"))
    y0 = net(x).asnumpy()
    net.hybridize()
    net(x)
    sym_f, par_f = net.export(str(tmp_path / "m"))
    blk = gluon.SymbolBlock.imports(sym_f, ["data"], par_f)
    y1 = blk(x).asnumpy()
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


def test_symbol_block_gradients_flow(tmp_path):
    net = _small_net()
    x = nd.array(np.random.RandomState(1).rand(4, 8).astype("float32"))
    net(x)
    sym_f, par_f = net.export(str(tmp_path / "m"))
    blk = gluon.SymbolBlock.imports(sym_f, ["data"], par_f)
    params = blk.collect_params()
    # aux (BN moving stats) must be non-differentiable, args trainable
    mean_name = [n for n in params if n.endswith("running_mean")][0]
    w_name = [n for n in params if n.endswith("weight")][0]
    assert params[mean_name]._grad_req == "null"
    assert params[w_name]._grad_req == "write"
    with autograd.record():
        loss = (blk(x) ** 2).sum()
    loss.backward()
    g = params[w_name].grad().asnumpy()
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_symbol_block_nested_in_hybridized_parent(tmp_path):
    net = _small_net()
    x = nd.array(np.random.RandomState(2).rand(3, 8).astype("float32"))
    net(x)
    sym_f, par_f = net.export(str(tmp_path / "m"))
    inner = gluon.SymbolBlock.imports(sym_f, ["data"], par_f)
    y0 = inner(x).asnumpy()

    class Wrap(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.inner = inner

        def hybrid_forward(self, F, x):
            return self.inner(x) * 2

    w = Wrap()
    w.hybridize()
    np.testing.assert_allclose(w(x).asnumpy(), 2 * y0, rtol=1e-5,
                               atol=1e-6)


def test_symbol_block_symbolic_compose():
    import mxnet_tpu.symbol as sym

    net = _small_net()
    x = nd.array(np.random.RandomState(3).rand(2, 8).astype("float32"))
    net(x)
    out, _ = __import__(
        "mxnet_tpu.symbol.export", fromlist=["trace_block_to_symbol"]
    ).trace_block_to_symbol(net)
    blk = gluon.SymbolBlock(out, [sym.var("data")])
    composed = blk(sym.var("data"))
    assert "data" in composed.list_arguments()
    assert any(n.endswith("weight") for n in composed.list_arguments())


def test_symbol_block_from_internals():
    """The classic SymbolBlock use: truncate a graph at an internal
    feature layer (ref: test_gluon.py test_symbol_block)."""
    import mxnet_tpu.symbol as sym

    net = _small_net()
    x = nd.array(np.random.RandomState(4).rand(2, 8).astype("float32"))
    net(x)
    from mxnet_tpu.symbol.export import trace_block_to_symbol

    out, _ = trace_block_to_symbol(net)
    internals = out.get_internals()
    feat = [s for s in internals
            if s._node.op == "FullyConnected"][0]
    blk = gluon.SymbolBlock(feat, [sym.var("data")])
    for name, p in net.collect_params().items():
        if name in blk.collect_params():
            q = blk.collect_params()[name]
            q.shape = p.shape
            q.initialize()
            q.set_data(p.data())
    y = blk(x)
    assert y.shape == (2, 16)


def test_sync_batch_norm_matches_batch_norm_single_device():
    from mxnet_tpu.gluon.contrib import nn as cnn

    x = nd.array(np.random.RandomState(0).rand(4, 6, 5, 5)
                 .astype("float32"))
    sbn = cnn.SyncBatchNorm(in_channels=6)
    bn = nn.BatchNorm(in_channels=6)
    sbn.initialize()
    bn.initialize()
    with autograd.record():
        y1 = sbn(x)
    with autograd.record():
        y2 = bn(x)
    np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(sbn.running_mean.data().asnumpy(),
                               bn.running_mean.data().asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_sync_batch_norm_pmean_across_shard_map():
    """Global stats under an explicit named axis equal single-big-batch
    stats (the reference's multi-device semantic)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.ops.contrib_ops import _k_sync_batch_norm

    rng = np.random.RandomState(0)
    x = rng.rand(8, 3, 4, 4).astype("float32")
    gamma = np.ones(3, "float32")
    beta = np.zeros(3, "float32")
    mm = np.zeros(3, "float32")
    mv = np.ones(3, "float32")

    full, _, _ = _k_sync_batch_norm(
        jnp.asarray(x), jnp.asarray(gamma), jnp.asarray(beta),
        jnp.asarray(mm), jnp.asarray(mv), fix_gamma=False, _train=True)

    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))

    def shard_fn(xs):
        out, _, _ = _k_sync_batch_norm(
            xs, jnp.asarray(gamma), jnp.asarray(beta), jnp.asarray(mm),
            jnp.asarray(mv), fix_gamma=False, _train=True,
            axis_name="dp")
        return out

    sharded = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(
            jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dims,factor,shape", [
    (1, 3, (2, 6, 5)),
    (2, 2, (2, 12, 4, 5)),
    (3, 2, (2, 8, 3, 4, 5)),
])
def test_pixel_shuffle_oracle(dims, factor, shape):
    from mxnet_tpu.gluon.contrib import nn as cnn

    x = np.random.RandomState(dims).rand(*shape).astype("float32")
    blk = getattr(cnn, f"PixelShuffle{dims}D")(factor)
    out = blk(nd.array(x)).asnumpy()
    N, C = shape[:2]
    sp = shape[2:]
    Co = C // factor ** dims
    # reference rearrangement (einops-style oracle)
    r = x.reshape((N, Co) + (factor,) * dims + sp)
    perm = [0, 1]
    for i in range(dims):
        perm += [2 + dims + i, 2 + i]
    r = r.transpose(perm)
    r = r.reshape((N, Co) + tuple(s * factor for s in sp))
    np.testing.assert_allclose(out, r, rtol=1e-6, atol=0)


def test_conv_lstm_cell_unroll_shapes_and_grad():
    from mxnet_tpu.gluon.contrib import rnn as crnn

    cell = crnn.Conv2DLSTMCell(input_shape=(3, 8, 8), hidden_channels=6,
                               i2h_kernel=3, h2h_kernel=3, i2h_pad=1)
    cell.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).rand(2, 4, 3, 8, 8)
                 .astype("float32"))
    with autograd.record():
        out, states = cell.unroll(4, x, layout="NTC")
        loss = (out ** 2).sum()
    loss.backward()
    assert out.shape == (2, 4, 6, 8, 8)
    assert states[0].shape == (2, 6, 8, 8)
    g = cell.i2h_weight.grad().asnumpy()
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_conv_rnn_cell_identity_oracle():
    from mxnet_tpu.gluon.contrib import rnn as crnn

    cell = crnn.Conv2DRNNCell(input_shape=(1, 4, 4), hidden_channels=1,
                              i2h_kernel=1, h2h_kernel=1)
    cell.initialize(mx.init.One())
    x = np.random.RandomState(0).rand(1, 1, 4, 4).astype("float32")
    out, _ = cell(nd.array(x))
    np.testing.assert_allclose(out.asnumpy(), np.tanh(x), rtol=1e-5,
                               atol=1e-6)


def test_conv_rnn_even_h2h_kernel_rejected():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon.contrib import rnn as crnn

    with pytest.raises(MXNetError):
        crnn.Conv2DRNNCell(input_shape=(1, 4, 4), hidden_channels=1,
                           i2h_kernel=1, h2h_kernel=2)


def test_lstmp_cell_projection_shapes_and_unroll():
    from mxnet_tpu.gluon.contrib import rnn as crnn

    cell = crnn.LSTMPCell(hidden_size=16, projection_size=8)
    cell.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).rand(3, 5, 12)
                 .astype("float32"))
    out, states = cell.unroll(5, x, layout="NTC")
    assert out.shape == (3, 5, 8)
    assert states[0].shape == (3, 8)        # projected recurrent state
    assert states[1].shape == (3, 16)       # cell state keeps hidden dim
    with autograd.record():
        o, _ = cell(nd.array(np.random.rand(3, 12).astype("float32")))
        loss = (o ** 2).sum()
    loss.backward()
    assert np.abs(cell.h2r_weight.grad().asnumpy()).max() > 0
