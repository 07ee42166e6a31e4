"""Pipeline (pp) and expert (ep) parallelism — oracle equivalence on
the virtual 8-device mesh (capability upgrades beyond the reference;
SURVEY §2.3 marks both ABSENT upstream)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import mesh as mesh_mod
from mxnet_tpu.parallel.moe import MoEBlock, moe_ffn
from mxnet_tpu.parallel.pipeline import pipeline_apply

P, D = 4, 8


def _stage(params, xb):
    W, b = params
    return jax.nn.relu(xb @ W + b)


def _pipeline_fixture():
    mesh = mesh_mod.make_mesh({"pp": P}, devices=jax.devices()[:P])
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(P, D, D).astype(np.float32) * 0.3)
    bs = jnp.asarray(rng.randn(P, D).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(8, D).astype(np.float32))
    return mesh, Ws, bs, x


def _sequential(Ws, bs, x):
    for i in range(P):
        x = jax.nn.relu(x @ Ws[i] + bs[i])
    return x


def test_pipeline_matches_sequential():
    mesh, Ws, bs, x = _pipeline_fixture()
    out = pipeline_apply(_stage, (Ws, bs), x, mesh, n_micro=4)
    assert np.allclose(np.asarray(out), np.asarray(_sequential(Ws, bs, x)),
                       atol=1e-5)
    # more microbatches than stages (smaller bubble) must also match
    out8 = pipeline_apply(_stage, (Ws, bs), x, mesh, n_micro=8)
    assert np.allclose(np.asarray(out8), np.asarray(out), atol=1e-5)


def test_pipeline_gradients_match():
    mesh, Ws, bs, x = _pipeline_fixture()

    def loss_pp(Ws, bs):
        return (pipeline_apply(_stage, (Ws, bs), x, mesh,
                               n_micro=4) ** 2).mean()

    def loss_seq(Ws, bs):
        return (_sequential(Ws, bs, x) ** 2).mean()

    g = jax.grad(loss_pp, argnums=(0, 1))(Ws, bs)
    gref = jax.grad(loss_seq, argnums=(0, 1))(Ws, bs)
    for a, b in zip(g, gref):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_pipeline_validates_microbatching():
    mesh, Ws, bs, x = _pipeline_fixture()
    with pytest.raises(MXNetError):
        pipeline_apply(_stage, (Ws, bs), x, mesh, n_micro=3)  # 8 % 3


def test_moe_sharded_matches_dense_oracle():
    mesh = mesh_mod.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    blk = MoEBlock(num_experts=4, d_model=8, d_hidden=16, seed=1)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 8).astype(np.float32))
    y, aux = jax.jit(lambda v: moe_ffn(v, *blk.params(), mesh=mesh))(x)
    # dense per-token oracle: each kept token = gate * expert_ffn(token)
    probs = jax.nn.softmax(x @ blk.router_w, -1)
    e = jnp.argmax(probs, -1)
    gate = jnp.max(probs, -1)
    onehot = jax.nn.one_hot(e, 4, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, 0) * onehot - 1).max(-1)
    C = max(1, int(1.25 * 32 / 4))
    keep = np.asarray(pos < C)
    ref = []
    for i in range(32):
        ei = int(e[i])
        h = jax.nn.relu(x[i] @ blk.w1[ei] + blk.b1[ei])
        ref.append((h @ blk.w2[ei] + blk.b2[ei]) * gate[i] * keep[i])
    assert np.allclose(np.asarray(y), np.asarray(jnp.stack(ref)),
                       atol=1e-4)
    assert float(aux) > 0


def test_moe_capacity_drops_overflow():
    """With capacity_factor << 1 most tokens overflow and pass zeros."""
    blk = MoEBlock(num_experts=2, d_model=4, d_hidden=8, seed=0)
    x = jnp.asarray(np.random.RandomState(1).randn(64, 4)
                    .astype(np.float32))
    y, _ = moe_ffn(x, *blk.params(), capacity_factor=0.05)
    routed = (jnp.abs(y).sum(-1) > 1e-6).sum()
    assert int(routed) <= 2 * max(1, int(0.05 * 64 / 2))


def test_moe_gradients_finite_and_balanced_loss():
    mesh = mesh_mod.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    blk = MoEBlock(num_experts=4, d_model=8, d_hidden=16, seed=2)
    x = jnp.asarray(np.random.RandomState(2).randn(32, 8)
                    .astype(np.float32))

    def loss(params):
        y, aux = moe_ffn(x, *params, mesh=mesh)
        return (y ** 2).mean() + 0.01 * aux

    g = jax.grad(loss)(blk.params())
    for leaf in g:
        arr = np.asarray(leaf)
        assert np.isfinite(arr).all()
    # router must receive gradient (through gate and aux loss)
    assert np.abs(np.asarray(g[0])).max() > 0


def test_gluon_moe_block_trains():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    mx.random.seed(0)
    moe = gluon.contrib.nn.MoEFFN(num_experts=4, d_model=8, d_hidden=16)
    moe.initialize(mx.init.Xavier())
    moe.hybridize()
    x = nd.random.uniform(shape=(32, 8))
    target = nd.array(np.sin(x.asnumpy() * 2))
    tr = gluon.Trainer(moe.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    losses = []
    for _ in range(30):
        with autograd.record():
            y, aux = moe(x)
            loss = ((y - target) ** 2).mean() + 0.01 * aux.sum()
        loss.backward()
        tr.step(1)
        losses.append(float(loss.asscalar()))
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])
    with pytest.raises(ValueError):
        gluon.contrib.nn.MoEFFN(num_experts=1, d_model=4, d_hidden=4)


def test_moe_accepts_sequence_input():
    """(batch, seq, d_model) transformer activations flatten through
    the token axis and come back in shape."""
    blk = MoEBlock(num_experts=4, d_model=8, d_hidden=16, seed=4)
    x3 = jnp.asarray(np.random.RandomState(3).randn(2, 16, 8)
                     .astype(np.float32))
    y3, aux = moe_ffn(x3, *blk.params())
    assert y3.shape == (2, 16, 8)
    y2, _ = moe_ffn(x3.reshape(32, 8), *blk.params())
    assert np.allclose(np.asarray(y3).reshape(32, 8), np.asarray(y2),
                       atol=1e-6)


# ---------------------------------------------------------------------------
# r3: PP/EP product surface (VERDICT r2 #4)


def test_pipeline_lm_matches_reference_all_axes():
    """PipelineLMTrainer's first-step loss must equal the single-device
    oracle on every axis combination: pure pp, pure tp, pure dp, and
    the combined 3D mesh (non-uniform stages: embed on stage 0, head
    on the last stage, real lax.cond branches)."""
    import jax

    from mxnet_tpu.parallel import mesh as mesh_mod
    from mxnet_tpu.parallel import pipeline_lm as plm

    V, D, L, F, H, S = 64, 32, 4, 64, 4, 16
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (8, S))
    tgts = np.roll(toks, -1, axis=1)
    devs = jax.devices()
    cases = [({"dp": 1, "tp": 1, "pp": 2}, 2, 2),
             ({"dp": 1, "tp": 2, "pp": 1}, 2, 1),
             ({"dp": 2, "tp": 1, "pp": 1}, 2, 1),
             ({"dp": 1, "tp": 1, "pp": 4}, 4, 4),
             ({"dp": 2, "tp": 2, "pp": 2}, 8, 2),
             # sequence parallelism (Ulysses all_to_all inside the
             # blocks), alone and composed into the full 4D mesh
             ({"dp": 1, "sp": 2, "tp": 1, "pp": 1}, 2, 1),
             ({"dp": 1, "sp": 4, "tp": 1, "pp": 1}, 4, 1),
             ({"dp": 1, "sp": 2, "tp": 2, "pp": 2}, 8, 2),
             ({"dp": 2, "sp": 2, "tp": 1, "pp": 2}, 8, 2)]
    for shape, n_dev, stages in cases:
        params = plm.init_pipeline_lm(V, D, L, F, H, S,
                                      n_stages=stages, seed=0)
        ref = float(plm.reference_lm_loss(
            params, np.asarray(toks), np.asarray(tgts), H))
        mesh = mesh_mod.make_mesh(shape, devices=devs[:n_dev])
        tr = plm.PipelineLMTrainer(params, mesh, n_heads=H, n_micro=2,
                                   lr=1e-3)
        got = tr.step(toks, tgts)
        assert abs(ref - got) < 2e-4, (shape, ref, got)


def test_pipeline_lm_trains_on_3d_mesh():
    """A transformer LM trains under dp x tp x pp on the 8-device mesh
    (the VERDICT r2 #4 done-criterion)."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    from mxnet_tpu.parallel import pipeline_lm as plm

    V, D, L, F, H, S = 64, 32, 4, 64, 4, 16
    params = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=2, seed=0)
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2, "pp": 2})
    tr = plm.PipelineLMTrainer(params, mesh, n_heads=H, n_micro=2,
                               lr=3e-3)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (8, S))
    tgts = np.roll(toks, -1, axis=1)
    losses = [tr.step(toks, tgts) for _ in range(13)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7, losses
    # stacking/mesh mismatch is a loud error, not silently-skipped layers
    import mxnet_tpu as mx
    bad = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=4, seed=0)
    with pytest.raises(mx.MXNetError, match="n_stages"):
        plm.PipelineLMTrainer(bad, mesh, n_heads=H)
    # heads must divide tp*sp for the Ulysses head split
    mesh4 = mesh_mod.make_mesh({"dp": 1, "sp": 2, "tp": 2, "pp": 2})
    p2 = plm.init_pipeline_lm(V, D, L, F, 2, S, n_stages=2, seed=0)
    with pytest.raises(mx.MXNetError, match="tp\\*sp"):
        plm.PipelineLMTrainer(p2, mesh4, n_heads=2)


def test_pipeline_lm_trains_on_4d_mesh():
    """dp x sp x tp x pp simultaneously: the long-context axis
    (Ulysses sequence parallelism) composes with the other three."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    from mxnet_tpu.parallel import pipeline_lm as plm

    V, D, L, F, H, S = 64, 32, 4, 64, 4, 16
    params = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=2, seed=0)
    mesh = mesh_mod.make_mesh({"dp": 1, "sp": 2, "tp": 2, "pp": 2})
    tr = plm.PipelineLMTrainer(params, mesh, n_heads=H, n_micro=2,
                               lr=3e-3)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (8, S))
    tgts = np.roll(toks, -1, axis=1)
    losses = [tr.step(toks, tgts) for _ in range(10)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.75, losses


def test_moe_top2_oracle_and_ep():
    """Top-2 GShard routing: renormalized pair gates, first-choice
    capacity priority; with generous capacity it must equal the dense
    two-expert mixture, sharded or not."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import mesh as mesh_mod, moe

    blk = moe.MoEBlock(4, 16, 32, seed=1)
    x = jnp.asarray(np.random.RandomState(0).rand(64, 16)
                    .astype(np.float32))
    router_w, w1, b1, w2, b2 = blk.params()
    got, _ = moe.moe_ffn(x, *blk.params(), top_k=2,
                         capacity_factor=100.0)
    probs = jax.nn.softmax(x @ router_w, -1)
    g, e = jax.lax.top_k(probs, 2)
    g = g / g.sum(-1, keepdims=True)
    want = []
    for i in range(x.shape[0]):
        acc = 0
        for j in range(2):
            ei = int(e[i, j])
            h = jax.nn.relu(x[i] @ w1[ei] + b1[ei])
            acc = acc + g[i, j] * (h @ w2[ei] + b2[ei])
        want.append(acc)
    want = jnp.stack(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    mesh = mesh_mod.make_mesh({"ep": 4}, devices=jax.devices()[:4])
    got_ep, _ = moe.moe_ffn(x, *blk.params(), mesh=mesh, top_k=2,
                            capacity_factor=100.0)
    np.testing.assert_allclose(np.asarray(got_ep), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_moe_top2_capacity_priority():
    """Over-capacity: every token's FIRST choice wins a slot before any
    second choice (GShard priority), so with capacity exactly S/E the
    primary routes survive and most secondaries drop."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import moe

    S, M, E = 16, 8, 4
    blk = moe.MoEBlock(E, M, 16, seed=3)
    x = jnp.asarray(np.random.RandomState(2).rand(S, M)
                    .astype(np.float32))
    # top_k=2 with capacity_factor=0.5 -> C = S/E: room for the
    # primaries only (if perfectly balanced)
    y, aux = moe.moe_ffn(x, *blk.params(), top_k=2,
                         capacity_factor=0.5)
    assert np.isfinite(np.asarray(y)).all()
    # must differ from the full-capacity result (secondaries dropped)
    y_full, _ = moe.moe_ffn(x, *blk.params(), top_k=2,
                            capacity_factor=100.0)
    assert not np.allclose(np.asarray(y), np.asarray(y_full))


def test_pipeline_lm_checkpoint_resume(tmp_path):
    """Kill-and-resume on the 4D trainer: save mid-run, rebuild a fresh
    trainer from a DIFFERENT init, load, and the continued loss curve
    must match the unbroken run exactly."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    from mxnet_tpu.parallel import pipeline_lm as plm

    V, D, L, F, H, S = 64, 32, 4, 64, 4, 16
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2, "pp": 2})
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (8, S))
    tgts = np.roll(toks, -1, axis=1)

    params = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=2, seed=0)
    tr = plm.PipelineLMTrainer(params, mesh, n_heads=H, n_micro=2,
                               lr=3e-3)
    for _ in range(3):
        tr.step(toks, tgts)
    ck = str(tmp_path / "plm.npz")
    tr.save_states(ck)
    unbroken = [tr.step(toks, tgts) for _ in range(2)]

    other = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=2, seed=9)
    tr2 = plm.PipelineLMTrainer(other, mesh, n_heads=H, n_micro=2,
                                lr=3e-3)
    tr2.load_states(ck)
    resumed = [tr2.step(toks, tgts) for _ in range(2)]
    np.testing.assert_allclose(resumed, unbroken, rtol=1e-6)
    # wrong-shape checkpoint is a loud error
    import mxnet_tpu as mx
    small = plm.init_pipeline_lm(V, 16, L, F, H, S, n_stages=2, seed=0)
    tr3 = plm.PipelineLMTrainer(small, mesh, n_heads=H, n_micro=2)
    with pytest.raises(mx.MXNetError, match="shape"):
        tr3.load_states(ck)


def test_pipeline_causal_attention_flash_parity(interpret_pallas,
                                                monkeypatch):
    """_causal_attention's TPU route (Pallas flash, no (S,S) matrix in
    HBM) must match the XLA reference — checked in interpret mode with
    the platform dispatch steered onto its TPU branch (on this CPU
    lax.platform_dependent lowers the XLA one), and with a spy proving
    the kernel ACTUALLY ran (a silent fallback would make this
    naive-vs-naive)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas import flash_attention as fa_mod
    from mxnet_tpu.parallel import pipeline_lm as plm

    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.rand(2, 2, 128, 64).astype(np.float32))
               for _ in range(3))
    calls = []
    orig = fa_mod._flash_sdpa

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(fa_mod, "_flash_sdpa", spy)
    monkeypatch.setenv("MXTPU_DISABLE_PALLAS", "1")
    naive = plm._causal_attention(q, k, v)
    assert not calls  # reference side really was the reference
    monkeypatch.delenv("MXTPU_DISABLE_PALLAS")
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    flash = plm._causal_attention(q, k, v)
    assert calls, "flash kernel never ran (silent fallback)"
    np.testing.assert_allclose(np.asarray(flash), np.asarray(naive),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_lm_remat_matches():
    """remat=True (jax.checkpoint around each block) trades FLOPs for
    memory: the first-step loss is identical, and the trajectory stays
    within recompute rounding (recomputed activations fuse differently
    at f32, so later steps drift at the 1e-3 level, not more)."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    from mxnet_tpu.parallel import pipeline_lm as plm

    V, D, L, F, H, S = 64, 32, 4, 64, 4, 16
    mesh = mesh_mod.make_mesh({"dp": 2, "tp": 2, "pp": 2})
    rng = np.random.RandomState(0)
    toks = rng.randint(0, V, (8, S))
    tgts = np.roll(toks, -1, axis=1)
    runs = {}
    for remat in (False, True):
        params = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=2,
                                      seed=0)
        tr = plm.PipelineLMTrainer(params, mesh, n_heads=H, n_micro=2,
                                   lr=3e-3, remat=remat)
        runs[remat] = [tr.step(toks, tgts) for _ in range(4)]
    np.testing.assert_allclose(runs[True][0], runs[False][0], rtol=1e-6)
    np.testing.assert_allclose(runs[True], runs[False], rtol=5e-3)
    assert runs[True][-1] < runs[True][0]
    # remat must actually be IN the graph (a dropped kwarg would leave
    # this test vacuously green): the jaxpr carries a remat/checkpoint
    # eqn only for the remat=True build
    import jax

    from mxnet_tpu.parallel.pipeline_lm import _stage

    params = plm.init_pipeline_lm(V, D, L, F, H, S, n_stages=1, seed=0)
    local = {k: v[0] for k, v in params["blocks"].items()}

    def has_remat(remat):
        jaxpr = jax.make_jaxpr(
            lambda b, h: _stage(b, h, n_heads_local=H, tp_axis=None,
                                tp=1, remat=remat))(
            local, np.zeros((2, S, D), np.float32))
        return "remat" in str(jaxpr) or "checkpoint" in str(jaxpr)

    assert has_remat(True) and not has_remat(False)


def test_moe_expert_parallel_trainer_parity():
    """EP as trainer-level product surface: DataParallelTrainer with
    gluon_moe_param_spec_fn shards MoEFFN's expert-stacked params over
    'ep' and the loss trajectory matches the unsharded run exactly."""
    import os
    import sys

    import jax

    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.parallel import data_parallel
    from mxnet_tpu.parallel import mesh as mesh_mod
    from mxnet_tpu.parallel.moe import gluon_moe_param_spec_fn

    sys.path.insert(0, os.path.join(_ROOT, "examples"))
    sys.path.insert(0, os.path.join(_ROOT, "examples", "moe"))
    from train_moe_lm import MoETransformerLM, synthetic_batch

    class LMWithAux:
        def __init__(self):
            self.sce = gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)

        def __call__(self, out, label):
            logits, aux = out
            return nd.mean(self.sce(logits, label)) + 0.01 * aux.sum()

    rng = np.random.RandomState(0)
    x, y = synthetic_batch(rng, 16, 16, 64)
    losses = {}
    for ep in (1, 2):
        mx.random.seed(0)
        np.random.seed(0)
        net = MoETransformerLM(64, n_experts=4)
        net.initialize(mx.init.Xavier())
        mesh = mesh_mod.make_mesh({"dp": 2, "ep": ep},
                                  devices=jax.devices()[:2 * ep])
        tr = data_parallel.DataParallelTrainer(
            net, LMWithAux(), "adam", {"learning_rate": 3e-3},
            mesh=mesh, param_spec_fn=gluon_moe_param_spec_fn(mesh))
        losses[ep] = [float(tr.step(x, y).asnumpy()) for _ in range(3)]
        if ep == 2:  # experts really sharded, not silently replicated
            specs = [str(s.spec) for (n, _), s in
                     zip(tr._named, tr._param_shardings)
                     if "moeffn" in n and "router" not in n]
            assert specs and all("ep" in s for s in specs), specs
    np.testing.assert_allclose(losses[1], losses[2], rtol=1e-4)
