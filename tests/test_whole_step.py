"""Whole-step SPMD compilation (ROADMAP item 4).

The contract under test: with ``Trainer(..., whole_step=True)`` (or
``MXTPU_WHOLE_STEP=1``) a post-warmup training step runs as ONE
compiled XLA executable — forward, loss, backward, in-program bucketed
allreduce, grouped ``_fk_*`` optimizer update, weight rebind — with
ZERO recompiles under a decaying LR schedule, BIT-identical weights and
states vs the PR-3 fused path and the sequential path on the same
inputs, loud fallback for every bypass configuration fusion already
recognizes, and state snapshots that move freely across
whole-step/fused restarts.
"""
import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _imperative, autograd, gluon, nd, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import trainer as trainer_mod
from mxnet_tpu.gluon.parameter import Parameter

X = np.random.RandomState(1).rand(8, 16).astype(np.float32)
Y = np.random.RandomState(2).rand(8, 4).astype(np.float32)


def loss_fn(out, y):
    return (out - y) ** 2


def build(whole_step, opt="sgd", opt_args=None, ctx=None, layers=3,
          aggregate_num=None):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(nn.Dense(16, in_units=16, activation="relu"))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    kwargs = dict(opt_args or {"learning_rate": 0.05, "momentum": 0.9,
                               "wd": 0.01})
    if aggregate_num is not None:
        kwargs["aggregate_num"] = aggregate_num
    tr = gluon.Trainer(net.collect_params(), opt, kwargs,
                       whole_step=whole_step)
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
def test_whole_step_bit_parity_vs_fused_and_sequential(opt, opt_args):
    """Three arms through the SAME whole_step() API: compiled
    whole-step vs eager fused vs eager sequential (aggregate_num=1) —
    weights must be bitwise identical after 5 steps."""
    arms = {}
    for name, ws, agg in (("whole", True, None), ("fused", False, None),
                          ("seq", False, 1)):
        net, tr = build(ws, opt=opt, opt_args=opt_args,
                        aggregate_num=agg)
        losses = [float(tr.whole_step(net, loss_fn, X, Y).asnumpy())
                  for _ in range(5)]
        arms[name] = (weights(net), losses, tr)
    for name in ("fused", "seq"):
        for a, b in zip(arms["whole"][0], arms[name][0]):
            np.testing.assert_array_equal(a, b)
        # the summed loss scalar may differ in the final ulp (the
        # standalone eager sum executable vs the fused in-program
        # reduction); weights/states above are the bitwise contract
        np.testing.assert_allclose(arms["whole"][1], arms[name][1],
                                   rtol=1e-6)
    assert arms["whole"][2].optimizer.num_update == \
        arms["fused"][2].optimizer.num_update


def test_whole_step_matches_classic_record_backward_step_loop():
    """The compiled step is bit-identical to the reference user loop
    (autograd.record + loss.backward + trainer.step)."""
    net_w, tr_w = build(True)
    for _ in range(4):
        tr_w.whole_step(net_w, loss_fn, X, Y)
    net_c, tr_c = build(False)
    for _ in range(4):
        with autograd.record():
            out = net_c(nd.array(X))
            loss = loss_fn(out, nd.array(Y))
        loss.backward()
        tr_c.step(8)
    for a, b in zip(weights(net_w), weights(net_c)):
        np.testing.assert_array_equal(a, b)


def test_whole_step_mixed_dtype_params_bit_parity():
    """Params of mixed fp16/fp32 dtypes ride separate traced update
    groups (same grouping fused_update dispatches) — parity holds."""
    class MixedBlock(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.w32 = self.params.get("w32", shape=(16, 4),
                                           dtype="float32")
                self.w16 = self.params.get("w16", shape=(16, 4),
                                           dtype="float16")
                self.b32 = self.params.get("b32", shape=(4,),
                                           dtype="float32",
                                           init="zeros")

        def hybrid_forward(self, F, x, w32=None, w16=None, b32=None):
            return (F.dot(x, w32) + F.dot(x, w16.astype("float32"))
                    + b32)

    def build_mixed(whole_step, agg=None):
        mx.random.seed(0)
        np.random.seed(0)
        blk = MixedBlock()
        blk.initialize()
        kwargs = {"learning_rate": 0.05, "momentum": 0.9}
        if agg is not None:
            kwargs["aggregate_num"] = agg
        tr = gluon.Trainer(blk.collect_params(), "sgd", kwargs,
                           whole_step=whole_step)
        return blk, tr

    arms = []
    for ws, agg in ((True, None), (False, None), (False, 1)):
        blk, tr = build_mixed(ws, agg)
        for _ in range(4):
            tr.whole_step(blk, loss_fn, X, Y)
        arms.append(weights(blk))
    for other in arms[1:]:
        for a, b in zip(arms[0], other):
            np.testing.assert_array_equal(a, b)


def test_whole_step_no_recompile_across_decaying_lr_schedule():
    from mxnet_tpu import lr_scheduler

    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    for _ in range(4):
        net.add(nn.Dense(16, in_units=16))
    net.initialize(mx.init.Xavier())
    sched = lr_scheduler.FactorScheduler(step=3, factor=0.9, base_lr=0.1)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.1, "lr_scheduler": sched},
                       whole_step=True)
    y16 = np.random.RandomState(3).rand(8, 16).astype(np.float32)
    for _ in range(3):
        tr.whole_step(net, loss_fn, X, y16)
    nd.waitall()
    lr0 = tr.learning_rate
    trainer_mod.reset_trainer_step_stats()
    c0 = _imperative.compiled_executable_count()
    d0 = _imperative.device_dispatch_count()
    for _ in range(15):
        tr.whole_step(net, loss_fn, X, y16)
    nd.waitall()
    stats = trainer_mod.trainer_step_stats()
    assert _imperative.compiled_executable_count() == c0
    # ONE device program submission per post-warmup step — measured by
    # the global dispatch counter, not self-reported stats
    assert _imperative.device_dispatch_count() - d0 == 15
    assert stats["whole_step_steps"] == 15
    assert stats["whole_step_compiles"] == 0
    assert stats["whole_step_fallbacks"] == 0
    assert stats["dispatches_per_step"] == 1.0
    assert tr.learning_rate < lr0


def test_whole_step_multi_device_parity_and_replica_consistency():
    """Virtual 8-device mesh (dryrun_multichip): the compiled SPMD step
    (batch sharded over 'dp', grads psum'ed in-program) matches the
    eager multi-replica fused path, and every replica context holds
    identical weights afterwards."""
    ctxs = [mx.xla(i) for i in range(4)]
    net_w, tr_w = build(True, ctx=ctxs, layers=2)
    lw = [float(tr_w.whole_step(net_w, loss_fn, X, Y).asnumpy())
          for _ in range(3)]
    net_f, tr_f = build(False, ctx=ctxs, layers=2)
    lf = [float(tr_f.whole_step(net_f, loss_fn, X, Y).asnumpy())
          for _ in range(3)]
    np.testing.assert_allclose(lw, lf, rtol=1e-5)
    for a, b in zip(net_w.collect_params().values(),
                    net_f.collect_params().values()):
        for c in ctxs:
            np.testing.assert_allclose(a.data(c).asnumpy(),
                                       b.data(c).asnumpy(),
                                       rtol=2e-6, atol=2e-7)
    for a in net_w.collect_params().values():
        ref = a.data(ctxs[0]).asnumpy()
        for c in ctxs[1:]:
            np.testing.assert_array_equal(a.data(c).asnumpy(), ref)


def test_whole_step_multi_device_one_dispatch_per_step():
    ctxs = [mx.xla(i) for i in range(4)]
    net, tr = build(True, ctx=ctxs, layers=2)
    for _ in range(2):
        tr.whole_step(net, loss_fn, X, Y)
    nd.waitall()
    trainer_mod.reset_trainer_step_stats()
    c0 = _imperative.compiled_executable_count()
    d0 = _imperative.device_dispatch_count()
    for _ in range(8):
        tr.whole_step(net, loss_fn, X, Y)
    nd.waitall()
    stats = trainer_mod.trainer_step_stats()
    assert _imperative.compiled_executable_count() == c0
    assert _imperative.device_dispatch_count() - d0 == 8
    assert stats["dispatches_per_step"] == 1.0
    # the traced allreduce built one fp32 flat bucket per step
    assert stats["buckets_built"] == 8


@pytest.mark.parametrize("case", ["amp", "no_fused_kernel",
                                  "update_on_kvstore", "compression",
                                  "grad_add"])
def test_whole_step_bypass_falls_back_without_error(case):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    opt = "lamb" if case == "no_fused_kernel" else "sgd"
    tkw = {}
    if case == "update_on_kvstore":
        tkw = dict(kvstore="dist_sync", update_on_kvstore=True)
    elif case == "compression":
        tkw = dict(kvstore="dist_sync",
                   compression_params={"type": "2bit"})
    tr = gluon.Trainer(net.collect_params(), opt,
                       {"learning_rate": 0.01}, whole_step=True, **tkw)
    if case == "amp":
        from mxnet_tpu.amp import LossScaler

        tr._amp_loss_scaler = LossScaler(init_scale=2.0)
        tr._amp_original_scale = tr._scale
    if case == "grad_add":
        for p in net.collect_params().values():
            p.grad_req = "add"
    before = weights(net)
    trainer_mod.reset_trainer_step_stats()
    tr.whole_step(net, loss_fn, X, Y)
    stats = trainer_mod.trainer_step_stats()
    assert stats["whole_step_fallbacks"] == 1
    assert stats["whole_step_steps"] == 0
    after = weights(net)
    # the eager step still trained (amp warms its scaler but updates)
    assert any(not np.array_equal(a, b) for a, b in zip(before, after))


def test_whole_step_sparse_param_bypasses():
    dense = Parameter("w", shape=(16, 4))
    dense.initialize()
    dense.set_data(nd.array(np.random.RandomState(3).rand(16, 4)
                            .astype(np.float32)))
    sp = Parameter("emb", shape=(12, 3), grad_stype="row_sparse")
    sp.initialize()
    sp.set_data(nd.array(np.random.RandomState(4).rand(12, 3)
                         .astype(np.float32)))

    class WBlock(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self._reg_params = {"w": dense, "emb": sp}
            self.params.update({"w": dense, "emb": sp})

        def hybrid_forward(self, F, x, w=None, emb=None):
            return F.dot(x, w) + emb.sum()

    blk = WBlock()
    tr = gluon.Trainer([dense, sp], "sgd", {"learning_rate": 0.05},
                       whole_step=True)
    trainer_mod.reset_trainer_step_stats()
    tr.whole_step(blk, loss_fn, X, Y)
    assert trainer_mod.trainer_step_stats()["whole_step_fallbacks"] == 1


def test_whole_step_disabled_runs_eager_silently():
    net, tr = build(False)
    trainer_mod.reset_trainer_step_stats()
    tr.whole_step(net, loss_fn, X, Y)
    stats = trainer_mod.trainer_step_stats()
    assert stats["steps"] == 1
    assert stats["whole_step_steps"] == 0
    assert stats["whole_step_fallbacks"] == 0  # disabled is not a bypass


def test_whole_step_env_knob(monkeypatch):
    monkeypatch.setenv("MXTPU_WHOLE_STEP", "1")
    net, tr = build(None)
    assert tr.whole_step_enabled
    monkeypatch.setenv("MXTPU_WHOLE_STEP", "0")
    _, tr2 = build(None)
    assert not tr2.whole_step_enabled
    # ctor arg beats nothing — explicit False under env 1
    monkeypatch.setenv("MXTPU_WHOLE_STEP", "1")
    _, tr3 = build(False)
    assert not tr3.whole_step_enabled


def test_states_dict_roundtrip_across_whole_step_fused_restart():
    opt_args = {"learning_rate": 0.01, "wd": 0.01}

    def build_adam(whole_step):
        return build(whole_step, opt="adam", opt_args=opt_args)

    cont_net, cont_tr = build_adam(True)
    for _ in range(5):
        cont_tr.whole_step(cont_net, loss_fn, X, Y)
    # whole-step 3 steps -> snapshot -> restart EAGER FUSED for 2 more
    a_net, a_tr = build_adam(True)
    for _ in range(3):
        a_tr.whole_step(a_net, loss_fn, X, Y)
    blob = a_tr.states_dict()
    b_net, b_tr = build_adam(False)
    for src, dst in zip(a_net.collect_params().values(),
                        b_net.collect_params().values()):
        dst.set_data(src.data())
    b_tr.load_states_dict(blob)
    for _ in range(2):
        b_tr.whole_step(b_net, loss_fn, X, Y)
    for a, b in zip(weights(cont_net), weights(b_net)):
        np.testing.assert_array_equal(a, b)
    # and back: fused snapshot resumed under the whole-step path
    blob2 = b_tr.states_dict()
    c_net, c_tr = build_adam(True)
    for src, dst in zip(b_net.collect_params().values(),
                        c_net.collect_params().values()):
        dst.set_data(src.data())
    c_tr.load_states_dict(blob2)
    for _ in range(2):
        c_tr.whole_step(c_net, loss_fn, X, Y)
    cont2_net, cont2_tr = build_adam(True)
    for _ in range(7):
        cont2_tr.whole_step(cont2_net, loss_fn, X, Y)
    for a, b in zip(weights(cont2_net), weights(c_net)):
        np.testing.assert_array_equal(a, b)


def test_checkpoint_manager_roundtrip_across_whole_step_restart(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager

    net_a, tr_a = build(True, opt="adam",
                        opt_args={"learning_rate": 0.01})
    for _ in range(3):
        tr_a.whole_step(net_a, loss_fn, X, Y)
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    mgr.save(3, params=net_a, trainer=tr_a, sync=True)
    net_b, tr_b = build(False, opt="adam",
                        opt_args={"learning_rate": 0.01})
    mgr2 = CheckpointManager(str(tmp_path), keep_n=2)
    meta = mgr2.restore(params=net_b, trainer=tr_b)
    assert meta["step"] == 3
    for _ in range(2):
        tr_b.whole_step(net_b, loss_fn, X, Y)
    cont_net, cont_tr = build(True, opt="adam",
                              opt_args={"learning_rate": 0.01})
    for _ in range(5):
        cont_tr.whole_step(cont_net, loss_fn, X, Y)
    for a, b in zip(weights(cont_net), weights(net_b)):
        np.testing.assert_array_equal(a, b)


def test_whole_step_donation_hold_switches_to_nondonating_twin(
        monkeypatch):
    """While an async checkpoint capture holds donation, the compiled
    step must run its pre-warmed NON-donating executable (never leave
    the compiled path, never compile mid-step)."""
    from mxnet_tpu import engine
    from mxnet_tpu import optimizer as opt_mod

    recorded = []
    real = _imperative.get_jitted

    def spy(fn, kwargs, donate_argnums=None):
        recorded.append(donate_argnums)
        return real(fn, kwargs)  # never actually donate (CPU backend)

    monkeypatch.setattr(_imperative, "get_jitted", spy)
    monkeypatch.setattr(opt_mod, "_donate_ok", True)  # fake accelerator
    net, tr = build(True)
    tr.whole_step(net, loss_fn, X, Y)
    assert recorded and all(d is None for d in recorded), recorded
    recorded.clear()
    tr.whole_step(net, loss_fn, X, Y)
    assert (1, 2) in recorded, recorded
    recorded.clear()
    engine.acquire_donation_hold()
    try:
        tr.whole_step(net, loss_fn, X, Y)
        assert recorded and all(d is None for d in recorded), recorded
    finally:
        engine.release_donation_hold()


def test_whole_step_batchnorm_aux_updates_single_device():
    """Aux-mutating forwards (BatchNorm moving stats) stay on the
    compiled path single-device and update stats identically to the
    eager arm."""
    def build_bn(whole_step):
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, in_units=16), nn.BatchNorm(in_channels=8),
                nn.Dense(4, in_units=8))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05},
                           whole_step=whole_step)
        return net, tr

    net_w, tr_w = build_bn(True)
    for _ in range(3):
        tr_w.whole_step(net_w, loss_fn, X, Y)
    stats = trainer_mod.trainer_step_stats()
    net_f, tr_f = build_bn(False)
    for _ in range(3):
        tr_f.whole_step(net_f, loss_fn, X, Y)
    for (na, a), (nb, b) in zip(
            net_w._collect_params_with_prefix().items(),
            net_f._collect_params_with_prefix().items()):
        assert na == nb
        np.testing.assert_allclose(a.data().asnumpy(),
                                   b.data().asnumpy(),
                                   rtol=1e-6, atol=1e-7, err_msg=na)


def test_whole_step_closure_cache_bounded_under_unstable_loss_fn():
    """A fresh lambda per call must retrace (documented) but NOT leak
    executables: the closure cache is bounded and evicted entries drop
    their compiled executables from the jit cache."""
    net, tr = build(True)
    cap = None
    for i in range(14):
        tr.whole_step(net, lambda out, y, _i=i: (out - y) ** 2, X, Y)
        comp = tr._whole_step_compiler
        cap = comp.MAX_CLOSURES
        assert len(comp._closures) <= cap
    # stable fn: cache stops churning and weights still train
    before = weights(net)
    tr.whole_step(net, loss_fn, X, Y)
    tr.whole_step(net, loss_fn, X, Y)
    after = weights(net)
    assert any(not np.array_equal(a, b) for a, b in zip(before, after))
    assert len(tr._whole_step_compiler._closures) <= cap + 1


def test_profiler_whole_step_counters_window_scoped():
    trainer_mod.reset_trainer_step_stats()
    net, tr = build(True)
    tr.whole_step(net, loss_fn, X, Y)
    tr.whole_step(net, loss_fn, X, Y)
    out = json.loads(profiler.dumps(reset=True))
    ts = out["trainerStep"]
    assert ts["whole_step_steps"] == 2
    assert ts["whole_step_compiles"] >= 1
    assert ts["dispatches_per_step"] == 1.0
    again = json.loads(profiler.dumps(reset=True))["trainerStep"]
    assert again["whole_step_steps"] == 0
    assert again["whole_step_compiles"] == 0


@pytest.mark.parametrize("n_ctx", [1, 4])
def test_whole_step_survives_real_donation(donation_on, n_ctx):
    """Found on four v5e chips (PR 22): after the donating step the
    mesh path asked the optimizer-state HOLDER for its device — the
    holder's array was the donated buffer, deleted on a chip (the CPU
    backend ignores donation, so nothing here could see it)."""
    ctxs = [mx.xla(i) for i in range(n_ctx)]
    net, tr = build(True, ctx=ctxs, layers=2)
    losses = [float(tr.whole_step(net, loss_fn, X, Y).asnumpy())
              for _ in range(5)]
    assert losses[-1] < losses[0]
    net_r, tr_r = build(True, ctx=ctxs, layers=2)
    ref = [float(tr_r.whole_step(net_r, loss_fn, X, Y).asnumpy())
           for _ in range(5)]
    np.testing.assert_allclose(losses, ref, rtol=1e-6)
