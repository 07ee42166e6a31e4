"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip: every phase below
    python chip_smoke.py --chips 4   # four chips: ONLY the multi-chip path

One process; it imports JAX once and starts no child that needs the
chip.  There is no CPU switch and no fallback: when ``jax.devices()[0]``
is not a TPU the script exits non-zero before any phase runs, and a
failure in any phase propagates (no ``try/except`` lets the run reach
exit 0).  Weights and data are made from ``SEED``.

Phases (one JSON object per phase on stdout, then the result line):

1. ``context`` — ``mx.cpu()`` / ``mx.xla(0)`` placement, an eager op and
   an autograd backward stay on the chip, the native libs are built.
2. ``gluon``   — the README path at the published BERT-base widths
   (12 layers, 768 units, 12 heads, 3072 FFN, vocab 30522, seq 128):
   ``initialize(ctx=mx.xla(0))`` / ``hybridize`` / ``record`` /
   ``backward`` / ``Trainer("adamw").step``, then ``Trainer.whole_step``,
   and the first forward against an ``mx.cpu()`` copy of the weights.
3. ``spmd``    — ``DataParallelTrainer(compute_dtype="bfloat16")``, the
   path the benchmark's cells and examples/bert/pretrain_bert.py use,
   batch 64 x 128, with the flash-attention kernel REQUIRED in the
   compiled step.
4. ``serve``   — ResNet-50 v1 NHWC at 224x224 behind ``ModelServer``.
5. ``kernels`` — every Pallas family compiled value-and-grad for the
   chip in bf16 and f32, and the flash kernel run against the reference.

The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Timings printed here are smoke timings of one run, not benchmark results.
"""
import argparse
import gc
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(REPO, "examples"),
           os.path.join(REPO, "examples", "bert"), REPO):
    sys.path.insert(0, _p)

SEED = 0
VOCAB = 30522        # BERT-base (models/bert.py bert_base)
SEQ = 128
SPMD_BATCH = 64      # the pretrain_bert.py batch
GLUON_BATCH = 8
ORACLE_BATCH = 2     # the mx.cpu() comparison forward
IMAGE = 224          # ResNet-50 v1 serving resolution
SERVE_BATCHES = (1, 4, 8)
SERVE_REQUESTS = 16
LR = 1e-4
# float32 on the MXU rounds matmul operands to bf16 at default precision
# (eps ~8e-3) and depth compounds it: the chip agrees with the XLA:CPU
# float32 oracle to this share of the oracle's largest logit — the
# tolerance users of the default precision actually get
ORACLE_TOL = 3e-2
# bf16 step on one chip vs the same step sharded over four: the same
# math in another reduction order.  The bf16 step's loss leaves the
# model AT bf16 resolution — one ulp at a loss of ~11 is 0.0625 — so
# two ulps; the fp32 whole step is held to 2e-2
BF16_LOSS_TOL = 0.125
F32_LOSS_TOL = 2e-2


def emit(phase, t0, **fields):
    rec = {"phase": phase, "seconds": round(time.perf_counter() - t0, 2)}
    rec.update(fields)
    print(json.dumps(rec), flush=True)


def timed(fn):
    """(fn(), seconds it took)."""
    t = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t, 3)


def require_tpu(n_chips):
    """The gate: no accelerator, no run.  Returns the jax devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: jax came up on {devs[0].platform!r} "
                 f"({devs[0].device_kind}), not a TPU; there is no CPU "
                 "mode — run it on the machine with the chip")
    if len(devs) != n_chips:
        sys.exit(f"chip_smoke: asked for {n_chips} chip(s), jax sees "
                 f"{len(devs)}")
    return devs


def on_device(arr, dev):
    raw = getattr(arr, "_data", arr)
    return set(raw.devices()) == {dev}


def release():
    """Drop the previous phase's arrays before the next one sizes up."""
    from mxnet_tpu import nd

    nd.waitall()
    gc.collect()


def bert_init():
    """The published BERT initialisation, as pretrain_bert.py uses it
    (Xavier at this depth and lr is unstable on a fixed batch)."""
    import mxnet_tpu as mx

    return mx.init.TruncNorm(stdev=0.02)


def bert_pretrain_net():
    from pretrain_bert import BERTForPretrain

    from mxnet_tpu.models import bert as bert_mod

    return BERTForPretrain(bert_mod.bert_base(vocab_size=VOCAB), VOCAB)


def identity_loss(out, _label=None):
    """BERTForPretrain already returns the scalar loss."""
    return out


def count_kernels(compiled_text):
    """Mosaic kernels in a compiled program's text."""
    return compiled_text.count("tpu_custom_call")


def compiled_text(trainer, x, y):
    """The compiled single-step program of a DataParallelTrainer, as
    text (a persistent-cache hit after the step has run once)."""
    import jax.numpy as jnp

    from mxnet_tpu import random as _random

    xj = tuple(jnp.asarray(v) for v in x)
    return trainer._step_fn.lower(
        trainer._params, trainer._states, xj, jnp.asarray(y),
        _random.next_key(), jnp.asarray(trainer._lr, jnp.float32),
        jnp.asarray(1.0, jnp.float32)).compile().as_text()


# ---------------------------------------------------------------------------
# phase 1: contexts
# ---------------------------------------------------------------------------

def phase_context(chip):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, storage
    from mxnet_tpu.parallel import data_parallel
    from mxnet_tpu.utils import native, native_engine

    t0 = time.perf_counter()
    assert mx.xla(0).jax_device() == chip
    host = jax.local_devices(backend="cpu")[0]
    a = nd.array([[1.0, 2.0], [3.0, 4.0]], ctx=mx.cpu())
    assert on_device(a, host), a._data.devices()
    b = a.as_in_context(mx.xla(0))
    assert on_device(b, chip), b._data.devices()
    assert b.context == mx.xla(0) and a.context == mx.cpu()
    b.attach_grad()
    with autograd.record():
        c = (b * b + 1.0).sum()
    c.backward()
    assert on_device(c, chip) and on_device(b.grad, chip)
    assert b.grad.asnumpy().tolist() == [[2.0, 4.0], [6.0, 8.0]]
    # arrays a layer creates for itself follow its input, not the
    # default context (the host): the implicit LSTM begin_state
    lstm = gluon.rnn.LSTM(8)
    lstm.initialize(ctx=mx.xla(0))
    assert on_device(lstm(nd.ones((3, 2, 4), ctx=mx.xla(0))), chip)
    # the examples and the benchmark initialise on the DEFAULT context (the
    # host) and hand the block to DataParallelTrainer: its parameters
    # and its step must still end up on the chip
    mx.random.seed(SEED)
    mlp = gluon.nn.HybridSequential()
    mlp.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    mlp.initialize(mx.init.Xavier())
    assert on_device(mlp[1].bias.data(), host)
    dpt = data_parallel.DataParallelTrainer(
        mlp, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1})
    rs = np.random.RandomState(SEED)
    dx, dy = rs.rand(8, 6).astype("float32"), rs.rand(8, 4).astype("float32")
    first = float(dpt.step(dx, dy).asnumpy())
    for _ in range(4):
        last = dpt.step(dx, dy)
    assert on_device(last, chip) and float(last.asnumpy()) < first
    assert all(on_device(p, chip) for p in dpt._params)
    # lib/ is not tracked: on a fresh checkout these loads BUILD the
    # native tier from src/, and a failed build raises (utils/libloader)
    libs = {"io": native.load(), "engine": native_engine.load(),
            "storage": storage._load_native()}
    assert all(v is not None for v in libs.values()), libs
    emit("context", t0, native_libs=sorted(libs),
         asserted=["mx.xla(0) is the TPU device",
                   "mx.cpu() array lives on the host device",
                   "as_in_context moves it to the chip",
                   "eager op + autograd backward stay on the chip",
                   "an LSTM's implicit begin_state follows its input",
                   "a block initialised on the default (host) context "
                   "trains on the chip through DataParallelTrainer",
                   "native io/engine/storage libs built and loaded"])


# ---------------------------------------------------------------------------
# phase 2: gluon (README path) + whole_step + the two-context oracle
# ---------------------------------------------------------------------------

def _steps_with_compile_count(step_fn, n_steps, warm):
    """Run step_fn n_steps times; returns (losses, seconds per call,
    executables compiled after the first `warm` calls)."""
    from mxnet_tpu import _imperative

    losses, secs, base = [], [], None
    for i in range(n_steps):
        if i == warm:
            base = _imperative.compiled_executable_count()
        loss, s = timed(lambda: float(step_fn().asnumpy()))
        losses.append(loss)
        secs.append(s)
    return losses, secs, _imperative.compiled_executable_count() - base


def phase_gluon(chip):
    import jax
    import numpy as np
    from pretrain_bert import synthetic_batch

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import trainer as trainer_mod

    t0 = time.perf_counter()
    ctx = mx.xla(0)
    rng = np.random.RandomState(SEED)
    batch_np = synthetic_batch(rng, GLUON_BATCH, SEQ, VOCAB)
    batch = tuple(nd.array(v, ctx=ctx) for v in batch_np)

    def build():
        mx.random.seed(SEED)
        net = bert_pretrain_net()
        net.initialize(bert_init(), ctx=ctx)
        net.hybridize()
        return net

    # -- the two-context story: chip logits vs an mx.cpu() copy ---------
    net = build()
    heads_in = tuple(batch[i][:ORACLE_BATCH] for i in (0, 1, 5, 6))
    chip_logits, first_fwd_s = timed(
        lambda: [o.asnumpy() for o in net.model(*heads_in)])
    mx.random.seed(SEED)
    cpu_net = bert_pretrain_net()
    cpu_net.initialize(bert_init(), ctx=mx.cpu())
    cpu_net.hybridize()
    for (_, p), (_, q) in zip(net._ordered_params(),
                              cpu_net._ordered_params()):
        q.set_data(p.data())          # chip -> host copy
    host = jax.local_devices(backend="cpu")[0]
    assert all(on_device(q.data(), host)
               for _, q in cpu_net._ordered_params())
    cpu_in = tuple(v.as_in_context(mx.cpu()) for v in heads_in)
    cpu_logits = [o.asnumpy() for o in cpu_net.model(*cpu_in)]
    oracle_err = []
    for got, want in zip(chip_logits, cpu_logits):
        assert got.shape == want.shape and np.isfinite(got).all()
        scale = max(1.0, float(np.abs(want).max()))
        err = float(np.abs(got - want).max()) / scale
        assert err <= ORACLE_TOL, (
            f"chip vs mx.cpu() logits differ by {err:.4f} of the "
            f"largest logit (tolerance {ORACLE_TOL})")
        oracle_err.append(round(err, 5))
    del cpu_net, cpu_logits, cpu_in
    release()

    # -- record / backward / Trainer.step ------------------------------
    trainer = gluon.Trainer(net.collect_params(), "adamw",
                            {"learning_rate": LR, "wd": 0.01})

    def classic_step():
        with autograd.record():
            loss = net(*batch)
        loss.backward()
        trainer.step(1)   # the block's loss is already a batch mean
        return loss

    losses, secs, late_compiles = _steps_with_compile_count(
        classic_step, 6, warm=2)
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    assert late_compiles == 0, late_compiles
    with autograd.record():
        loss = net(*batch)
    assert on_device(loss, chip)
    assert all(on_device(p.data(), chip) and p.data().context == ctx
               for _, p in net._ordered_params())
    del trainer, net, loss
    release()

    # -- the same through Trainer.whole_step ----------------------------
    net = build()
    # deferred init needs its shape-inference forward (the classic loop
    # got it from its first record()); whole_step says so otherwise
    net(*batch).wait_to_read()
    trainer = gluon.Trainer(net.collect_params(), "adamw",
                            {"learning_rate": LR, "wd": 0.01},
                            whole_step=True)
    trainer_mod.reset_trainer_step_stats()
    # warm=2: the first call compiles the non-donating twin, the second
    # the donating executable every later step reuses
    ws_losses, ws_secs, ws_late = _steps_with_compile_count(
        lambda: trainer.whole_step(net, identity_loss, batch,
                                   batch_size=1), 6, warm=2)
    stats = trainer_mod.trainer_step_stats()
    assert all(np.isfinite(ws_losses)) and ws_losses[-1] < ws_losses[0], \
        ws_losses
    assert stats["whole_step_fallbacks"] == 0, stats
    assert stats["whole_step_steps"] == 6, stats
    assert ws_late == 0, ws_late
    assert all(on_device(p.data(), chip) for _, p in net._ordered_params())
    n_params = sum(p.data().size for _, p in net._ordered_params())
    del trainer, net
    release()
    emit("gluon", t0, model="bert_base L12 H768 A12 FFN3072 V30522",
         parameters=n_params, batch=GLUON_BATCH, seq=SEQ,
         first_forward_seconds=first_fwd_s,
         oracle_max_err=oracle_err, oracle_tol=ORACLE_TOL,
         step_losses=[round(v, 4) for v in losses],
         step_seconds=secs, cold_compile_seconds=round(
             secs[0] - secs[-1], 2),
         whole_step_losses=[round(v, 4) for v in ws_losses],
         whole_step_seconds=ws_secs,
         whole_step_cold_compile_seconds=round(
             ws_secs[0] + ws_secs[1] - 2 * ws_secs[-1], 2),
         whole_step_fallbacks=stats["whole_step_fallbacks"],
         post_warmup_compiles=late_compiles + ws_late,
         asserted=["chip logits == mx.cpu() copy within oracle_tol",
                   "params and loss live on the TPU device",
                   "loss finite and lower after 6 steps (both loops)",
                   "whole_step_fallbacks == 0",
                   "0 executables compiled after warm-up"])


# ---------------------------------------------------------------------------
# phase 3: DataParallelTrainer, bf16, the kernel required
# ---------------------------------------------------------------------------

def build_spmd_trainer(mesh=None):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import data_parallel

    mx.random.seed(SEED)
    net = bert_pretrain_net()
    net.initialize(bert_init(), ctx=mx.xla(0))
    return data_parallel.DataParallelTrainer(
        net, identity_loss, "adamw", {"learning_rate": LR, "wd": 0.01},
        compute_dtype="bfloat16", mesh=mesh)


def require_flash_kernel(text, x):
    """The phase fails unless the Mosaic kernel is in the compiled
    step, and says which gate turned it away."""
    n_calls = count_kernels(text)
    if n_calls:
        return n_calls
    import jax.numpy as jnp

    from mxnet_tpu.base import getenv
    from mxnet_tpu.ops.pallas import flash_attention as fa

    b, s = x[0].shape
    q = jnp.zeros((b, 12, s, 64), jnp.bfloat16)
    mask = jnp.zeros((b, 1, 1, s), jnp.float32)
    gates = {
        "MXTPU_DISABLE_PALLAS unset": not getenv(
            "DISABLE_PALLAS", False, bool),
        "_tiles_ok(q, k)": bool(fa._tiles_ok(q, q)),
        "key-padding mask shape (b,1,1,sk)":
            fa._as_key_padding_mask(mask, q, q) is not None,
    }
    raise AssertionError(
        "no tpu_custom_call in the compiled BERT step — the "
        f"flash-attention kernel is absent; gates: {gates}; if all are "
        "True the platform branch of ops/attention._k_sdpa did not "
        "lower for 'tpu'")


def phase_spmd(chip):
    import numpy as np
    from pretrain_bert import synthetic_batch

    t0 = time.perf_counter()
    trainer = build_spmd_trainer()
    assert list(trainer.mesh.devices.flat) == [chip]
    rng = np.random.RandomState(SEED)
    x = synthetic_batch(rng, SPMD_BATCH, SEQ, VOCAB)
    y = np.zeros((SPMD_BATCH,), np.float32)   # unused by the loss head
    losses, secs = [], []
    for _ in range(3):
        loss, s = timed(lambda: float(trainer.step(x, y).asnumpy()))
        losses.append(loss)
        secs.append(s)
    step_execs = trainer._step_fn._cache_size()

    def five_scanned_steps():
        return trainer.step_many(x, y, n_steps=5).asnumpy()

    many, many_first_s = timed(five_scanned_steps)
    many2, many_warm_s = timed(five_scanned_steps)
    losses += [float(v) for v in many] + [float(v) for v in many2]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    assert trainer._step_fn._cache_size() == step_execs == 1
    assert all(on_device(p, chip) for p in trainer._params)
    n_kernels = require_flash_kernel(compiled_text(trainer, x, y), x)
    tokens = SPMD_BATCH * SEQ
    del trainer
    release()
    emit("spmd", t0, batch=SPMD_BATCH, seq=SEQ, compute_dtype="bfloat16",
         losses=[round(v, 4) for v in losses], step_seconds=secs,
         cold_compile_seconds=round(secs[0] - secs[-1], 2),
         step_many_first_seconds=many_first_s,
         step_many_warm_seconds=many_warm_s,
         smoke_tokens_per_second=round(5 * tokens / many_warm_s),
         tpu_custom_calls_in_step=n_kernels,
         asserted=["the mesh is the one TPU device",
                   "loss finite and lower after 13 steps",
                   "flash-attention kernel (tpu_custom_call) is in the "
                   "compiled step",
                   "step() compiled exactly one executable"])


# ---------------------------------------------------------------------------
# phase 4: ResNet-50 behind ModelServer
# ---------------------------------------------------------------------------

def phase_serve(chip):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, serve
    from mxnet_tpu.gluon.model_zoo import vision

    t0 = time.perf_counter()
    ctx = mx.xla(0)
    mx.random.seed(SEED)
    net = vision.resnet50_v1(layout="NHWC")
    net.initialize(mx.init.Xavier(), ctx=ctx)
    spec = serve.BucketSpec(batch_sizes=SERVE_BATCHES,
                            example_shape=(IMAGE, IMAGE, 3))
    srv = serve.ModelServer(net, spec, max_queue=SERVE_REQUESTS + 8,
                            linger_ms=2.0, ctx=ctx)
    # hybridize + AOT warm-up of every bucket
    _, warm_s = timed(srv.start)

    rng = np.random.RandomState(SEED)
    images = [rng.rand(IMAGE, IMAGE, 3).astype(np.float32)
              for _ in range(SERVE_REQUESTS)]
    futures = [None] * SERVE_REQUESTS

    def client(i):
        futures[i] = srv.submit(images[i])

    t = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(SERVE_REQUESTS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
        assert not th.is_alive()
    served = [f.result(timeout=300) for f in futures]
    burst_s = round(time.perf_counter() - t, 3)

    # reference: the same hybridized net called directly on the chip,
    # one image at a time through the (already compiled) batch-1 bucket
    worst = 0.0
    for img, got in zip(images, served):
        direct = net(nd.array(img[None], ctx=ctx))
        assert on_device(direct, chip)
        want = direct.asnumpy()[0]
        assert got.shape == want.shape == (1000,)
        assert np.isfinite(got).all()
        scale = max(1.0, float(np.abs(want).max()))
        worst = max(worst, float(np.abs(got - want).max()) / scale)
    assert worst <= ORACLE_TOL, worst
    srv.drain()
    s = srv.stats()
    assert s["graph"]["post_warmup_compiles"] == 0, s["graph"]
    assert s["submitted"] == SERVE_REQUESTS and s["rejected_overload"] == 0
    assert s["served"] + s["expired_deadline"] + s["failed"] \
        + s["cancelled"] == s["submitted"]
    assert s["served"] == SERVE_REQUESTS
    assert s["queue_depth"] == 0 and s["in_flight"] == 0
    assert s["warmup_batches"] == len(spec.bucket_shapes())
    assert set(s["bucket_hits"]) <= {spec.key(b, None)
                                     for b in spec.batch_sizes}
    assert s["latency"]["count"] == s["served"]
    assert all(on_device(p.data(), chip) for _, p in net._ordered_params())
    del srv, net
    release()
    emit("serve", t0, model="resnet50_v1 NHWC", image=IMAGE,
         buckets=list(SERVE_BATCHES), requests=SERVE_REQUESTS,
         warmup_seconds=warm_s, burst_seconds=burst_s,
         batches=s["batches"], batch_fill_ratio=s["batch_fill_ratio"],
         p50_ms=s["latency"]["p50_ms"], p99_ms=s["latency"]["p99_ms"],
         max_err_vs_direct=round(worst, 6),
         asserted=["every served output == direct net(x) on the chip",
                   "graph.post_warmup_compiles == 0",
                   "serve_smoke stats invariants",
                   "the server's parameters and outputs are on the TPU"])


# ---------------------------------------------------------------------------
# phase 5: every Pallas family, compiled for the chip
# ---------------------------------------------------------------------------

def phase_kernels(chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import batch_norm as pbn
    from mxnet_tpu.ops.pallas import conv_fused as cf
    from mxnet_tpu.ops.pallas.flash_attention import _flash_sdpa
    from mxnet_tpu.ops.pallas.rnn import gru_layer, lstm_layer

    t0 = time.perf_counter()
    compiled = []

    def grad_compile(name, loss, *args):
        # value AND grad: where a family's backward is plain XLA the
        # forward kernel is dead code in the gradient alone
        text = jax.jit(jax.value_and_grad(loss)).lower(*args) \
            .compile().as_text()
        assert count_kernels(text), f"no Mosaic kernel in {name}"
        compiled.append(name)

    def flash(shape, dt, causal, masked):
        b, _h, s, d = shape
        q = jnp.zeros(shape, dt)
        km = jnp.zeros((b, s), jnp.float32) if masked else None
        grad_compile(
            f"flash{shape}/{jnp.dtype(dt).name}"
            f"{'/causal' if causal else ''}{'/masked' if masked else ''}",
            lambda a: _flash_sdpa(a, a, a, km, causal, d ** -0.5)
            .astype(jnp.float32).sum(), q)

    # the shapes the phases above ran: spmd, gluon, oracle
    flash((SPMD_BATCH, 12, SEQ, 64), jnp.bfloat16, False, True)
    flash((GLUON_BATCH, 12, SEQ, 64), jnp.float32, False, True)
    flash((ORACLE_BATCH, 12, SEQ, 64), jnp.float32, False, True)
    for dt in (jnp.float32, jnp.bfloat16):
        for d, causal, masked in ((128, False, False), (128, True, False),
                                  (128, False, True), (64, False, False),
                                  (64, True, False), (64, False, True)):
            flash((1, 2, 256, d), dt, causal, masked)
    # streamed K/V (past the resident VMEM bound)
    for dt, causal in ((jnp.bfloat16, False), (jnp.bfloat16, True),
                       (jnp.float32, True)):
        flash((1, 1, 16384, 128), dt, causal, False)
    for dt in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dt).name
        x = jnp.zeros((512, 256), dt)
        w = jnp.zeros((256, 256), dt)
        sc = jnp.zeros((1, 256), dt)
        sh = jnp.zeros((1, 256), dt)
        grad_compile(f"matmul_bn_stats/{name}",
                     lambda a: cf.matmul_bn_stats(a, w)[0]
                     .astype(jnp.float32).sum(), x)
        grad_compile(f"bn_act_matmul/{name}",
                     lambda a: cf.bn_act_matmul(a, sc, sh, w)
                     .astype(jnp.float32).sum(), x)
        grad_compile(f"bn_act_matmul_stats/{name}",
                     lambda a: cf.bn_act_matmul_stats(a, sc, sh, w)[0]
                     .astype(jnp.float32).sum(), x)
        grad_compile(f"bn_stats/{name}",
                     lambda a: pbn.bn_stats(a)[0]
                     .astype(jnp.float32).sum(), x)
        T, N, H = 4, 16, 128
        h0 = jnp.zeros((N, H), dt)
        wl = jnp.zeros((4 * H, H), dt)
        grad_compile(f"lstm/{name}",
                     lambda a: lstm_layer(a, wl, h0, h0)[0]
                     .astype(jnp.float32).sum(),
                     jnp.zeros((T, N, 4 * H), dt))
        wg = jnp.zeros((3 * H, H), dt)
        bg = jnp.zeros((3 * H,), dt)
        grad_compile(f"gru/{name}",
                     lambda a: gru_layer(a, wg, bg, h0)[0]
                     .astype(jnp.float32).sum(),
                     jnp.zeros((T, N, 3 * H), dt))

    # and the main path's kernel RUN against the XLA reference, at the
    # oracle forward's shape, values and gradients
    rng = np.random.RandomState(SEED)
    shape = (ORACLE_BATCH, 12, SEQ, 64)
    q, k, v = (jnp.asarray(rng.randn(*shape).astype(np.float32))
               for _ in range(3))
    valid = np.array([SEQ, SEQ // 2])[:ORACLE_BATCH]
    km = jnp.asarray(np.where(np.arange(SEQ)[None] < valid[:, None],
                              0.0, -1e9).astype(np.float32))

    def kernel_loss(q, k, v):
        return jnp.sum(_flash_sdpa(q, k, v, km, False, 0.125) ** 2)

    def ref_loss(q, k, v):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(sdpa_reference(
                q, k, v, km.reshape(ORACLE_BATCH, 1, 1, SEQ),
                scale=0.125) ** 2)

    got = jax.jit(jax.value_and_grad(kernel_loss, argnums=(0, 1, 2)))(
        q, k, v)
    want = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2)))(
        q, k, v)
    errs = []
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert set(a.devices()) == {chip}
        a, b = np.asarray(a), np.asarray(b)
        errs.append(float(np.abs(a - b).max()
                          / max(1.0, np.abs(b).max())))
    assert max(errs) <= ORACLE_TOL, errs
    emit("kernels", t0, compiled=len(compiled), families=compiled,
         flash_vs_reference_max_err=round(max(errs), 6),
         asserted=["every family compiles value-and-grad for the chip "
                   "in f32 and bf16 with a tpu_custom_call",
                   "flash kernel == XLA reference (values and grads) "
                   "within oracle_tol"])


# ---------------------------------------------------------------------------
# --chips 4: the multi-chip path and what it is compared with, only
# ---------------------------------------------------------------------------

def distinct_devices(arr):
    return {s.device for s in arr.addressable_shards}


def multichip_spmd(devs):
    """(a) DataParallelTrainer on a dp=4 mesh vs the same seed and
    global batch on a 1-device mesh of the first chip."""
    import jax
    import numpy as np
    from pretrain_bert import synthetic_batch

    from mxnet_tpu.parallel import mesh as mesh_mod

    t0 = time.perf_counter()
    n = len(devs)
    x = synthetic_batch(np.random.RandomState(SEED), SPMD_BATCH, SEQ, VOCAB)
    y = np.zeros((SPMD_BATCH,), np.float32)
    runs = {}
    for name, mesh_devs in (("dp1", devs[:1]), (f"dp{n}", devs)):
        trainer = build_spmd_trainer(
            mesh_mod.make_mesh({"dp": len(mesh_devs)}, mesh_devs))
        losses, secs = [], []
        for _ in range(3):
            t = time.perf_counter()
            losses.append(float(trainer.step(x, y).asnumpy()))
            secs.append(round(time.perf_counter() - t, 3))
        runs[name] = (losses, secs)
        if len(mesh_devs) > 1:
            for arr in trainer._params + tuple(
                    jax.tree.leaves(trainer._states)):
                assert distinct_devices(arr) == set(devs), \
                    distinct_devices(arr)
            text = compiled_text(trainer, x, y)
            assert "all-reduce" in text
            n_kernels = require_flash_kernel(text, x)
        del trainer
        release()
    one, many = runs["dp1"][0], runs[f"dp{n}"][0]
    assert all(np.isfinite(one + many))
    diffs = [abs(a - b) for a, b in zip(one, many)]
    assert max(diffs) <= BF16_LOSS_TOL, (one, many)
    emit("multichip_spmd", t0, chips=n, global_batch=SPMD_BATCH,
         losses_dp1=[round(v, 4) for v in one],
         **{f"losses_dp{n}": [round(v, 4) for v in many]},
         max_abs_loss_diff=round(max(diffs), 5), tol=BF16_LOSS_TOL,
         step_seconds_dp1=runs["dp1"][1],
         **{f"step_seconds_dp{n}": runs[f"dp{n}"][1]},
         tpu_custom_calls_in_step=n_kernels,
         asserted=["losses agree step by step within tol",
                   f"every parameter and optimizer-state array has "
                   f"shards on {n} distinct devices",
                   "the compiled step contains an all-reduce and the "
                   "flash kernel"])


def per_example_loss(out, labels):
    """MLM + NSP negative log-likelihood per EXAMPLE over the BERTModel
    heads: its batch sum does not depend on how the batch is split over
    replicas (BERTForPretrain's scalar normalises per shard).  The two
    layouts are compared with dropout off: under shard_map every
    replica draws its mask from the same key, so the masks — not the
    math — would differ from the one-chip run."""
    from mxnet_tpu import nd

    mlm_scores, nsp_scores = out
    k = mlm_scores.shape[1]
    mlm_t = labels.slice_axis(1, 0, k)
    nsp_t = labels.slice_axis(1, k, k + 1).reshape((-1,))
    mlm = -nd.pick(nd.log_softmax(mlm_scores), mlm_t, axis=-1).mean(axis=1)
    nsp = -nd.pick(nd.log_softmax(nsp_scores), nsp_t, axis=-1)
    return mlm + nsp


def multichip_gluon(devs):
    """(b) gluon.Trainer over four replica contexts (kvstore='device',
    whole_step) vs the same seed and global batch on one chip."""
    import numpy as np
    from pretrain_bert import synthetic_batch

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import trainer as trainer_mod
    from mxnet_tpu.models import bert as bert_mod

    t0 = time.perf_counter()
    n = len(devs)
    b = synthetic_batch(np.random.RandomState(SEED), SPMD_BATCH, SEQ, VOCAB)
    # BERTModel(inputs, token_types, valid_length, masked_positions)
    x = tuple(nd.array(b[i], ctx=mx.xla(0)) for i in (0, 1, 5, 6))
    labels = nd.array(np.concatenate([b[2], b[3][:, None]], axis=1),
                      ctx=mx.xla(0))
    runs = {}
    for name, ctxs in (("1ctx", [mx.xla(0)]),
                       (f"{n}ctx", [mx.xla(i) for i in range(n)])):
        mx.random.seed(SEED)
        net = bert_mod.bert_base(vocab_size=VOCAB, dropout=0.0)
        net.initialize(bert_init(), ctx=ctxs)
        net.hybridize()
        # the shape-inference forward that finishes deferred init
        net(*(v[:ORACLE_BATCH] for v in x))[0].wait_to_read()
        trainer = gluon.Trainer(net.collect_params(), "adamw",
                                {"learning_rate": LR, "wd": 0.01},
                                kvstore="device", whole_step=True)
        trainer_mod.reset_trainer_step_stats()
        losses, secs = [], []
        for _ in range(3):
            t = time.perf_counter()
            loss = trainer.whole_step(net, per_example_loss, x, labels)
            losses.append(float(loss.asnumpy()) / SPMD_BATCH)
            secs.append(round(time.perf_counter() - t, 3))
        stats = trainer_mod.trainer_step_stats()
        assert stats["whole_step_fallbacks"] == 0, stats
        assert stats["whole_step_steps"] == 3, stats
        runs[name] = (losses, secs)
        if len(ctxs) > 1:
            assert stats["buckets_built"] > 0, stats   # traced psum buckets
            for _, p in net._ordered_params():
                assert {d for c in p.list_ctx()
                        for d in p.data(c)._data.devices()} == set(devs)
            comp = trainer._whole_step_compiler
            for st in comp._gstates:
                for arr in st:
                    assert distinct_devices(arr) == set(devs)
            buckets = stats["buckets_built"] // 3
        del trainer, net
        release()
    one, many = runs["1ctx"][0], runs[f"{n}ctx"][0]
    assert all(np.isfinite(one + many))
    diffs = [abs(a - c) for a, c in zip(one, many)]
    assert max(diffs) <= F32_LOSS_TOL, (one, many)
    emit("multichip_gluon", t0, chips=n, global_batch=SPMD_BATCH,
         losses_1ctx=[round(v, 4) for v in one],
         **{f"losses_{n}ctx": [round(v, 4) for v in many]},
         max_abs_loss_diff=round(max(diffs), 5), tol=F32_LOSS_TOL,
         step_seconds_1ctx=runs["1ctx"][1],
         **{f"step_seconds_{n}ctx": runs[f"{n}ctx"][1]},
         allreduce_buckets_per_step=buckets,
         asserted=["per-example mean losses agree step by step within "
                   "tol", "whole_step_fallbacks == 0",
                   f"every parameter has a replica on each of {n} "
                   "distinct devices, and so does every optimizer state",
                   "the compiled step reduces gradients in-program "
                   "(traced all-reduce buckets > 0)"])


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the multi-chip comparison")
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from mxnet_tpu.utils import compile_cache

    t0 = time.perf_counter()
    cache_dir = compile_cache.enable()
    print(json.dumps({"phase": "start", "chips": len(devs),
                      "device_kind": devs[0].device_kind,
                      "compile_cache": cache_dir,
                      "cache_entries_at_start": len(os.listdir(cache_dir))
                      if os.path.isdir(cache_dir) else 0}), flush=True)
    if args.chips == 1:
        phase_context(devs[0])
        phase_gluon(devs[0])
        phase_spmd(devs[0])
        phase_serve(devs[0])
        phase_kernels(devs[0])
    else:
        multichip_spmd(devs)
        multichip_gluon(devs)
    print(json.dumps({"phase": "total", "seconds": round(
        time.perf_counter() - t0, 1)}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
