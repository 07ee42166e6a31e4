"""Benchmark entry point — prints ONE JSON line.

    python bench.py         # on the machine with the chip
    python bench.py --dry   # CPU, tiny sizes: control flow only

Two north-star workloads ride in that line:

- **BERT-base MLM+NSP** (BASELINE config #3) — the compute-bound
  workload the >=50%-MFU north star was written for.  The TOP-LEVEL
  metric/value/vs_baseline come from this record.
- **ResNet-50 v1** (BASELINE config #2) — the flagship image model,
  reported with its bandwidth-implied MFU ceiling.

All full records are under "records"; the top level mirrors the BERT
record (vs_baseline = bert_mfu / 0.50).

The platform is what the caller asked for.  Without ``--dry`` every
leaf must come up on a TPU: one that does not, or that fails for any
other reason, makes the run exit non-zero with the cause — there is no
retry on the CPU and no record under a device metric's name from
anything but the device.  ``--dry`` runs the same leaves on the CPU at
toy sizes and reports only which of them ran to their end.

The parent process NEVER imports jax: a chip belongs to one process at
a time, so each leaf is a subprocess that takes the chip, measures, and
gives it back before the next one starts.

Each record's 'roofline_mfu_bound' is COMPUTED from the lowered step's
own cost analysis (flops / bytes-accessed arithmetic intensity x HBM
bandwidth / peak), not hardcoded; it is the ceiling to compare the
measured MFU against on any chip/config.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

MFU_TARGET = 0.50  # BASELINE.json north star: >=50% MFU

# peak dense bf16 FLOP/s by device_kind substring.  v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s, 16 GB of HBM
# (jax reports that chip as "TPU v5 lite"); the others from the same
# public spec sheets
_PEAK_BF16 = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6", 918e12), ("trillium", 918e12),
    ("v4", 275e12),
    ("v3", 123e12), ("v2", 45e12),
)

# HBM bandwidth bytes/s, same keys and sources
_HBM_BW = (
    ("v5 lite", 819e9), ("v5litepod", 819e9), ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6", 1640e9), ("trillium", 1640e9),
    ("v4", 1228e9),
    ("v3", 900e9), ("v2", 700e9),
)


def _lookup(table, device_kind):
    """A device that is not in the table is an error, not a default: a
    guessed peak turns every utilization figure into a guess."""
    kind = device_kind.lower()
    for key, val in table:
        if key in kind:
            return val
    raise ValueError(
        f"no published peak for device_kind {device_kind!r}; add it to "
        "bench.py's tables with its source")


def _peak_flops(device_kind):
    return _lookup(_PEAK_BF16, device_kind)


def _hbm_bw(device_kind):
    return _lookup(_HBM_BW, device_kind)


# ---------------------------------------------------------------------------
# leaf helpers (subprocess side)
# ---------------------------------------------------------------------------

def _leaf_setup(platform, cpu_devices=None):
    """Every leaf starts here: the platform is what the caller asked
    for, and a leaf that comes up on another one fails before it
    measures anything.  ``cpu_devices``: virtual device count for the
    CPU dry mode of a leaf that needs a mesh."""
    import jax

    from mxnet_tpu.utils import compile_cache

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
        if cpu_devices:
            jax.config.update("jax_num_cpu_devices", cpu_devices)
    compile_cache.enable()
    got = jax.devices()[0].platform
    if got != platform:
        raise SystemExit(
            f"bench leaf asked for platform {platform!r} but jax came up "
            f"on {got!r} ({jax.devices()[0].device_kind})")
    return jax


def _step_cost(trainer, x, y, allow_compile):
    """(flops, bytes_accessed) for ONE step.

    With `allow_compile` (TPU path): from the compiled executable's
    post-fusion cost analysis — fusion is what determines real HBM
    traffic, and the step warmup already populated the persistent
    compile cache so the AOT .compile() deserializes rather than
    recompiling.  Without it (CPU dry mode, where the single-step fn is
    never compiled and a cold compile would blow the leaf budget): the
    HLO-level lowering's analysis, flops-accurate, traffic-pessimistic
    (roofline is None on CPU anyway)."""
    import jax.numpy as jnp

    from mxnet_tpu import random as _random

    xj = tuple(jnp.asarray(v) for v in x) if isinstance(
        x, (tuple, list)) else jnp.asarray(x)
    try:
        lowered = trainer._step_fn.lower(
            trainer._params, trainer._states, xj, jnp.asarray(y),
            _random.next_key(),
            jnp.asarray(trainer._lr, jnp.float32),
            jnp.asarray(3.0, jnp.float32))
    except Exception:
        return None, None
    cost = None
    if allow_compile:
        try:
            cost = lowered.compile().cost_analysis()
        except Exception:
            pass
    if not cost:
        try:
            cost = lowered.cost_analysis()
        except Exception:
            pass
    if not cost:
        return None, None
    c = cost[0] if isinstance(cost, (list, tuple)) else cost
    flops = float(c.get("flops", 0.0)) or None
    nbytes = float(c.get("bytes accessed", 0.0)) or None
    return flops, nbytes


def _roofline_bound(flops, nbytes, dev):
    """Bandwidth-implied MFU ceiling: arithmetic intensity (flops/byte)
    x HBM bytes/s / peak flop/s, capped at 1.  None off-TPU or when the
    cost analysis didn't yield both terms."""
    if not flops or not nbytes or dev.platform == "cpu":
        return None
    bw, peak = _hbm_bw(dev.device_kind), _peak_flops(dev.device_kind)
    return round(min(1.0, (flops / nbytes) * bw / peak), 4)


def _time_step_many(trainer, x_dev, y_dev, iters, windows):
    """Best-of-N bulk-scan timing; returns (dt, last_losses)."""
    trainer.step_many(x_dev, y_dev, n_steps=iters).asnumpy()  # warm scan
    dt, losses = None, None
    for _ in range(windows):
        t0 = time.perf_counter()
        losses = trainer.step_many(x_dev, y_dev, n_steps=iters)
        losses.asnumpy()
        w = time.perf_counter() - t0
        dt = w if dt is None or w < dt else dt
    return dt, losses


def _leaf_resnet(platform):
    jax = _leaf_setup(platform)
    if platform == "cpu":
        bs, iters, image = 8, 2, 112
    else:
        bs, iters, image = 128, 30, 224

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import data_parallel

    dev = jax.devices()[0]
    mx.random.seed(0)
    np.random.seed(0)

    # NHWC: channel on the minormost (128-lane) tile dim — conv relayouts
    # and per-channel BN reductions are dramatically cheaper than NCHW
    # (profiled; the reference's perf guide likewise prescribes NHWC+fp16
    # for tensor cores, docs/faq/perf.md)
    net = vision.resnet50_v1(layout="NHWC")
    net.initialize(mx.init.Xavier())
    # bf16 compute (fp32 master params): the MXU runs bf16 at full rate
    # and fp32 at ~1/4; the reference's headline numbers are likewise
    # mixed-precision (fp16 + fp32 master, docs/faq/perf.md)
    compute_dtype = "bfloat16" if platform != "cpu" else None
    trainer = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        compute_dtype=compute_dtype)

    x = np.random.rand(bs, image, image, 3).astype(np.float32)
    y = np.random.randint(0, 1000, bs).astype(np.float32)

    # warmup / compile.  The CPU dry mode skips the eager-step warmup
    # entirely — step_many() builds its own scanned executable, and
    # compiling the single-step one too nearly doubles the ResNet-50
    # CPU compile time
    if platform != "cpu":
        trainer.step(x, y).wait_to_read()
        for _ in range(5):
            trainer.step(x, y)
        trainer.step(x, y).asnumpy()
    else:
        trainer.build(x)

    # pre-stage the synthetic batch on device (benchmark_score.py
    # --benchmark 1 semantics: measure compute, not the host feed; the
    # input pipeline's async H2D overlap is exercised by the IO tests)
    from mxnet_tpu.ndarray.ndarray import _wrap as _nd_wrap

    sharding = data_parallel.mesh_mod.batch_sharding(trainer.mesh)
    x_dev = _nd_wrap(jax.device_put(x, sharding))
    y_dev = _nd_wrap(jax.device_put(y, sharding))

    dt, losses = _time_step_many(trainer, x_dev, y_dev, iters,
                                 windows=3 if platform != "cpu" else 1)
    ips = iters * bs / dt

    flops_per_step, bytes_per_step = _step_cost(
        trainer, x, y, allow_compile=(platform != "cpu"))
    if flops_per_step is None:
        # analytic fallback: ResNet-50 fwd ~= 4.09 GFLOP/img at 224^2,
        # scaled by image area; training ~= 3x forward
        flops_per_step = 3 * 4.089e9 * (image / 224.0) ** 2 * bs

    # flops cover the GLOBAL batch over the whole dp mesh, so peak must
    # aggregate every chip the step ran on
    chip_peak = _peak_flops(dev.device_kind) \
        if dev.platform != "cpu" else None
    n_chips = len(trainer.mesh.devices.flat)
    peak = chip_peak * n_chips if chip_peak else None
    mfu = (flops_per_step * iters / dt / peak) if peak else None

    # eager per-op dispatch overhead (SURVEY §3.1 hot-loop risk)
    from mxnet_tpu import nd

    a = nd.ones((8, 8))
    b = nd.ones((8, 8))
    (a + b).wait_to_read()  # compile/cache
    n_ops = 300
    t0 = time.perf_counter()
    for _ in range(n_ops):
        c = a + b
    c.wait_to_read()
    eager_us = (time.perf_counter() - t0) / n_ops * 1e6

    print(json.dumps({
        "metric": "resnet50_train_throughput",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(mfu / MFU_TARGET, 4) if mfu else 0.0,
        "mfu": round(mfu, 4) if mfu else None,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "batch_size": bs,
        "image_size": image,
        "compute_dtype": compute_dtype or "float32",
        "flops_per_step": flops_per_step,
        "bytes_per_step": bytes_per_step,
        "roofline_mfu_bound": _roofline_bound(
            flops_per_step, bytes_per_step, dev),
        "eager_us_per_op": round(eager_us, 1),
        "final_loss": round(float(losses[-1].asscalar()), 4),
    }))


def _leaf_bert(platform):
    """BERT-base MLM+NSP train step (BASELINE config #3) — the
    compute-bound north-star workload."""
    jax = _leaf_setup(platform)
    if platform == "cpu":
        bs, seq_len, iters = 4, 64, 2
    else:
        # bs 64: preflight (docs/WORKLOADS.md) puts the bs-256 static
        # tier at 2.4 GB of 16 GB — batch is nowhere near the memory
        # wall, and MXU utilization rises with batch; 64 keeps a wide
        # safety margin for compiled temps on the first chip session
        bs, seq_len, iters = 64, 128, 20

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import bert as bert_mod
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "bert"))
    sys.path.insert(0, os.path.join(REPO, "examples"))
    from pretrain_bert import BERTForPretrain, synthetic_batch

    dev = jax.devices()[0]
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    vocab = 30522
    model = bert_mod.bert_base(vocab_size=vocab)
    net = BERTForPretrain(model, vocab)
    net.initialize(mx.init.Xavier())

    compute_dtype = "bfloat16" if platform != "cpu" else None

    class _Identity:
        def __call__(self, out, _):
            return out

    trainer = data_parallel.DataParallelTrainer(
        net, _Identity(), "adamw", {"learning_rate": 1e-4, "wd": 0.01},
        compute_dtype=compute_dtype)
    x = synthetic_batch(rng, bs, seq_len, vocab)
    y = np.zeros((bs,), np.float32)  # unused by the loss head
    if platform != "cpu":
        trainer.step(x, y).wait_to_read()
        trainer.step(x, y).asnumpy()
    else:
        trainer.build(x)

    dt, losses = _time_step_many(trainer, x, y, iters,
                                 windows=3 if platform != "cpu" else 1)
    tokens_per_sec = iters * bs * seq_len / dt

    flops_per_step, bytes_per_step = _step_cost(
        trainer, x, y, allow_compile=(platform != "cpu"))
    chip_peak = _peak_flops(dev.device_kind) \
        if dev.platform != "cpu" else None
    n_chips = len(trainer.mesh.devices.flat)
    peak = chip_peak * n_chips if chip_peak else None
    mfu = (flops_per_step * iters / dt / peak) \
        if (peak and flops_per_step) else None

    print(json.dumps({
        "metric": "bert_base_mlm_throughput",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / MFU_TARGET, 4) if mfu else 0.0,
        "mfu": round(mfu, 4) if mfu else None,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "batch_size": bs,
        "seq_len": seq_len,
        "compute_dtype": compute_dtype or "float32",
        "flops_per_step": flops_per_step,
        "bytes_per_step": bytes_per_step,
        "roofline_mfu_bound": _roofline_bound(
            flops_per_step, bytes_per_step, dev),
        "final_loss": round(float(losses[-1].asscalar()), 4),
    }))


def _leaf_serve(platform):
    """Dynamic-batching serving record (mxnet_tpu.serve): offered-load
    throughput + p50/p99 latency over a fixed bucket set, against the
    sequential single-request baseline on the very same warmed model —
    the A/B that shows batching (not compilation caching) is what the
    serving tier buys."""
    _leaf_setup(platform)
    if platform == "cpu":
        n_requests, feat = 120, 32
    else:
        n_requests, feat = 400, 64

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(128, flatten=False, in_units=feat, activation="relu"),
            nn.Dense(128, flatten=False, in_units=128, activation="relu"),
            nn.Dense(32, flatten=False, in_units=128))
    net.initialize(mx.init.Xavier())

    lengths = (16, 32, 64)
    spec = serve.BucketSpec(batch_sizes=(1, 2, 4, 8, 16),
                            example_shape=(None, feat), lengths=lengths)
    requests = [rng.rand(int(rng.choice(lengths)) - int(rng.choice(5)),
                         feat).astype(np.float32)
                for _ in range(n_requests)]

    srv = serve.ModelServer(net, spec, max_queue=n_requests + 8,
                            linger_ms=1.0)
    srv.start()  # AOT warmup of every bucket

    t0 = time.perf_counter()
    futs = [srv.submit(x) for x in requests]
    for f in futs:
        f.result(timeout=300)
    serve_dt = time.perf_counter() - t0
    srv.drain()
    stats = srv.stats()

    # sequential baseline: one request at a time through the same warmed
    # executables (batch-1 buckets), so the delta is pure batching win
    from mxnet_tpu.ndarray.ndarray import array as nd_array

    def _seq_one(x):
        _, length = spec.pick(1, x.shape[0])
        net(nd_array(spec.pad_batch([x], 1, length))).asnumpy()

    _seq_one(requests[0])  # steady-state entry
    t0 = time.perf_counter()
    for x in requests:
        _seq_one(x)
    seq_dt = time.perf_counter() - t0

    serve_rps = n_requests / serve_dt
    seq_rps = n_requests / seq_dt
    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "serve_offered_load_throughput",
        "value": round(serve_rps, 2),
        "unit": "requests/sec",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_requests": n_requests,
        "bucket_batch_sizes": [1, 2, 4, 8, 16],
        "bucket_lengths": list(lengths),
        "p50_ms": stats["latency"]["p50_ms"],
        "p99_ms": stats["latency"]["p99_ms"],
        "batch_fill_ratio": stats["batch_fill_ratio"],
        "batches": stats["batches"],
        "post_warmup_compiles": stats["graph"]["post_warmup_compiles"],
        "sequential_rps": round(seq_rps, 2),
        "speedup_vs_sequential": round(serve_rps / seq_rps, 4),
    }))


def _leaf_serve_router(platform):
    """Fault-tolerant-serving record (serve.Router): offered-load
    rps + p50/p99 for a 1-replica baseline vs a routed 3-replica pool,
    with an IN-RUN eviction event on the pooled arm — a seeded fault
    plan kills one replica mid-burst, the circuit breaker evicts it,
    and a warm spare rejoins.  The record carries requests_lost (must
    be 0) and the eviction->readmission recovery time: the pool's
    robustness priced under load, not just its throughput.  (On a
    CPU-bound host the 3-replica arm measures fault tolerance, not
    speedup — XLA:CPU anti-scales against concurrent replicas, see the
    input_pipeline leaf's note.)"""
    _leaf_setup(platform)
    if platform == "cpu":
        n_requests, feat = 120, 32
    else:
        n_requests, feat = 400, 64

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serve
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import RetryPolicy, faults

    lengths = (16, 32, 64)
    spec = serve.BucketSpec(batch_sizes=(1, 2, 4, 8, 16),
                            example_shape=(None, feat), lengths=lengths)

    def make_net():
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(128, flatten=False, in_units=feat,
                         activation="relu"),
                nn.Dense(128, flatten=False, in_units=128,
                         activation="relu"),
                nn.Dense(32, flatten=False, in_units=128))
        net.initialize(mx.init.Xavier())
        return net

    def factory(rid):
        return serve.ModelServer(make_net(), spec,
                                 max_queue=n_requests + 8,
                                 linger_ms=1.0)

    rng = np.random.RandomState(0)
    requests = [rng.rand(int(rng.choice(lengths)) - int(rng.choice(5)),
                         feat).astype(np.float32)
                for _ in range(n_requests)]

    def run_arm(n_replicas, plan=None):
        router = serve.Router(
            factory, n_replicas, health_sec=0.25, evict_after=3,
            retry=RetryPolicy(max_retries=3, base_delay=0.01,
                              max_delay=0.05, seed=7))
        router.start()
        if plan is not None:
            plan.reset().arm()
        t0 = time.perf_counter()
        futs = [router.submit(x, deadline_ms=120_000)
                for x in requests]
        for f in futs:
            f.result(timeout=300)
        dt = time.perf_counter() - t0
        if plan is not None:
            # wait for the warm spare so recovery time is on record
            t_heal = time.monotonic() + 120
            while time.monotonic() < t_heal:
                s = router.stats()
                if s["healthy"] == n_replicas \
                        and s["replacements"] >= 1:
                    break
                time.sleep(0.02)
            plan.disarm()
        router.drain(timeout=120)
        s = router.stats()
        compiles = sum(r.server.stats()["graph"]["post_warmup_compiles"]
                       for r in router.replicas)
        return dt, s, compiles

    single_dt, single_s, single_compiles = run_arm(1)
    plan = faults.FaultPlan([
        {"site": "serve.replica.submit", "action": "raise",
         "match": {"replica": 1}, "times": None}], seed=7)
    pool_dt, pool_s, pool_compiles = run_arm(3, plan=plan)

    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "serve_router_pool_throughput",
        "value": round(n_requests / pool_dt, 2),
        "unit": "requests/sec",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_requests": n_requests,
        "pool_replicas": 3,
        "pool_p50_ms": pool_s["latency"]["p50_ms"],
        "pool_p99_ms": pool_s["latency"]["p99_ms"],
        "single_rps": round(n_requests / single_dt, 2),
        "single_p50_ms": single_s["latency"]["p50_ms"],
        "single_p99_ms": single_s["latency"]["p99_ms"],
        "evictions": pool_s["evictions"],
        "replacements": pool_s["replacements"],
        "retries": pool_s["retries"],
        "requests_lost": pool_s["requests_lost"]
        + single_s["requests_lost"],
        "recovery_ms": pool_s["last_recovery_ms"],
        "post_warmup_compiles": single_compiles + pool_compiles,
    }))


def _leaf_serve_int8(platform):
    """Compiled-INT8 serving A/B (contrib.quantization + ModelServer):
    the same trained classifier served three ways through identically
    configured warmed servers — fp32 compiled, int8 compiled
    (quantize_net: one fused int8 executable per bucket, activations
    int8 between layers), and the old eager-quantized arm (per-op
    dispatch, fp32 between every layer — what quantize_net emitted
    before the compile-native rebuild).  Gates recorded: compiled-int8
    >= 2x the eager-quantized arm, >= 99% argmax agreement with fp32,
    compiled==eager bit parity, zero post-warmup compiles."""
    _leaf_setup(platform)
    n_requests = 150 if platform == "cpu" else 400

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd, serve
    from mxnet_tpu.contrib import quantization as qz
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import Block

    # geometry: deep-and-narrow with small buckets keeps the serve loop
    # DISPATCH-bound — the regime the eager-quantized path loses in
    # (per-op dispatch × layers × chain stages per batch) and the whole
    # reason the compiled path exists.  Compute-bound geometries
    # converge to the matmul cost on every arm.
    feat, hidden, classes, layers = 32, 96, 10, 12
    rs = np.random.RandomState(0)
    centers = rs.randn(classes, feat).astype(np.float32) * 2.0

    def sample(n, rng):
        y = rng.randint(0, classes, n)
        return (centers[y] + rng.randn(n, feat)).astype(np.float32), y

    def build(seed):
        mx.random.seed(seed)
        net = nn.HybridSequential()
        prev = feat
        for _ in range(layers - 1):
            net.add(nn.Dense(hidden, activation="relu", in_units=prev,
                             flatten=False))
            prev = hidden
        net.add(nn.Dense(classes, in_units=prev, flatten=False))
        net.initialize(mx.init.Xavier())
        return net

    # brief training: the quality gate is defined on a net with real
    # decision margins
    fp32 = build(0)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(fp32.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    for _ in range(150):
        x, y = sample(64, rs)
        with autograd.record():
            loss = loss_fn(fp32(nd.array(x)), nd.array(y.astype(np.int32)))
        loss.backward()
        trainer.step(64)

    def clone():
        net = build(1)
        for dst, src in zip(net.collect_params().values(),
                            fp32.collect_params().values()):
            dst.set_data(src.data())
        return net

    # naive calibration: entropy's aggressive clipping COMPOUNDS
    # through a deep folded chain (every int8 boundary re-clips) and
    # wrecks agreement past ~10 layers; min/max is the right mode here
    # (docs/quantization.md, accuracy expectations)
    calib, _ = sample(256, rs)
    q_compiled = qz.quantize_net(clone(), calib_data=calib,
                                 calib_mode="naive")
    # the old arm: per-layer eager dispatch with fp32 boundaries (no
    # fold), behind a Block facade so ModelServer can't hybridize it
    q_eager_inner = qz.quantize_net(clone(), calib_data=calib,
                                    calib_mode="naive", fold=False)

    class _EagerFacade(Block):
        def __init__(self, inner):
            super().__init__()
            self._inner = inner

        def forward(self, x):
            return self._inner(x)

    requests, _ = sample(n_requests, rs)
    spec = serve.BucketSpec(batch_sizes=(1, 2, 4),
                            example_shape=(feat,))

    def run_arm(net):
        srv = serve.ModelServer(net, spec, max_queue=n_requests + 8,
                                linger_ms=1.0)
        srv.start()
        t0 = time.perf_counter()
        futs = [srv.submit(x) for x in requests]
        for f in futs:
            f.result(timeout=300)
        dt = time.perf_counter() - t0
        srv.drain()
        stats = srv.stats()
        srv.shutdown()
        return n_requests / dt, stats

    fp32_rps, fp32_stats = run_arm(fp32)
    int8_rps, int8_stats = run_arm(q_compiled)
    eager_rps, _ = run_arm(_EagerFacade(q_eager_inner))

    # quality + parity on held-out data (after serving: direct forwards
    # would otherwise add executables under the servers' counters)
    xe, _ = sample(500, np.random.RandomState(42))
    ref = fp32(nd.array(xe)).asnumpy()
    got = q_compiled(nd.array(xe)).asnumpy()
    agreement = float((got.argmax(1) == ref.argmax(1)).mean())
    xb = xe[:16]
    compiled_out = q_compiled(nd.array(xb)).asnumpy()
    q_compiled._active = False
    eager_out = q_compiled(nd.array(xb)).asnumpy()
    q_compiled._active = True
    bit_identical = bool(np.array_equal(compiled_out, eager_out))

    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "serve_int8_throughput",
        "value": round(int8_rps, 2),
        "unit": "requests/sec",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_requests": n_requests,
        "fp32_rps": round(fp32_rps, 2),
        "eager_int8_rps": round(eager_rps, 2),
        "speedup_vs_eager_int8": round(int8_rps / eager_rps, 4),
        "speedup_vs_fp32": round(int8_rps / fp32_rps, 4),
        "agreement_argmax_vs_fp32": agreement,
        "compiled_eager_bit_identical": bit_identical,
        "p50_ms": int8_stats["latency"]["p50_ms"],
        "p99_ms": int8_stats["latency"]["p99_ms"],
        "post_warmup_compiles": int8_stats["graph"]
        ["post_warmup_compiles"],
        "fp32_post_warmup_compiles": fp32_stats["graph"]
        ["post_warmup_compiles"],
    }))


def _leaf_serve_decode(platform):
    """Continuous-batching decode A/B (mxnet_tpu.serve.DecodeServer):
    the same staggered request stream decoded twice through the same
    warmed slot arena — token-level admission (``continuous``) vs
    whole-batch admission (``batch``, every sequence waits for the
    batch's straggler).  Both arms run the SAME single fixed-shape step
    executable, so the delta is pure scheduling: continuous keeps the
    arena full, whole-batch decays to the straggler.  Records tokens/s
    per arm, p50/p99 TTFT and per-token latency, slot occupancy, the
    zero-post-warmup-compile counter, and the honest dispatch
    accounting.

    A THIRD arm (``paged_speculative``) decodes the same stream through
    a PAGED KV arena sized to HALF the contiguous arena's cache HBM
    with a TinyDraft proposing ``spec_k`` tokens per verify dispatch —
    the capacity claim as a benchmark number: at that fixed memory a
    contiguous arena fits ``budget_tokens // max_len`` resident
    sequences, the paged arm's sampled peak live slots give the
    measured ``concurrent_sequences_at_fixed_mem`` multiple, and
    tokens/s is recorded head-to-head against the contiguous
    continuous arm on the same heavy-tailed workload."""
    _leaf_setup(platform)
    if platform == "cpu":
        n_requests, slots = 50, 8
    else:
        n_requests, slots = 150, 16

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _imperative, serve

    mx.random.seed(0)
    model = serve.TinyDecoder(vocab=256, embed=64)
    model.initialize(mx.init.Xavier())
    lengths = (4, 8, 16)
    spec = serve.BucketSpec(batch_sizes=(1, 2, 4, 8),
                            example_shape=(None,), lengths=lengths,
                            dtype="int32")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 256, size=int(rng.randint(2, 17)))
               .astype(np.int32) for _ in range(n_requests)]
    # heavy-tailed budgets — the realistic serving shape and the exact
    # scenario continuous batching exists for: most generations are
    # short, a few are long, and under whole-batch scheduling every
    # batch runs to its longest member
    budgets = [int(rng.randint(48, 73)) if rng.rand() < 0.25
               else int(rng.randint(4, 13)) for _ in range(n_requests)]

    def run(admission, n_slots=None):
        srv = serve.DecodeServer(model, spec,
                                 max_slots=n_slots or slots,
                                 max_len=96,
                                 max_queue=n_requests + 8,
                                 admission=admission)
        srv.start()
        d0 = _imperative.device_dispatch_count()
        t0 = time.perf_counter()
        handles = []
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            handles.append(srv.submit(p, max_new_tokens=m))
            if i % 4 == 0:
                time.sleep(0.0005)      # staggered offered load
        for h in handles:
            h.result(timeout=600)
        dt = time.perf_counter() - t0
        srv.drain()
        s = srv.stats()
        d1 = _imperative.device_dispatch_count()
        assert s["served"] == n_requests
        return {
            "tokens_per_sec": round(s["tokens"] / dt, 2),
            "tokens": s["tokens"],
            "decode_steps": s["decode_steps"],
            "slot_occupancy": s["slots"]["occupancy"],
            "ttft_p50_ms": s["ttft"]["p50_ms"],
            "ttft_p99_ms": s["ttft"]["p99_ms"],
            "token_p50_ms": s["token_latency"]["p50_ms"],
            "token_p99_ms": s["token_latency"]["p99_ms"],
            "post_warmup_compiles": s["graph"]["post_warmup_compiles"],
            "dispatch_accounting_exact": bool(
                d1 - d0 == s["decode_steps"] + s["batches"]),
        }

    def run_paged():
        import threading

        page_tokens = 16
        # HALF the contiguous arena's cache HBM: the contiguous arena
        # above commits slots * max_len token rows up front; the paged
        # pool gets half that many tokens' worth of pages and still
        # serves the full slot count
        budget_tokens = slots * 96 // 2
        srv = serve.DecodeServer(model, spec, max_slots=slots,
                                 max_len=96, page_tokens=page_tokens,
                                 num_pages=budget_tokens // page_tokens,
                                 draft=serve.TinyDraft(model),
                                 spec_k=4,
                                 max_queue=n_requests + 8)
        srv.start()
        peak = [0]
        stop = threading.Event()

        def _sample():
            while not stop.is_set():
                live = srv.live_slots()
                if live > peak[0]:
                    peak[0] = live
                time.sleep(0.001)

        sampler = threading.Thread(target=_sample, daemon=True)
        sampler.start()
        d0 = _imperative.device_dispatch_count()
        t0 = time.perf_counter()
        handles = []
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            handles.append(srv.submit(p, max_new_tokens=m))
            if i % 4 == 0:
                time.sleep(0.0005)      # staggered offered load
        for h in handles:
            h.result(timeout=600)
        dt = time.perf_counter() - t0
        stop.set()
        sampler.join(timeout=5)
        srv.drain()
        s = srv.stats()
        d1 = _imperative.device_dispatch_count()
        assert s["served"] == n_requests
        # at this memory budget a contiguous arena fits this many
        # resident sequences; the paged arm's sampled peak is the
        # measured concurrency at the SAME cache HBM
        contig_seqs = budget_tokens // 96
        return {
            "tokens_per_sec": round(s["tokens"] / dt, 2),
            "tokens": s["tokens"],
            "decode_steps": s["decode_steps"],
            "spec_draft_steps": s["spec_draft_steps"],
            "accept_rate": s["spec"]["accept_rate"],
            "slot_occupancy": s["slots"]["occupancy"],
            "peak_live_slots": peak[0],
            "pages_in_flight": s["pages"]["in_flight"],
            "page_allocs": s["page_allocs"],
            "page_cow": s["page_cow"],
            "hbm_bytes": s["pages"]["hbm_bytes"],
            "contiguous_seqs_at_this_mem": contig_seqs,
            "concurrent_sequences_at_fixed_mem": round(
                peak[0] / contig_seqs, 4),
            "ttft_p50_ms": s["ttft"]["p50_ms"],
            "ttft_p99_ms": s["ttft"]["p99_ms"],
            "token_p50_ms": s["token_latency"]["p50_ms"],
            "token_p99_ms": s["token_latency"]["p99_ms"],
            "post_warmup_compiles": s["graph"]["post_warmup_compiles"],
            "dispatch_accounting_exact": bool(
                d1 - d0 == s["decode_steps"] + s["spec_draft_steps"]
                + s["batches"]),
        }

    cont = run("continuous")
    whole = run("batch")
    # the fixed-memory baseline: a contiguous arena holding the SAME
    # cache HBM as the paged arm's pool can only keep
    # budget_tokens // max_len sequences resident
    cont_half = run("continuous", n_slots=slots * 96 // 2 // 96)
    paged = run_paged()
    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "serve_decode_throughput",
        "value": cont["tokens_per_sec"],
        "unit": "tokens/sec",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_requests": n_requests,
        "max_slots": slots,
        "continuous": cont,
        "whole_batch": whole,
        "continuous_fixed_mem": cont_half,
        "paged_speculative": paged,
        "speedup_vs_whole_batch": round(
            cont["tokens_per_sec"] / whole["tokens_per_sec"], 4),
        "paged_speedup_at_fixed_mem": round(
            paged["tokens_per_sec"] / cont_half["tokens_per_sec"], 4),
        "concurrent_sequences_at_fixed_mem":
            paged["concurrent_sequences_at_fixed_mem"],
    }))


def _leaf_trainer_step(platform):
    """Full-training-step three-arm A/B (gluon.Trainer.whole_step):
    sequential (aggregate_num=1) / fused (the PR-3 default) /
    whole-step (ONE compiled executable per step) on a ~100-parameter
    model, all through the same ``whole_step()`` API so every arm pays
    for forward + backward + allreduce + update.  Reports per-arm step
    latency, dispatches per step (the global device-dispatch counter,
    not self-reported stats), and post-warmup compiles, plus the
    no-recompile check across a decaying LR schedule.

    A FOURTH arm (whole-step + ZeRO-1, ``zero_shard=True``) runs the
    same model on an 8-replica mesh (virtual on CPU) and records the
    MEASURED per-replica optimizer-state bytes next to an unsharded
    whole-step run on the same mesh — the 1/world_size memory claim
    as a benchmark number, not a docstring."""
    # the ZeRO arm needs a replica mesh: 8 virtual devices in the CPU
    # dry mode (arms A-C still build on device 0, unchanged)
    jax = _leaf_setup(platform, cpu_devices=8)

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _imperative, gluon, lr_scheduler, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon import trainer as trainer_mod

    n_layers, units, iters, windows = 50, 16, 30, 3

    # the A/B/C must control its own knobs: the env spellings beat the
    # ctor args by documented precedence, so an exported aggregation
    # size or MXTPU_WHOLE_STEP would silently collapse arms (leaves
    # run in their own subprocess, so popping is side-effect free)
    for _var in ("MXNET_OPTIMIZER_AGGREGATION_SIZE",
                 "MXTPU_OPTIMIZER_AGGREGATION_SIZE",
                 "MXTPU_WHOLE_STEP", "MXNET_WHOLE_STEP",
                 "MXTPU_ZERO_SHARD", "MXNET_ZERO_SHARD"):
        os.environ.pop(_var, None)

    def loss_fn(out, y):
        return (out - y) ** 2

    def measure(whole_step, aggregate_num, zero_shard=False, ctx=None,
                arm_iters=None, arm_windows=None):
        arm_iters = arm_iters or iters
        arm_windows = arm_windows or windows
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.HybridSequential()
        for _ in range(n_layers):
            # tanh bounds the deep linear stack so no arm diverges over
            # the measurement window
            net.add(nn.Dense(units, in_units=units, activation="tanh"))
        net.initialize(mx.init.Xavier(), ctx=ctx)
        sched = lr_scheduler.FactorScheduler(step=5, factor=0.97,
                                             base_lr=0.1)
        kwargs = {"learning_rate": 0.1, "momentum": 0.9,
                  "lr_scheduler": sched}
        if aggregate_num is not None:
            kwargs["aggregate_num"] = aggregate_num
        trainer = gluon.Trainer(net.collect_params(), "sgd", kwargs,
                                whole_step=whole_step,
                                zero_shard=zero_shard)
        x = np.random.rand(8, units).astype(np.float32)
        y = np.random.rand(8, units).astype(np.float32)
        for _ in range(5):
            trainer.whole_step(net, loss_fn, x, y)
        nd.waitall()
        trainer_mod.reset_trainer_step_stats()
        c0 = _imperative.compiled_executable_count()
        d0 = _imperative.device_dispatch_count()
        best = None
        for _ in range(arm_windows):
            t0 = time.perf_counter()
            for _ in range(arm_iters):
                trainer.whole_step(net, loss_fn, x, y)
            nd.waitall()
            dt = (time.perf_counter() - t0) / arm_iters
            best = dt if best is None or dt < best else best
        stats = trainer_mod.trainer_step_stats()
        compiles = _imperative.compiled_executable_count() - c0
        disp = round((_imperative.device_dispatch_count() - d0)
                     / max(stats["steps"], 1), 2)
        return best, stats, compiles, disp, trainer

    n_params = 2 * n_layers
    seq_s, seq_stats, seq_compiles, seq_disp, _ = measure(False, 1)
    fused_s, fused_stats, fused_compiles, fused_disp, _ = measure(
        False, None)
    whole_s, whole_stats, whole_compiles, whole_disp, _ = measure(
        True, None)

    # arm D: whole-step + ZeRO-1 on the replica mesh, next to an
    # unsharded whole-step run on the SAME mesh for the state-bytes
    # ratio (fewer iters — this arm prices memory, not latency)
    zero_arm = None
    mesh_ctxs = [mx.xla(i) for i in range(len(jax.devices()))]
    if len(mesh_ctxs) > 1:
        ubase_s, _us, _uc, _ud, utr = measure(
            True, None, ctx=mesh_ctxs, arm_iters=10, arm_windows=2)
        zero_s, zero_stats, zero_compiles, zero_disp, ztr = measure(
            True, None, zero_shard=True, ctx=mesh_ctxs,
            arm_iters=10, arm_windows=2)
        ubytes = utr.optimizer_state_bytes()["per_replica"]
        zbytes = ztr.optimizer_state_bytes()["per_replica"]
        zero_arm = {
            "ms_per_step": round(zero_s * 1e3, 3),
            "unsharded_mesh_ms_per_step": round(ubase_s * 1e3, 3),
            "dispatches_per_step": zero_disp,
            "post_warmup_compiles": zero_compiles,
            "zero_steps": zero_stats["zero_steps"],
            "fallbacks": zero_stats["zero_fallbacks"],
            "world_size": len(mesh_ctxs),
            "state_bytes_per_replica": zbytes,
            "state_bytes_per_replica_unsharded": ubytes,
            "state_shrink_ratio": round(zbytes / max(ubytes, 1), 4),
        }

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "trainer_step_latency",
        "value": round(whole_s * 1e3, 3),
        "unit": "ms/step",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_params": n_params,
        "arms": {
            "sequential": {
                "ms_per_step": round(seq_s * 1e3, 3),
                "dispatches_per_step": seq_disp,
                "post_warmup_compiles": seq_compiles,
            },
            "fused": {
                "ms_per_step": round(fused_s * 1e3, 3),
                "dispatches_per_step": fused_disp,
                "post_warmup_compiles": fused_compiles,
            },
            "whole_step": {
                "ms_per_step": round(whole_s * 1e3, 3),
                "dispatches_per_step": whole_disp,
                "post_warmup_compiles": whole_compiles,
                "whole_step_steps": whole_stats["whole_step_steps"],
                "fallbacks": whole_stats["whole_step_fallbacks"],
            },
            "whole_step_zero": zero_arm,
        },
        "speedup_whole_vs_fused": round(fused_s / whole_s, 4),
        "speedup_whole_vs_sequential": round(seq_s / whole_s, 4),
        "dispatch_reduction_vs_fused": round(
            fused_disp / max(whole_disp, 1e-9), 2),
        "post_warmup_compiles": whole_compiles,
    }))


def _leaf_whole_step_mp(platform):
    """Multi-axis mesh A/B (parallel.spmd): the same whole-step train
    loop on ONE device vs a (dp=4,mp=2) mesh, model sized so its params
    + momenta exceed a single device's share of the mesh budget — the
    configuration tensor parallelism exists for.  Both arms run the
    ONE-executable-per-step path; the mesh arm adds GSPMD collectives
    inside that executable, and ZeRO shards the optimizer state over
    both axes.  Reports per-arm step latency, dispatches/compiles, and
    the MEASURED per-device param and optimizer-state bytes — the
    memory claim (each device holds ~1/mp of the params, ~1/(dp*mp) of
    the state) as benchmark numbers."""
    jax = _leaf_setup(platform, cpu_devices=8)

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _imperative, gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon import trainer as trainer_mod

    for _var in ("MXNET_OPTIMIZER_AGGREGATION_SIZE",
                 "MXTPU_OPTIMIZER_AGGREGATION_SIZE",
                 "MXTPU_WHOLE_STEP", "MXNET_WHOLE_STEP",
                 "MXTPU_ZERO_SHARD", "MXNET_ZERO_SHARD",
                 "MXTPU_MESH_SHAPE", "MXNET_MESH_SHAPE"):
        os.environ.pop(_var, None)

    # 8 x (512, 512) weights + momenta: ~16 MB of fp32 train state —
    # small for a CPU but proportioned like the models whose per-device
    # HBM budget forces the 'mp' axis
    n_layers, units, batch, iters, windows = 8, 512, 32, 10, 3

    def loss_fn(out, y):
        return (out - y) ** 2

    def dev0_bytes(arrs, mesh):
        d0 = mesh.devices.flat[0]
        return sum(s.data.size * s.data.dtype.itemsize
                   for a in arrs if a is not None
                   for s in a.addressable_shards if s.device == d0)

    def host_bytes(trainer):
        pb = sum(int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
                 for p in trainer._params)
        sb = 0
        for st in trainer._states:
            entry = next(iter(st.values())) if st else None
            if entry is None:
                continue
            leaves = entry if isinstance(entry, (tuple, list)) \
                else (entry,)
            sb += sum(int(np.prod(s.shape))
                      * np.dtype(s.dtype).itemsize for s in leaves)
        return pb, sb

    def measure(mesh_shape):
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.HybridSequential()
        for _ in range(n_layers):
            net.add(nn.Dense(units, in_units=units, activation="tanh"))
        net.initialize(mx.init.Xavier(), ctx=mx.xla(0))
        trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            whole_step=True if mesh_shape is None else None,
            mesh_shape=mesh_shape,
            zero_shard=mesh_shape is not None)
        x = np.random.rand(batch, units).astype(np.float32)
        y = np.random.rand(batch, units).astype(np.float32)
        for _ in range(5):
            trainer.whole_step(net, loss_fn, x, y)
        nd.waitall()
        trainer_mod.reset_trainer_step_stats()
        c0 = _imperative.compiled_executable_count()
        d0 = _imperative.device_dispatch_count()
        best = None
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                trainer.whole_step(net, loss_fn, x, y)
            nd.waitall()
            dt = (time.perf_counter() - t0) / iters
            best = dt if best is None or dt < best else best
        stats = trainer_mod.trainer_step_stats()
        compiles = _imperative.compiled_executable_count() - c0
        disp = round((_imperative.device_dispatch_count() - d0)
                     / max(stats["steps"], 1), 2)
        comp = trainer._whole_step_compiler
        mesh = getattr(comp, "mesh", None)
        if mesh is not None:
            param_b = dev0_bytes(comp._gparams, mesh)
            state_b = comp.state_bytes_per_device()
        else:
            param_b, state_b = host_bytes(trainer)
        arm = {
            "ms_per_step": round(best * 1e3, 3),
            "dispatches_per_step": disp,
            "post_warmup_compiles": compiles,
            "fallbacks": stats["whole_step_fallbacks"],
            "param_bytes_per_device": param_b,
            "state_bytes_per_device": state_b,
        }
        if mesh_shape is not None:
            arm["spmd_steps"] = stats["spmd_steps"]
        return arm

    single = measure(None)
    mesh_arm = measure("dp=4,mp=2")

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "whole_step_mp_latency",
        "value": mesh_arm["ms_per_step"],
        "unit": "ms/step",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_params": 2 * n_layers,
        "mesh_shape": "dp=4,mp=2",
        "arms": {"single_device": single, "mesh_dp4_mp2": mesh_arm},
        "param_bytes_shrink_ratio": round(
            mesh_arm["param_bytes_per_device"]
            / max(single["param_bytes_per_device"], 1), 4),
        "state_bytes_shrink_ratio": round(
            mesh_arm["state_bytes_per_device"]
            / max(single["state_bytes_per_device"], 1), 4),
        "post_warmup_compiles": mesh_arm["post_warmup_compiles"],
    }))


def _leaf_input_pipeline(platform):
    """Input-pipeline A/B (mxnet_tpu.pipeline): end-to-end train-loop
    throughput with prefetch_to_device vs synchronous feeding, through
    a real hybridized train step (DataParallelTrainer's single jitted
    SPMD step — the GIL-light consumer the pipeline is designed for).

    The ingest stage models the production input shape: a per-sample
    blocking fetch (real file read + a fixed remote-storage service
    latency, MXTPU_BENCH_INGEST_MS) and a light decode.  Synchronous
    feeding serializes fetch latency into every step; the pipeline's
    map workers + h2d double-buffering hide it behind the previous
    step.  A/B on the same warmed executables: post_warmup_compiles
    must stay 0 (the acceptance invariant)."""
    # parallel blocking fetches need headroom beyond the default 4 host
    # workers; set BEFORE mxnet_tpu reads it at pool creation
    os.environ.setdefault("MXTPU_CPU_WORKER_NTHREADS", "8")
    _leaf_setup(platform)
    import shutil
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import _imperative, gluon, pipeline
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import data_parallel
    from mxnet_tpu.pipeline import pipeline_stats, reset_pipeline_stats

    from mxnet_tpu.base import getenv

    feat, bs, n, rounds = 4096, 8, 64, 3
    service_ms = getenv("BENCH_INGEST_MS", 8.0, float)
    workdir = tempfile.mkdtemp(prefix="mxtpu-input-pipeline-")
    try:
        rng = np.random.RandomState(0)
        files = []
        for i in range(n):
            p = os.path.join(workdir, f"s{i}.bin")
            with open(p, "wb") as f:
                f.write(rng.rand(feat).astype(np.float32).tobytes())
            files.append((p, np.float32(i % 10)))

        def ingest(s):
            path, y = s
            with open(path, "rb") as f:
                payload = f.read()
            time.sleep(service_ms / 1e3)  # remote-storage service time
            return np.frombuffer(payload, np.float32) * (1.0 / 255.0), y

        def build_pipe(sync):
            return (pipeline.Pipeline(files, sync=sync)
                    .map(ingest, inflight=8)
                    .batch(bs, last_batch="discard")
                    .prefetch_to_device(mx.cpu(), depth=2))

        mx.random.seed(0)
        np.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(512, in_units=feat, activation="relu"),
                nn.Dense(512, in_units=512, activation="relu"),
                nn.Dense(10, in_units=512))
        net.initialize(mx.init.Xavier())
        trainer = data_parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.01})

        def epoch(pipe):
            for x, y in pipe:
                trainer.step(x, y).asnumpy()

        epoch(build_pipe(True))   # warmup: compiles the step once
        epoch(build_pipe(False))
        c0 = _imperative.compiled_executable_count()
        step_cache0 = trainer._step_fn._cache_size() \
            if hasattr(trainer._step_fn, "_cache_size") else None
        sync_times, pf_times, sync_wait, pf_wait = [], [], [], []
        pf_stats = None
        for _ in range(rounds):           # interleaved A/B rounds
            reset_pipeline_stats()
            t0 = time.perf_counter()
            epoch(build_pipe(True))
            sync_times.append(time.perf_counter() - t0)
            sync_wait.append(pipeline_stats()["wait_ms"])
            reset_pipeline_stats()
            t0 = time.perf_counter()
            epoch(build_pipe(False))
            pf_times.append(time.perf_counter() - t0)
            pf_stats = pipeline_stats()
            pf_wait.append(pf_stats["wait_ms"])
        compiles = _imperative.compiled_executable_count() - c0
        if step_cache0 is not None:
            compiles += trainer._step_fn._cache_size() - step_cache0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    n_batches = n // bs
    sync_s, pf_s = min(sync_times), min(pf_times)
    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "input_pipeline_train_throughput",
        "value": round(n_batches / pf_s, 2),
        "unit": "batches/sec",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "batch_size": bs,
        "feature_dim": feat,
        "ingest_service_ms": service_ms,
        "synchronous_batches_per_sec": round(n_batches / sync_s, 2),
        "speedup_vs_synchronous": round(sync_s / pf_s, 4),
        "post_warmup_compiles": compiles,
        "wait_on_input_ms_sync": round(min(sync_wait), 1),
        "wait_on_input_ms_prefetch": round(min(pf_wait), 1),
        "prefetch_hits": pf_stats["prefetch_hits"],
        "prefetch_misses": pf_stats["prefetch_misses"],
        "h2d_ms": pf_stats["h2d_ms"],
    }))


def _leaf_recovery(platform):
    """Recovery record (mxnet_tpu.resilience): time-to-resume and steps
    lost after a HARD kill (a preemption whose final-save window was
    missed — no preemption state registered) of a supervised training
    run checkpointing every K steps.  The supervisor restarts
    in-process, restore falls back to the last committed step, and the
    replayed tail must leave the final params bit-identical to an
    uninjected run — the recovery-cost twin of the chaos-smoke
    correctness gate."""
    _leaf_setup(platform)
    import shutil
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, checkpoint, gluon, pipeline, resilience
    from mxnet_tpu.gluon import nn

    feat, bs, n, ckpt_every, kill_step = 64, 8, 160, 4, 10

    rng = np.random.RandomState(0)
    data = [(rng.rand(feat).astype(np.float32), np.float32(i % 2))
            for i in range(n)]

    def build_model():
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=feat, activation="relu"),
                nn.Dense(1, in_units=32))
        net.initialize(mx.init.Xavier())
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05},
                                kvstore="dist_sync",
                                update_on_kvstore=False)
        return net, trainer

    def run(ckdir, plan):
        if plan is not None:
            resilience.install_plan(plan)
        try:
            mgr = checkpoint.CheckpointManager(ckdir, keep_n=3)
            sup = resilience.Supervisor(
                mgr, on_preemption="resume", max_restarts=2,
                retry=resilience.RetryPolicy(max_retries=2,
                                             base_delay=0.01))
            executed, marks = [], {}

            def train(ctx):
                net, trainer = build_model()
                pipe = (pipeline.Pipeline(data).shuffle(8, seed=5)
                        .batch(bs, last_batch="discard"))
                start = 0
                if ctx.manager.latest() is not None:
                    t0 = time.perf_counter()
                    meta = ctx.manager.restore(params=net,
                                               trainer=trainer,
                                               pipeline=pipe)
                    marks["restore_done"] = time.perf_counter()
                    marks["restore_ms"] = (marks["restore_done"] - t0) \
                        * 1e3
                    start = meta["step"] + 1
                # NO preemption state: a kill loses everything since the
                # last periodic checkpoint (the hard-kill model)
                step = start
                for x, y in pipe:
                    with autograd.record():
                        loss = ((net(x) - y.reshape((-1, 1))) ** 2).sum()
                    loss.backward()
                    trainer.step(bs)
                    executed.append(step)
                    save = dict(params=net, trainer=trainer,
                                pipeline=pipe, sync=True) \
                        if step % ckpt_every == 0 else None
                    ctx.step_done(step, save=save)
                    step += 1
                return {k: v.data().asnumpy() for k, v in
                        net._collect_params_with_prefix().items()}

            params = sup.run(train)
            return params, executed, marks
        finally:
            if plan is not None:
                resilience.clear_plan()

    d_ref = tempfile.mkdtemp(prefix="mxtpu-recovery-ref-")
    d_chaos = tempfile.mkdtemp(prefix="mxtpu-recovery-")
    try:
        ref, _, _ = run(d_ref, None)
        resilience.reset_resilience_stats()  # scope time_lost to the run
        plan = resilience.FaultPlan([
            {"site": "train.step", "action": "kill",
             "match": {"step": kill_step}}])
        got, executed, marks = run(d_chaos, plan)
    finally:
        shutil.rmtree(d_ref, ignore_errors=True)
        shutil.rmtree(d_chaos, ignore_errors=True)

    assert plan.fired(), "kill never fired"
    bit_identical = set(ref) == set(got) and all(
        np.array_equal(ref[k], got[k]) for k in ref)
    steps_lost = len(executed) - len(set(executed))
    # time to resume = fail->re-invocation (supervisor's time_lost_ms)
    # + the restore itself; the replayed steps_lost are priced
    # separately since they run at normal step speed
    stats = resilience.resilience_stats()
    time_to_resume_ms = round(stats["time_lost_ms"]
                              + marks.get("restore_ms", 0.0), 2)
    import jax

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "recovery_time_to_resume",
        "value": time_to_resume_ms,
        "unit": "ms",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "steps_lost": steps_lost,
        "checkpoint_every": ckpt_every,
        "kill_step": kill_step,
        "restore_ms": round(marks.get("restore_ms", 0.0), 2),
        "restarts": stats["restarts"],
        "final_params_bit_identical": bool(bit_identical),
    }))


_LEAVES = {"resnet": _leaf_resnet, "bert": _leaf_bert,
           "serve": _leaf_serve, "serve_decode": _leaf_serve_decode,
           "serve_int8": _leaf_serve_int8,
           "serve_router": _leaf_serve_router,
           "trainer_step": _leaf_trainer_step,
           "whole_step_mp": _leaf_whole_step_mp,
           "input_pipeline": _leaf_input_pipeline,
           "recovery": _leaf_recovery}


# ---------------------------------------------------------------------------
# parent orchestration (never imports jax: a parent that has touched
# jax holds the chip, and a leaf that needs it then fails or hangs)
# ---------------------------------------------------------------------------

LEAF_TIMEOUT_S = 1800  # a cold whole-step compile is minutes, not seconds


def _run(args, timeout, extra_env=None):
    """Run a bench subprocess; returns (rc, stdout, stderr-tail)."""
    env = None
    if extra_env:
        env = dict(os.environ)
        env.update(extra_env)
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + args,
            capture_output=True, text=True, timeout=timeout, cwd=REPO,
            env=env)
        return p.returncode, p.stdout, p.stderr[-2000:]
    except subprocess.TimeoutExpired as e:
        out = e.stdout.decode() if isinstance(e.stdout, bytes) else \
            (e.stdout or "")
        return -1, out, f"timeout after {timeout}s"


def _last_json_line(out):
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _measure(model, platform, failures, extra_env=None):
    """Run one workload leaf ONCE on the platform the caller asked for.
    A leaf that prints no record is a failure with its cause — there is
    no retry on another platform."""
    rc, out, err = _run(["--leaf", platform, "--model", model],
                        timeout=LEAF_TIMEOUT_S, extra_env=extra_env)
    rec = _last_json_line(out) if rc == 0 else None
    if rec is None:
        failures.append(f"{model} {platform} leaf failed (rc={rc}):\n"
                        f"{err.strip() or 'no output'}")
    return rec


def main(platform):
    """``platform`` is what the caller asked for: 'tpu' (the default —
    every leaf fails unless jax comes up on a TPU) or 'cpu' (``--dry``:
    tiny sizes, control flow only, no record kept).  Any failed leaf
    makes the run exit non-zero with the cause."""
    failures = []
    records = {}
    # BERT's MFU carries vs_baseline, so it runs first
    for model in ("bert", "resnet", "serve", "serve_decode",
                  "serve_int8", "serve_router", "trainer_step",
                  "whole_step_mp", "input_pipeline", "recovery"):
        rec = _measure(model, platform, failures)
        if rec is not None:
            records[model] = rec

    if platform == "cpu":
        # a CPU timing says how fast XLA:CPU is; it is never printed
        # under a device metric's name — the dry mode reports only
        # which leaves ran to their end
        print(json.dumps({"dry": True, "platform": "cpu",
                          "leaves_ok": sorted(records),
                          "leaves_failed": len(failures)}))
    else:
        # the Pallas conv+BN+ReLU epilogue path, A/B against the
        # standard ResNet record above
        rec = _measure("resnet", platform, failures,
                       extra_env={"MXTPU_CONV_EPILOGUE": "pallas"})
        if rec is not None:
            rec["metric"] = "resnet50_train_throughput_convfuse"
            rec["conv_epilogue"] = "pallas"
            records["resnet_convfuse"] = rec
        if "bert" in records:
            result = dict(records["bert"])
            result["records"] = records
            print(json.dumps(result))
            _append_history(result)
    if failures:
        sys.exit("bench.py: " + "\n".join(failures))


def _append_history(result):
    """Append this run's full record to BENCH_HISTORY.jsonl (newest
    last; MXTPU_BENCH_HISTORY moves the file) — the trajectory
    tools/bench_diff.py reads to flag per-leaf regressions between
    consecutive runs.  Best-effort: a read-only checkout must not fail
    the bench."""
    path = os.environ.get("MXTPU_BENCH_HISTORY") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_HISTORY.jsonl")
    try:
        entry = dict(result)
        entry["ts"] = time.time()
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError:
        pass


if __name__ == "__main__":
    if "--leaf" in sys.argv:
        plat = sys.argv[sys.argv.index("--leaf") + 1]
        model = sys.argv[sys.argv.index("--model") + 1] \
            if "--model" in sys.argv else "resnet"
        _LEAVES[model](plat)
    else:
        main("cpu" if "--dry" in sys.argv else "tpu")
