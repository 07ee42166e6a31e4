"""Autotuner gate for `make verify` (docs/tuning.md).

Start from a deliberately bad config — 1 MB kvstore buckets,
aggregate_num=1, no pipeline prefetch/overlap, zero batcher linger,
and ONE giant serve bucket (every request padded to batch 1 x len 512)
— then run the closed loop on a real training+serving rehearsal and
hold it to the acceptance bar:

1. the tuner ESCAPES: best/baseline objective ratio past a gated
   margin, with the winning knob moves named;
2. autotuned >= hand-tuned: the registry defaults are measured as a
   first-class reference trial and the recommendation beats-or-ties
   them;
3. the evidence trail is real: every trial landed in the history
   jsonl;
4. the settled config's serving surface is closed: a fresh server
   built FROM the recommendation serves a mixed burst with zero
   post-warmup compiles;
5. geometry feeds the search: a grid derived from the probe burst's
   ServerStats shape histograms joins the serve_buckets domain.

Runs on the CPU backend so the gate is deterministic and fast anywhere.
"""
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, pipeline, profiler, serve, tune  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.tune import (derive_bucket_spec, format_grid,  # noqa: E402
                            parse_grid, reset_tune_stats)

FEAT, BS, N_TRAIN, N_SERVE = 8, 4, 32, 64
FETCH_MS = 4.0          # simulated remote-storage latency per sample
GATE = 1.10             # best/baseline ratio the tuner must clear

#: the knobs the rehearsal searches (a subset keeps wall time modest;
#: the full registry is still validated below)
KNOBS = ["serve_buckets", "serve_linger_ms", "pipeline_prefetch",
         "pipeline_map_inflight", "aggregate_num", "kvstore_bucket_mb"]

BAD_CONFIG = {
    "kvstore_bucket_mb": 1.0,      # tiny buckets: max dispatches
    "aggregate_num": 1,            # sequential optimizer updates
    "pipeline_prefetch": 0,        # no h2d overlap
    "pipeline_map_inflight": 1,    # fetch latency fully serialized
    "serve_linger_ms": 0.0,        # no coalescing window
    "serve_buckets": "1x512",      # one giant bucket: batch 1, pad 512
}


def build_train():
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=FEAT, activation="relu"),
            nn.Dense(1, in_units=16))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, whole_step=True)
    return net, trainer


def build_serve_net():
    mx.random.seed(1)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, flatten=False, in_units=FEAT,
                     activation="relu"),
            nn.Dense(4, flatten=False, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def loss_fn(out, y):
    return (out - y.reshape((-1, 1))) ** 2


def make_train_data():
    rng = np.random.RandomState(0)
    return [(rng.rand(FEAT).astype(np.float32), np.float32(i % 2))
            for i in range(N_TRAIN)]


def make_requests():
    """Heavy-tailed request lengths: mostly short, thin tail to 48."""
    rng = np.random.RandomState(1)
    lens = rng.choice([6, 8, 12, 16, 24, 32, 48], size=N_SERVE,
                      p=[0.30, 0.25, 0.18, 0.12, 0.08, 0.05, 0.02])
    return [rng.rand(int(L), FEAT).astype(np.float32) for L in lens]


def spec_from_grid(grid):
    batches, lengths = parse_grid(grid)
    return serve.BucketSpec(batch_sizes=batches,
                            example_shape=(None, FEAT),
                            lengths=lengths)


def slow_fetch(sample):
    time.sleep(FETCH_MS / 1e3)
    return sample


def serve_burst(srv, requests):
    futs = [srv.submit(x) for x in requests]
    for f in futs:
        f.result(timeout=120)
    return len(futs)


def measure(cfg, train_data, requests, serve_net):
    """One rehearsal window: a pipeline-fed whole-step training burst
    plus a mixed-length serving burst, on freshly built components so
    every env-backed knob actually reaches a constructor.  Warmup
    (XLA compiles) happens OUTSIDE the timed window — the knobs are
    judged on steady-state throughput, and the compile cost they
    induce is accounted separately by the trial runner's recompile
    debit."""
    net, trainer = build_train()
    xw = mx.nd.array(np.zeros((BS, FEAT), np.float32))
    yw = mx.nd.array(np.zeros((BS,), np.float32))
    trainer.whole_step(net, loss_fn, xw, yw)          # warm the step
    pipe = pipeline.Pipeline(train_data).map(
        slow_fetch).batch(BS, last_batch="discard").prefetch_to_device()
    t0 = time.perf_counter()
    n_samples = 0
    for x, y in pipe:
        trainer.whole_step(net, loss_fn, x, y)
        n_samples += BS
    t_train = time.perf_counter() - t0

    srv = serve.ModelServer(serve_net, spec_from_grid(
        cfg["serve_buckets"]), max_queue=2 * N_SERVE)
    srv.start()                                       # AOT warmup
    t1 = time.perf_counter()
    n_served = serve_burst(srv, requests)
    t_serve = time.perf_counter() - t1
    srv.shutdown(drain=True)

    total = t_train + t_serve
    return {"samples_per_s": (n_samples + n_served) / total,
            "train_ms": t_train * 1e3, "serve_ms": t_serve * 1e3}


def main():
    reset_tune_stats()
    reg = tune.default_registry()
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "ENV_VARS.md")) as f:
        doc = f.read()
    reg.validate(documented_env=set(
        w for w in doc.replace("`", " ").replace("|", " ").split()
        if w.startswith("MXTPU_")))

    train_data = make_train_data()
    requests = make_requests()
    serve_net = build_serve_net()

    # -- the deliberately bad starting config -------------------------------
    reg.get("serve_buckets").extend_domain(BAD_CONFIG["serve_buckets"])
    reg.apply(BAD_CONFIG)

    # -- probe burst: observed shapes -> derived grid joins the search -----
    probe = serve.ModelServer(serve_net, spec_from_grid(
        BAD_CONFIG["serve_buckets"]), max_queue=2 * N_SERVE)
    probe.start()
    serve_burst(probe, requests)
    snap = probe.stats()
    probe.shutdown(drain=True)
    assert snap["request_lengths"], "probe recorded no shape stats"
    derived = derive_bucket_spec(snap, (None, FEAT), max_buckets=3)
    derived_grid = format_grid(derived.batch_sizes, derived.lengths)
    reg.get("serve_buckets").extend_domain(derived_grid)

    # -- the closed loop ----------------------------------------------------
    hist = os.path.join(tempfile.mkdtemp(prefix="tune-smoke-"),
                        "TUNE_HISTORY.jsonl")
    runner = tune.TrialRunner(
        reg, lambda cfg: measure(cfg, train_data, requests, serve_net),
        history=hist, seed=0, recompile_penalty=0.001)
    tuner = tune.Tuner(reg, runner=runner, knobs=KNOBS, seed=0,
                       top_k=1)
    rec = tuner.run()
    print(rec.summary())

    failures = []

    def check(name, cond):
        if not cond:
            failures.append(name)

    # 1: escaped the bad config by the gated margin, with real moves
    check(f"ratio {rec.ratio:.3f} >= {GATE}", rec.ratio >= GATE)
    check("tuner moved at least one knob", rec.moved())
    check("no move was silently blocked", rec.blocked_moves == 0)

    # 2: autotuned >= hand-tuned defaults (measured, not assumed)
    refs = [t for t in rec.trials if t["label"] == "ref:defaults"]
    check("defaults measured as a reference trial", len(refs) == 1)
    check("autotuned >= hand-tuned defaults",
          refs and rec.best["score"] >= refs[0]["score"])

    # 3: evidence trail — every trial on disk
    with open(hist) as f:
        lines = [json.loads(line) for line in f]
    check("history holds every trial",
          len(lines) == len(rec.trials) and
          all(r["kind"] == "tune_trial" for r in lines))

    # 4: the settled config's serving surface is closed
    final_grid = reg.get("serve_buckets").read()
    check("winning grid applied to the env surface",
          final_grid == rec.config["serve_buckets"])
    srv = serve.ModelServer(serve_net, spec_from_grid(final_grid),
                            max_queue=2 * N_SERVE)
    srv.start()
    serve_burst(srv, requests)
    s = srv.stats()
    srv.shutdown(drain=True)
    check("zero post-warmup compiles after settling",
          s["graph"]["post_warmup_compiles"] == 0)

    # 5: the tune profiler section saw the whole run
    sec = profiler.sections()["tune"]
    check("tune section counted every trial",
          sec["trials"] == len(rec.trials))
    check("tune section best_over_baseline agrees",
          abs(sec["best_over_baseline"] - rec.ratio) < 1e-9)

    if failures:
        print("TUNE_SMOKE_FAILED:")
        for f in failures:
            print(f"  - {f}")
        sys.exit(1)

    print(f"TUNE_SMOKE_OK trials={len(rec.trials)} "
          f"ratio={rec.ratio:.3f} moved={len(rec.moved())} "
          f"derived_grid={derived_grid} "
          f"final_grid={final_grid} "
          f"recompiles_spent={sec['recompiles_spent']} "
          f"post_warmup_compiles=0")


if __name__ == "__main__":
    main()
