"""mxnet_tpu.telemetry: span tracer schema round-trip, disarmed
zero-overhead contract, flight-recorder crash dumps (injected watchdog
fire + injected SIGTERM via the fault plan), the Prometheus /metrics
endpoint agreeing with profiler.dumps(), and multi-rank aggregate()
machinery on the virtual 8-device mesh (docs/observability.md)."""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.telemetry import flight, metrics, tracer


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Every test starts and ends disarmed with fresh counters."""
    assert not tracer.tracing(), "tracing leaked into this test"
    tracer.reset_telemetry_stats()
    yield
    if tracer.tracing():
        tracer.stop_trace()
    flight.disable()
    assert tracer.span_begin is tracer._noop


# ---------------------------------------------------------------------------
# disarmed contract


def test_disarmed_hooks_are_the_noop_with_zero_overhead():
    for name in ("span_begin", "span_end", "instant", "request_begin",
                 "request_instant", "request_end"):
        assert getattr(tracer, name) is tracer._noop, name
    assert tracer.request_begin("serve.request") is None
    tracer.request_end("serve.request", None)  # rid None: no-op
    fire = tracer.span_begin
    t0 = time.perf_counter()
    for _ in range(100_000):
        fire("trainer.step", "trainer")
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disarmed span hook cost {dt:.3f}s / 100k fires"
    # nothing was recorded anywhere
    assert tracer.telemetry_stats()["spans"] == 0


def test_trace_rearm_guard_and_stop_without_start(tmp_path):
    assert tracer.stop_trace() is None
    with telemetry.trace(str(tmp_path / "t.json")):
        with pytest.raises(MXNetError, match="already armed"):
            tracer.start_trace(str(tmp_path / "t2.json"))


# ---------------------------------------------------------------------------
# chrome-trace schema round-trip


def _validate_chrome_trace(events):
    opens = {}
    pids = set()
    for ev in events:
        for field in ("name", "ph", "pid", "tid"):
            assert field in ev, ev
        if ev["ph"] != "M":
            assert "ts" in ev, ev
        if ev["ph"] == "X":
            assert ev["dur"] > 0
        if ev["ph"] in ("b", "n", "e"):
            assert "id" in ev and "cat" in ev
            key = (ev["cat"], ev["name"], ev["id"])
            if ev["ph"] == "b":
                opens[key] = opens.get(key, 0) + 1
            elif ev["ph"] == "e":
                assert opens.get(key, 0) > 0, f"e without b: {ev}"
                opens[key] -= 1
        pids.add(ev["pid"])
    assert len(pids) == 1
    assert not {k: v for k, v in opens.items() if v}


def test_trace_roundtrip_nested_spans_and_threads(tmp_path):
    path = str(tmp_path / "t.json")
    with telemetry.trace(path):
        with profiler.op_scope("trainer.step", cat="trainer"):
            with profiler.op_scope("allreduce", cat="trainer"):
                pass
            with profiler.op_scope("fused_update", cat="trainer"):
                pass

        def other():
            with profiler.op_scope("pipeline.map", cat="dataPipeline"):
                pass

        th = threading.Thread(target=other, name="worker-lane")
        th.start()
        th.join()
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    _validate_chrome_trace(events)
    by_name = {ev["name"]: ev for ev in events if ev["ph"] == "X"}
    assert set(by_name) == {"trainer.step", "allreduce", "fused_update",
                            "pipeline.map"}
    # nesting: children fall inside the parent's [ts, ts+dur] window
    parent = by_name["trainer.step"]
    for child in ("allreduce", "fused_update"):
        c = by_name[child]
        assert c["ts"] >= parent["ts"]
        assert c["ts"] + c["dur"] <= parent["ts"] + parent["dur"] + 1
        assert c["tid"] == parent["tid"]
    # the worker thread got its own lane + thread_name metadata
    assert by_name["pipeline.map"]["tid"] != parent["tid"]
    lanes = {ev["args"]["name"] for ev in events
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "worker-lane" in lanes
    # counters booked (and window-scoped: a reset dump rewinds them)
    assert json.loads(profiler.dumps(reset=True))["telemetry"][
        "spans"] == 4
    assert json.loads(profiler.dumps())["telemetry"]["spans"] == 0


def test_async_request_spans_cross_thread(tmp_path):
    path = str(tmp_path / "t.json")
    with telemetry.trace(path):
        rid = tracer.request_begin("serve.request", cat="serve",
                                   length=7)
        assert rid is not None

        def resolve():
            tracer.request_instant("serve.dequeue", rid, cat="serve")
            tracer.request_end("serve.request", rid, cat="serve",
                               outcome="served", queue_ms=1.5)

        th = threading.Thread(target=resolve)
        th.start()
        th.join()
        tracer.instant("resilience.retry", cat="resilience",
                       kind="transient")
    events = json.load(open(path))["traceEvents"]
    _validate_chrome_trace(events)
    phases = sorted(ev["ph"] for ev in events if ev.get("cat") == "serve")
    assert phases == ["b", "e", "n"]
    end = next(ev for ev in events if ev["ph"] == "e")
    assert end["args"]["outcome"] == "served"
    inst = next(ev for ev in events if ev["ph"] == "i")
    assert inst["name"] == "resilience.retry" and inst["s"] == "t"


def test_trace_env_var_arming(tmp_path, monkeypatch):
    path = str(tmp_path / "env.trace.json")
    monkeypatch.setenv("MXTPU_TRACE", path)
    telemetry._arm_from_env()
    try:
        assert tracer.tracing()
        with profiler.op_scope("pipeline.wait", cat="dataPipeline"):
            pass
    finally:
        assert tracer.stop_trace() == path
    names = {ev["name"] for ev in json.load(open(path))["traceEvents"]}
    assert "pipeline.wait" in names


def test_lane_cap_drops_are_counted(tmp_path):
    cap = tracer._LANE_CAP
    tracer._LANE_CAP = 8
    try:
        with telemetry.trace(str(tmp_path / "t.json")):
            for i in range(20):
                with profiler.op_scope("pipeline.batch",
                                       cat="dataPipeline"):
                    pass
    finally:
        tracer._LANE_CAP = cap
    stats = tracer.telemetry_stats()
    assert stats["dropped"] > 0
    events = json.load(open(tmp_path / "t.json"))["traceEvents"]
    assert len([e for e in events if e["ph"] == "X"]) <= 8


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_ring_bounded_and_dump_loads(tmp_path):
    flight.enable(size=16, directory=str(tmp_path))
    assert flight.enabled()
    assert tracer.span_begin is not tracer._noop  # ring arms the hooks
    for i in range(50):
        with profiler.op_scope("serve.pad", cat="serve"):
            pass
    path = flight.dump("unit-test", extra={"note": "hi"})
    doc = json.load(open(path))
    assert doc["reason"] == "unit-test"
    assert len(doc["traceEvents"]) == 16          # ring bound held
    assert doc["ring_size"] == 16
    assert doc["extra"]["note"] == "hi"
    assert "telemetry" in doc["counters"]
    _validate_chrome_trace(doc["traceEvents"])
    # a second same-ms dump never overwrites the first
    path2 = flight.dump("unit-test")
    assert path2 != path and os.path.exists(path) \
        and os.path.exists(path2)
    assert tracer.telemetry_stats()["flight_dumps"] == 2
    flight.disable()
    assert flight.dump_if_enabled("nope") is None


def test_flight_dump_on_injected_watchdog_fire(tmp_path):
    """A fault-plan-injected stall past the watchdog window leaves a
    loadable post-mortem with the watchdog diagnostic attached."""
    from mxnet_tpu import resilience

    resilience.reset_resilience_stats()
    plan = resilience.FaultPlan([
        {"site": "train.step", "action": "delay", "on_hit": 1,
         "delay_s": 1.2},
    ], seed=0)
    sup = resilience.Supervisor(manager=None, watchdog_sec=0.3,
                                max_restarts=2,
                                resume_marker=str(tmp_path / "RESUME"))
    calls = []
    flight.enable(directory=str(tmp_path))  # aim dumps at tmp_path

    def train(ctx):
        calls.append(1)
        ctx.step_done(0)      # first attempt: stalls in the fault point
        return "done"

    with resilience.armed(plan):
        assert sup.run(train) == "done"
    assert len(calls) == 2    # stall + clean retry
    dumps = sorted(f for f in os.listdir(tmp_path)
                   if f.startswith("flight-"))
    assert dumps, os.listdir(tmp_path)
    doc = json.load(open(tmp_path / dumps[0]))
    assert doc["reason"] == "watchdog"
    assert "watchdog" in doc["extra"]["diagnostic"]
    assert "counters" in doc


def test_flight_dump_on_injected_sigterm(tmp_path):
    """The PR-1 final-save hook dumps the ring after committing the
    final checkpoint on an injected SIGTERM (kill fault)."""
    from mxnet_tpu import autograd, checkpoint, gluon, resilience
    from mxnet_tpu.gluon import nn

    resilience.reset_resilience_stats()
    ckdir = str(tmp_path / "ck")
    mgr = checkpoint.CheckpointManager(ckdir, keep_n=2)
    sup = resilience.Supervisor(mgr, on_preemption="resume",
                                max_restarts=2)
    plan = resilience.FaultPlan([
        {"site": "train.step", "action": "kill", "match": {"step": 1}},
    ], seed=0)

    def train(ctx):
        mx.random.seed(0)
        np.random.seed(0)
        net = nn.Dense(1, in_units=3)
        net.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        start = 0
        if ctx.manager.latest() is not None:
            start = ctx.manager.restore(params=net,
                                        trainer=trainer)["step"] + 1
        ctx.set_preemption_state(lambda: dict(params=net,
                                              trainer=trainer))
        x = mx.nd.array(np.ones((2, 3), np.float32))
        for step in range(start, 3):
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            trainer.step(2)
            ctx.step_done(step)
        return "ok"

    with resilience.armed(plan):
        assert sup.run(train) == "ok"
    dumps = [f for f in os.listdir(ckdir) if f.startswith("flight-")]
    assert dumps, os.listdir(ckdir)
    doc = json.load(open(os.path.join(ckdir, dumps[0])))
    assert doc["reason"] == "sigterm"
    assert not flight.enabled()   # supervisor exit disarmed the ring


# ---------------------------------------------------------------------------
# profiler section registry


def test_section_registry_window_scoping_and_table():
    counters = {"hits": 3}

    profiler.register_section(
        "customSection", lambda: dict(counters),
        lambda: counters.update(hits=0),
        lambda s: ["Custom:", f"hits {s['hits']}"])
    try:
        assert "customSection" in profiler.section_names()
        # the registry, not the owner, zeroes the section it has read
        d = json.loads(profiler.dumps(reset=True))
        assert d["customSection"] == {"hits": 3}
        assert json.loads(profiler.dumps())["customSection"] == \
            {"hits": 0}
        counters["hits"] = 5
        profiler.set_config(aggregate_stats=True)
        assert "hits 5" in profiler.dumps(format="table", reset=True)
        table = profiler.dumps(format="table")
        assert "Custom:" in table and "hits 0" in table
    finally:
        profiler.unregister_section("customSection")
        profiler.set_config(aggregate_stats=False)
    assert "customSection" not in json.loads(profiler.dumps())


def _flash_row(fa):
    fa._built[("resident", "fwd", (1, 1, 128, 128, 64), "bfloat16", 1,
               (1, 1), 1, None)] += 1


def _dp_record(dp):
    dp._step_log.append((0, 1, time.perf_counter_ns(), 1000, 1000, 1000, 8))


# (section, the module that owns its counters and registers it, the
# keys it had when profiler.py still named every owner, one of them
# counted up by `bump(owner)`): None where only a live trainer counts
SECTIONS = [
    ("cachedGraph", "gluon.block", "compiles reuses", "compiles",
     lambda m: m._graph_stats.update(compiles=m._graph_stats["compiles"] + 1)),
    ("ctrl", "serve.control_plane",
     "blocked_bounds blocked_cooldown load replicas retired rpc_errors "
     "rpc_requests rpc_streams scale_downs scale_ups spawn_failures spawns "
     "stale_leases_rejected ticks", "ticks",
     lambda m: m._sec_bump(ticks=1)),
    ("dataParallelStep", "parallel.data_parallel",
     "args_ms builds enqueue_ms put_bytes put_ms remat_children remat_saves "
     "steps", "steps", _dp_record),
    ("dataPipeline", "pipeline.stats",
     "batches h2d_ms host_build_ms prefetch_hits prefetch_misses wait_ms",
     "batches", lambda m: m.add("batches", 1)),
    ("decodeServe", "serve.decode",
     "accept_rate admitted cow_copies draft_steps expired_deadlines finished "
     "pages_in_flight prefill_batches prefix_hit_pages slot_occupancy "
     "spec_accepted spec_proposed steps tokens", "tokens",
     lambda m: m._sec_bump(tokens=1)),
    ("flashAttention", "ops.pallas.flash_attention",
     "built grouped kernels resident residual_bytes residual_pairs "
     "residuals_named streamed", "kernels", _flash_row),
    ("health", "telemetry.health",
     "alerts checkpoint_ms collective_ms compile_ms compute_ms "
     "flops_per_step goodput h2d_ms input_wait_ms lost_ms mfu optimizer_ms "
     "rules_firing step_ms step_p95_ms steps stragglers ticks", "ticks",
     lambda m: m._counters.update(ticks=m._counters["ticks"] + 1)),
    ("linearAttention", "ops.linear_attention",
     "chunk chunks_per_sequence kernel_traces layers state_bytes_kept traces "
     "xla_traces", "layers",
     lambda m: m._traced.update({(1, 2, 128, 16, 16, "bfloat16"): 1})),
    ("moeRouting", "models.decoder_lm",
     "capacity capacity_share layers max_over_mean rows_here rows_per_expert "
     "share_here", "layers",
     None),
    ("quantize", "contrib.quantization",
     "calib_batches calib_ms int8_serve_batches layers_quantized "
     "requant_folds", "requant_folds",
     lambda m: m._sec_bump(requant_folds=1)),
    ("resilience", "resilience.stats",
     "fallback_restores ranks_lost reshard_ms resizes restarts retries "
     "time_lost_ms watchdog_fires", "restarts",
     lambda m: m.add("restarts")),
    ("router", "serve.router",
     "dispatched evictions hedge_wins hedges probe_failures probes reloads "
     "replacements retries", "dispatched",
     lambda m: m._sec_bump(dispatched=1)),
    ("telemetry", "telemetry.tracer",
     "aggregations dropped flight_dumps instants requests scrapes spans",
     "scrapes", lambda m: m.bump("scrapes")),
    ("trainerStep", "gluon.trainer",
     "buckets_built dispatches dispatches_per_step params_fused spmd_steps "
     "steps whole_step_compiles whole_step_fallbacks whole_step_steps "
     "zero_fallbacks zero_steps", "steps",
     lambda m: m._step_stats.update(steps=m._step_stats["steps"] + 1)),
    ("tune", "tune",
     "baseline_score best_over_baseline best_score blocked_moves "
     "candidates_ranked knobs_moved measurements recompiles_spent trials",
     "trials", lambda m: m.trials._counters.update(
         trials=m.trials._counters["trials"] + 1)),
]


@pytest.mark.parametrize("name,owner,keys,counted,bump", SECTIONS,
                         ids=[row[0] for row in SECTIONS])
def test_registered_sections_cover_all_subsystems(name, owner, keys, counted,
                                                  bump, monkeypatch):
    """Importing the module that owns a section's counters registers
    the section, with the keys it always had, and a reset read zeroes
    it: the registry calls the owner's reset, no owner decides."""
    import importlib

    from mxnet_tpu.telemetry import health

    # `health` says nothing until a monitor has been armed
    monkeypatch.setattr(health, "_ever_armed", True)
    module = importlib.import_module("mxnet_tpu." + owner)
    assert name in profiler.section_names()
    if bump is not None:
        bump(module)
    before = profiler.sections()[name]
    assert sorted(before) == sorted(keys.split())
    if bump is not None:
        assert before[counted] >= 1
    assert profiler.sections(reset=True)[name] == before
    after = profiler.sections().get(name)
    assert after is None or after[counted] == 0, after
    assert json.loads(profiler.dumps())[name].keys() == before.keys()


def test_profiler_names_no_subsystem():
    """profiler.py is below every module that owns a section: it looks
    none of them up, and imports `base`, `storage` and `telemetry`
    alone of the package."""
    import re

    source = open(profiler.__file__).read()
    assert "sys.modules" not in source
    assert not re.search(r"def \w+_counters\(", source)
    imported = set(re.findall(r"^\s*from \.(\w*) import", source, re.M))
    assert imported <= {"base", "storage", "telemetry"}, imported
    assert "__package__" not in source and "import_module" not in source


# ---------------------------------------------------------------------------
# metrics registry + endpoint


def test_metrics_registry_render_format():
    reg = metrics.Registry()
    c = reg.counter("mxtpu_test_total", "a counter")
    c.inc(2, kind="a")
    c.inc(3, kind='b"quoted')
    g = reg.gauge("mxtpu_test_gauge")
    g.set(1.5)
    h = reg.histogram("mxtpu_test_ms", "a histogram",
                      buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.render()
    assert '# TYPE mxtpu_test_total counter' in text
    assert 'mxtpu_test_total{kind="a"} 2' in text
    assert '\\"quoted' in text
    assert 'mxtpu_test_gauge 1.5' in text
    assert 'mxtpu_test_ms_bucket{le="1"} 1' in text
    assert 'mxtpu_test_ms_bucket{le="10"} 2' in text
    assert 'mxtpu_test_ms_bucket{le="+Inf"} 3' in text
    assert 'mxtpu_test_ms_sum 105.5' in text
    assert 'mxtpu_test_ms_count 3' in text
    with pytest.raises(MXNetError, match="only go up"):
        c.inc(-1)
    with pytest.raises(MXNetError, match="invalid metric name"):
        reg.counter("bad name")
    with pytest.raises(MXNetError, match="already registered"):
        reg.gauge("mxtpu_test_total")


def test_metrics_endpoint_scrape_agrees_with_dumps():
    with profiler.op_scope("checkpoint.restore", cat="checkpoint"):
        pass
    srv = telemetry.MetricsServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        values = {}
        for line in body.splitlines():
            assert line, "blank line in exposition output"
            if line.startswith("#"):
                assert line.split()[1] in ("HELP", "TYPE"), line
                continue
            name, value = line.rsplit(" ", 1)
            values[name] = float(value.replace("+Inf", "inf"))
        d = json.loads(profiler.dumps())
        for key in ("spans", "instants", "flight_dumps"):
            assert values[f"mxtpu_telemetry_{key}"] == \
                d["telemetry"][key], key
        assert values["mxtpu_metrics_scrapes_total"] >= 1
        health = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=10).read())
        assert health["status"] == "ok" and health["pid"] == os.getpid()
        code = urllib.request.urlopen(base + "/metrics").status
        assert code == 200
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")
    finally:
        srv.stop()


def test_metrics_server_singleton_lifecycle():
    s1 = telemetry.start_metrics_server(port=0)
    try:
        assert telemetry.start_metrics_server(port=0) is s1
        assert telemetry.metrics_server() is s1
    finally:
        telemetry.stop_metrics_server()
    assert telemetry.metrics_server() is None


# ---------------------------------------------------------------------------
# aggregate()


def test_aggregate_single_process_agrees_with_sections():
    agg = telemetry.aggregate()
    assert agg["world_size"] == 1 and agg["rank"] == 0
    assert agg["ranks"][0]["telemetry"].keys() == \
        telemetry.sections()["telemetry"].keys()
    assert json.loads(profiler.dumps())["telemetry"][
        "aggregations"] >= 1


def test_allgather_bytes_single_process_identity():
    from mxnet_tpu.parallel import dist

    assert dist.allgather_bytes(b"abc") == [b"abc"]


def test_allgather_rows_multichip_mesh():
    """The exact gather/replication path a multi-process aggregate()
    runs, driven on the virtual 8-device mesh with every rank's shard
    supplied locally (dryrun_multichip)."""
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import dist

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    mesh = Mesh(np.array(devs[:8]), ("world",))
    rows = [np.full(4, i, np.int32) for i in range(8)]
    out = dist._allgather_rows(mesh, 8, 0, None, _local_rows=rows)
    assert out.shape == (8, 4)
    assert all((out[i] == i).all() for i in range(8))


def test_allgather_bytes_multichip_varlen_payloads():
    """Variable-length padding + length exchange, end to end on the
    8-device mesh — distinct JSON snapshots per 'rank' survive the
    uint8 pad/trim round-trip byte-exactly."""
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import dist

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    mesh = Mesh(np.array(devs[:8]), ("world",))
    payloads = [json.dumps({"rank": i, "pad": "x" * (3 * i)}).encode()
                for i in range(8)]
    got = dist._allgather_bytes_impl(mesh, 8, 0, None,
                                     _all_payloads=payloads)
    assert got == payloads
    assert [json.loads(p)["rank"] for p in got] == list(range(8))


# ---------------------------------------------------------------------------
# review-pass regressions


def test_supervisor_auto_enable_rides_a_manual_ring(tmp_path):
    """A ring armed before the supervised run keeps its size,
    directory and post-run lifetime — auto_enable only refcounts."""
    flight.enable(size=4096, directory=str(tmp_path))
    token = flight.auto_enable(directory="/somewhere/else")
    assert token == "riding"
    assert tracer.flight_ring().maxlen == 4096    # not shrunk to 512
    flight.auto_disable(token)
    assert flight.enabled()                        # not disarmed
    assert flight._directory == str(tmp_path)
    # and the supervisor-owned lifecycle still disarms what IT armed
    flight.disable()
    token = flight.auto_enable(directory=str(tmp_path))
    assert token == "armed"
    flight.auto_disable(token)
    assert not flight.enabled()


def test_stop_trace_releases_lane_buffers(tmp_path):
    with telemetry.trace(str(tmp_path / "t.json")):
        for _ in range(32):
            with profiler.op_scope("serve.pad", cat="serve"):
                pass
    assert all(not lane["events"] for lane in tracer._lanes)


def test_span_begun_in_one_session_never_closes_in_another(tmp_path):
    """Arm/disarm mid-span must drop the span, not emit a phantom one
    whose duration reaches back into the previous trace session."""
    scope = profiler.op_scope("checkpoint.restore", cat="checkpoint")
    tracer.start_trace(str(tmp_path / "a.json"))
    scope.__enter__()            # begun under session A
    tracer.stop_trace()
    tracer.start_trace(str(tmp_path / "b.json"))
    scope.__exit__(None, None, None)   # ends under session B: dropped
    with profiler.op_scope("checkpoint.restore", cat="checkpoint"):
        pass                     # a real same-name span still records
    tracer.stop_trace()
    events = [e for e in json.load(open(tmp_path / "b.json"))
              ["traceEvents"] if e["ph"] == "X"]
    assert len(events) == 1 and events[0]["dur"] < 1e6, events
