"""telemetry.health: disarmed zero-overhead contract, per-step phase
breakdown from the scope sink, goodput debits, whole-step MFU via jax
cost analysis, SLO rule evaluation + /healthz flip, cross-rank
straggler detection fed by an injected dist.allreduce delay fault on
one virtual rank, multi-rank aggregate() merge of health sections on
the 8-device mesh, watchdog-diagnostic enrichment, and the bench
trajectory differ (docs/observability.md, "Health monitor")."""
import json
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, profiler, resilience, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import health
from mxnet_tpu.telemetry.health import HealthMonitor, SLORule


@pytest.fixture(autouse=True)
def _health_clean():
    """Every test starts and ends disarmed with a fresh window."""
    mon = health.active_monitor()
    if mon is not None:
        mon.disarm()
    health.reset_health_stats()
    health._reset_learned_flops()
    yield
    mon = health.active_monitor()
    if mon is not None:
        mon.disarm()
    health.reset_health_stats()
    health._reset_learned_flops()
    assert health.scope_end is health._noop


FEAT, BS = 4, 4


def _build_model(kvstore=None, whole_step=False):
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=FEAT, activation="relu"),
            nn.Dense(1, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    kwargs = {}
    if kvstore is not None:
        # dist_sync + local update keeps the dist.allreduce fault
        # point on the step path in one process (chaos-smoke idiom)
        kwargs = dict(kvstore=kvstore, update_on_kvstore=False)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05},
                            whole_step=whole_step, **kwargs)
    return net, trainer


def _train_steps(net, trainer, n=3):
    from mxnet_tpu import autograd

    x = mx.nd.array(np.random.rand(BS, FEAT).astype(np.float32))
    y = mx.nd.array(np.random.rand(BS).astype(np.float32))
    for _ in range(n):
        with autograd.record():
            loss = ((net(x) - y.reshape((-1, 1))) ** 2).sum()
        loss.backward()
        trainer.step(BS)


# ---------------------------------------------------------------------------
# disarmed contract


def test_disarmed_hooks_are_the_noop_with_zero_overhead():
    for name in ("scope_end", "note_whole_step",
                 "note_whole_step_compiled"):
        assert getattr(health, name) is health._noop, name
    fire = health.scope_end
    t0 = time.perf_counter()
    for _ in range(100_000):
        fire("trainer.step", "trainer", 0.0, 1.0)
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disarmed health hook cost {dt:.3f}s / 100k fires"
    # nothing accumulated, and the section stays absent until an arm
    assert health.health_stats() is None
    assert "health" not in json.loads(profiler.dumps())


def test_single_armed_monitor_owns_the_hooks():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        assert health.active_monitor() is mon
        assert health.scope_end is health._scope_end
        with pytest.raises(MXNetError, match="already armed"):
            HealthMonitor(tick_sec=0).arm()
    finally:
        mon.disarm()
    assert health.active_monitor() is None
    assert health.scope_end is health._noop


# ---------------------------------------------------------------------------
# phase breakdown


def test_scope_sink_books_phases_and_steps():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        with profiler.op_scope("trainer.step", cat="trainer"):
            with profiler.op_scope("allreduce", cat="trainer"):
                time.sleep(0.02)
            with profiler.op_scope("fused_update", cat="trainer"):
                time.sleep(0.01)
        with profiler.op_scope("checkpoint.save.commit",
                               cat="checkpoint"):
            time.sleep(0.005)
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["steps"] == 1
    ph = w["phases"]
    assert ph["collective_ms"] >= 15.0
    assert ph["optimizer_ms"] >= 7.0
    assert ph["checkpoint_ms"] >= 3.0
    # compute = step minus instrumented children
    assert 0.0 <= ph["compute_ms"] < w["step_ms"]
    assert w["step_ms"] >= ph["collective_ms"] + ph["optimizer_ms"]
    # the section carries the same accumulation for aggregate()
    sec = profiler.sections()["health"]
    assert sec["steps"] == 1 and sec["collective_ms"] >= 15.0


def test_aborted_scope_books_no_phase_time():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        with pytest.raises(RuntimeError):
            with profiler.op_scope("trainer.step", cat="trainer"):
                raise RuntimeError("boom")
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["steps"] == 0 and w["step_ms"] == 0.0


def test_real_training_steps_feed_the_breakdown():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        net, trainer = _build_model()
        _train_steps(net, trainer, n=4)
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["steps"] == 4
    assert w["step_ms"] > 0
    assert w["phases"]["optimizer_ms"] > 0      # fused_update scopes
    assert w["goodput"] is not None and 0 < w["goodput"] <= 1.0
    assert w["step_p95_ms"] > 0


def test_health_section_window_scoping():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        with profiler.op_scope("trainer.step", cat="trainer"):
            pass
        mon.tick()
        assert json.loads(profiler.dumps(reset=True))["health"][
            "steps"] == 1
        # the reset dump started a fresh window
        assert json.loads(profiler.dumps())["health"]["steps"] == 0
    finally:
        mon.disarm()


def test_ticker_thread_closes_windows():
    mon = HealthMonitor(tick_sec=0.05, flight_on_breach=False).arm()
    try:
        with profiler.op_scope("trainer.step", cat="trainer"):
            time.sleep(0.002)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            sec = profiler.sections()["health"]
            if sec["ticks"] >= 2 and mon.snapshot() is not None:
                break
            time.sleep(0.02)
        assert sec["ticks"] >= 2, sec
        assert mon.snapshot()["status"] == "ok"
    finally:
        mon.disarm()
    assert mon._thread is None


# ---------------------------------------------------------------------------
# goodput


def test_goodput_debits_injected_recovery_time():
    from mxnet_tpu.resilience import stats as rstats

    mon = HealthMonitor(tick_sec=0).arm()
    try:
        mon.tick()                       # open a fresh window
        with profiler.op_scope("trainer.step", cat="trainer"):
            time.sleep(0.005)
        rstats.add("time_lost_ms", 123.0)   # an injected restart debit
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["lost_ms"] >= 123.0
    assert w["goodput"] is not None and w["goodput"] < 1.0
    assert profiler.sections()["health"]["lost_ms"] >= 123.0


def test_goodput_none_without_steps():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["goodput"] is None and w["steps"] == 0


# ---------------------------------------------------------------------------
# MFU (whole-step path)


def test_whole_step_reports_mfu_from_cost_analysis():
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        net, trainer = _build_model(whole_step=True)

        def loss_fn(out, y):
            return (out - y.reshape((-1, 1))) ** 2

        x = mx.nd.array(np.random.rand(BS, FEAT).astype(np.float32))
        y = mx.nd.array(np.random.rand(BS).astype(np.float32))
        for _ in range(4):
            trainer.whole_step(net, loss_fn, x, y)
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["steps"] == 4
    assert w["flops_per_step"] > 0
    assert w["flops_source"] == "cost_analysis"
    assert w["mfu"] is not None and w["mfu"] > 0
    sec = profiler.sections()["health"]
    assert sec["flops_per_step"] == w["flops_per_step"]


def test_analytic_flop_fallback_and_peak_override(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_PEAK_FLOPS", "1e9")
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        assert mon.peak_flops == 1e9
        net, trainer = _build_model()
        # drive the analytic fallback directly (no compiled whole step)
        health.note_whole_step(trainer, BS)
        elems = sum(int(np.prod(p.shape)) for p in trainer._params)
        with profiler.op_scope("trainer.step", cat="trainer"):
            time.sleep(0.002)
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["flops_per_step"] == 6 * elems * BS
    assert w["flops_source"] == "analytic"
    assert w["mfu"] is not None and w["mfu"] > 0


def test_learned_flops_survive_window_reset():
    """The cost-analysis FLOP count only lands on a FRESH compile, so
    a routine dumps(reset=True) must not downgrade later MFU windows
    to the analytic guess (review-pass regression)."""
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        net, trainer = _build_model(whole_step=True)

        def loss_fn(out, y):
            return (out - y.reshape((-1, 1))) ** 2

        x = mx.nd.array(np.random.rand(BS, FEAT).astype(np.float32))
        y = mx.nd.array(np.random.rand(BS).astype(np.float32))
        trainer.whole_step(net, loss_fn, x, y)
        flops = profiler.sections()["health"]["flops_per_step"]
        assert flops > 0
        profiler.dumps(reset=True)              # window rewind
        trainer.whole_step(net, loss_fn, x, y)  # steady: no recompile
        w = mon.tick()
    finally:
        mon.disarm()
    assert w["flops_per_step"] == flops
    assert w["flops_source"] == "cost_analysis"
    assert w["mfu"] is not None and w["mfu"] > 0


# ---------------------------------------------------------------------------
# SLO rules + /healthz


def test_slo_rule_validation():
    with pytest.raises(MXNetError, match="needs a bound"):
        SLORule("r", "goodput")
    with pytest.raises(MXNetError, match="duplicate"):
        HealthMonitor(tick_sec=0, rules=[
            SLORule("r", "goodput", below=0.5),
            SLORule("r", "mfu", below=0.5)])


def test_slo_rule_fires_clears_and_flips_healthz():
    from mxnet_tpu.pipeline import stats as pstats

    mon = HealthMonitor(tick_sec=0, rules=[
        SLORule("input_starvation", "input_starvation", above=0.5)],
        flight_on_breach=False).arm()
    try:
        # healthy window: steps, no input wait
        with profiler.op_scope("trainer.step", cat="trainer"):
            time.sleep(0.002)
        w = mon.tick()
        assert w["status"] == "ok" and not w["firing"]
        assert health.healthz()["status"] == "ok"
        # starved window: wait dominates
        with profiler.op_scope("trainer.step", cat="trainer"):
            time.sleep(0.001)
        pstats.add("wait_ms", 500.0)
        w = mon.tick()
        assert w["status"] == "degraded"
        assert "input_starvation" in w["firing"]
        hz = health.healthz()
        assert hz["status"] == "degraded"
        assert "input_starvation" in hz["rules"]
        assert profiler.sections()["health"]["alerts"] == 1
        assert profiler.sections()["health"]["rules_firing"] == 1
        # recovered window: back to ok, alert does not re-fire
        with profiler.op_scope("trainer.step", cat="trainer"):
            time.sleep(0.002)
        w = mon.tick()
        assert w["status"] == "ok" and not w["firing"]
        assert health.healthz()["status"] == "ok"
        assert profiler.sections()["health"]["alerts"] == 1
    finally:
        mon.disarm()
    # disarmed: /healthz payload reverts to plain liveness
    assert health.healthz() is None


def test_slo_alert_emits_instant_span(tmp_path):
    path = str(tmp_path / "alerts.trace.json")
    mon = HealthMonitor(tick_sec=0, rules=[
        SLORule("floor", "goodput", below=0.99)],
        flight_on_breach=False).arm()
    try:
        with telemetry.trace(path):
            with profiler.op_scope("trainer.step", cat="trainer"):
                time.sleep(0.001)
            time.sleep(0.02)      # wall >> step: goodput under floor
            mon.tick()
    finally:
        mon.disarm()
    events = json.load(open(path))["traceEvents"]
    alerts = [e for e in events if e.get("name") == "telemetry.alert"]
    assert alerts and alerts[0]["args"]["rule"] == "floor"
    assert alerts[0]["args"]["state"] == "firing"


def test_rule_for_ticks_debounce():
    from mxnet_tpu.pipeline import stats as pstats

    mon = HealthMonitor(tick_sec=0, rules=[
        SLORule("starve", "input_starvation", above=0.5, for_ticks=2)],
        flight_on_breach=False).arm()
    try:
        for i in range(2):
            with profiler.op_scope("trainer.step", cat="trainer"):
                time.sleep(0.001)
            pstats.add("wait_ms", 300.0)
            w = mon.tick()
            if i == 0:
                assert not w["firing"], "fired before for_ticks windows"
        assert "starve" in w["firing"]
    finally:
        mon.disarm()


def test_watched_source_signals_router_shaped():
    lost = {"v": 0.0}
    mon = HealthMonitor(tick_sec=0, rules=[
        SLORule("lost", "pool.requests_lost", above=0.0),
        SLORule("p99", "pool.latency.p99_ms", above=50.0)],
        flight_on_breach=False)
    mon.watch("pool", lambda: {"requests_lost": lost["v"],
                               "latency": {"p99_ms": 12.0}})
    mon.arm()
    try:
        w = mon.tick()
        assert not w["firing"]
        lost["v"] = 2.0
        w = mon.tick()
        assert "lost" in w["firing"]
        assert w["firing"]["lost"]["value"] == 2.0
        assert "p99" not in w["firing"]
    finally:
        mon.disarm()


def test_healthz_endpoint_flips_with_monitor(monkeypatch):
    from mxnet_tpu.pipeline import stats as pstats
    from mxnet_tpu.telemetry.httpd import MetricsServer

    srv = MetricsServer(port=0).start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=30) as r:
                return r.status, r.read().decode()

        code, body = get("/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        assert "rules" not in json.loads(body)   # plain liveness

        mon = HealthMonitor(tick_sec=0, rules=[
            SLORule("starve", "input_starvation", above=0.5)],
            flight_on_breach=False).arm()
        try:
            with profiler.op_scope("trainer.step", cat="trainer"):
                time.sleep(0.001)
            pstats.add("wait_ms", 400.0)
            mon.tick()
            code, body = get("/healthz")
            payload = json.loads(body)
            assert code == 200 and payload["status"] == "degraded"
            assert "starve" in payload["rules"]
            # scrape agrees with the section (mxtpu_health_* gauges)
            _, scrape = get("/metrics")
            sec = profiler.sections()["health"]
            for line in scrape.splitlines():
                if line.startswith("mxtpu_health_alerts "):
                    assert float(line.split()[-1]) == sec["alerts"]
                    break
            else:
                raise AssertionError("mxtpu_health_alerts not scraped")
            # recovery flips it back
            with profiler.op_scope("trainer.step", cat="trainer"):
                time.sleep(0.002)
            mon.tick()
            code, body = get("/healthz")
            assert json.loads(body)["status"] == "ok"
        finally:
            mon.disarm()
        code, body = get("/healthz")
        payload = json.loads(body)
        assert payload["status"] == "ok" and "rules" not in payload
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# straggler detection


def _virtual_rank_windows(n_ranks, straggler, windows, delay_s=0.05):
    """Run ``windows`` rounds of real training per virtual rank, with a
    dist.allreduce DELAY fault armed only on the straggler rank, and
    return per-window per-rank CUMULATIVE health+dataPipeline section
    dicts (what each rank's aggregate() snapshot would carry)."""
    totals = [{} for _ in range(n_ranks)]
    feeds = []
    nets = [_build_model(kvstore="dist_sync") for _ in range(n_ranks)]
    for _w in range(windows):
        for r in range(n_ranks):
            net, trainer = nets[r]
            before = dict(profiler.sections()["health"])
            if r == straggler:
                resilience.install_plan(resilience.FaultPlan([
                    {"site": "dist.allreduce", "action": "delay",
                     "delay_s": delay_s, "times": None}], seed=0))
            try:
                _train_steps(net, trainer, n=2)
            finally:
                if r == straggler:
                    resilience.clear_plan()
            after = profiler.sections()["health"]
            for k, v in after.items():
                if isinstance(v, (int, float)):
                    d = v - before.get(k, 0)
                    totals[r][k] = totals[r].get(k, 0) + max(d, 0)
        feeds.append([{"health": dict(t), "dataPipeline": {}}
                      for t in totals])
    return feeds


def test_straggler_named_rank_and_phase_within_k_ticks():
    """The satellite gate: a dist.allreduce delay fault on ONE virtual
    rank makes the monitor name that rank and the collective phase
    within K ticks."""
    mon = HealthMonitor(tick_sec=0, straggler_ratio=1.5,
                        straggler_ticks=2,
                        flight_on_breach=False)
    feeds = {"i": 0, "data": None}

    def fake_aggregate():
        w = feeds["data"][min(feeds["i"], len(feeds["data"]) - 1)]
        return {"world_size": len(w), "rank": 0, "ranks": w}

    mon._aggregate_fn = fake_aggregate
    mon.arm()
    try:
        feeds["data"] = _virtual_rank_windows(
            n_ranks=4, straggler=2, windows=4)
        named_at = None
        for i in range(4):
            feeds["i"] = i
            w = mon.tick()
            if w["stragglers"]:
                named_at = i + 1
                break
        # K=2 consecutive windows is the earliest possible flag; one
        # grace window absorbs scheduler noise on a loaded 2-vCPU box
        assert named_at is not None and named_at <= 3, \
            "straggler not named within K=2 ticks (+1 grace)"
        s = w["stragglers"][0]
        assert s["rank"] == 2, s
        assert s["phase"] == "collective", s
        assert s["ratio"] > 1.5
        assert w["status"] == "degraded"
        state, names = mon.status()
        assert state == "degraded" and "rank 2" in names[0]
        assert profiler.sections()["health"]["stragglers"] == 1
    finally:
        mon.disarm()


def test_straggler_clears_when_the_pool_evens_out():
    mon = HealthMonitor(tick_sec=0, straggler_ratio=1.5,
                        straggler_ticks=1, flight_on_breach=False)
    ranks = [{"health": {"steps": 2, "step_ms": 10.0,
                         "collective_ms": 2.0, "optimizer_ms": 1.0,
                         "checkpoint_ms": 0.0}, "dataPipeline": {}}
             for _ in range(4)]
    slow = {"health": {"steps": 2, "step_ms": 100.0,
                       "collective_ms": 80.0, "optimizer_ms": 1.0,
                       "checkpoint_ms": 0.0}, "dataPipeline": {}}
    feed = {"ranks": [slow] + ranks[1:]}
    mon._aggregate_fn = lambda: {"world_size": 4, "rank": 0,
                                 "ranks": feed["ranks"]}
    mon.arm()
    try:
        w = mon.tick()
        assert w["stragglers"] and w["stragglers"][0]["rank"] == 0
        # next window: every rank advances evenly -> flag clears
        feed["ranks"] = [
            {"health": {"steps": r["health"]["steps"] + 2,
                        "step_ms": r["health"]["step_ms"] + 10.0,
                        "collective_ms":
                            r["health"]["collective_ms"] + 2.0,
                        "optimizer_ms": 1.0, "checkpoint_ms": 0.0},
             "dataPipeline": {}}
            for r in ([slow] + ranks[1:])]
        w = mon.tick()
        assert not w["stragglers"] and w["status"] == "ok"
    finally:
        mon.disarm()


def test_single_rank_pool_never_flags():
    mon = HealthMonitor(tick_sec=0, flight_on_breach=False)
    mon._aggregate_fn = lambda: {"world_size": 1, "rank": 0, "ranks": [
        {"health": {"steps": 1, "step_ms": 100.0}}]}
    mon.arm()
    try:
        assert mon.tick()["stragglers"] == []
    finally:
        mon.disarm()


def test_aggregate_merges_health_sections_on_the_8_device_mesh():
    """Multi-rank aggregate() merge of per-rank health sections driven
    on the virtual 8-device mesh (the _allgather_bytes_impl seam —
    the exact path a multi-process aggregate() runs)."""
    import jax
    from jax.sharding import Mesh

    from mxnet_tpu.parallel import dist

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    mon = HealthMonitor(tick_sec=0).arm()
    try:
        with profiler.op_scope("trainer.step", cat="trainer"):
            pass
        base = profiler.sections()
        assert "health" in base
        mesh = Mesh(np.array(devs[:8]), ("world",))
        payloads = []
        for r in range(8):
            secs = json.loads(json.dumps(base))
            secs["health"]["collective_ms"] = 10.0 * (r + 1)
            payloads.append(json.dumps(secs, sort_keys=True).encode())
        got = dist._allgather_bytes_impl(mesh, 8, 0, None,
                                         _all_payloads=payloads)
        ranks = [json.loads(p.decode()) for p in got]
        assert len(ranks) == 8
        assert [r["health"]["collective_ms"] for r in ranks] == \
            [10.0 * (i + 1) for i in range(8)]
        # and the monitor consumes exactly this shape
        mon._aggregate_fn = lambda: {"world_size": 8, "rank": 0,
                                     "ranks": ranks}
        assert mon.tick()["stragglers"] == []   # one window: no rates
    finally:
        mon.disarm()


# ---------------------------------------------------------------------------
# watchdog diagnostic enrichment


def test_watchdog_diagnostic_includes_health_snapshot():
    sup = resilience.Supervisor(watchdog_sec=1.0)
    assert "Last health window" not in sup._diagnose(1.0)
    mon = HealthMonitor(tick_sec=0, rules=[
        SLORule("starve", "input_starvation", above=0.5)],
        flight_on_breach=False).arm()
    try:
        from mxnet_tpu.pipeline import stats as pstats

        with profiler.op_scope("trainer.step", cat="trainer"):
            with profiler.op_scope("allreduce", cat="trainer"):
                time.sleep(0.002)
        pstats.add("wait_ms", 400.0)
        mon.tick()
        diag = sup._diagnose(1.0)
        assert "Last health window" in diag
        assert "collective=" in diag
        assert "firing SLO rules: starve" in diag
    finally:
        mon.disarm()
    # disarmed: the diagnostic stays the plain scope report
    assert "Last health window" not in sup._diagnose(1.0)
