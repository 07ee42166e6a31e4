"""Tracing inside `parallel.DataParallelTrainer`: `op_scope` spans on
the host plane of a jax.profiler trace, the always-on step log and the
`dataParallelStep` section summed from it, the phase names in the
compiled step (and that a persistent cache filled by an unscoped build
cannot hide them), and what all of it costs with nothing armed
(docs/observability.md, "Device trace")."""
import collections
import contextlib
import glob
import json
import os
import statistics
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, gluon, profiler, telemetry
from mxnet_tpu.parallel import data_parallel as dp
from mxnet_tpu.telemetry import tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_SPANS = ("dp.step", "dp.step.put", "dp.step.args", "dp.step.enqueue")


def _batch(n=16):
    rng = np.random.RandomState(3)
    return (rng.rand(n, 10).astype("float32"),
            rng.randint(0, 4, (n,)).astype("float32"))


def _trainer(optimizer="adamw", **kwargs):
    mx.random.seed(5)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return dp.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
        {"learning_rate": 1e-2}, **kwargs)


def _host_events(trace_dir):
    """[(name, {stat: value})] of every host-plane event of the trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, dict(e.stats)) for e in line.events]
    return events


@contextlib.contextmanager
def _profiler_session(trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# -- spans on the profiler's host plane ---------------------------------------

def test_build_and_step_spans_are_on_the_xplane_host_plane(tmp_path):
    trainer, (x, y) = _trainer(), _batch()
    with _profiler_session(tmp_path):
        trainer.step(x, y)
        trainer.step(x, y).wait_to_read()
    events = _host_events(tmp_path)
    names = collections.Counter(name for name, _ in events)
    assert names["dp.build"] == 1
    for span in STEP_SPANS:
        assert names[span] == 2, (span, names[span])
    steps = sorted(int(stats["step_num"]) for name, stats in events
                   if name == "dp.step")
    assert steps == [1, 2]
    put = [stats for name, stats in events if name == "dp.step.put"]
    assert {int(s["bytes"]) for s in put} == {x.nbytes + y.nbytes}
    built = [stats for name, stats in events if name == "dp.build"]
    assert int(built[0]["params"]) == 4


def test_only_what_runs_inside_a_session_is_in_its_trace(tmp_path):
    trainer, (x, y) = _trainer(), _batch()
    trainer.step(x, y)
    trainer.step(x, y).wait_to_read()
    with _profiler_session(tmp_path):
        trainer.step(x, y).wait_to_read()
    names = collections.Counter(name for name, _ in _host_events(tmp_path))
    assert names["dp.build"] == 0
    assert [names[span] for span in STEP_SPANS] == [1, 1, 1, 1]


def test_step_many_spans_are_on_the_host_plane(tmp_path):
    trainer, (x, y) = _trainer("sgd"), _batch()
    trainer.build(x)
    with _profiler_session(tmp_path):
        trainer.step_many(x, y, n_steps=3).wait_to_read()
    events = _host_events(tmp_path)
    names = collections.Counter(name for name, _ in events)
    for span in ("dp.step_many", "dp.step_many.put", "dp.step_many.enqueue"):
        assert names[span] == 1, span
    many = [stats for name, stats in events if name == "dp.step_many"]
    assert int(many[0]["n_steps"]) == 3
    assert names["dp.step"] == 0


def test_scope_attrs_reach_the_telemetry_span(tmp_path):
    trainer, (x, y) = _trainer(), _batch()
    trainer.build(x)
    path = tmp_path / "spans.json"
    with telemetry.trace(str(path)):
        trainer.step(x, y).wait_to_read()
    with open(path) as f:
        spans = {ev["name"]: ev for ev in json.load(f)["traceEvents"]
                 if ev["ph"] == "X"}
    assert set(STEP_SPANS) <= set(spans)
    assert spans["dp.step.put"]["args"]["bytes"] == x.nbytes + y.nbytes
    assert spans["dp.step"]["args"]["t"] == 1
    assert spans["dp.step"]["cat"] == "trainer"


def test_losses_are_bit_equal_with_and_without_a_profiler_session(tmp_path):
    x, y = _batch()
    plain = _trainer()
    mx.random.seed(9)
    want = [plain.step(x, y).asnumpy() for _ in range(3)]
    traced = _trainer()
    mx.random.seed(9)
    with _profiler_session(tmp_path):
        got = [traced.step(x, y).asnumpy() for _ in range(3)]
    assert [g.tobytes() for g in got] == [w.tobytes() for w in want]


# -- the step log and its section ---------------------------------------------

def test_step_log_holds_one_record_a_step():
    trainer, (x, y) = _trainer(), _batch()
    before = len(dp.step_log())
    for _ in range(4):
        trainer.step(x, y)
    records = dp.step_log(last=4)
    assert len(dp.step_log()) == min(before + 4, 4096)
    assert [r[1] for r in records] == [1, 2, 3, 4]
    assert {r[0] for r in records} == {trainer._serial}
    assert {r[6] for r in records} == {x.nbytes + y.nbytes}
    begins = [r[2] for r in records]
    assert begins == sorted(begins)
    # the first seven fields keep their meaning and order; the compile
    # account's four come after them
    assert {len(r) for r in records} == {11}
    for _serial, _t, begin, put, args, enqueue, _bytes in (
            r[:7] for r in records):
        assert put > 0 and args > 0 and enqueue > 0
    # a step's phases end before the next step's begin
    for a, b in zip(records, records[1:]):
        assert a[2] + a[3] + a[4] + a[5] <= b[2]
    assert dp.step_log(last=0) == []
    assert len(dp.step_log(last=10 ** 6)) == len(dp.step_log())


def test_step_log_is_bounded(monkeypatch):
    assert dp._step_log.maxlen == 4096
    monkeypatch.setattr(dp, "_step_log", collections.deque(maxlen=5))
    trainer, (x, y) = _trainer("sgd"), _batch()
    for _ in range(8):
        trainer.step(x, y)
    assert [r[1] for r in dp.step_log()] == [4, 5, 6, 7, 8]


def test_section_is_summed_from_the_log_and_resets():
    profiler.sections(reset=True)
    trainer, (x, y) = _trainer(), _batch()
    for _ in range(3):
        trainer.step(x, y)
    records = dp.step_log(last=3)
    stats = profiler.sections()["dataParallelStep"]
    assert stats["steps"] == 3 and stats["builds"] == 1
    assert stats["put_bytes"] == 3 * (x.nbytes + y.nbytes)
    for key, field in (("put_ms", 3), ("args_ms", 4), ("enqueue_ms", 5)):
        assert stats[key] == pytest.approx(
            sum(r[field] for r in records) / 1e6, abs=1e-3)
    assert "dataParallelStep" in json.loads(profiler.dumps())
    assert "Data-Parallel Step (host side):" in profiler._section_tables()
    assert json.loads(profiler.dumps(reset=True))[
        "dataParallelStep"]["steps"] == 3
    assert profiler.sections()["dataParallelStep"] == {
        "steps": 0, "builds": 0, "put_ms": 0, "args_ms": 0,
        "enqueue_ms": 0, "put_bytes": 0, "remat_children": 0,
        "remat_saves": {}}
    trainer.step(x, y)
    after = profiler.sections()["dataParallelStep"]
    assert after["steps"] == 1 and after["builds"] == 0
    from mxnet_tpu.telemetry import metrics

    text = metrics.default_registry().render()
    assert "mxtpu_data_parallel_step_steps 1" in text
    assert "mxtpu_data_parallel_step_put_bytes" in text


def test_section_says_what_remat_wrapped_and_keeps():
    """`remat_children`: the child blocks the trainers built in the
    window wrapped in a `jax.checkpoint` (none by a trainer without
    `remat`, none by one whose model is flat: its whole forward is one
    checkpoint); `remat_saves`: the names that checkpoint's policy
    keeps, the flash kernels' own, each with the trainers under it.
    On /metrics and in the table as the other rows, and window-scoped."""
    from mxnet_tpu.ops.pallas.flash_attention import RESIDUAL_NAMES
    from mxnet_tpu.telemetry import metrics

    assert RESIDUAL_NAMES == ("flash_out", "flash_lse")
    profiler.sections(reset=True)
    x, y = _batch()
    _trainer("sgd").step(x, y)
    stats = profiler.sections()["dataParallelStep"]
    assert stats["remat_children"] == 0 and stats["remat_saves"] == {}
    _trainer("sgd", remat=True).step(x, y)
    stats = profiler.sections()["dataParallelStep"]
    assert stats["builds"] == 2 and stats["remat_children"] == 2
    assert stats["remat_saves"] == {
        "flash_out": 1, "flash_lse": 1, "delta_rule_out": 1, "ssd_out": 1}

    flat = gluon.nn.Dense(4)
    flat.initialize(mx.init.Xavier())
    trainer = dp.DataParallelTrainer(
        flat, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 1e-2}, remat=True)
    trainer.step(x, y)
    trainer.build(x)        # built once: counted once
    stats = profiler.sections()["dataParallelStep"]
    assert stats["remat_children"] == 2
    assert stats["remat_saves"] == {
        "flash_out": 2, "flash_lse": 2, "delta_rule_out": 2, "ssd_out": 2}

    table = profiler._section_tables()
    assert f"{'remat: children checkpointed':<40}{2:>12}" in table
    assert f"{'remat keeps[flash_lse] (trainers)':<40}{2:>12}" in table
    text = metrics.default_registry().render()
    assert "mxtpu_data_parallel_step_remat_children 2" in text
    assert 'mxtpu_data_parallel_step_remat_saves{key="flash_out"} 2' in text
    assert json.loads(profiler.dumps(reset=True))[
        "dataParallelStep"]["remat_children"] == 2
    stats = profiler.sections()["dataParallelStep"]
    assert stats["remat_children"] == 0 and stats["remat_saves"] == {}


# -- the compile account ---------------------------------------------------

def _fresh_jit(scale):
    """A function jax has not traced yet, so every call of it compiles."""
    return jax.jit(lambda v: jnp.sin(v) * scale + scale)


def test_jit_inside_jit_books_the_union():
    inner = [_fresh_jit(float(k)) for k in range(1, 6)]

    @jax.jit
    def outer(v):
        return sum(f(v) for f in inner) + jnp.cos(v)

    x = jnp.ones(7)
    with profiler.op_scope("test.union", "test"):
        outer(x).block_until_ready()
        account = profiler.compile_account()
    traces = [e for e in profiler.compile_log()
              if e[3] == "test.union" and e[1] == "trace"]
    walls = [dur for _end, _kind, dur, _path in traces]
    # the outer trace ends last and encloses the inner ones
    assert len(traces) >= 6 and max(walls) == walls[-1]
    assert account.traces == len(traces)
    assert 0 < account.trace_ns <= walls[-1]
    assert sum(walls) > walls[-1], "a plain sum counts the inner traces twice"
    assert account.lowers >= 1 and account.compiles >= 1
    assert account.lower_ns > 0 and account.compile_ns > 0


def test_a_scope_holds_its_childrens_account_and_a_warm_call_books_nothing():
    f = _fresh_jit(3.0)
    x = jnp.ones(5)
    with profiler.op_scope("test.outer", "test"):
        with profiler.op_scope("test.inner", "test"):
            f(x).block_until_ready()
            inner = profiler.compile_account()
        outer = profiler.compile_account()
        with profiler.op_scope("test.warm", "test"):
            f(x).block_until_ready()
            assert profiler.compile_account() == profiler.NO_COMPILE
    assert inner.compiles >= 1 and outer == inner
    paths = {e[3] for e in profiler.compile_log(last=inner.traces
                                                + inner.lowers
                                                + inner.compiles)}
    assert paths == {"test.outer/test.inner"}
    assert profiler.compile_account() == profiler.NO_COMPILE


def test_the_compile_log_is_bounded_and_shaped_like_the_step_log(monkeypatch):
    assert profiler._compile_log.maxlen == profiler.COMPILE_LOG_KEPT
    monkeypatch.setattr(profiler, "_compile_log", collections.deque(maxlen=3))
    _fresh_jit(2.0)(jnp.ones(3)).block_until_ready()
    log = profiler.compile_log()
    assert len(log) == 3 and profiler.compile_log(last=0) == []
    assert profiler.compile_log(last=2) == log[1:]
    assert [e[1] for e in log] == ["trace", "lower", "compile"]
    ends = [e[0] for e in log]
    assert ends == sorted(ends) and all(e[2] > 0 for e in log)
    assert {e[3] for e in log} == {""}, "made outside every scope"


def test_the_compile_section_sums_every_event_and_resets():
    from mxnet_tpu.telemetry import metrics

    profiler.sections(reset=True)
    with profiler.op_scope("test.section", "test"):
        _fresh_jit(4.0)(jnp.ones(6)).block_until_ready()
        account = profiler.compile_account()
    stats = profiler.sections()["compile"]
    assert stats["traces"] >= account.traces >= 1
    assert stats["compiles"] >= account.compiles >= 1
    assert stats["compile_ms"] >= round(account.compile_ns / 1e6, 3) > 0
    text = metrics.default_registry().render()
    assert "mxtpu_compile_compiles" in text
    assert "mxtpu_compile_trace_ms" in text
    assert "Compile (jax: trace / lower / compile or cache load):" in \
        profiler._section_tables()
    profiler.sections(reset=True)
    assert set(profiler.sections()["compile"].values()) == {0}


def test_scopes_closed_out_of_order_leave_the_stack_sound():
    a = profiler.op_scope("test.a", "test").__enter__()
    b = profiler.op_scope("test.b", "test").__enter__()
    a.__exit__(None, None, None)
    assert [s.name for s in profiler._thread.stack] == ["test.b"]
    b.__exit__(None, None, None)
    assert profiler._thread.stack == []


def test_each_thread_books_to_its_own_scopes():
    f = _fresh_jit(5.0)
    seen = []

    def other():
        f(jnp.ones(4)).block_until_ready()
        seen.append(profiler.compile_account())

    with profiler.op_scope("test.main", "test"):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join(timeout=60)
        mine = profiler.compile_account()
    assert not worker.is_alive() and seen == [profiler.NO_COMPILE]
    assert mine == profiler.NO_COMPILE, "compiled on another thread"


CACHE_HIT = r"""
import json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from mxnet_tpu import profiler
f = jax.jit(lambda v: jnp.sin(v) * 3.0 + 1.0)
x = jnp.ones(5)
accounts = []
for _ in range(2):   # compiling, then loading from the persistent cache
    jax.clear_caches()
    with profiler.op_scope("probe", "test"):
        f(x).block_until_ready()
        accounts.append(list(profiler.compile_account()))
events = [e for e in profiler.compile_log() if e[3] == "probe"]
print(json.dumps({{"accounts": accounts, "events": events,
                  "section": profiler.compile_stats()}}))
"""


def test_a_cache_hit_books_its_load_once(tmp_path):
    """On a persistent-cache hit jax reports the compile event AND, inside
    it, the load: the account books the compile's time once, and counts
    the load apart."""
    script = tmp_path / "hit.py"
    script.write_text(CACHE_HIT.format(repo=REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("MXTPU_TRACE", None)
    done = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    first, second = (profiler.CompileAccount(*a) for a in out["accounts"])
    assert first.compiles >= 1 and first.cache_loads == 0
    assert second.cache_loads >= 1
    loads = [e for e in out["events"] if e[1] == "cache_load"]
    compiles = [e for e in out["events"] if e[1] == "compile"][-len(loads):]
    # every load lies inside the compile event that reports it after it
    for (l_end, _, l_dur, _), (c_end, _, c_dur, _) in zip(loads, compiles):
        assert c_end - c_dur <= l_end - l_dur and l_end <= c_end
    assert 0 < second.cache_load_ns <= second.compile_ns
    assert second.compile_ns <= sum(e[2] for e in compiles)
    assert second.compile_ns < sum(e[2] for e in compiles + loads)
    assert out["section"]["cache_loads"] == second.cache_loads


# -- the trainer's set-up in its records ------------------------------------

def test_build_record_splits_the_build_and_books_its_compiles():
    trainer, (x, y) = _trainer(), _batch()
    assert trainer.build_record is None
    profiler.sections(reset=True)
    trainer.build(x)
    record = trainer.build_record
    assert isinstance(record, dp.BuildRecord)
    assert record.serial == trainer._serial
    assert record.params_ns > 0 and record.opt_state_ns > 0
    assert record.params_ns + record.opt_state_ns <= record.total_ns
    made = record.trace_ns + record.lower_ns + record.compile_ns
    assert 0 <= made <= record.total_ns
    inside = [e for e in profiler.compile_log()
              if e[0] >= record.begin_ns and e[3].startswith("dp.build")]
    assert all(e[3].split("/")[1] in ("dp.build.params", "dp.build.opt_state")
               for e in inside)
    trainer.step(x, y)
    assert trainer.build_record == record, "built once"
    with pytest.raises(AttributeError):
        trainer.build_record = None


def test_a_warm_step_books_zeros_and_a_new_shape_names_its_recompile():
    trainer, (x, y) = _trainer(), _batch()
    for _ in range(3):
        trainer.step(x, y)
    x2, y2 = _batch(8)
    trainer.step(x2, y2)
    trainer.step(x2, y2).wait_to_read()
    records = dp.step_log(last=5)
    assert [r[1] for r in records] == [1, 2, 3, 4, 5]
    first, warm, new_shape, again = (
        records[0], records[1:3], records[3], records[4])
    for record in (first, new_shape):
        trace_ns, lower_ns, compile_ns, _loads = record[7:]
        assert trace_ns > 0 and lower_ns > 0 and compile_ns > 0
        # what was made lies inside the enqueue (the step's own program)
        # and the put (its first `global_put` of that shape)
        assert trace_ns + lower_ns + compile_ns <= sum(record[3:6])
    for record in warm + [again]:
        assert record[7:] == (0, 0, 0, 0)


def test_build_phases_are_on_the_xplane_host_plane(tmp_path):
    trainer, (x, y) = _trainer(), _batch()
    with _profiler_session(tmp_path):
        trainer.step(x, y).wait_to_read()
    events = _host_events(tmp_path)
    names = collections.Counter(name for name, _ in events)
    assert names["dp.build"] == names["dp.build.params"] == \
        names["dp.build.opt_state"] == 1
    assert names["dp.step"] == 1


def test_step_log_and_trace_share_a_clock_by_one_offset(tmp_path):
    """A `dp.step.put` event of the xplane and the step-log record of the
    same `t` differ by one offset, the same for every step: the trace's
    clock counts from the session's start, the log's is perf_counter_ns
    (docs/observability.md, "Step log and the dataParallelStep
    section")."""
    from jax.profiler import ProfileData

    trainer, (x, y) = _trainer("sgd"), _batch()
    trainer.step(x, y).wait_to_read()
    with _profiler_session(tmp_path):
        for _ in range(6):
            trainer.step(x, y)
        trainer.step(x, y).wait_to_read()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    steps, puts = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "dp.step":
                        steps[int(dict(e.stats)["step_num"])] = (
                            e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name == "dp.step.put":
                        puts.append(e.start_ns)
    by_t = {}
    for start in puts:
        t, = [t for t, (lo, hi) in steps.items() if lo <= start <= hi]
        by_t[t] = start
    records = {r[1]: r for r in dp.step_log(last=7)}
    assert sorted(by_t) == sorted(records) == list(range(2, 9))
    offsets = [by_t[t] - records[t][2] for t in by_t]
    middle = statistics.median(offsets)
    assert all(abs(o - middle) < 500_000 for o in offsets), offsets


def test_section_and_span_names_pass_the_invariant_passes():
    result = analysis.analyze(REPO, baseline_path=None,
                              passes=["invariants"])
    assert [f.key for f in result["findings"]
            if f.code in ("MXA403", "MXA405")] == []
    assert "dataParallelStep" in profiler.section_names()


# -- phase names in the compiled step -----------------------------------------

def _step_text(trainer, x, y):
    """The step compiled a second time, outside the trainer, as
    benchmarks/harness/gluon_program.py:step_program_text does."""
    return trainer._step_fn.lower(
        trainer._params, trainer._states, jnp.asarray(x), jnp.asarray(y),
        mx.random.next_key(), jnp.asarray(trainer._lr, jnp.float32),
        jnp.asarray(1.0, jnp.float32)).compile().as_text()


@pytest.mark.parametrize("optimizer", ["adamw", "sgd"])
def test_compiled_step_names_its_phases(optimizer):
    trainer, (x, y) = _trainer(optimizer), _batch()
    trainer.build(x)
    text = _step_text(trainer, x, y)
    assert "jvp(forward)" in text
    assert "transpose(jvp(forward))" in text
    assert "/optimizer/" in text
    # the name the traces' readers look for, and not the unscoped step's
    assert text.startswith("HloModule jit_step_phases")


CACHE_TRAP = r"""
import contextlib, json, sys
sys.path.insert(0, {repo!r})
import jax
mode = sys.argv[1]
if mode == "parent":
    # the step before it named its phases: the same arithmetic, no scope
    jax.named_scope = contextlib.contextmanager(lambda name: iter([None]))
if mode in ("parent", "old_name"):
    real_jit = jax.jit
    def jit(fun, *args, **kwargs):
        if getattr(fun, "__name__", "") == "step_phases":
            fun.__name__ = "step"
        return real_jit(fun, *args, **kwargs)
    jax.jit = jit
from jax._src import compiler
own = []
real = compiler.compile_or_get_cached
def spy(backend, computation, *args, **kwargs):
    executable = real(backend, computation, *args, **kwargs)
    if "jit_step" in str(computation.operation.attributes["sym_name"]):
        own.append(executable.hlo_modules()[0].to_string())
    return executable
compiler.compile_or_get_cached = spy
sys.path.insert(0, {tests!r})
import test_dp_tracing as t
trainer, (x, y) = t._trainer(), t._batch()
loss = float(trainer.step(x, y).asnumpy())
outside = t._step_text(trainer, x, y)
marks = ("jvp(forward)", "transpose(jvp(forward))", "/optimizer/")
print(json.dumps({{"loss": loss,
                  "own": [m in own[0] for m in marks],
                  "outside": [m in outside for m in marks]}}))
"""


def test_scopes_survive_a_cache_filled_by_the_unscoped_step(tmp_path):
    """JAX leaves metadata out of the persistent cache's key: under the
    name the step had before it named its phases, the scoped step LOADS
    the unscoped executable a shared cache directory holds, and its text
    names no phase.  Under its own name it has its own entries, for the
    trainer's call and for a `.lower().compile()` made outside it, and
    every other program still hits."""
    script = tmp_path / "trap.py"
    script.write_text(CACHE_TRAP.format(
        repo=REPO, tests=os.path.join(REPO, "tests")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("MXTPU_TRACE", None)

    def run(mode):
        done = subprocess.run([sys.executable, str(script), mode], env=env,
                              capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-2000:]
        return json.loads(done.stdout.strip().splitlines()[-1])

    def entries():
        return {name for name in os.listdir(tmp_path / "cache")
                if name.endswith("-cache")}

    parent = run("parent")
    assert parent["own"] == parent["outside"] == [False, False, False]
    filled = entries()
    assert any(name.startswith("jit_step-") for name in filled)
    # the trap: the scoped step under the old name loads that entry
    stale = run("old_name")
    assert stale["own"] == stale["outside"] == [False, False, False]
    assert entries() == filled
    added = None
    for _ in range(2):  # compiling, then loading its own entries
        scoped = run("scoped")
        assert scoped["own"] == scoped["outside"] == [True, True, True]
        assert added in (None, entries() - filled)
        added = entries() - filled
        # the step alone is new (JAX numbers the private functions of
        # the call's lowering and of the outside one differently, here as
        # in the unscoped step: at most two entries)
        assert 1 <= len(added) <= 2
        assert all(name.startswith("jit_step_phases-") for name in added)
        # metadata only: the same arithmetic
        assert scoped["loss"] == parent["loss"]


# -- what it costs with nothing armed ----------------------------------------

def _median_ns(fn, calls=1000):
    clock = time.perf_counter_ns
    samples = []
    for _ in range(calls):
        t0 = clock()
        fn()
        samples.append(clock() - t0)
    return statistics.median(samples)


def _added_ns(with_it, without_it, rounds=9):
    """Per-call difference of medians, the least of `rounds` taken in
    turn: a loaded machine only adds time."""
    return min(with_it() - without_it() for _ in range(rounds))


def test_disarmed_op_scope_stays_under_three_microseconds():
    assert tracer.span_begin is tracer._noop and not profiler.is_running()

    def scope():
        with profiler.op_scope("dp.step.put", "trainer"):
            pass

    def nothing():
        pass

    cost = _added_ns(lambda: _median_ns(scope), lambda: _median_ns(nothing))
    assert cost < 3000, f"one disarmed op_scope costs {cost} ns"


def test_what_tracing_adds_to_a_step_stays_under_twenty_microseconds(
        monkeypatch):
    """Per-call difference between `step()` as it is and `step()` with
    its spans and its log taken out; the device work is stubbed out of
    both, so the difference is not lost in it."""
    trainer = _trainer("sgd")
    x, y = (jnp.asarray(v) for v in _batch())
    trainer.step(x, y).wait_to_read()
    loss = jnp.float32(0)
    params, states = trainer._params, trainer._states
    key = mx.random.next_key()
    monkeypatch.setattr(trainer, "_step_fn",
                        lambda *args: (loss, params, states))
    monkeypatch.setattr(dp.mesh_mod, "global_put", lambda v, sharding: v)
    monkeypatch.setattr(dp._random, "next_key", lambda: key)
    monkeypatch.setattr(dp.jnp, "asarray", lambda v, dtype=None: v)

    def step():
        trainer.step(x, y)

    class NoScope:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def note(self, **attrs):
            pass

    class NoLog:
        def append(self, record):
            pass

    no_scope = NoScope()

    def without_tracing():
        with monkeypatch.context() as bare:
            bare.setattr(dp._profiler, "op_scope",
                         lambda name, cat="operator", **attrs: no_scope)
            bare.setattr(dp, "_step_log", NoLog())
            return _median_ns(step)

    logged = len(dp.step_log())
    added = _added_ns(lambda: _median_ns(step), without_tracing)
    assert len(dp.step_log()) > logged
    assert added < 20000, f"tracing adds {added} ns to a step() call"


# -- repairs in the files touched ---------------------------------------------

def test_no_event_takes_the_tracers_global_lock(monkeypatch):
    class CountingLock:
        def __init__(self):
            self.lock, self.taken = threading.Lock(), 0

        def __enter__(self):
            self.taken += 1
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    tracer.reset_telemetry_stats()
    lanes_done = []

    def emit(n):
        for _ in range(n):
            with profiler.op_scope("dp.step.args", "trainer"):
                pass
            tracer.instant("resilience.retry")
            tracer.request_end("serve.request",
                               tracer.request_begin("serve.request"))
        lanes_done.append(n)

    from mxnet_tpu.telemetry import flight

    flight.enable()
    try:
        emit(1)  # this thread's lane exists from here on
        counting = CountingLock()
        monkeypatch.setattr(tracer, "_lock", counting)
        emit(50)
        assert counting.taken == 0
        worker = threading.Thread(target=emit, args=(20,))
        worker.start()
        worker.join(timeout=60)
        assert not worker.is_alive() and lanes_done == [1, 50, 20]
        assert counting.taken == 1  # the new thread's lane, registered once
        stats = tracer.telemetry_stats()
    finally:
        flight.disable()
    assert (stats["spans"], stats["instants"], stats["requests"]) == (
        71, 71, 71)
    tracer.reset_telemetry_stats()
    assert tracer.telemetry_stats()["spans"] == 0


def test_memory_peak_is_updated_under_a_lock(monkeypatch):
    seen = []

    class Watched(dict):
        def __setitem__(self, key, value):
            seen.append(profiler._mem_lock.locked())
            super().__setitem__(key, value)

    monkeypatch.setattr(profiler, "_mem_peak", Watched(
        device_bytes_in_use=0, pool_used_bytes=0))
    profiler._memory_sample()
    profiler.reset()
    assert seen and all(seen)
