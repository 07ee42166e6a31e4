"""The five per-layer metrics that split `setup_s` by the program's own
compile account (harness/setup_log.py): on a synthetic account with the
values by hand, on a tiny cell through `run.read_layer_metrics`, where
they are disjoint and add up to no more than the run's `setup_s`, and
that they came as new files and new entries alone."""
from __future__ import annotations

import collections
import os
import types

import pytest

import tiny

tiny.on_path()

from harness import setup_log  # noqa: E402
from harness.manifest import Manifest  # noqa: E402

MANIFEST = Manifest(tiny.REPO)
SETUP = ["setup_build_s", "setup_trace_s", "setup_lower_s", "setup_compile_s",
         "setup_other_programs_s"]


def _read(run, name):
    return MANIFEST.module("layer_metrics", name).read(run)


# -- a synthetic account ------------------------------------------------------

Build = collections.namedtuple(
    "Build", "serial begin_ns params_ns opt_state_ns total_ns trace_ns "
    "lower_ns compile_ns cache_loads")
BUILD = Build(7, 1000, 300, 200, 600, 50, 30, 100, 0)
# (serial, t, begin_ns, put, args, enqueue, bytes, trace, lower, compile,
# loads): steps 1 and 2 are the check steps, step 3 opens the window at
# 9000, another trainer's records around them
STEPS = [(6, 1, 1500, 1, 1, 1, 8, 5, 5, 5, 0),
         (7, 1, 2000, 10, 10, 1900, 8, 400, 200, 1000, 0),
         (7, 2, 5000, 10, 10, 20, 8, 0, 0, 0, 0),
         (7, 3, 9000, 10, 10, 20, 8, 0, 0, 0, 0),
         (7, 4, 9100, 10, 10, 20, 8, 0, 0, 0, 0)]
LOG = [(500, "trace", 100, ""),
       (700, "compile", 150, ""),
       (650, "cache_load", 50, ""),                   # inside a compile
       (1500, "trace", 50, "dp.build/dp.build.params"),
       (4000, "lower", 200, "dp.step/dp.step.enqueue"),
       (4450, "trace", 100, ""),                      # inside the next
       (4500, "trace", 300, ""),
       (9500, "compile", 400, "")]                    # after set-up's end
RUN = types.SimpleNamespace(traffic={"check_steps": 2})


@pytest.fixture
def program(monkeypatch):
    """The program's three records replaced by the synthetic ones."""
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import data_parallel

    trainer = types.SimpleNamespace(build_record=BUILD)
    monkeypatch.setattr(data_parallel, "live_trainers", lambda: [trainer])
    monkeypatch.setattr(data_parallel, "step_log", lambda last=None: STEPS)
    monkeypatch.setattr(profiler, "compile_log", lambda last=None: LOG)
    return types.SimpleNamespace(trainer=trainer, data_parallel=data_parallel,
                                 profiler=profiler)


def test_readers_on_a_synthetic_account(program):
    values = {name: _read(RUN, name) for name in SETUP}
    assert values == {
        "setup_build_s": pytest.approx((600 - 180) / 1e9),
        "setup_trace_s": pytest.approx((50 + 400) / 1e9),
        "setup_lower_s": pytest.approx((30 + 200) / 1e9),
        "setup_compile_s": pytest.approx((100 + 1000) / 1e9),
        # 400-500, 550-700 and 4200-4500: the nested trace once, the
        # load inside its compile not at all, the trainer's spans and
        # the compile after set-up's end (9000) left out
        "setup_other_programs_s": pytest.approx((100 + 150 + 300) / 1e9)}
    assert sum(values.values()) * 1e9 <= STEPS[3][2]


def test_an_event_after_set_ups_end_is_left_out(program, monkeypatch):
    before = _read(RUN, "setup_other_programs_s")
    # what `program_text()` compiles after the window, and a step's
    # recompile in it: neither is set-up
    monkeypatch.setattr(program.profiler, "compile_log", lambda last=None: (
        LOG + [(20000, "compile", 10000, ""),
               (9300, "trace", 50, "dp.step/dp.step.enqueue")]))
    assert _read(RUN, "setup_other_programs_s") == before


def test_a_union_counts_an_overlap_once():
    assert setup_log.union_ns([]) == 0
    assert setup_log.union_ns([(30, 40), (0, 10), (5, 20), (32, 35)]) == 30


@pytest.mark.parametrize("change", [
    "no_build_record", "not_built", "no_trainer", "records_of_seven_fields",
    "a_check_step_missing", "no_compile_log", "compile_log_full"])
def test_without_an_account_there_is_no_number(program, monkeypatch, change):
    dp, prof = program.data_parallel, program.profiler
    expect_none = SETUP
    if change == "no_build_record":      # the parent's trainer
        monkeypatch.setattr(dp, "live_trainers",
                            lambda: [types.SimpleNamespace()])
    elif change == "not_built":
        program.trainer.build_record = None
    elif change == "no_trainer":
        monkeypatch.setattr(dp, "live_trainers", lambda: [])
    elif change == "records_of_seven_fields":   # the parent's step log
        monkeypatch.setattr(dp, "step_log",
                            lambda last=None: [r[:7] for r in STEPS])
    elif change == "a_check_step_missing":
        monkeypatch.setattr(dp, "step_log", lambda last=None: STEPS[2:])
    elif change == "no_compile_log":     # the parent's profiler
        monkeypatch.delattr(prof, "compile_log")
        expect_none = ["setup_other_programs_s"]
    else:   # set-up's first events may have gone
        monkeypatch.setattr(prof, "COMPILE_LOG_KEPT", 64)
        monkeypatch.setattr(prof, "compile_log",
                            lambda last=None: LOG * 8)
        expect_none = ["setup_other_programs_s"]
    values = {name: _read(RUN, name) for name in SETUP}
    assert [n for n in SETUP if values[n] is None] == expect_none


# -- a tiny cell -----------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """The tiny ResNet cell measured with a compile log of its own, as a
    fresh process has."""
    from mxnet_tpu import profiler

    run = tiny.load_run_module()
    manifest = Manifest(
        tiny.make_root(tmp_path_factory.mktemp("setup") / "r"))
    job = run.make_job(manifest, "resnet_tiny.train32", 3100000007, 0.3, 0,
                       tiny.CPU_DEVICE)
    kept = profiler._compile_log
    profiler._compile_log = collections.deque(maxlen=kept.maxlen)
    try:
        record = job.traffic_mod.measure(job)
        yield run, job, record
    finally:
        profiler._compile_log = kept


def test_the_five_split_set_up_and_add_up_to_no_more_than_setup_s(tiny_run):
    run, job, record = tiny_run
    layer = run.read_layer_metrics(job, record, None)
    assert set(SETUP) <= set(layer)
    values = [layer[name]["value"] for name in SETUP]
    assert all(v >= 0 for v in values), values
    assert {layer[name]["unit"] for name in SETUP} == {"s"}
    # the step program is traced, lowered and compiled in the first
    # check step; the build places and copies
    assert min(values[:4]) > 0, values
    assert sum(values) <= record["end_to_end"]["setup_s"]


def test_the_windows_steps_book_nothing(tiny_run):
    from mxnet_tpu.parallel import data_parallel

    _run, _job, record = tiny_run
    steps = record["spans"]["window"]["count"]
    records = data_parallel.step_log(last=steps)
    assert len(records) == steps
    assert {r[7:] for r in records} == {(0, 0, 0, 0)}
    assert record["counters"]["compiles_in_window"] == 0


def test_the_step_compiled_again_after_the_window_is_left_out(tiny_run):
    from mxnet_tpu import profiler

    run, job, record = tiny_run
    before = {n: v["value"] for n, v in
              run.read_layer_metrics(job, record, None).items()
              if n in SETUP}
    logged = len(profiler.compile_log())
    record["program_text"]()
    assert [e[1] for e in profiler.compile_log()[logged:]].count(
        "compile") >= 1
    after = {n: v["value"] for n, v in
             run.read_layer_metrics(job, record, None).items() if n in SETUP}
    assert after == before


# -- new files and new entries only -------------------------------------------

def test_the_five_are_new_files_and_appended_entries_only():
    entries = MANIFEST.data["per_layer"]
    assert [m["name"] for m in entries[-len(SETUP):]] == SETUP
    for m in entries[-len(SETUP):]:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "set-up",
                     "moves": "setup_s"}
        assert callable(MANIFEST.module("layer_metrics", m["name"]).read)
    for cell in (w["name"] for w in MANIFEST.data["workloads"]):
        reported = [m["name"] for m in MANIFEST.metrics("per_layer", cell)]
        assert set(SETUP) <= set(reported)
    assert os.path.isfile(os.path.join(tiny.BENCH, "harness", "setup_log.py"))
