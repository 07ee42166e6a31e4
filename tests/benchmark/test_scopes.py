"""The six per-layer metrics that read the program's own tracing: the
device time of a step by the phase its compiled text names
(harness/scopes.py) on a made-up record with the values by hand, the
host's three phases from the program's step log through
`run.read_layer_metrics` on a tiny cell, and that all six came as new
files and new entries alone."""
from __future__ import annotations

import os
import types

import pytest

import tiny

tiny.on_path()

from harness import scopes, trace  # noqa: E402
from harness.manifest import Manifest  # noqa: E402

MANIFEST = Manifest(tiny.REPO)
ACCEPTED = ["host_dispatch_ms", "compiles_in_window", "kernel_calls_per_step",
            "flash_attn_roofline", "step_mfu", "device_idle_pct"]
HOST = ["step_put_ms", "step_args_ms", "step_enqueue_ms"]
DEVICE = ["fwd_ms", "bwd_ms", "optimizer_ms"]

PROGRAM_TEXT = """\
HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(forward)/mul" source_file="m.py" source_line=3}
}

ENTRY %main.7 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="params[0]"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(forward)/dot_general" source_file="m.py" source_line=3}
  %copy.3 = f32[8]{0} copy(f32[8]{0} %fusion.1)
  %copy-start.6 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %copy-done.6 = f32[8]{0} copy-done(%copy-start.6)
  %bitcast.8 = f32[8]{0} bitcast(%copy-done.6)
  fusion.2 = f32[8]{0} fusion(f32[8]{0} %copy.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(forward))/mul" source_file="m.py"}
  %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(forward))/branch_0_fun/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %custom-call.4, %bitcast.8), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/optimizer/add"}
  %copy.7 = f32[8]{0} copy(%fusion.5)
  ROOT %tuple.9 = (f32[8]{0}) tuple(%copy.7)
}
"""


def _record(copy_ns):
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1 f32[8]", 100, 50],
                    ["copy.7 f32[8]", 150, copy_ns],
                    ["copy.3 f32[8]", 390, 4],
                    ["copy-done.6 f32[8]", 394, 6],
                    ["fusion.2 f32[8]", 400, 60],
                    ["custom-call.4 tpu_custom_call(f32[8])", 460, 40],
                    ["fusion.5 f32[8]", 600, 30],
                    ["fusion.1 f32[8]", 990, 50],    # 10 ns inside
                    ["fusion.2 f32[8]", 2000, 60]],  # outside
            "modules": [["jit_step(1)", 100, 600], ["jit_step(1)", 990, 600],
                        ["jit_other(2)", 700, 10]]}},
        "host": [["bench_window", 0, 1000], ["step_call", 0, 90]]}


def _run(record, text=PROGRAM_TEXT):
    calls = []

    def program_text():
        calls.append(1)
        return text

    return calls, types.SimpleNamespace(
        trace=record, program_text=program_text, cell={"name": "made_up"},
        traffic={"step_program": "jit_step"})


def _read(run, name):
    return MANIFEST.module("layer_metrics", name).read(run)


def test_phase_of_an_op_name():
    assert scopes.phase_of("jit(step)/jvp(forward)/dot_general") == "forward"
    assert scopes.phase_of(
        "jit(step)/transpose(jvp(forward))/mul") == "backward"
    assert scopes.phase_of("jit(step)/optimizer/add") == "optimizer"
    assert scopes.phase_of("jit(step)/while/body/jvp(forward)/x") == "forward"
    assert scopes.phase_of("params[0]") is None
    assert scopes.instruction_phases(PROGRAM_TEXT) == {
        "multiply.9": "forward", "fusion.1": "forward",
        "fusion.2": "backward", "custom-call.4": "backward",
        "fusion.5": "optimizer",
        # no op_name of their own: where their first consumer goes, the
        # async copy through the bitcast; the output's copy has none
        "copy.3": "backward", "copy-start.6": "optimizer",
        "copy-done.6": "optimizer", "bitcast.8": "optimizer",
        # the fused computation's parameter feeds a forward multiply; the
        # entry's has an op_name of its own, which names no phase
        "p": "forward"}


def test_device_readers_on_a_made_up_record():
    calls, run = _run(_record(copy_ns=10))
    # two steps start in the window; forward 50 + 10 ns, backward 60 + 40
    # and its layout copy's 4, optimizer 30 and its async copy's 6, the
    # output copy's 10 unplaced: 200 of 210 ns placed
    assert _read(run, "fwd_ms") == pytest.approx(60 / 2 / 1e6)
    assert _read(run, "bwd_ms") == pytest.approx(104 / 2 / 1e6)
    assert _read(run, "optimizer_ms") == pytest.approx(36 / 2 / 1e6)
    assert len(calls) == 1, "the compiled text is read once a run"
    values, share, heaviest = scopes.split(
        run.trace, scopes.instruction_phases(PROGRAM_TEXT), "jit_step")
    assert share == pytest.approx(200 / 210)
    assert heaviest == [["copy.7 f32[8]", pytest.approx(10 / 2 / 1e6)]]


def test_two_devices_are_averaged():
    record = _record(copy_ns=10)
    record["devices"]["/device:TPU:1"] = {
        "ops": [["fusion.1 f32[8]", 100, 150]], "modules": []}
    values, share, _ = scopes.split(
        record, scopes.instruction_phases(PROGRAM_TEXT), "jit_step")
    assert values["forward"] == pytest.approx((60 + 150) / 2 / 2 / 1e6)
    assert values["backward"] == pytest.approx(104 / 2 / 2 / 1e6)


# a step whose layers share a lowered function: XLA clones the loop and
# the switch as it inlines it and names them `.clone.M`
FWD, BWD = "jit(step)/jvp(forward)", "jit(step)/transpose(jvp(forward))"
LOOPS_TEXT = f"""\
%fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{FWD}/moe/experts/branch_1_fun/mul"}}
%cond.2.clone.1 = f32[8]{{0}} conditional(%i, %p, %p), metadata={{op_name="{FWD}/moe/experts/cond"}}
%fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{FWD}/delta_rule/while/body/dot_general"}}
%while.4 = (s32[], f32[8]{{0}}) while(%t), metadata={{op_name="{FWD}/delta_rule/while"}}
%fusion.5 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{BWD}/delta_rule/while/body/while/body/dot_general"}}
%while.6.clone.2 = (s32[], f32[8]{{0}}) while(%t), metadata={{op_name="{BWD}/delta_rule/while/body/while"}}
%while.7.clone.1.clone.3 = (s32[], f32[8]{{0}}) while(%t), metadata={{op_name="{BWD}/delta_rule/while"}}
%conditional.8 = f32[8]{{0}} conditional(%i, %p, %p), metadata={{op_name="{BWD}/moe/experts/cond"}}
%fusion.9 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{BWD}/moe/experts/branch_0_fun/mul"}}
%call.10 = f32[8]{{0}} call(%p), to_apply=%f
%while_fusion.11 = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="jit(step)/optimizer/add"}}
"""


def _loops_record():
    ops = [["cond.2.clone.1 f32[8]", 100, 40],   # spans its branch
           ["fusion.1 f32[8]", 105, 30],
           ["while.4 s32[]", 150, 50],           # two turns of its body
           ["fusion.3 f32[8]", 155, 20],
           ["fusion.3 f32[8]", 178, 20],
           # a loop in a loop, both cloned, around three turns
           ["while.7.clone.1.clone.3 s32[]", 300, 200],
           ["while.6.clone.2 s32[]", 310, 90],
           ["fusion.5 f32[8]", 315, 25],
           ["fusion.5 f32[8]", 345, 25],
           ["while.6.clone.2 s32[]", 405, 90],
           ["fusion.5 f32[8]", 410, 25],
           ["conditional.8 f32[8]", 520, 60],
           ["fusion.9 f32[8]", 525, 50],
           ["call.10 f32[8]", 600, 100],         # no op_name at all
           ["while_fusion.11 f32[8]", 700, 10]]  # a fusion, no container
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step(1)", 100, 700]]}},
        "host": [["bench_window", 0, 1000]]}


def test_a_containers_own_event_is_left_out_and_its_body_placed_once():
    values, share, heaviest = scopes.split(
        _loops_record(), scopes.instruction_phases(LOOPS_TEXT), "jit_step")
    # one step; forward 30 + 20 + 20, backward 3 x 25 + 50, optimizer 10:
    # not the switches' 40 and 60, the loops' 50, 200 and 2 x 90
    assert values == {"forward": pytest.approx(70 / 1e6),
                      "backward": pytest.approx(125 / 1e6),
                      "optimizer": pytest.approx(10 / 1e6)}
    # the call's event, which no phase would claim, is no operation
    # either: it does not lower the placed share
    assert share == 1.0 and heaviest == []
    busy, _ = trace.busy_seconds(_loops_record())
    assert sum(values.values()) * 1e6 <= busy * 1e9


def test_top_device_ops_lists_operations_and_no_container():
    top = trace.top_device_ops(_loops_record())
    assert [name for name, _ in top] == [
        "fusion.5 f32[8]", "fusion.9 f32[8]", "fusion.3 f32[8]",
        "fusion.1 f32[8]", "while_fusion.11 f32[8]"]
    assert top[0][1] == pytest.approx(75e-9)
    for name in ("while.245 s32[]", "while.6.clone.1", "cond.68.clone.2 "
                 "bf16[16384,2048]", "conditional.3", "call.9 f32[8]", "while"):
        assert trace.CONTAINER.match(name), name
    # the match is the instruction's kind and its numbering, no prefix
    for name in ("while_fusion.11 f32[8]", "condition_fusion f32[8]",
                 "fusion.2 f32[8]", "call-start.3", "branch_0_fun.43 "
                 "tpu_custom_call(bf16[16,6,8192,128])"):
        assert not trace.CONTAINER.match(name), name


@pytest.mark.parametrize("record, text, share", [
    (_record(copy_ns=240), PROGRAM_TEXT, "45.5 %"),       # 200 of 440 ns
    # a forward pass that lost its name: 140 of 210 ns still placed
    (_record(copy_ns=10), PROGRAM_TEXT.replace("forward", "fn"), "66.7 %"),
    (_record(copy_ns=10), "HloModule jit_step\n", "0.0 %")],
    ids=["heavy_unplaced_event", "scope_lost", "unscoped_program"])
def test_under_ninety_percent_placed_there_is_a_note_and_no_number(
        capsys, record, text, share):
    calls, run = _run(record, text)
    assert [_read(run, name) for name in DEVICE] == [None, None, None]
    err = capsys.readouterr().err
    assert err.count("scope-note") == 1 and len(calls) == 1
    assert f"scope-note made_up: {share}" in err
    if share == "45.5 %":
        assert "copy.7 f32[8]" in err


def test_without_a_trace_or_a_program_text_there_is_nothing_to_read(capsys):
    for run in (types.SimpleNamespace(trace=None, program_text=lambda: ""),
                types.SimpleNamespace(trace=_record(10), program_text=None)):
        assert [_read(run, name) for name in DEVICE] == [None, None, None]
    assert capsys.readouterr().err == ""


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    run = tiny.load_run_module()
    manifest = Manifest(
        tiny.make_root(tmp_path_factory.mktemp("scopes") / "r"))
    job = run.make_job(manifest, "resnet_tiny.train32", 21, 0.3, 0,
                       tiny.CPU_DEVICE)
    record = job.traffic_mod.measure(job)
    return run, job, record


def test_host_readers_add_up_to_the_step_call(tiny_run):
    run, job, record = tiny_run
    layer = run.read_layer_metrics(job, record, None)
    assert set(HOST) <= set(layer) and not set(DEVICE) & set(layer)
    parts = [layer[name]["value"] for name in HOST]
    assert all(v > 0 for v in parts)
    assert {layer[name]["unit"] for name in HOST} == {"ms"}
    whole = layer["host_dispatch_ms"]["value"]
    # the harness's clock is around the whole call: the three phases and,
    # besides, the unwrapping and the spans' own cost
    assert sum(parts) <= whole
    assert whole - sum(parts) < max(0.05 * whole, 0.2)
    from mxnet_tpu.parallel import data_parallel

    steps = record["spans"]["window"]["count"]
    records = data_parallel.step_log(last=steps)
    assert layer["step_put_ms"]["value"] == pytest.approx(
        sum(r[3] for r in records) / steps / 1e6)
    batch = record["pool"][0]
    assert {r[6] for r in records} == {batch[0].nbytes + batch[1].nbytes}


def test_host_readers_find_nothing_in_another_window(tiny_run, monkeypatch):
    run, job, record = tiny_run
    from mxnet_tpu.parallel import data_parallel

    def layer(**spans):
        changed = dict(record, spans=dict(record["spans"], **spans))
        return run.read_layer_metrics(job, changed, None)

    steps = record["spans"]["window"]["count"]
    # more steps than this trainer has made: its check steps came first
    # and the log's older records are other trainers'
    assert not set(HOST) & set(layer(window={
        "seconds": 1.0, "count": steps + 4}))
    assert not set(HOST) & set(layer(window={"seconds": 1.0, "count": 0}))
    # the parent of the PR that brought the log has no such function
    monkeypatch.delattr(data_parallel, "step_log")
    assert not set(HOST) & set(layer())
    assert "host_dispatch_ms" in layer()


def test_the_six_are_new_files_and_new_entries_only():
    entries = MANIFEST.data["per_layer"]
    names = [m["name"] for m in entries]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names[len(ACCEPTED):len(ACCEPTED) + 6] == HOST + DEVICE
    for m in entries[len(ACCEPTED):len(ACCEPTED) + 6]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": ("program_span" if m["name"] in HOST
                                else "device_trace"),
                     "layer": ("entry points" if m["name"] in HOST
                               else "whole step"),
                     "moves": "step_ms"}
        assert callable(MANIFEST.module("layer_metrics", m["name"]).read)
    layers = {m["layer"] for m in entries[:len(ACCEPTED)]}
    assert {"entry points", "whole step"} <= layers
    for cell in (w["name"] for w in MANIFEST.data["workloads"]):
        reported = [m["name"] for m in MANIFEST.metrics("per_layer", cell)]
        assert set(HOST + DEVICE) <= set(reported)
    for helper in ("scopes.py", "step_log.py"):
        assert os.path.isfile(os.path.join(tiny.BENCH, "harness", helper))
