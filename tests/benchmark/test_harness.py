"""The harness on the CPU: BENCHMARK.json against its contract and its
files, the trace reduction on a small recorded trace, the FLOP counts
against hand counts, the comparison's arithmetic, and that a cell, a
traffic kind and a per-layer metric are added by new files alone.  No
chip is described and libtpu is not loaded here."""
from __future__ import annotations

import gzip
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import tiny

tiny.on_path()

from harness import compare, trace  # noqa: E402
from harness.manifest import NAME, UNIT, Manifest  # noqa: E402

MANIFEST = Manifest(tiny.REPO)
CELLS = [w["name"] for w in MANIFEST.data["workloads"]]
METRICS = MANIFEST.data["end_to_end"] + MANIFEST.data["per_layer"]


# -- BENCHMARK.json ---------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    entry = MANIFEST.cell(cell)
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    traffic = MANIFEST.cell_params(cell)
    config = MANIFEST.config(entry["config"])
    assert config["name"] == entry["config"]
    config_mod = MANIFEST.module("configs", entry["config"])
    reference = MANIFEST.module("reference", entry["config"])
    kind = MANIFEST.module("traffic", traffic["kind"])
    assert callable(kind.measure) and callable(kind.verify)
    for fn in ("make_batch", "build", "model_flops_per_step",
               "units_per_step", "reference_batch"):
        assert callable(getattr(config_mod, fn)), fn
    specs = reference.param_specs(config)
    assert len(reference.leaf_parts(config)) == len(specs)
    readers = MANIFEST.metrics("per_layer", cell)
    assert readers, "every cell reports at least one per-layer metric"
    for metric in readers:
        assert callable(MANIFEST.module("layer_metrics", metric["name"]).read)
    reported = {m["name"] for m in MANIFEST.metrics("end_to_end", cell)}
    assert "setup_s" in reported and len(reported) >= 2
    assert traffic["rate_metric"] in reported
    assert {m["moves"] for m in readers} <= reported
    assert set(traffic["limits"]) <= {
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "grad_norm_median_gap", "grad_norm_p90_gap", "change_norm_gap",
        "change_norm_median_gap"}
    assert traffic["limits"], "a cell compares at least one number"


def test_names_units_and_lengths():
    data = MANIFEST.data
    names = [e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in data[group]]
    for name in names + [w["traffic"] for w in data["workloads"]] + [
            k for c in data["configs"] for k in c["reduced"]]:
        assert NAME.match(name), name
    for metric in METRICS:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in data[group]]
        assert len(set(group_names)) == len(group_names)
    for text in ([w["why"] for w in data["workloads"]]
                 + [c["why"] for c in data["configs"]]
                 + [c["source"] for c in data["configs"]]
                 + [m["layer"] for m in data["per_layer"]] + data["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_contract_shape():
    data = MANIFEST.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in data["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in data["paths"])
        assert any(w["config"] == c["name"] for w in data["workloads"])
    assert len({c["file"] for c in data["configs"]}) == len(data["configs"])
    for w in data["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in data["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in data["workloads"])
    assert four <= max(1, len(data["workloads"]) // 4)
    for m in data["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in data["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in data["end_to_end"]}
    # a full check with all 24 cells has to fit the driver's day
    runs = 2 + 14 * 24
    assert runs * (data["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(tiny.REPO, "BENCHMARK.json")) < 65536


# What `reduced` may never name (the `model-configs` guide, section 4):
# a hidden, intermediate, latent, state or projection size, a key that
# ends in `_dim` or `_rank`, a head size, an expansion factor, a window,
# the experts a token.  It MAY count layers, experts held, rows of the
# vocabulary or the entries of a per-layer list.
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj(ection)?|embedding|head)"
    r"_(size|dim)$|_dim$|_rank$|expand|expansion|window|experts_per_tok"
    r"|top_k")


@pytest.mark.parametrize("reduced, refused", [
    (sorted({k for c in MANIFEST.data["configs"] for k in c["reduced"]}), []),
    (["num_hidden_layers", "hidden_size"], ["hidden_size"]),
    (["num_experts", "moe_intermediate_size"], ["moe_intermediate_size"]),
    (["vocab_size", "layer_types", "num_attention_heads_per_layer",
      "num_key_value_heads", "max_position_embeddings", "layers"], []),
    (["head_dim", "linear_key_head_dim", "q_lora_rank", "sliding_window",
      "linear_conv_kernel_dim", "num_experts_per_tok", "intermediate_size",
      "shared_expert_intermediate_size", "mamba_expand", "ssm_state_size"],
     None)],
    ids=["the_configurations", "hidden_size", "moe_intermediate_size",
         "counts_and_lists", "every_kind_of_width"])
def test_width_keys_are_never_reduced(reduced, refused):
    assert reduced, "nothing to judge"
    found = [key for key in reduced if WIDTH.search(key)]
    assert found == (reduced if refused is None else refused)


def test_reduced_is_the_same_list_in_the_configurations_own_file():
    for c in MANIFEST.data["configs"]:
        assert MANIFEST.config(c["name"])["reduced"] == c["reduced"]


# -- the trace reduction ----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """Two steps of bert_base.seq128 cut from a trace taken on a TPU v5
    lite (PR 26), names already shortened by harness.trace.short_name."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "bert_seq128_two_steps.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _sweep_busy_ns(events, start, end):
    """Busy nanoseconds by a sweep over edges: another method than
    harness.trace.merged."""
    edges = []
    for _, s, d in events:
        lo, hi = max(s, start), min(s + d, end)
        if hi > lo:
            edges += [(lo, 1), (hi, -1)]
    busy = depth = 0
    last = None
    for t, step in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_trace_sums(recorded):
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    start, end = trace.window(recorded)
    busy_s, window_s = trace.busy_seconds(recorded)
    assert window_s == pytest.approx((end - start) / 1e9)
    assert busy_s * 1e9 == pytest.approx(_sweep_busy_ns(ops, start, end))
    # the numbers this trace is known to hold
    assert window_s == pytest.approx(0.278169357)
    assert busy_s == pytest.approx(0.277640518)
    kernel_s, events = trace.kernel_seconds(
        recorded, r"tpu_custom_call\(bf16\[1536,128,64\]\)")
    assert events == 72  # 36 kernel calls a step, two steps
    assert kernel_s == pytest.approx(0.069200783)
    assert kernel_s * 1e9 == pytest.approx(sum(
        min(s + d, end) - max(s, start) for n, s, d in ops
        if "tpu_custom_call(bf16[1536,128,64])" in n))
    assert trace.mean_step_period_s(recorded, "jit_step") == pytest.approx(
        0.1388846785)
    assert len(trace.step_starts(recorded, "jit_step")) == 3
    idle = trace.idle_gaps(recorded)
    assert sum(s for _, s in idle) == pytest.approx(window_s - busy_s)
    top = trace.top_device_ops(recorded)
    assert len(top) == 10 and top[0][0] == "fusion.135 f32[30522,768]"
    assert top == sorted(top, key=lambda kv: -kv[1])


def test_trace_arithmetic_on_a_made_up_record():
    record = {
        "devices": {"/device:TPU:0": {
            "ops": [["a", 100, 50], ["k tpu_custom_call(x)", 120, 60],
                    ["b", 300, 100], ["outside", 2000, 10]],
            "modules": [["jit_step(1)", 100, 300], ["jit_step(1)", 500, 100],
                        ["jit_other(2)", 600, 10]]}},
        "host": [["bench_window", 0, 1000], ["step_call", 180, 100],
                 ["loss_read", 400, 500]]}
    busy, window = trace.busy_seconds(record)
    assert (busy, window) == (pytest.approx(180e-9), pytest.approx(1e-6))
    assert trace.kernel_seconds(record, "tpu_custom_call") == (
        pytest.approx(60e-9), 1)
    assert trace.mean_step_period_s(record, "jit_step") == pytest.approx(4e-7)
    assert trace.mean_step_period_s(record, "jit_other") is None
    gaps = dict(trace.idle_gaps(record))
    # 0-100 nobody, 180-300 step_call, 400-1000 loss_read
    assert gaps == {"host_other": pytest.approx(1e-7),
                    "step_call": pytest.approx(1.2e-7),
                    "loss_read": pytest.approx(6e-7)}


def test_short_name():
    hlo = ('%branch_0_fun.107 = (bf16[1536,128,64]{2,1,0:T(8,128)(2,1)}) '
           'custom-call(bf16[1536,128,64]{2,1,0} %bitcast.1751, f32[4]{0} '
           '%x), custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert trace.short_name(hlo) == \
        "branch_0_fun.107 tpu_custom_call(bf16[1536,128,64])"
    assert trace.short_name(
        "%fusion.135 = (f32[30522,768]{1,0:T(8,128)}) fusion(f32[] %p)") == \
        "fusion.135 f32[30522,768]"
    assert trace.short_name("jit_step(123)") == "jit_step(123)"


# -- FLOPs by shapes against hand counts ------------------------------------

def test_bert_base_flops_hand_count():
    mod = MANIFEST.module("configs", "bert_base")
    config = MANIFEST.config("bert_base")
    traffic = MANIFEST.cell_params("bert_base.seq128")
    tokens, masked = 128 * 128, 128 * 19
    layer = (2 * tokens * 768 * 2304        # q, k, v projections
             + 2 * tokens * 768 * 768       # attention output projection
             + 2 * tokens * 768 * 3072 * 2  # the two FFN products
             + 2 * 128 * 12 * 128 * 128 * 64 * 2)  # QK^T and PV
    heads = (2 * 128 * 768 * 768 + 2 * 128 * 768 * 2
             + 2 * masked * 768 * 768 + 2 * masked * 768 * 30522)
    assert mod.masked_per_sequence(config, traffic) == 19
    assert mod.model_flops_per_step(config, traffic) == 3 * (12 * layer + heads)
    assert mod.model_flops_per_step(config, traffic) / tokens == \
        pytest.approx(545e6, rel=0.01)
    assert mod.units_per_step(config, traffic) == tokens


def test_resnet50_flops_hand_count():
    mod = MANIFEST.module("configs", "resnet50_v1")
    config = MANIFEST.config("resnet50_v1")
    traffic = MANIFEST.cell_params("resnet50_v1.train224")

    def stage(size, cin, mid, cout, blocks):
        first = size * size * (cin * mid + 9 * mid * mid + mid * cout
                               + cin * cout)
        rest = size * size * (cout * mid + 9 * mid * mid + mid * cout)
        return first + (blocks - 1) * rest

    macs = (112 * 112 * 49 * 3 * 64
            + stage(56, 64, 64, 256, 3) + stage(28, 256, 128, 512, 4)
            + stage(14, 512, 256, 1024, 6) + stage(7, 1024, 512, 2048, 3)
            + 2048 * 1000)
    # He et al. 2015 Table 1: 3.8e9 multiply-adds for the 50-layer net
    assert macs == pytest.approx(3.8e9, rel=0.03)
    assert mod.model_flops_per_step(config, traffic) == 3 * 2 * macs * 128
    assert mod.units_per_step(config, traffic) == 128


def test_flash_attention_work_by_shapes():
    mod = MANIFEST.module("layer_metrics", "flash_attn_roofline")
    flops, moved = mod.work(128, 12, 128, 64, 12)
    assert flops == 12 * (4 + 8) * 128 * 12 * 128 * 128 * 64
    assert moved == 12 * (4 + 8) * 128 * 12 * 128 * 64 * 2
    # bandwidth-bound at head size 64: bytes / 819e9 over FLOPs / 197e12
    assert moved / 819e9 > flops / 197e12


# -- the comparison ---------------------------------------------------------

def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = np.array([1.0, 2.0, 4.0, 1e-6])
    gap, leaf = compare.worst_leaf_gap([1.0, 2.2, 4.0, 3e-6], ref)
    assert (leaf, gap) == (1, pytest.approx(0.1))
    # the all-but-zero leaf is measured against the median leaf (1.5)
    gap, leaf = compare.worst_leaf_gap([1.0, 2.0, 4.0, 0.3], ref)
    assert (leaf, gap) == (3, pytest.approx(0.3 / 1.5, rel=1e-3))


def test_unchanged_state_reads_one_and_negligible_gradients_are_left_out():
    reference = {"losses": [2.0, 1.9, 1.8],
                 "grad_norms": np.array([1.0, 1.0, 1e-9, 0.0]),
                 "change_norms": np.array([0.3, 0.3, 0.3, 0.2])}
    trainable = [True, True, True, False]
    same = compare.readings(reference, reference, trainable)
    assert all(same[k] == 0 for k in ("loss1_gap", "grad_norm_gap",
                                      "change_norm_gap"))
    stuck = dict(reference, change_norms=np.zeros(4))
    assert compare.readings(stuck, reference, trainable)[
        "change_norm_gap"] == pytest.approx(1.0)
    # leaf 2 moves by round-off alone under Adam: not compared
    noisy = dict(reference, change_norms=np.array([0.3, 0.3, 0.9, 0.2]))
    assert compare.readings(noisy, reference, trainable)[
        "change_norm_gap"] == 0
    compared, correct = compare.judge({"loss1_gap": float("nan")},
                                      {"loss1_gap": 0.1})
    assert correct is False


# -- run.py -----------------------------------------------------------------

def test_run_py_exits_nonzero_without_a_chip_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tiny.REPO)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "not a TPU" in done.stderr


# -- data-driven: new files and new entries only ---------------------------

def test_a_cell_a_traffic_kind_and_a_metric_are_new_files_only(tmp_path):
    root = tiny.make_root(tmp_path / "r")
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "traffic", "dummy_kind.py"), "w") as f:
        f.write(
            "def measure(job):\n"
            "    return {'attempted': 1, 'failed': 0, 'spans': {},\n"
            "            'counters': {'answers': 42},\n"
            "            'end_to_end': {'setup_s': 1.0, 'step_ms': 2.0}}\n"
            "def verify(job, record):\n"
            "    record['reference_s'] = 0.0\n"
            "    return {}, True\n")
    with open(os.path.join(bench, "layer_metrics", "dummy_answers.py"),
              "w") as f:
        f.write("def read(run):\n    return run.counters.get('answers')\n")
    with open(os.path.join(bench, "workloads", "bert_tiny.dummy.json"),
              "w") as f:
        json.dump({"kind": "dummy_kind", "limits": {}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    before = json.dumps(data["workloads"][:2])
    data["workloads"].append({"name": "bert_tiny.dummy", "config": "bert_tiny",
                              "traffic": "dummy", "chips": 1, "why": "test"})
    data["per_layer"].append(
        {"name": "dummy_answers", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "entry points",
         "moves": "step_ms", "workloads": ["bert_tiny.dummy"]})
    assert json.dumps(data["workloads"][:2]) == before
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    run = tiny.load_run_module()
    manifest = Manifest(root)
    job = run.make_job(manifest, "bert_tiny.dummy", 1, 0.1, 0,
                       tiny.CPU_DEVICE)
    result = run.drive(job)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "step_ms"}
    record = job.traffic_mod.measure(job)
    layer = run.read_layer_metrics(job, record, None)
    assert layer["dummy_answers"] == {"value": 42, "unit": "count"}
    # readers that find nothing to read are left out, never reported as 0
    assert "device_idle_pct" not in layer and "step_mfu" not in layer
    with pytest.raises(KeyError):
        manifest.cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        manifest.module("layer_metrics", "no_such_metric")
