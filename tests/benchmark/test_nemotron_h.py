"""The `nemotron3_nano_30b` configuration at a tiny size on the CPU,
through the harness as tiny.py drives the others: the plain reference
(the SSD token by token) against the decoder (the chunked scan, layers
of one part) through `DataParallelTrainer.step`, the int8 control and
the half-batch fault failing the limits, a sound run past the look for
a chip; the issue's arithmetic of the cut at the published sizes; and
the two per-layer readers this configuration brings, on a synthetic
trace."""
from __future__ import annotations

import copy
import json
import os
import shutil
import types

import pytest

import tiny

CELL = "nemotron_h_tiny.seq80"
REAL, REAL_CELL = "nemotron3_nano_30b", "nemotron3_nano_30b.seq8k"


def _tiny_nemotron(config):
    config = copy.deepcopy(config)
    config.update(
        name="nemotron_h_tiny", vocab_size=128, hidden_size=64, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, mamba_num_heads=4,
        mamba_head_dim=16, n_groups=2, ssm_state_size=16, chunk_size=32,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        n_routed_experts=4, router_width=16, first_expert=4,
        num_experts_per_tok=3, num_hidden_layers=5,
        hybrid_override_pattern="MEM*E")
    config["assumed"]["compute_dtype"] = "float32"
    config["assumed"]["init_stdev"] = 0.05
    return config


def make_root(tmp):
    """tiny.make_root's copy with a tiny nemotron_h configuration and
    cell added the way a PR adds them: new files, new entries."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", REAL + ".json")) as f:
        config = _tiny_nemotron(json.load(f))
    with open(os.path.join(bench, "configs", "nemotron_h_tiny.json"),
              "w") as f:
        json.dump(config, f)
    for directory in ("configs", "reference"):
        shutil.copy(os.path.join(bench, directory, REAL + ".py"),
                    os.path.join(bench, directory, "nemotron_h_tiny.py"))
    with open(os.path.join(bench, "workloads", CELL + ".json"), "w") as f:
        # 80 tokens: two chunks and a half of the program's scan
        json.dump({"kind": "train_steps", "batch": 8, "seq_len": 80,
                   "pool": 4, "loss_every": 2, "check_steps": 3,
                   "rate_metric": "train_tokens_per_s",
                   "step_program": "jit_step", "traced_steps": 4,
                   "limits": tiny.TINY_LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "nemotron_h_tiny", "source": "test", "reduced": [],
         "why": "test", "file": "benchmarks/configs/nemotron_h_tiny.json"})
    manifest["workloads"].append(
        {"name": CELL, "config": "nemotron_h_tiny", "traffic": "seq80",
         "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if REAL_CELL in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    run = tiny.load_run_module()
    from harness.manifest import Manifest

    manifest = Manifest(make_root(tmp_path_factory.mktemp("nemotron") / "r"))
    return run, manifest


@pytest.fixture(scope="module")
def readings(bench):
    run, manifest = bench
    job = run.make_job(manifest, CELL, 2 ** 31 + 11, 0.0, 0, tiny.CPU_DEVICE)
    return job.traffic_mod.read_seed(job, control=True, faults=True)


def test_reference_agrees_with_the_decoder(readings):
    from harness import compare

    compared, correct = compare.judge(readings["program"], tiny.TINY_LIMITS)
    assert correct, compared


def test_control_precision_is_not_correct(readings):
    from harness import compare

    compared, correct = compare.judge(readings["control"], tiny.TINY_LIMITS)
    assert not correct, compared
    assert any(c["value"] > 3 * c["limit"] for c in compared.values())


def test_half_batch_reference_is_not_correct(readings):
    from harness import compare

    compared, correct = compare.judge(readings["half_batch"],
                                      tiny.TINY_LIMITS)
    assert not correct, compared


def test_sound_run_is_correct_and_reports_the_cells_metrics(bench):
    run, manifest = bench
    result = run.drive(run.make_job(manifest, CELL, 13, 0.2, 0,
                                    tiny.CPU_DEVICE))
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"setup_s", "step_ms", "train_tokens_per_s"} <= set(
        result["metrics"])
    names = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert {"attn_mixed_roofline", "expert_matmul_roofline", "moe_ms",
            "kernel_calls_per_step", "step_mfu", "fwd_ms",
            "device_idle_pct"} <= names
    # the two readers of the state-space layers are no entries yet
    assert not names & {"ssm_ms", "ssd_roofline"}
    assert not names & {"flash_attn_roofline", "linear_attn_ms",
                        "delta_rule_roofline", "mixer_proj_roofline"}
    # the new cell reports what the accepted cells report
    real = {m["name"] for m in manifest.metrics("per_layer", REAL_CELL)}
    assert names == real
    assert {m["name"] for m in manifest.metrics("end_to_end", REAL_CELL)} \
        == {"train_tokens_per_s", "step_ms", "setup_s"}


def test_half_batch_fault_of_one_sequence_keeps_half_its_tokens(bench):
    """The cell trains on ONE sequence a step, whose half is no whole
    sequence (`rows` = batch // 2 = 0): the fault then keeps the first
    half of its tokens, and reads another loss and gradient."""
    import numpy as np

    from harness import weights

    _, manifest = bench
    reference = manifest.module("reference", "nemotron_h_tiny")
    config = dict(manifest.config("nemotron_h_tiny"), num_hidden_layers=2,
                  hybrid_override_pattern="ME")
    params0 = weights.make(5, reference.param_specs(config))
    tokens = np.random.RandomState(2).randint(0, 128, (1, 65)).astype(
        np.int32)
    batches = [(tokens[:, :-1], tokens[:, 1:])]
    whole = reference.follow(config, params0, batches, 0)
    half = reference.follow(config, params0, batches, 0, rows=0)
    shorter = reference.follow(
        config, params0, [(tokens[:, :32], tokens[:, 1:33])], 0)
    assert half["losses"] == shorter["losses"] != whole["losses"]
    np.testing.assert_allclose(half["grad_norms"], shorter["grad_norms"])


def test_leaves_follow_the_programs_order_and_name_every_part(bench):
    import numpy as np

    _, manifest = bench
    reference = manifest.module("reference", "nemotron_h_tiny")
    config = manifest.config("nemotron_h_tiny")
    specs, parts = reference.param_specs(config), reference.leaf_parts(config)
    assert len(specs) == len(parts) == len(reference.trainable(config))
    assert len(reference.leaf_names(config)) == sum(parts)
    # [x | B | C | z] (64 | 32 | 32 | 64) in pieces of the group width
    assert parts[4] == 6 and specs[4][0] == (192, 64)
    assert reference.trainable(config)[:3] == [False, False, True]
    assert all(np.prod(shape) > 0 for shape, _, _ in specs)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        reference.param_specs(dict(config, hybrid_override_pattern="MEM-E"))


def test_the_cut_follows_the_issues_arithmetic(bench):
    """The real configuration: every width as published, the share's
    parameters, the step's FLOPs and the scan's work by hand."""
    import numpy as np

    _, manifest = bench
    module = manifest.module("configs", REAL)
    config = manifest.config(REAL)
    traffic = manifest.cell_params(REAL_CELL)
    reference = manifest.module("reference", REAL)
    published = {
        "hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "mamba_num_heads": 64,
        "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
        "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "use_conv_bias": True,
        "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-5}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "hybrid_override_pattern"]
    assert config["published"]["hybrid_override_pattern"].startswith(
        config["hybrid_override_pattern"]) and config["published"][
        "hybrid_override_pattern"].count("*") == 6
    assert config["vocab_size"] == 16384 == config["published"][
        "vocab_size"] // 8
    assert config["n_routed_experts"] == 8 == len(config["held_expert_ids"])
    assert config["router_width"] == 128 == config["published"][
        "n_routed_experts"]
    assert module.layer_types(config) == [
        "mamba", None, "mamba", None, "mamba", "full_attention", None,
        "mamba", None]
    assert module.mlp_layer_types(config).count("sparse") == 4
    # 667.0 M parameters: 38.7 M a Mamba-2 mixer, 23.4 M the attention,
    # 20.3 M an expert layer outside its 8 experts of 9.98 M
    count = sum(int(np.prod(shape))
                for shape, _, _ in reference.param_specs(config)[2:])
    mamba = 2688 * 10240 + 2688 * 64 + 6144 * 4 + 6144 + 3 * 64 + 4096 \
        + 4096 * 2688 + 2688
    attention = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    expert_layer = 2688 * 128 + 2 * 2688 * 3712 + 2688
    expert = 2 * 2688 * 1856
    assert (mamba, attention, expert_layer, expert) == (
        38_744_896, 23_399_040, 20_302_464, 9_977_856)
    assert count == 4 * mamba + attention + 4 * (expert_layer + 8 * expert) \
        + 2 * 16384 * 2688 + 2688 == 666_962_944
    # and the correction bias, 128 a layer, beside the routing log
    assert reference.param_specs(config)[1][0] == (4, 128)
    # 18 bytes a parameter: 12.0 GB before any activation
    assert 18 * count == pytest.approx(12.005e9, rel=1e-4)
    # [x | B | C | z] compared as x, B, C and z in pieces of 1,024
    assert reference.leaf_parts(config)[4] == 10
    tokens = 8192
    assert module.units_per_step(config, traffic) == tokens
    assert module.expected_expert_rows(config, traffic) == 3072
    flops = module.model_flops_per_step(config, traffic)
    forward = (
        4 * (2 * tokens * 2688 * (10240 + 64 + 4096)
             + 4 * tokens * 64 * 64 * 128)
        + 2 * tokens * 2688 * (4096 + 512 + 4096)
        + 4 * 32 * (8192 * 8193 // 2) * 128
        + 2 * tokens * 2688 * 16384
        + 4 * (2 * tokens * 2688 * 128 + 4 * tokens * 2688 * 3712
               + 4 * 3072 * 2688 * 1856))
    assert flops == 3 * forward and 1.7e13 < flops < 1.8e13
    ssd_flops, ssd_bytes = module.ssd_work(config, traffic)
    assert ssd_flops == 4 * 12 * tokens * 64 * 64 * 128
    one_pass = tokens * (2 * (4096 + 2048) + 4 * 64)
    assert ssd_bytes == 4 * (3 * one_pass + 2 * tokens * 4096 * 2)
    # bandwidth bounds it: 2.2 ms a step against 1.0 ms of products
    assert ssd_bytes / 819e9 > ssd_flops / 197e12
    attention_flops, moved = module.attention_work(config, traffic)
    assert attention_flops == 12 * 32 * (8192 * 8193 // 2) * 128
    assert moved == (6 * 32 + 6 * 2) * tokens * 128 * 2
    assert module.attention_kernel_events(config, traffic) == (
        r"tpu_custom_call\(bf16\[2,16,8192,128\]\)")
    assert module.expert_work(config, [3072])[0] == 12 * 3072 * 2688 * 1856


def test_make_batch_is_seeded_zipf_with_shifted_labels(bench):
    import numpy as np

    _, manifest = bench
    module = manifest.module("configs", "nemotron_h_tiny")
    config, traffic = manifest.config("nemotron_h_tiny"), \
        manifest.cell_params(CELL)
    (ids, labels), y = module.make_batch(np.random.RandomState(2 ** 31 + 3),
                                         config, traffic)
    assert ids.shape == labels.shape == (8, 80) and y.shape == (8,)
    np.testing.assert_array_equal(ids[:, 1:], labels[:, :-1])
    assert ids.max() < 128 and (ids < 8).mean() > 0.3   # Zipf: a heavy head


# -- the two readers on a synthetic trace -----------------------------------

MB = "jit(step_phases)/jvp(forward)/mamba"
SCAN = MB + "/jit(_k_ssd)/ssd"
STEP_TEXT = f"""
%fusion.1 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{MB}/dot_general"}}
%fusion.2 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{MB}/jit(_k_causal_conv1d)/conv/mul"}}
%fusion.3 = f32[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{SCAN}/checkpoint/dot_general"}}
%fusion.4 = f32[8,8] fusion(%p), kind=kLoop, metadata={{op_name="jit(step_phases)/transpose(jvp(forward))/jvp(forward)/checkpoint/mamba/jit(_k_ssd)/ssd/checkpoint/exp"}}
%call.5 = f32[8] call(%p), metadata={{op_name="{SCAN}/checkpoint"}}
%fusion.6 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="jit(step_phases)/jvp(forward)/attention_full/dot_general"}}
%fusion.7 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="jit(step_phases)/jvp(forward)/moe/router/dot_general"}}
"""


def _synthetic_run(manifest, text=STEP_TEXT):
    ms = 1_000_000
    ops = [["fusion.1 bf16[8,8]", 10 * ms, 2 * ms],
           ["fusion.2 bf16[8,8]", 12 * ms, 3 * ms],
           ["fusion.3 f32[8,8]", 15 * ms, 4 * ms],
           ["fusion.4 f32[8,8]", 20 * ms, 6 * ms],
           # the call's own event around its body's
           ["call.5 f32[8]", 15 * ms, 20 * ms],
           ["fusion.6 bf16[8,8]", 40 * ms, 7 * ms],
           ["fusion.7 bf16[8,8]", 50 * ms, 5 * ms]]
    # two steps in the window: the same events again 100 ms later
    ops += [[n, s + 100 * ms, d] for n, s, d in ops]
    record = {"devices": {"/device:TPU:0": {
        "ops": ops,
        "modules": [["jit_step_phases(1)", 5 * ms, 90 * ms],
                    ["jit_step_phases(1)", 105 * ms, 90 * ms]]}},
        "host": [["bench_window", 0, 200 * ms]]}
    return types.SimpleNamespace(
        trace=record, spans={}, counters={},
        cell=manifest.cell(REAL_CELL),
        traffic=manifest.cell_params(REAL_CELL),
        config=manifest.config(REAL),
        config_mod=manifest.module("configs", REAL),
        peaks={"peak_flops_bf16": 197e12, "peak_hbm_bytes_per_s": 819e9},
        chips=1, program_text=lambda: text)


def test_ssm_ms_counts_the_mixers_and_no_container(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "ssm_ms")
    # projection 2 + conv 3 + the scan's 4 and its backward's 6; not the
    # call's own 20, the attention's 7 or the router's 5
    assert reader.read(_synthetic_run(manifest)) == pytest.approx(15.0)
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    parent = _synthetic_run(manifest, STEP_TEXT.replace("/mamba", "/mixer"))
    assert reader.read(parent) is None


def test_ssd_roofline_reads_the_scan_alone(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "ssd_roofline")
    run = _synthetic_run(manifest)
    flops, moved = run.config_mod.ssd_work(run.config, run.traffic)
    least = max(flops / 197e12, moved / 819e9)
    # 4 + 6 ms a step under `ssd`
    assert reader.read(run) == pytest.approx(100 * least / 0.010)
    assert 0 < reader.read(run) < 100
    no_scope = _synthetic_run(manifest, STEP_TEXT.replace("/ssd", "/scan"))
    assert reader.read(no_scope) is None
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    # a configuration without the work function (the accepted ones)
    other = _synthetic_run(manifest)
    other.config_mod = manifest.module("configs", "qwen3_next_80b")
    assert reader.read(other) is None
