"""The `qwen3_next_80b` configuration at a tiny size on the CPU, through
the harness as tiny.py drives the others: the plain reference (the
delta rule token by token) against the decoder (the chunked rule)
through `DataParallelTrainer.step`, the int8 control and the half-batch
fault failing the limits, a sound run past the look for a chip; the
issue's arithmetic of the cut at the published sizes; and the two
per-layer readers this configuration brings, on a synthetic trace."""
from __future__ import annotations

import copy
import json
import os
import shutil
import types

import pytest

import tiny

CELL = "qwen3_next_tiny.seq96"
REAL, REAL_CELL = "qwen3_next_80b", "qwen3_next_80b.seq8k"


def _tiny_qwen(config):
    config = copy.deepcopy(config)
    config.update(
        name="qwen3_next_tiny", vocab_size=128, hidden_size=64, head_dim=16,
        num_attention_heads=8, num_key_value_heads=2,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=4, router_width=16, first_expert=4,
        num_experts_per_tok=3, num_hidden_layers=4)
    config["assumed"]["compute_dtype"] = "float32"
    config["assumed"]["init_stdev"] = 0.05
    return config


def make_root(tmp):
    """tiny.make_root's copy with a tiny qwen3_next configuration and
    cell added the way a PR adds them: new files, new entries."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", REAL + ".json")) as f:
        config = _tiny_qwen(json.load(f))
    with open(os.path.join(bench, "configs", "qwen3_next_tiny.json"),
              "w") as f:
        json.dump(config, f)
    for directory in ("configs", "reference"):
        shutil.copy(os.path.join(bench, directory, REAL + ".py"),
                    os.path.join(bench, directory, "qwen3_next_tiny.py"))
    with open(os.path.join(bench, "workloads", CELL + ".json"), "w") as f:
        # 96 tokens: a chunk and a half of the program's rule
        json.dump({"kind": "train_steps", "batch": 8, "seq_len": 96,
                   "pool": 4, "loss_every": 2, "check_steps": 3,
                   "rate_metric": "train_tokens_per_s",
                   "step_program": "jit_step", "traced_steps": 4,
                   "limits": tiny.TINY_LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "qwen3_next_tiny", "source": "test", "reduced": [],
         "why": "test", "file": "benchmarks/configs/qwen3_next_tiny.json"})
    manifest["workloads"].append(
        {"name": CELL, "config": "qwen3_next_tiny", "traffic": "seq96",
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

    manifest = Manifest(make_root(tmp_path_factory.mktemp("qwen") / "r"))
    return run, manifest


@pytest.fixture(scope="module")
def readings(bench):
    run, manifest = bench
    job = run.make_job(manifest, CELL, 2 ** 31 + 7, 0.0, 0, tiny.CPU_DEVICE)
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
    result = run.drive(run.make_job(manifest, CELL, 11, 0.2, 0,
                                    tiny.CPU_DEVICE))
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"setup_s", "step_ms", "train_tokens_per_s"} <= set(
        result["metrics"])
    names = {m["name"] for m in manifest.metrics("per_layer", CELL)}
    assert {"linear_attn_ms", "delta_rule_roofline", "attn_mixed_roofline",
            "expert_matmul_roofline", "moe_ms", "kernel_calls_per_step",
            "step_mfu", "fwd_ms", "device_idle_pct"} <= names
    assert "flash_attn_roofline" not in names
    # the cell the PR adds reports what the accepted cells report
    real = {m["name"] for m in manifest.metrics("per_layer", REAL_CELL)}
    assert names == real
    assert {m["name"] for m in manifest.metrics("end_to_end", REAL_CELL)} \
        == {"train_tokens_per_s", "step_ms", "setup_s"}


def test_leaves_follow_the_programs_order_and_name_every_part(bench):
    import numpy as np

    _, manifest = bench
    reference = manifest.module("reference", "qwen3_next_tiny")
    config = manifest.config("qwen3_next_tiny")
    specs, parts = reference.param_specs(config), reference.leaf_parts(config)
    assert len(specs) == len(parts) == len(reference.trainable(config))
    assert len(reference.leaf_names(config)) == sum(parts)
    # [q | k | v | z] (16 | 16 | 64 | 64) in pieces of the key width
    assert parts[3] == 10 and specs[3][0] == (2 * 16 + 2 * 64, 64)
    assert reference.trainable(config)[0] is False
    assert all(np.prod(shape) > 0 for shape, _, _ in specs)
    with pytest.raises(ValueError, match="published pattern"):
        reference.param_specs(dict(config, mlp_only_layers=[0]))


def test_the_cut_follows_the_issues_arithmetic(bench):
    """The real configuration: every width as published, the share's
    parameters, the step's FLOPs and the rule's work by hand."""
    import numpy as np

    _, manifest = bench
    module = manifest.module("configs", REAL)
    config = manifest.config(REAL)
    traffic = manifest.cell_params(REAL_CELL)
    reference = manifest.module("reference", REAL)
    published = {
        "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
        "num_key_value_heads": 2, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "num_experts_per_tok": 10, "router_width": 512,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "full_attention_interval": 4, "rms_norm_eps": 1e-6}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["vocab_size"] == 18992 == config["published"][
        "vocab_size"] // 8
    assert config["num_experts"] == 16 == len(config["held_expert_ids"])
    assert module.layer_types(config) == ["linear_attention"] * 3 + [
        "full_attention"]
    assert module.mlp_layer_types(config) == ["sparse"] * 4
    # 424.3 M parameters: 33.7 M a DeltaNet mixer, 27.3 M the attention
    count = sum(int(np.prod(shape))
                for shape, _, _ in reference.param_specs(config)[1:])
    mixers = 3 * (2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128
                  + 4096 * 2048) + (2048 * 8192 + 2 * 2048 * 512 + 2 * 256
                                    + 4096 * 2048)
    experts = 4 * (2048 * 512 + 16 * 3 * 2048 * 512 + 3 * 2048 * 512 + 2048)
    assert count == mixers + experts + 2 * 18992 * 2048 + 9 * 2048
    assert 424.0e6 < count < 424.6e6
    # [q | k | v | z] compared as q, k and the halves of v and of z
    assert reference.leaf_parts(config)[3] == 6
    tokens = 2 * 8192
    assert module.units_per_step(config, traffic) == tokens
    assert module.expected_expert_rows(config, traffic) == 5120
    flops = module.model_flops_per_step(config, traffic)
    forward = (
        3 * (2 * tokens * 2048 * (12288 + 64 + 4096)
             + 6 * tokens * 32 * 128 * 128)
        + 2 * tokens * 2048 * (8192 + 512 + 512 + 4096)
        + 4 * 2 * 16 * (8192 * 8193 // 2) * 256
        + 2 * tokens * 2048 * 18992
        + 4 * (2 * tokens * 2048 * 512 + tokens * 2048 * (6 * 512 + 2)
               + 6 * 5120 * 2048 * 512))
    assert flops == 3 * forward and 2.1e13 < flops < 2.3e13
    rule_flops, rule_bytes = module.delta_rule_work(config, traffic)
    assert rule_flops == 3 * 18 * tokens * 32 * 128 * 128
    one_pass = tokens * (2 * (2 * 2048 + 4096) + 2 * 4 * 32)
    assert rule_bytes == 3 * (3 * one_pass + 2 * tokens * 4096 * 2)
    # bandwidth bounds it: 4 ms a step against 2.4 ms of products
    assert rule_bytes / 819e9 > rule_flops / 197e12
    attention, moved = module.attention_work(config, traffic)
    assert attention == 12 * 2 * 16 * (8192 * 8193 // 2) * 256
    assert moved == (6 * 16 + 6 * 2) * tokens * 256 * 2
    assert module.attention_kernel_events(config, traffic) == (
        r"tpu_custom_call\(bf16\[4,8,8192,256\]\)")
    assert module.expert_work(config, [5120])[0] == 18 * 5120 * 2048 * 512
    # the mixers' dense products: [q | gate], k, v, out of the attention
    # layer; [q | k | v | z], [b | a], out of each Gated DeltaNet layer
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    linear = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert (full, linear) == (27_262_976, 33_685_504)
    rows = (10240 + 2 * 2560 + 6144) + 3 * (14336 + 2112 + 6144)
    proj_flops, proj_bytes = module.projection_work(config, traffic)
    assert proj_flops == 6 * tokens * (full + 3 * linear)
    assert proj_bytes == 3 * 2 * (full + 3 * linear + tokens * rows)
    # 12.61 TFLOP, 64.0 ms at the chip's peak; the bytes need 11.7 ms
    assert proj_flops / 197e12 == pytest.approx(0.0640, rel=1e-3)
    assert proj_bytes / 819e9 == pytest.approx(0.01166, rel=1e-2)


def test_make_batch_is_seeded_zipf_with_shifted_labels(bench):
    import numpy as np

    _, manifest = bench
    module = manifest.module("configs", "qwen3_next_tiny")
    config, traffic = manifest.config("qwen3_next_tiny"), \
        manifest.cell_params(CELL)
    (ids, labels), y = module.make_batch(np.random.RandomState(2 ** 31 + 3),
                                         config, traffic)
    again = module.make_batch(np.random.RandomState(2 ** 31 + 3), config,
                              traffic)[0][0]
    assert ids.shape == labels.shape == (8, 96) and y.shape == (8,)
    np.testing.assert_array_equal(ids, again)
    np.testing.assert_array_equal(ids[:, 1:], labels[:, :-1])
    assert ids.max() < 128 and (ids < 8).mean() > 0.3   # Zipf: a heavy head


# -- the two readers on a synthetic trace -----------------------------------

LA = "jit(step_phases)/jvp(forward)/linear_attention"
RULE = LA + "/jit(_k_gated_delta_rule)/delta_rule"
STEP_TEXT = f"""
%fusion.1 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{LA}/dot_general"}}
%fusion.2 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{LA}/jit(_k_causal_conv1d)/conv/mul"}}
%fusion.3 = f32[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{RULE}/closed_call/dot_general"}}
%while.4 = s32[] while(%p), metadata={{op_name="{RULE}/closed_call/while"}}
%fusion.5 = f32[8,8] fusion(%p), kind=kLoop, metadata={{op_name="{RULE}/closed_call/while/body/dot_general"}}
%while.6.clone.1 = s32[] while(%p), metadata={{op_name="{RULE}/while"}}
%fusion.7 = f32[8,8] fusion(%p), kind=kLoop, metadata={{op_name="jit(step_phases)/transpose(jvp(forward))/jvp(forward)/checkpoint/linear_attention/jit(_k_gated_delta_rule)/delta_rule/while/body/closed_call/checkpoint/dot_general"}}
%fusion.8 = bf16[8,8] fusion(%p), kind=kLoop, metadata={{op_name="jit(step_phases)/jvp(forward)/attention_full/dot_general"}}
%call.9 = f32[8] call(%p), metadata={{op_name="{RULE}/closed_call"}}
%attn.10 = bf16[4,8,8192,256] custom-call(%q, %k), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step_phases)/jvp(forward)/attention_full/pallas_call"}}
"""


def _synthetic_run(manifest, text=STEP_TEXT):
    ms = 1_000_000
    ops = [["fusion.1 bf16[8,8]", 10 * ms, 2 * ms],
           ["fusion.2 bf16[8,8]", 12 * ms, 3 * ms],
           ["fusion.3 f32[8,8]", 15 * ms, 4 * ms],
           # the chunk scan: the loop's own event around two turns of
           # its body, and the cloned loop of the recomputed forward
           ["while.4 s32[]", 19 * ms, 12 * ms],
           ["fusion.5 f32[8,8]", 20 * ms, 5 * ms],
           ["fusion.5 f32[8,8]", 25 * ms, 5 * ms],
           ["while.6.clone.1 s32[]", 40 * ms, 10 * ms],
           ["fusion.7 f32[8,8]", 41 * ms, 8 * ms],
           ["fusion.8 bf16[8,8]", 60 * ms, 7 * ms],
           ["call.9 f32[8]", 15 * ms, 20 * ms],
           ["attn.10 tpu_custom_call(bf16[4,8,8192,256])", 70 * ms, 11 * ms]]
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


def test_linear_attn_ms_counts_no_container_twice(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "linear_attn_ms")
    # projection 2 + conv 3 + the rule's products 4 + the scan's body
    # 5 + 5 + the backward's 8; neither loop's own event (12, 10), the
    # cloned one included, nor the call's (20); not the attention's 7
    assert reader.read(_synthetic_run(manifest)) == pytest.approx(27.0)
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    parent = _synthetic_run(manifest, STEP_TEXT.replace(
        "linear_attention", "attention_window"))
    assert reader.read(parent) is None


def test_delta_rule_roofline_reads_the_rule_alone(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "delta_rule_roofline")
    run = _synthetic_run(manifest)
    flops, moved = run.config_mod.delta_rule_work(run.config, run.traffic)
    least = max(flops / 197e12, moved / 819e9)
    # 4 + 5 + 5 + 8 ms a step under `delta_rule`
    assert reader.read(run) == pytest.approx(100 * least / 0.022)
    no_scope = _synthetic_run(manifest, STEP_TEXT.replace(
        "/delta_rule", "/rule").replace("_delta_rule", "_rule"))
    assert reader.read(no_scope) is None
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    # a configuration without the work function (the accepted ones)
    other = _synthetic_run(manifest)
    other.config_mod = manifest.module("configs", "laguna_xs2")
    assert reader.read(other) is None


def test_mixer_proj_roofline_leaves_rule_conv_kernels_and_containers_out(
        bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "mixer_proj_roofline")
    run = _synthetic_run(manifest)
    flops, moved = run.config_mod.projection_work(run.config, run.traffic)
    least = max(flops / 197e12, moved / 819e9)
    # the linear mixer's projection 2 ms and the attention layer's 7: not
    # the convolution's 3, the rule's 4 + 5 + 5 + 8, the attention
    # kernel's 11, nor any loop's or call's own event
    assert reader.read(run) == pytest.approx(100 * least / 0.009)
    # with the scope's other readers the mixers' time splits into parts
    # that add up: 27 under `linear_attention` = 2 + conv 3 + rule 22
    linear = manifest.module("layer_metrics", "linear_attn_ms").read(run)
    assert linear == pytest.approx(2.0 + 3.0 + 22.0)
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    no_scopes = _synthetic_run(manifest, STEP_TEXT.replace(
        "linear_attention", "mixer").replace("attention_full", "mixer"))
    assert reader.read(no_scopes) is None
    other = _synthetic_run(manifest)
    other.config_mod = manifest.module("configs", "bert_base")
    assert reader.read(other) is None
