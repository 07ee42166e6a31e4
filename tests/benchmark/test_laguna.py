"""The `laguna_xs2` configuration at a tiny size on the CPU, through
the harness as tiny.py drives the others: the plain reference against
the decoder through `DataParallelTrainer.step`, the int8 control and
the half-batch fault failing the limits, a sound run and broken steps
past the look for a chip; and the three per-layer readers this
configuration brings, on a synthetic trace."""
from __future__ import annotations

import copy
import json
import os
import shutil
import types

import pytest

import tiny

CELL = "laguna_tiny.seq32"


def _tiny_laguna(config):
    config = copy.deepcopy(config)
    config.update(
        name="laguna_tiny", vocab_size=128, hidden_size=64, head_dim=16,
        num_key_value_heads=2, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_experts=4, router_width=16, first_expert=4,
        num_experts_per_tok=2, sliding_window=8, num_hidden_layers=3,
        layer_types=["full_attention", "sliding_attention",
                     "full_attention"],
        num_attention_heads_per_layer=[12, 16, 12],
        mlp_layer_types=["dense", "sparse", "sparse"])
    config["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    config["assumed"]["compute_dtype"] = "float32"
    config["assumed"]["init_stdev"] = 0.05
    return config


def make_root(tmp):
    """tiny.make_root's copy with a tiny laguna configuration and cell
    added the way a PR adds them: new files, new entries."""
    root = tiny.make_root(tmp)
    bench = os.path.join(root, "benchmarks")
    with open(os.path.join(bench, "configs", "laguna_xs2.json")) as f:
        config = _tiny_laguna(json.load(f))
    with open(os.path.join(bench, "configs", "laguna_tiny.json"), "w") as f:
        json.dump(config, f)
    for directory in ("configs", "reference"):
        shutil.copy(os.path.join(bench, directory, "laguna_xs2.py"),
                    os.path.join(bench, directory, "laguna_tiny.py"))
    with open(os.path.join(bench, "workloads", CELL + ".json"), "w") as f:
        json.dump({"kind": "train_steps", "batch": 16, "seq_len": 32,
                   "pool": 4, "loss_every": 2, "check_steps": 3,
                   "rate_metric": "train_tokens_per_s",
                   "step_program": "jit_step", "traced_steps": 4,
                   "limits": tiny.TINY_LIMITS}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "laguna_tiny", "source": "test", "reduced": [],
         "why": "test", "file": "benchmarks/configs/laguna_tiny.json"})
    manifest["workloads"].append(
        {"name": CELL, "config": "laguna_tiny", "traffic": "seq32",
         "chips": 1, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "laguna_xs2.seq8k" in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    run = tiny.load_run_module()
    from harness.manifest import Manifest

    manifest = Manifest(make_root(tmp_path_factory.mktemp("laguna") / "r"))
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
    assert {"attn_mixed_roofline", "expert_matmul_roofline", "moe_ms",
            "kernel_calls_per_step", "step_mfu", "fwd_ms"} <= names
    assert "flash_attn_roofline" not in names


def test_look_names_the_worst_leaves_with_their_rows(bench):
    """benchmarks/look.py: rows and gradient norms a step on both
    sides, expert by expert; in float32 the two sides agree."""
    import importlib.util

    import numpy as np

    run, manifest = bench
    spec = importlib.util.spec_from_file_location(
        "benchmarks_look", os.path.join(manifest.root, "benchmarks",
                                        "look.py"))
    look = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(look)
    record = look.look_seed(run.make_job(manifest, CELL, 13, 0.0, 0,
                                         tiny.CPU_DEVICE), worst=60)
    assert record["readings"]["change_norm_gap"] < 2e-3
    experts = [w for w in record["worst"] if "expert_" in w["name"]]
    assert len(experts) == 16 and len(record["worst"]) == 52
    for w in experts:
        assert w["rows_program"] == w["rows_reference"]
        assert len(w["rows_program"]) == 3
        np.testing.assert_allclose(w["gradient_program"],
                                   w["gradient_reference"], rtol=1e-3,
                                   atol=1e-7)
    assert np.shape(record["rows_program"]) == (3, 2, 4)
    names = manifest.module("reference", "laguna_tiny").leaf_names(
        manifest.config("laguna_tiny"))
    assert len(names) == 52
    assert names[record["readings"]["change_norm_leaf"]]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(bench, monkeypatch, fault):
    import test_faults

    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    broken = getattr(test_faults, "_" + fault)
    monkeypatch.setattr(DataParallelTrainer, "step",
                        broken(DataParallelTrainer.step))
    run, manifest = bench
    result = run.drive(run.make_job(manifest, CELL, 12, 0.2, 0,
                                    tiny.CPU_DEVICE))
    assert result["correct"] is False, result["compared"]


def test_weights_seed_fixes_the_weights_and_leaves_the_batches_to_the_seed(
        bench):
    """The real cell states one: the routing, and with it the rows the
    expert path works on, follows the weights."""
    import numpy as np

    run, manifest = bench
    assert "weights_seed" not in manifest.cell_params(CELL)

    def made(seed, **more):
        job = run.make_job(manifest, CELL, seed, 0.0, 0, tiny.CPU_DEVICE)
        job.traffic = dict(job.traffic, **more)
        return (np.asarray(job.traffic_mod.seeded_weights(job)[1]),
                job.traffic_mod.make_pool(job)[0][0][0])

    (w1, x1), (w2, x2) = made(2 ** 31 + 1), made(2 ** 31 + 2)
    assert not np.array_equal(w1, w2) and not np.array_equal(x1, x2)
    (f1, y1), (f2, y2) = (made(2 ** 31 + 1, weights_seed=7),
                          made(2 ** 31 + 2, weights_seed=7))
    np.testing.assert_array_equal(f1, f2)
    np.testing.assert_array_equal(x1, y1)      # the batches are the seed's
    np.testing.assert_array_equal(x2, y2)
    assert not np.array_equal(f1, w1)


def test_the_real_cell_states_its_weights_seed_and_the_rule_that_picked_it(
        bench):
    """The draw is picked by a rule on the routing log that the cell's
    file states, with the readings of the seeds it passed over."""
    _, manifest = bench
    cell = manifest.cell_params("laguna_xs2.seq8k")
    assert cell["weights_seed"] == 3600000014
    note = cell["weights_seed_note"]
    assert "RULE ON THE ROUTING LOG" in note and "12,288" in note
    for passed_over in ("20,527", "14,328", "14,371"):
        assert passed_over in note
    # the cells whose work does not follow their weights state none
    for name in ("bert_base.seq128", "resnet50_v1.train224",
                 "qwen3_next_80b.seq8k"):
        assert "weights_seed" not in manifest.cell_params(name)


def test_make_batch_is_seeded_zipf_with_shifted_labels(bench):
    import numpy as np

    _, manifest = bench
    module = manifest.module("configs", "laguna_tiny")
    config, traffic = manifest.config("laguna_tiny"), manifest.cell_params(
        CELL)
    (ids, labels), y = module.make_batch(np.random.RandomState(2 ** 31 + 3),
                                         config, traffic)
    again = module.make_batch(np.random.RandomState(2 ** 31 + 3), config,
                              traffic)[0][0]
    assert ids.shape == labels.shape == (16, 32) and y.shape == (16,)
    np.testing.assert_array_equal(ids, again)
    np.testing.assert_array_equal(ids[:, 1:], labels[:, :-1])
    assert ids.max() < 128 and (ids < 8).mean() > 0.3   # Zipf: a heavy head


def test_model_flops_follow_the_published_sizes(bench):
    """The real configuration: the issue's arithmetic of the cut."""
    _, manifest = bench
    module = manifest.module("configs", "laguna_xs2")
    config = manifest.config("laguna_xs2")
    traffic = manifest.cell_params("laguna_xs2.seq8k")
    reference = manifest.module("reference", "laguna_xs2")
    import numpy as np

    count = sum(int(np.prod(shape))
                for shape, _, _ in reference.param_specs(config)[1:])
    assert count == 490_297_344
    assert config["vocab_size"] == 12544 == config["published"][
        "vocab_size"] // 8
    flops = module.model_flops_per_step(config, traffic)
    assert 38.0e12 < flops < 39.5e12
    assert module.visible_pairs(8192, None) == 8192 * 8193 // 2
    assert module.visible_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert module.expected_expert_rows(config, traffic) == 8192
    attention, _ = module.attention_work(config, traffic)
    assert 0.25 < attention / flops < 0.6


def test_projection_work_by_hand_at_the_published_sizes(bench):
    _, manifest = bench
    module = manifest.module("configs", "laguna_xs2")
    config = manifest.config("laguna_xs2")
    traffic = manifest.cell_params("laguna_xs2.seq8k")
    tokens = 2 * 8192
    # q, k, v, the per-head gate and the output projection of a layer of
    # 48 query heads (the dense layer 0 and the full layer 4) and of 64
    # (the three window layers), 8 K/V heads, head size 128
    full = 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48 + 6144 * 2048
    window = 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64 + 8192 * 2048
    assert (full, window) == (29_458_432, 37_879_808)
    weights = 2 * full + 3 * window
    # every product's input and output row: (2048 + 6144) + ...
    rows = 2 * (8192 + 2 * 3072 + 2096 + 8192) \
        + 3 * (10240 + 2 * 3072 + 2112 + 10240)
    flops, moved = module.projection_work(config, traffic)
    assert flops == 6 * tokens * weights
    assert moved == 3 * 2 * (weights + tokens * rows)
    # 16.96 TFLOP, 86.1 ms at the chip's peak; the bytes need 17.5 ms
    assert flops / 197e12 == pytest.approx(0.0861, rel=1e-3)
    assert moved / 819e9 == pytest.approx(0.0175, rel=1e-2)
    # the arithmetic itself, on one 8 x 4 weight
    from harness import work

    assert work.dense_work(tokens, [(8, 4)]) == (
        6 * tokens * 32, 6 * (32 + tokens * 12))


# -- the three readers on a synthetic trace ---------------------------------

STEP_TEXT = """
%fusion.1 = bf16[8,8] fusion(%p), kind=kLoop, metadata={op_name="jit(step_phases)/jvp(forward)/checkpoint/jit(f)/moe/router/dot_general"}
%sort.2 = s32[8] sort(%p), metadata={op_name="jit(step_phases)/jvp(forward)/checkpoint/jit(f)/moe/dispatch/sort"}
%ragged-dot-none.3 = bf16[8,8] custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_phases)/jvp(forward)/jit(f)/ragged-dot-none"}
%ragged-dot-metadata.4 = s32[17] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_phases)/transpose(jvp(forward))/jvp(forward)/checkpoint/jit(f)/ragged-dot-metadata"}
%fusion.9 = bf16[8,8] fusion(%p), kind=kLoop, metadata={op_name="jit(step_phases)/jvp(forward)/jit(f)/moe/experts/jit(silu)/mul"}
%attn.5 = bf16[16,8,8192,128] custom-call(%q, %k), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_phases)/jvp(forward)/attention_window/pallas_call"}
%fusion.6 = f32[8] fusion(%p), kind=kLoop, metadata={op_name="jit(step_phases)/optimizer/mul"}
%while.8 = s32[] while(%p), metadata={op_name="jit(step_phases)/jvp(forward)/checkpoint/jit(f)/moe/dispatch/while"}
%fusion.10 = bf16[8,8] fusion(%p), kind=kLoop, metadata={op_name="jit(step_phases)/jvp(forward)/checkpoint/attention_window/dot_general"}
%fusion.11 = bf16[8,8] fusion(%p), kind=kLoop, metadata={op_name="jit(step_phases)/transpose(jvp(forward))/checkpoint/attention_full/jit(sigmoid)/mul"}
%cond.12.clone.1 = bf16[8,8] conditional(%i, %p), metadata={op_name="jit(step_phases)/transpose(jvp(forward))/checkpoint/attention_full/cond"}
"""


def _synthetic_run(manifest, text=STEP_TEXT):
    ms = 1_000_000
    ops = [["fusion.1 bf16[8,8]", 10 * ms, 2 * ms],
           ["sort.2 s32[8]", 12 * ms, 3 * ms],
           # the grouped products' kernels, as the TPU compiler names
           # them: outside every scope of the program's
           ["ragged-dot-none.3 tpu_custom_call(s32[1])", 15 * ms, 4 * ms],
           ["ragged-dot-metadata.4 tpu_custom_call(s32[16])", 19 * ms,
            6 * ms],
           ["fusion.9 bf16[8,8]", 80 * ms, 1 * ms],
           ["attn.5 tpu_custom_call(bf16[16,8,8192,128])", 25 * ms, 20 * ms],
           ["attn.7 tpu_custom_call(bf16[16,6,8192,128])", 45 * ms, 30 * ms],
           ["fusion.6 f32[8]", 75 * ms, 5 * ms],
           # a loop's own event, around events counted already
           ["while.8 s32[]", 10 * ms, 15 * ms],
           # around the attention kernels: a projection, the gate, and a
           # cloned switch's own event that spans the gate's
           ["fusion.10 bf16[8,8]", 81 * ms, 3 * ms],
           ["fusion.11 bf16[8,8]", 85 * ms, 5 * ms],
           ["cond.12.clone.1 bf16[8,8]", 84 * ms, 9 * ms]]
    # two steps in the window: the same events again 100 ms later
    ops += [[n, s + 100 * ms, d] for n, s, d in ops]
    record = {"devices": {"/device:TPU:0": {
        "ops": ops,
        "modules": [["jit_step_phases(1)", 5 * ms, 90 * ms],
                    ["jit_step_phases(1)", 105 * ms, 90 * ms]]}},
        "host": [["bench_window", 0, 200 * ms]]}
    return types.SimpleNamespace(
        trace=record, spans={}, counters={},
        cell=manifest.cell("laguna_xs2.seq8k"),
        traffic=manifest.cell_params("laguna_xs2.seq8k"),
        config=manifest.config("laguna_xs2"),
        config_mod=manifest.module("configs", "laguna_xs2"),
        peaks={"peak_flops_bf16": 197e12, "peak_hbm_bytes_per_s": 819e9},
        chips=1, program_text=lambda: text)


def test_moe_ms_sums_the_events_under_the_moe_scope(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "moe_ms")
    # router 2 + sort 3 + the grouped products' kernels 4 + 6 + the
    # SwiGLU pass 1; the loop's own event is not counted twice
    assert reader.read(_synthetic_run(manifest)) == pytest.approx(16.0)
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    no_scopes = _synthetic_run(manifest, STEP_TEXT.replace(
        "/moe/", "/ffn/").replace("/ragged-dot-", "/dot-"))
    assert reader.read(no_scopes) is None


def test_attn_mixed_roofline_reads_both_kinds_of_kernel(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "attn_mixed_roofline")
    run = _synthetic_run(manifest)
    flops, moved = run.config_mod.attention_work(run.config, run.traffic)
    least = max(flops / 197e12, moved / 819e9)
    assert reader.read(run) == pytest.approx(100 * least / 0.050)
    run.trace["devices"]["/device:TPU:0"]["ops"] = [
        e for e in run.trace["devices"]["/device:TPU:0"]["ops"]
        if "8192" not in e[0]]
    assert reader.read(run) is None


def test_mixer_proj_roofline_reads_what_runs_around_the_kernels(bench):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "mixer_proj_roofline")
    run = _synthetic_run(manifest)
    flops, moved = run.config_mod.projection_work(run.config, run.traffic)
    least = max(flops / 197e12, moved / 819e9)
    # the projection's 3 ms and the gate's 5: not the window kernel's 20
    # under the same scope, nor the switch's own 9
    assert reader.read(run) == pytest.approx(100 * least / 0.008)
    untraced = _synthetic_run(manifest)
    untraced.trace = None
    assert reader.read(untraced) is None
    no_scopes = _synthetic_run(manifest, STEP_TEXT.replace(
        "/attention_", "/mixer_"))
    assert reader.read(no_scopes) is None
    for other in ("bert_base", "resnet50_v1"):   # no `projection_work`
        run.config_mod = manifest.module("configs", other)
        assert reader.read(run) is None


def test_expert_matmul_roofline_uses_the_routing_logs_rows(bench,
                                                           monkeypatch):
    _, manifest = bench
    reader = manifest.module("layer_metrics", "expert_matmul_roofline")
    run = _synthetic_run(manifest)
    monkeypatch.setattr(reader, "routed_rows", lambda: [8192, 4096])
    flops, moved = run.config_mod.expert_work(run.config, [8192, 4096])
    assert flops == 18 * (8192 + 4096) * 2048 * 512
    least = max(flops / 197e12, moved / 819e9)
    # the grouped products' kernels alone (4 + 6 ms), not the SwiGLU
    # pass under `moe/experts`
    assert reader.read(run) == pytest.approx(100 * least / 0.010)
    monkeypatch.setattr(reader, "routed_rows", lambda: None)
    assert reader.read(run) is None


def test_routed_rows_are_the_newest_trainers(bench):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import decoder_lm
    from mxnet_tpu.parallel import data_parallel

    _, manifest = bench
    reader = manifest.module("layer_metrics", "expert_matmul_roofline")
    config = manifest.config("laguna_tiny")
    # 8 sequences: one for each of conftest's virtual devices
    ids = np.arange(256).reshape(8, 32).astype(np.int32) % 128
    trainers = []
    for _ in range(2):
        net = decoder_lm.DecoderLM(config)
        net.initialize(mx.init.Normal(0.05))
        trainers.append(data_parallel.DataParallelTrainer(
            net, lambda out, _: out, "sgd", {"learning_rate": 0.1}))
        trainers[-1].build((ids, ids))
    older, newest = trainers
    assert reader.routed_rows() == [0, 0]          # no step yet
    newest.step((ids, ids), np.zeros((8,), np.float32))
    rows = reader.routed_rows()
    assert len(rows) == 2 and all(0 < n <= 512 for n in rows)
    log = newest.aux_params()[newest.block.routing_log.name]
    assert rows == [float(r[:-1].sum())
                    for r in newest.block.routing_rows(log).values()]
    del older
