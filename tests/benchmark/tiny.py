"""A copy of the benchmark in a temporary root with tiny configurations
and cells beside the real ones: what the CPU tests drive.  The tiny
files are NEW files and new BENCHMARK.json entries only, which is how a
later PR adds a configuration and a cell."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")

TINY_LIMITS = {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
               "grad_norm_gap": 2e-3, "change_norm_gap": 2e-3}

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"peak_flops_bf16": 1.0, "peak_hbm_bytes_per_s": 1.0,
                        "hbm_bytes": 1.0}}


def on_path():
    """Make `harness` and `mxnet_tpu` importable, as run.py does."""
    for p in (REPO, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_run_module():
    on_path()
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tiny_bert(config):
    config = copy.deepcopy(config)
    config.update(name="bert_tiny", hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=128,
                  vocab_size=1000, max_position_embeddings=64)
    config["assumed"]["compute_dtype"] = "float32"
    return config


def _tiny_resnet(config):
    config = copy.deepcopy(config)
    config.update(name="resnet_tiny", layers=[1, 1], channels=[32, 64],
                  stem_channels=16, image_size=32, num_classes=10)
    config["assumed"]["compute_dtype"] = "float32"
    return config


def make_root(tmp):
    """tmp/BENCHMARK.json + tmp/benchmarks with two tiny cells added."""
    root = str(tmp)
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for real, tiny, shrink, traffic, cell in (
            ("bert_base", "bert_tiny", _tiny_bert, "seq32",
             {"kind": "train_steps", "batch": 16, "seq_len": 32, "pool": 4,
              "loss_every": 2, "check_steps": 3,
              "rate_metric": "train_tokens_per_s",
              "step_program": "jit_step", "traced_steps": 4,
              "limits": TINY_LIMITS}),
            ("resnet50_v1", "resnet_tiny", _tiny_resnet, "train32",
             {"kind": "train_steps", "batch": 16, "pool": 4, "loss_every": 2,
              "check_steps": 3, "rate_metric": "train_images_per_s",
              "step_program": "jit_step", "traced_steps": 4,
              "limits": TINY_LIMITS})):
        with open(os.path.join(bench, "configs", real + ".json")) as f:
            config = shrink(json.load(f))
        with open(os.path.join(bench, "configs", tiny + ".json"), "w") as f:
            json.dump(config, f)
        for directory in ("configs", "reference"):
            shutil.copy(os.path.join(bench, directory, real + ".py"),
                        os.path.join(bench, directory, tiny + ".py"))
        name = f"{tiny}.{traffic}"
        with open(os.path.join(bench, "workloads", name + ".json"), "w") as f:
            json.dump(cell, f)
        manifest["configs"].append(
            {"name": tiny, "source": "test", "reduced": [], "why": "test",
             "file": f"benchmarks/configs/{tiny}.json"})
        manifest["workloads"].append(
            {"name": name, "config": tiny, "traffic": traffic, "chips": 1,
             "why": "test"})
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            if f"{real}." in " ".join(metric.get("workloads", [])):
                metric["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
