"""Runs one cell of BENCHMARK.json once, on the chip it is started on:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result.  Without a TPU, with
fewer or more chips than the cell asks for, or on a device that
harness/peaks.json does not list, it exits non-zero and prints none.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def make_job(manifest, workload, seed, seconds, trace, device):
    cell = manifest.cell(workload)
    traffic = manifest.cell_params(workload)
    return types.SimpleNamespace(
        manifest=manifest, cell=cell, traffic=traffic,
        config=manifest.config(cell["config"]),
        config_mod=manifest.module("configs", cell["config"]),
        reference_mod=manifest.module("reference", cell["config"]),
        traffic_mod=manifest.module("traffic", traffic["kind"]),
        seed=seed, seconds=seconds, trace=bool(trace), device=device,
        trace_dir=os.path.join(manifest.root, ".bench_trace", workload),
        t_start=T_START)


def read_layer_metrics(job, record, trace_record):
    """{name: {"value", "unit"}} from the cell's per-layer readers; a
    reader that finds nothing to read returns None and is left out."""
    inputs = types.SimpleNamespace(
        trace=trace_record, spans=record["spans"],
        counters=record["counters"], cell=job.cell, traffic=job.traffic,
        config=job.config, config_mod=job.config_mod,
        peaks=job.device["peaks"], chips=job.device["count"],
        program_text=record.get("program_text"))
    out = {}
    for entry in job.manifest.metrics("per_layer", job.cell["name"]):
        value = job.manifest.module("layer_metrics", entry["name"]).read(inputs)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def drive(job):
    """Everything of a run after the look for the chip: measure, read
    the metrics, then compare with the reference.  Returns the result."""
    from harness import compare, device, trace

    if job.trace:
        shutil.rmtree(job.trace_dir, ignore_errors=True)
    record = job.traffic_mod.measure(job)
    dev = {k: job.device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = device.memory_peak_bytes()
    print(f"memory-note {device.memory_stats()!r}", file=sys.stderr)
    result = {"correct": False, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": None, "device": dev}
    if job.trace:
        trace_record = trace.load(record["trace_path"])
        shutil.rmtree(job.trace_dir, ignore_errors=True)
        dev["busy_s"], dev["window_s"] = trace.busy_seconds(trace_record)
        result["metrics"] = read_layer_metrics(job, record, trace_record)
        result["breakdown"] = {
            "device_ops": trace.top_device_ops(trace_record),
            "idle_gaps": trace.idle_gaps(trace_record)}
    else:
        result["metrics"] = {
            m["name"]: {"value": record["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in job.manifest.metrics("end_to_end", job.cell["name"])}
    compared, result["correct"] = job.traffic_mod.verify(job, record)
    result["reference_s"] = record["reference_s"]
    result["compared"] = compared
    compare.print_compared(compared, record.get("readings"))
    return result


def setup_environment():
    """The compile cache's directory and the import path, before jax is
    imported.  The environment branch of the cache rule is the one this
    sets (both branches hit on the chip, PERF.md PR 26); a fixed path
    inside the checkout, since the path is part of the cache's key."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sys.path[:0] = [BENCH_DIR, ROOT]


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    setup_environment()
    from harness import device
    from harness.manifest import Manifest

    manifest = Manifest(ROOT)
    try:
        cell = manifest.cell(args.workload)
        dev = device.require_chips(cell["chips"])
    except (KeyError, device.NoChip) as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    result = drive(make_job(manifest, args.workload, args.seed,
                            args.seconds, args.trace, dev))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
