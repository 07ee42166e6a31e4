"""The readings a cell's limits are set from, on the chip at the cell's
own size, several seeds in one process and no measured window:

    python3 benchmarks/readings.py --workload <name> --seeds 1,2,3 [--control] [--faults]

One JSON line a seed: the program's numbers against the reference
(lower readings), with --control the reference in the precision below
the configuration's against it, with --faults the half-batch fault
(upper readings).  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--faults", action="store_true")
    parser.add_argument("--raw", action="store_true",
                        help="also print every leaf's norms")
    parser.add_argument("--compute-dtype",
                        help="the program in another compute dtype than "
                             "the configuration's (a witness, 'float32')")
    args = parser.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    import run

    run.setup_environment()
    from harness import device
    from harness.manifest import Manifest

    manifest = Manifest(run.ROOT)
    try:
        dev = device.require_chips(manifest.cell(args.workload)["chips"])
    except (KeyError, device.NoChip) as e:
        print(f"benchmarks/readings.py: {e}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        job = run.make_job(manifest, args.workload, seed, 0.0, 0, dev)
        if args.compute_dtype:
            job.config["assumed"]["compute_dtype"] = args.compute_dtype
        print(json.dumps(job.traffic_mod.read_seed(
            job, control=args.control, faults=args.faults, raw=args.raw)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
