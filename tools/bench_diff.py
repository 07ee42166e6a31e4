#!/usr/bin/env python
"""Bench trajectory differ: compare the latest bench run against the
previous one, flagging per-leaf regressions past a tolerance.

``bench.py`` appends every run's full record to ``BENCH_HISTORY.jsonl``
(one JSON object per line, newest last; ``MXTPU_BENCH_HISTORY`` moves
the file).  This tool flattens the two newest records' numeric leaves
(``records.<leaf>.<key>`` plus the top-level primary metric), classifies
each key's direction — throughput-like (higher is better),
latency/cost-like (lower is better), or informational — and reports
every leaf whose value moved PAST its tolerance in the bad direction.

A short history is padded from ``BENCH_r*.json`` driver snapshots
beside it, if any are there (their ``parsed`` field is the same record
shape); none are kept in the repo.

Usage::

    python tools/bench_diff.py                 # report, exit 0
    python tools/bench_diff.py --strict        # exit 1 on any regression
    python tools/bench_diff.py --tolerance 0.2 # global tolerance 20%
    python tools/bench_diff.py --json          # machine-readable report
    python tools/bench_diff.py --file TUNE_HISTORY.jsonl
                                               # diff the two newest
                                               # records of any jsonl
                                               # (tuner trial records)
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_HISTORY = os.path.join(REPO, "BENCH_HISTORY.jsonl")

# direction classification by key substring (first match wins).
# Anything unmatched is informational: reported, never flagged —
# batch_size changing is a config drift to eyeball, not a regression.
_LOWER_IS_BETTER = (
    "p50", "p95", "p99", "latency", "_ms", "ms_per", "us_per",
    "lost", "compiles", "dispatches", "steps_lost", "time_to_resume",
    "overhead", "wait", "blocked_moves", "pages_in_flight",
    "hbm_bytes", "spawn_failures", "rpc_errors",
    "stale_leases_rejected", "blocked_cooldown", "blocked_bounds",
    # spmd mesh leaf: per-device memory footprints and their ratio to
    # the single-device arm shrink as sharding improves; fallbacks are
    # eager escapes from the compiled step path
    "bytes_per_device", "shrink_ratio", "fallbacks",
)
_HIGHER_IS_BETTER = (
    "throughput", "tokens_per", "images_per", "rps", "speedup",
    "value", "mfu", "goodput", "fill", "hit", "occupancy",
    "vs_baseline", "best_over_baseline", "score", "samples_per",
    "accept_rate", "concurrent_sequences",
)

# per-leaf tolerance overrides (fraction of the previous value) for
# leaves known to be noisy on shared CPU boxes; everything else uses
# --tolerance (default 10%)
PER_LEAF_TOLERANCE = {
    re.compile(r"records\.(serve|serve_decode|serve_int8|serve_router)"
               r"\..*(value|rps|p99_ms|p50_ms|tokens_per_sec"
               r"|_at_fixed_mem)$"): 0.35,
    re.compile(r"records\.(trainer_step|whole_step_mp|input_pipeline"
               r"|recovery)\."): 0.35,
    re.compile(r"(^|\.)value$"): 0.25,
}


def _direction(key):
    k = key.lower()
    for s in _LOWER_IS_BETTER:
        if s in k:
            return "lower"
    for s in _HIGHER_IS_BETTER:
        if s in k:
            return "higher"
    return "info"


def _tolerance_for(leaf, default):
    for pat, tol in PER_LEAF_TOLERANCE.items():
        if pat.search(leaf):
            return tol
    return default


def flatten(record, prefix=""):
    """``{"records": {"serve": {"value": 1}}}`` ->
    ``{"records.serve.value": 1.0}`` (numeric leaves only)."""
    out = {}
    if not isinstance(record, dict):
        return out
    for k, v in record.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool) or v is None:
            continue
        if isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, dict):
            out.update(flatten(v, key + "."))
    return out


def load_history(path):
    records = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except ValueError:
                    continue   # a truncated tail line is not fatal
    return records


def load_bench_r_files(directory):
    """The archived driver snapshots, oldest first (their ``parsed``
    field is the bench record)."""
    out = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "BENCH_r*.json"))):
        try:
            with open(path) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            continue
        rec = snap.get("parsed")
        if isinstance(rec, dict):
            out.append(rec)
    return out


def load_last_two(history_path, fallback_dir=None, explicit=False):
    """(previous, latest) bench records — from the history file, padded
    from the archived BENCH_r*.json snapshots when the history is
    short.  ``explicit=True`` (the ``--file`` path) never pads: an
    arbitrary jsonl (tuner trial records) must stand on its own two
    lines rather than be diffed against an unrelated bench snapshot."""
    records = load_history(history_path)
    if len(records) < 2 and not explicit:
        records = load_bench_r_files(fallback_dir or REPO) + records
    if len(records) < 2:
        raise SystemExit(
            f"need two bench records to diff; found {len(records)} "
            f"(history: {history_path}). Run `python bench.py` twice — "
            "each run appends to the history.")
    return records[-2], records[-1]


def diff_records(prev, new, tolerance=0.10):
    """Per-leaf comparison: ``[{"leaf", "prev", "new", "delta_pct",
    "direction", "tolerance", "verdict"}]`` with verdicts ``ok`` /
    ``improved`` / ``REGRESSED`` / ``info`` / ``new`` / ``dropped``."""
    fp, fn = flatten(prev), flatten(new)
    report = []
    for leaf in sorted(set(fp) | set(fn)):
        p, n = fp.get(leaf), fn.get(leaf)
        if p is None or n is None:
            report.append({"leaf": leaf, "prev": p, "new": n,
                           "delta_pct": None, "direction": "info",
                           "tolerance": None,
                           "verdict": "new" if p is None else "dropped"})
            continue
        direction = _direction(leaf)
        delta = (n - p) / abs(p) if p else (0.0 if n == p else None)
        tol = _tolerance_for(leaf, tolerance)
        verdict = "info"
        if direction != "info" and delta is not None:
            worse = delta < -tol if direction == "higher" else delta > tol
            better = delta > tol if direction == "higher" else delta < -tol
            verdict = ("REGRESSED" if worse else
                       "improved" if better else "ok")
        elif direction != "info":
            # previous value was 0: any nonzero move on a lower-is-
            # better leaf (lost requests, post-warmup compiles) is a
            # regression outright
            verdict = ("REGRESSED" if direction == "lower" and n > 0
                       else "ok")
        report.append({"leaf": leaf, "prev": p, "new": n,
                       "delta_pct": (round(delta * 100.0, 2)
                                     if delta is not None else None),
                       "direction": direction, "tolerance": tol,
                       "verdict": verdict})
    return report


def has_regression(report):
    return any(r["verdict"] == "REGRESSED" for r in report)


def render(report, show_all=False):
    lines = []
    header = (f"{'leaf':<52}{'prev':>14}{'new':>14}{'delta':>9}  "
              f"verdict")
    lines.append(header)
    lines.append("-" * len(header))
    for r in report:
        if not show_all and r["verdict"] in ("ok", "info"):
            continue
        delta = (f"{r['delta_pct']:+.1f}%" if r["delta_pct"] is not None
                 else "-")
        prev = f"{r['prev']:.4g}" if r["prev"] is not None else "-"
        new = f"{r['new']:.4g}" if r["new"] is not None else "-"
        lines.append(f"{r['leaf']:<52}{prev:>14}{new:>14}{delta:>9}  "
                     f"{r['verdict']}")
    if len(lines) == 2:
        lines.append("(no leaf moved past tolerance)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--history",
                    default=os.environ.get("MXTPU_BENCH_HISTORY",
                                           DEFAULT_HISTORY),
                    help="bench history jsonl (newest last)")
    ap.add_argument("--file", dest="file", default=None,
                    help="diff the two newest records of this jsonl "
                         "instead of the bench history (tuner trial "
                         "records, ad-hoc measurement logs); no "
                         "BENCH_r*.json fallback padding")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="default per-leaf tolerance fraction (0.10)")
    ap.add_argument("--all", action="store_true",
                    help="show every leaf, not just flagged ones")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any leaf REGRESSED")
    args = ap.parse_args(argv)

    if args.file:
        prev, new = load_last_two(args.file, explicit=True)
    else:
        prev, new = load_last_two(args.history)
    report = diff_records(prev, new, tolerance=args.tolerance)
    regressed = has_regression(report)
    if args.json:
        print(json.dumps({"regressed": regressed, "report": report}))
    else:
        print(render(report, show_all=args.all))
        n_reg = sum(1 for r in report if r["verdict"] == "REGRESSED")
        n_imp = sum(1 for r in report if r["verdict"] == "improved")
        print(f"\nBENCH_DIFF {'REGRESSED' if regressed else 'OK'} "
              f"regressed={n_reg} improved={n_imp} "
              f"leaves={len(report)}")
    return 1 if (regressed and args.strict) else 0


if __name__ == "__main__":
    sys.exit(main())
