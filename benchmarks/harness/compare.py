"""The comparison that decides `correct` for a training cell: what the
timed step object produced in its first steps against what the plain
reference gives for the same seed, batches and steps."""
from __future__ import annotations

import sys

import numpy as np

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone: left out of the change
NEGLIGIBLE_GRADIENT = 1e-3


def leaf_gaps(program, reference, keep=None):
    """|program norm - reference norm| of every kept leaf, each against
    the reference's norm of that leaf or of the median kept leaf,
    whichever is larger; -1 where a leaf is not kept."""
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    keep = np.ones(reference.shape, bool) if keep is None else np.asarray(keep)
    if not keep.any():
        raise ValueError("no leaf left to compare")
    scale = np.maximum(reference, np.median(reference[keep]))
    gaps = np.abs(program - reference) / scale
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    return np.where(keep, gaps, -1.0)


def worst_leaf_gap(program, reference, keep=None):
    """(largest gap over the kept leaves, index of that leaf)."""
    gaps = leaf_gaps(program, reference, keep)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst


def quantile_leaf_gap(program, reference, keep, q):
    """The kept leaves' q-quantile gap (0.5: the median leaf; 0.9: all
    but the worst tenth): steady from seed to seed where the worst
    leaf's is the rounding noise of one small leaf."""
    gaps = leaf_gaps(program, reference, keep)
    return float(np.quantile(gaps[gaps >= 0], q))


def readings(program, reference, trainable):
    """{name: value} of every number a cell may compare.  `program` and
    `reference` hold `losses`, `grad_norms`, `change_norms`."""
    out = {}
    for i, (lp, lr) in enumerate(zip(program["losses"],
                                     reference["losses"]), 1):
        gap = abs(lp - lr) / abs(lr)
        out[f"loss{i}_gap"] = float(gap) if np.isfinite(gap) else float("inf")
    trainable = np.asarray(trainable, bool)
    ref_grad = np.asarray(reference["grad_norms"], np.float64)
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(
        program["grad_norms"], ref_grad, trainable)
    out["grad_norm_median_gap"] = quantile_leaf_gap(
        program["grad_norms"], ref_grad, trainable, 0.5)
    out["grad_norm_p90_gap"] = quantile_leaf_gap(
        program["grad_norms"], ref_grad, trainable, 0.9)
    moved = ~trainable | (
        ref_grad >= NEGLIGIBLE_GRADIENT * np.median(ref_grad[trainable]))
    out["change_norm_gap"], out["change_norm_leaf"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], moved)
    out["change_norm_median_gap"] = quantile_leaf_gap(
        program["change_norms"], reference["change_norms"], moved, 0.5)
    return out


def judge(values, limits):
    """({name: {"value", "limit"}}, correct) for the numbers that have a
    limit; a number without one is not compared."""
    compared = {name: {"value": values[name], "limit": limit}
                for name, limit in limits.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())
    return compared, bool(correct)


def print_compared(compared, extra=None, stream=None):
    stream = stream or sys.stderr
    for name, c in compared.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} value={c['value']!r} limit={c['limit']!r} "
              f"{verdict}", file=stream)
    for name, value in (extra or {}).items():
        print(f"compared-note {name}={value!r}", file=stream)
    stream.flush()
