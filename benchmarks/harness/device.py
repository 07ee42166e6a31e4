"""The look for the chip, and the one table of peaks."""
from __future__ import annotations

import json
import os


class NoChip(RuntimeError):
    """The machine does not hold what the cell asks for."""


def peaks_table():
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        return json.load(f)["device_kinds"]


def require_chips(chips):
    """The device record of the result line, or NoChip.  No default for
    a device the table does not list."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise NoChip(f"JAX found platform {first.platform!r}, not a TPU: "
                     "the benchmark measures on the chip only")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX found "
                     f"{len(devices)}")
    table = peaks_table()
    if first.device_kind not in table:
        raise NoChip(f"device kind {first.device_kind!r} is not in "
                     "benchmarks/harness/peaks.json")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices), "peaks": table[first.device_kind]}


def memory_peak_bytes():
    """Peak bytes on the fullest chip: what the process held there
    (`peak_bytes_in_use`) plus what the compiled programs reserved for
    their temporaries (`peak_bytes_reserved`).  The TPU runtime counts
    the two apart, and a step's activations are all in the second."""
    import jax

    def peak(stats):
        # a backend that reports nothing (the CPU tests) reads 0
        stats = stats or {}
        return int(stats.get("peak_bytes_in_use", 0)) + int(
            stats.get("peak_bytes_reserved", 0))

    return max(peak(d.memory_stats()) for d in jax.devices())


def memory_stats():
    """The first device's whole memory record, for the run's notes."""
    import jax

    return jax.devices()[0].memory_stats()
