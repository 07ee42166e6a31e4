"""Input-pipeline telemetry — the ``dataPipeline`` profiler section.

PR 3 made the compute step cheap; whether a job is now INPUT-bound is
exactly what these counters answer.  The decisive signal is
``wait_ms``: total time the consumer (the train loop) spent blocked
inside ``next(pipeline)``.  A well-overlapped pipeline keeps it near
zero while ``host_build_ms``/``h2d_ms`` run large in the background; a
``wait_ms`` that tracks ``host_build_ms`` means the chip is starving
and the pipeline needs more map workers or deeper prefetch (see
docs/data.md, "diagnosing an input-bound job").

Window-scoped like the cachedGraph/trainerStep sections:
``profiler.dumps(reset=True)`` resets them with the event buffer.
"""
from __future__ import annotations

import threading

from .. import profiler

_lock = threading.Lock()
_stats = {
    "batches": 0,           # batches delivered to the consumer
    "host_build_ms": 0.0,   # map-fn + batchify time on host workers
    "h2d_ms": 0.0,          # host->device staging time on the h2d lane
    "wait_ms": 0.0,         # consumer time blocked on next() — the
                            # input-bound signal
    "prefetch_hits": 0,     # batch already device-resident at request
    "prefetch_misses": 0,   # consumer had to wait on the transfer
}


def add(key, value):
    """Accumulate one counter (thread-safe; called from pool workers)."""
    with _lock:
        _stats[key] += value


def pipeline_stats():
    """Snapshot of the dataPipeline counters since the last reset."""
    with _lock:
        s = dict(_stats)
    for k in ("host_build_ms", "h2d_ms", "wait_ms"):
        s[k] = round(s[k], 3)
    return s


def reset_pipeline_stats():
    with _lock:
        for k in _stats:
            _stats[k] = 0.0 if isinstance(_stats[k], float) else 0


profiler.register_section(
    "dataPipeline", pipeline_stats, reset_pipeline_stats,
    profiler.rows_table(
        "Data Pipeline",
        (("batches delivered", "batches"),
         ("host build (ms)", "host_build_ms"),
         ("h2d staging (ms)", "h2d_ms"),
         ("step wait-on-input (ms)", "wait_ms"),
         ("prefetch hits", "prefetch_hits"),
         ("prefetch misses", "prefetch_misses"))))
