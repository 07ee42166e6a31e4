"""Profiler (ref: src/profiler/profiler.{h,cc} + python/mxnet/profiler.py).

Two tiers, per SURVEY §5:
1. Op-level chrome://tracing JSON — every imperative invoke is bracketed
   (dispatch + optional sync timing), dumped via ``dumps()``/``dump()``
   exactly like the reference's MXDumpProfile.
2. XLA-level — ``start()`` can also open a jax.profiler trace
   (tensorboard-plugin-profile readable) capturing device timelines.
   Every ``op_scope`` is also a ``jax.profiler.TraceAnnotation``, so
   whoever runs a jax.profiler session finds the tier-1 scopes on the
   host plane of the same ``.xplane.pb`` as the device's ops.
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from .base import getenv
from .telemetry import health as _health
from .telemetry import tracer as _tracer

_state = threading.local()
_config = {
    "profile_all": False,
    "profile_imperative": True,
    "profile_memory": False,  # per-op HBM/pool counter events
    "filename": "profile.json",
    "aggregate_stats": False,
    "xla_trace_dir": None,
    "sync": False,  # block per op for accurate durations
}
_events = []
_events_lock = threading.Lock()
_running = False
_xla_running = False
# running peaks across the profiled window (ref: the reference's
# profiler records memory-pool events per device — profiler.cc
# DeviceStats); sampled from PjRt memory_stats + the native staging pool
_mem_peak = {"device_bytes_in_use": 0, "pool_used_bytes": 0}
_mem_lock = threading.Lock()  # ops on several threads sample at once


def set_config(**kwargs):
    """Ref: mx.profiler.set_config(profile_all=True, filename=...)."""
    for k, v in kwargs.items():
        if k in ("profile_symbolic", "profile_api", "continuous_dump"):
            continue  # accepted for parity
        _config[k] = v


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start():
    global _running, _xla_running
    _running = True
    if _config.get("xla_trace_dir"):
        import jax

        jax.profiler.start_trace(_config["xla_trace_dir"])
        _xla_running = True


def stop():
    global _running, _xla_running
    _running = False
    if _xla_running:
        import jax

        jax.profiler.stop_trace()
        _xla_running = False


def is_running():
    return _running


def _memory_sample():
    """Current device HBM + host staging-pool occupancy, in bytes.

    Device side: PjRt per-device allocator stats (bytes_in_use /
    peak_bytes_in_use — present on TPU, absent on some CPU builds).
    Host side: the native storage pool's counters (src/storage.cc).
    """
    sample = {}
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        if stats:
            for k in ("bytes_in_use", "peak_bytes_in_use"):
                if k in stats:
                    sample[f"device_{k}"] = int(stats[k])
    except Exception:
        pass
    try:
        from .storage import Storage

        st = Storage.get().stats()
        sample["pool_used_bytes"] = int(st.get("used_bytes", 0))
        if "pool_bytes" in st:
            sample["pool_reserved_bytes"] = int(st["pool_bytes"])
    except Exception:
        pass
    with _mem_lock:
        for k in _mem_peak:
            if sample.get(k, 0) > _mem_peak[k]:
                _mem_peak[k] = sample[k]
    return sample


def record_op(name, begin_us, end_us, shapes=None, cat="operator"):
    if not _running:
        return
    mem = _memory_sample() if _config.get("profile_memory") else None
    with _events_lock:
        _events.append({
            "name": name, "ph": "X", "ts": begin_us,
            "dur": max(end_us - begin_us, 0.01),
            "pid": os.getpid(), "tid": threading.get_ident() % 100000,
            "cat": cat,
            "args": {"shapes": str(shapes)} if shapes else {},
        })
        if mem:
            # chrome counter track: stacked view of HBM + staging pool
            _events.append({
                "name": "memory", "ph": "C", "ts": end_us,
                "pid": os.getpid(), "cat": "memory", "args": mem,
            })


# Open-scope registry: while armed (the supervisor's watchdog turns it
# on via track_scopes), every entered-but-not-exited op scope is
# visible per thread — how a stalled job names its stuck PHASE (a
# completed-events trace can only name what finished).  One global
# boolean check per scope when disarmed.
_scope_track = False
_scope_lock = threading.Lock()
_open_scopes = {}  # thread ident -> [scope names, innermost last]


def track_scopes(on=True):
    """Arm/disarm open-scope tracking (watchdog diagnostics)."""
    global _scope_track
    _scope_track = bool(on)
    if not on:
        with _scope_lock:
            _open_scopes.clear()


def active_scopes():
    """Snapshot of currently OPEN op scopes per thread; populated only
    while ``track_scopes(True)``."""
    with _scope_lock:
        return {tid: list(stack) for tid, stack in _open_scopes.items()
                if stack}


class _OpScope:
    __slots__ = ("name", "cat", "attrs", "t0", "_ann")

    def __init__(self, name, cat, attrs):
        self.name = name
        self.cat = cat
        self.attrs = attrs

    def __enter__(self):
        if _scope_track:
            with _scope_lock:
                _open_scopes.setdefault(threading.get_ident(),
                                        []).append(self.name)
        # an event on the host plane of whatever jax.profiler session is
        # running (ours, a benchmark's, a TensorBoard capture), so host
        # spans and device ops share one file and one clock; without a
        # session TraceMe does nothing.  Unconditional: a binding armed
        # by start() would miss every session something else starts
        ann = self._ann = TraceAnnotation(self.name, **self.attrs)
        ann.__enter__()
        # telemetry span hook: the disarmed binding is a ~ns no-op
        # (engine.fault_point pattern); armed, every op scope is a
        # span in the exported trace / flight-recorder ring
        _tracer.span_begin(self.name, self.cat)
        self.t0 = time.perf_counter() * 1e6
        return self

    def note(self, **attrs):
        """Attach what is known only inside the scope (bytes moved) to
        the span and to its profiler-trace event."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter() * 1e6
        record_op(self.name, self.t0, t1, cat=self.cat)
        _tracer.span_end(self.name, self.cat, **self.attrs)
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None:
            # health-monitor phase sink (telemetry.health): disarmed
            # it IS the module no-op, same ~ns contract as the tracer
            # hook above; a scope aborted by an exception books no
            # phase time (a failed step is not a completed step)
            _health.scope_end(self.name, self.cat, self.t0, t1)
        if _scope_track:
            with _scope_lock:
                stack = _open_scopes.get(threading.get_ident())
                # entered before arming: nothing of ours to pop
                if stack and stack[-1] == self.name:
                    stack.pop()


def op_scope(name, cat="operator", **attrs):
    """Trace bracket; `cat` groups rows in chrome://tracing (checkpoint
    save/restore phases are tagged cat="checkpoint").  `attrs` ride on
    the telemetry span and on the jax.profiler trace event
    (`step_num=` and `_r=1` make it a step of xprof's step view)."""
    return _OpScope(name, cat, attrs)


# ---------------------------------------------------------------------------
# Section registry: a counter section belongs to the module that owns
# its counters, and that module registers it here when it is imported
# (docs/observability.md, "section registry").  This module names none
# of them: dumps(), the aggregate table, sections() and /metrics read
# whatever is registered, in name order, so output does not follow
# import order.


_sections = {}   # name -> (stats, reset, table)


def register_section(name, stats, reset, table=None):
    """Register a counter section, from the module that owns its
    counters.

    ``stats()`` returns the section's dict (or None while it has
    nothing to say) and ``reset()`` zeroes it.  The registry, not the
    owner, decides when to call ``reset``: every section is
    window-scoped, so a reset dump never mixes per-window events with
    forever-cumulative counts.  ``table(stats)`` (optional) returns the
    section's lines for ``dumps(format="table")``; ``rows_table`` makes
    the usual one.  Re-registering a name replaces it.
    """
    _sections[name] = (stats, reset, table)


def unregister_section(name):
    """Drop a registered section (tests / unloading subsystems)."""
    _sections.pop(name, None)


def section_names():
    return sorted(_sections)


def _read_sections(reset):
    """[(name, stats, table)] of every section that has something to
    say, in name order; under ``reset`` each is zeroed once read."""
    out = []
    for name in section_names():
        stats_fn, reset_fn, table = _sections[name]
        stats = stats_fn()
        if reset:
            reset_fn()
        if stats is not None:
            out.append((name, stats, table))
    return out


def sections(reset=False):
    """Public snapshot of every registered section: ``{name: stats}`` —
    the dict ``dumps()`` embeds and the /metrics collector exports."""
    return {name: stats for name, stats, _table in _read_sections(reset)}


def _section_tables(reset=False):
    lines = []
    for _name, stats, table in _read_sections(reset):
        if table is not None:
            lines.append("")
            lines.extend(table(stats))
    return lines


def rows_table(title, rows):
    """Standard section renderer: a title plus label/value rows."""
    def render(stats):
        out = [title + ":"]
        for label, key in rows:
            out.append(f"{label:<40}{stats[key]:>12}")
        return out
    return render


# the two sections whose counters live BELOW this module (it imports
# telemetry.health and telemetry.tracer for its hooks; they cannot
# import it back): registered here, a downward edge
register_section(
    "health", _health.health_stats, _health.reset_health_stats, rows_table(
        "Health Monitor",
        (("steps observed", "steps"),
         ("step time (ms)", "step_ms"),
         ("input wait (ms)", "input_wait_ms"),
         ("h2d staging (ms)", "h2d_ms"),
         ("compute (ms)", "compute_ms"),
         ("collective (ms)", "collective_ms"),
         ("optimizer (ms)", "optimizer_ms"),
         ("checkpoint stall (ms)", "checkpoint_ms"),
         ("compile (ms)", "compile_ms"),
         ("lost to recovery (ms)", "lost_ms"),
         ("monitor ticks", "ticks"),
         ("SLO alerts fired", "alerts"),
         ("stragglers flagged", "stragglers"),
         ("rules firing now", "rules_firing"),
         ("goodput (last window)", "goodput"),
         ("MFU (last window)", "mfu"),
         ("FLOPs per step", "flops_per_step"),
         ("step p95 (ms)", "step_p95_ms"))))
register_section(
    "telemetry", _tracer.telemetry_stats, _tracer.reset_telemetry_stats,
    rows_table(
        "Telemetry (tracer / flight recorder / metrics)",
        (("spans recorded", "spans"),
         ("instant events", "instants"),
         ("request spans opened", "requests"),
         ("events dropped (lane cap)", "dropped"),
         ("flight-recorder dumps", "flight_dumps"),
         ("/metrics scrapes", "scrapes"),
         ("aggregate() calls", "aggregations"))))


def dumps(reset=False, format="json"):
    """Return the trace (ref: mx.profiler.dumps).

    format="json": chrome://tracing event JSON (the default).
    format="table": per-op aggregate summary — name, count, total/min/
    max/avg ms — requires set_config(aggregate_stats=True) like the
    reference's MXAggregateProfileStatsPrint (ref:
    src/profiler/aggregate_stats.cc)."""
    if format == "table":
        if not _config.get("aggregate_stats"):
            raise RuntimeError(
                "aggregate stats not enabled: call "
                "profiler.set_config(aggregate_stats=True) before "
                "profiling (ref: MXAggregateProfileStatsPrint)")
        return _aggregate_table(reset)
    with _events_lock:
        data = {"traceEvents": list(_events),
                "displayTimeUnit": "ms"}
        if _config.get("profile_memory"):
            data["memoryPeaks"] = dict(_mem_peak)
        if reset:
            _events.clear()
    # every registered counter section, window-scoped with the events
    data.update(sections(reset))
    return json.dumps(data)


def _aggregate_table(reset=False):
    """Per-op totals across recorded events, formatted like the
    reference's aggregate stats table (ref: aggregate_stats.cc
    DumpTable: Name / Total Count / Time columns, sorted by total)."""
    with _events_lock:
        events = list(_events)
        if reset:
            _events.clear()
    stats = {}
    for ev in events:
        if "dur" not in ev:  # counter (memory) events have no duration
            continue
        s = stats.setdefault(ev["name"], [0, 0.0, float("inf"), 0.0])
        dur_ms = ev["dur"] / 1000.0
        s[0] += 1
        s[1] += dur_ms
        s[2] = min(s[2], dur_ms)
        s[3] = max(s[3], dur_ms)
    header = (f"{'Name':<40}{'Total Count':>12}{'Total (ms)':>14}"
              f"{'Min (ms)':>12}{'Max (ms)':>12}{'Avg (ms)':>12}")
    lines = ["Profile Statistics:", header, "-" * len(header)]
    for name, (cnt, tot, mn, mx) in sorted(
            stats.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"{name:<40}{cnt:>12}{tot:>14.4f}"
                     f"{mn:>12.4f}{mx:>12.4f}{tot / cnt:>12.4f}")
    if _config.get("profile_memory"):
        # memory-pool section (ref: profiler.cc DeviceStats / the
        # reference table's Memory: Device columns)
        lines.append("")
        lines.append("Memory Statistics (peak over profiled window):")
        for key, val in _mem_peak.items():
            lines.append(f"{key:<40}{val / 1e6:>14.3f} MB")
    # counter sections are window-scoped under reset=True exactly like
    # the event table above (and like the JSON format path)
    lines.extend(_section_tables(reset))
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):
    """Write the trace file (ref: mx.profiler.dump)."""
    with open(_config["filename"], "w") as f:
        f.write(dumps())


def reset():
    with _events_lock:
        _events.clear()
    with _mem_lock:
        for k in _mem_peak:
            _mem_peak[k] = 0


def pause(profile_process="worker"):
    global _running
    _running = False


def resume(profile_process="worker"):
    global _running
    _running = True


# env autostart (ref: MXNET_PROFILER_AUTOSTART)
if getenv("PROFILER_AUTOSTART", False, bool):
    _config["profile_all"] = True
    start()
