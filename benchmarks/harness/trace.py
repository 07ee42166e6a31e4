"""Reduction from a profiler trace to numbers.

`load(path)` turns jax.profiler's .xplane.pb into a plain record:

    {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                         "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

`host` holds only the harness's own annotations (HOST_SPANS).  All the
arithmetic below works on that record, so a recorded one checks it on
the CPU (tests/benchmark/data/).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_window"
HOST_SPANS = ("bench_window", "step_call", "loss_read", "host_batch")


SHAPE = re.compile(r"[a-z]+[0-9]+\[[0-9,]*\]")
CUSTOM_CALL = re.compile(r'custom_call_target="([^"]+)"')
# A loop's, a branch's or a call's own event spans the events of its
# body, which the trace holds too: whoever SUMS device time leaves it
# out, or the body counts twice.  XLA names a container it clones as it
# inlines a function the layers share `while.N.clone.M` / `cond.N.clone.M`.
# This is the benchmark's one match: `operations` applies it for every
# reader that sums (the phases, the scopes, the heaviest operations).
CONTAINER = re.compile(r"^(while|conditional|cond|call)(\.[\w.-]*)?( |$)")


def short_name(hlo):
    """A device event is named by its whole HLO instruction; keep the
    instruction's name, what a custom call targets, and its first
    operand's shape (a custom call) or its result's (anything else):
    `branch_0_fun.107 tpu_custom_call(bf16[1536,128,64])`,
    `fusion.135 f32[30522,768]`."""
    head, sep, rest = hlo.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head
    target = CUSTOM_CALL.search(rest)
    if target:
        operand = SHAPE.search(rest.partition("custom-call(")[2])
        return f"{head} {target.group(1)}({operand.group(0) if operand else ''})"
    shape = SHAPE.search(rest)
    return f"{head} {shape.group(0)}" if shape else head


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    record = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    lines[key] += [[short_name(e.name), int(e.start_ns),
                                    int(e.duration_ns)] for e in line.events]
            record["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                record["host"] += [
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events if e.name in HOST_SPANS]
    return record


def window(record):
    """(start_ns, end_ns) of the traced window: the harness's own
    `bench_window` span, which is on the trace's clock."""
    spans = [e for e in record["host"] if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span in the trace, "
                         f"found {len(spans)}")
    _, start, dur = spans[0]
    return start, start + dur


def merged(intervals):
    """Sorted, non-overlapping [start, end] from any [start, end]s."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def clipped(events, start, end):
    """[start, end] of each event, cut to the window; those outside go."""
    out = []
    for _, s, d in events:
        lo, hi = max(s, start), min(s + d, end)
        if hi > lo:
            out.append([lo, hi])
    return out


def busy_seconds(record):
    """Seconds in which an operation ran on the device within the
    window, averaged over the devices; and the window's seconds."""
    start, end = window(record)
    if not record["devices"]:
        raise ValueError("the trace holds no device plane")
    busy = [sum(hi - lo for lo, hi in merged(clipped(dev["ops"], start, end)))
            for dev in record["devices"].values()]
    return sum(busy) / len(busy) / 1e9, (end - start) / 1e9


def operations(record):
    """(name, nanoseconds inside the window) of every device operation,
    one device after another, the containers' own events left out."""
    start, end = window(record)
    for dev in record["devices"].values():
        for name, s, d in dev["ops"]:
            inside = min(s + d, end) - max(s, start)
            if inside > 0 and not CONTAINER.match(name):
                yield name, inside


def kernel_seconds(record, pattern):
    """Summed device durations, within the window and averaged over the
    devices, of the operations whose name matches `pattern`; and how
    many such events one device ran."""
    start, end = window(record)
    rx = re.compile(pattern)
    total = count = 0
    for dev in record["devices"].values():
        hits = clipped([e for e in dev["ops"] if rx.search(e[0])], start, end)
        total += sum(hi - lo for lo, hi in hits)
        count += len(hits)
    n = len(record["devices"])
    return total / n / 1e9, count // n


def step_starts(record, pattern):
    """Start times (ns) within the window of the device programs whose
    name matches `pattern`, on the first device."""
    start, end = window(record)
    rx = re.compile(pattern)
    dev = record["devices"][sorted(record["devices"])[0]]
    return sorted(s for name, s, _ in dev["modules"]
                  if rx.search(name) and start <= s < end)


def mean_step_period_s(record, pattern):
    """Mean time from the start of one step program to the start of the
    next, idle gaps included; None with fewer than two steps."""
    starts = step_starts(record, pattern)
    if len(starts) < 2:
        return None
    return (starts[-1] - starts[0]) / (len(starts) - 1) / 1e9


def top_device_ops(record, n=10):
    """The `n` operations that took most device seconds within the
    window; a container is no operation, its body's events are."""
    total = {}
    for name, inside in operations(record):
        total[name] = total.get(name, 0) + inside
    k = len(record["devices"])
    heavy = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, d / k / 1e9] for name, d in heavy]


def idle_gaps(record, n=10):
    """Idle seconds of the first device within the window, by what the
    host was doing: each gap goes to the harness span that covers most
    of it, or to `host_other`."""
    start, end = window(record)
    dev = record["devices"][sorted(record["devices"])[0]]
    busy = merged(clipped(dev["ops"], start, end))
    edges = [start] + [t for iv in busy for t in iv] + [end]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [e for e in record["host"] if e[0] != WINDOW_SPAN]
    by_label = {}
    for lo, hi in gaps:
        cover = {}
        for name, s, d in spans:
            overlap = min(hi, s + d) - max(lo, s)
            if overlap > 0:
                cover[name] = cover.get(name, 0) + overlap
        label = max(cover, key=cover.get) if cover else "host_other"
        by_label[label] = by_label.get(label, 0) + (hi - lo)
    heavy = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
    return [[name, d / 1e9] for name, d in heavy]
