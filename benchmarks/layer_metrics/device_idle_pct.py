"""Share of a step in which no operation runs on the device, as the
untraced window would show it: 1 - (device-busy seconds a step, from
the union of the device operations' intervals in the traced stretch,
averaged over the chips) / (mean step period of the run's untraced
window).

The traced stretch's own idle share (`busy_s` / `window_s` in the
result's `device`) is higher wherever the profiler slows the host's
step call; the device's busy time a step does not depend on that."""
from harness import trace


def read(run):
    window = run.spans.get("window")
    if run.trace is None or not window or not window["count"]:
        return None
    busy, _ = trace.busy_seconds(run.trace)
    steps = len(trace.step_starts(run.trace, run.traffic["step_program"]))
    if not steps:
        return None
    period = window["seconds"] / window["count"]
    return 100.0 * (1.0 - busy / steps / period)
