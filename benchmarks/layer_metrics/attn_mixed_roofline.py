"""The mixed (full and sliding-window, grouped-query) attention
kernels' share of their roofline: the least time the chip could take
for the attention a step needs in every layer held (the configuration's
`attention_work`: the visible pairs only, forward and backward, K/V at
their own head count, nothing recomputed), over the summed device time
a step of the kernels' events (the recomputed forward included)."""
from harness import trace


def read(run):
    if run.trace is None or not hasattr(run.config_mod, "attention_work"):
        return None
    seconds, events = trace.kernel_seconds(
        run.trace,
        run.config_mod.attention_kernel_events(run.config, run.traffic))
    steps = len(trace.step_starts(run.trace, run.traffic["step_program"]))
    if not events or not steps:
        return None
    flops, moved = run.config_mod.attention_work(run.config, run.traffic)
    least = max(flops / run.peaks["peak_flops_bf16"],
                moved / run.peaks["peak_hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
