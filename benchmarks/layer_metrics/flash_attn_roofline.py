"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the attention a step needs, over the summed
device time of the kernels' events in a step.

Work by shapes, whatever implements it, for (batch b, heads h, sequence
s, head size d) and every layer, forward and backward, bf16:
  FLOPs: QK^T and PV forward (4 b h s^2 d); dV, dP, dQ, dK backward
         (8 b h s^2 d); the backward's recomputation of QK^T is not
         counted.
  bytes: forward reads q, k, v and writes o (4 b h s d elements);
         backward reads q, k, v, o, do and writes dq, dk, dv (8).
"""
from harness import trace

BYTES_PER_ELEMENT = 2


def kernel_events(batch, heads, seq, head_dim):
    """How the kernels' device events are named in the trace (see
    harness.trace.short_name): Mosaic calls whose first operand is the
    (batch * heads, seq, head size) query."""
    return (rf"tpu_custom_call\(bf16\[{batch * heads},{seq},"
            rf"{head_dim}\]\)")


def work(batch, heads, seq, head_dim, layers):
    flops = 12 * batch * heads * seq * seq * head_dim * layers
    moved = 12 * batch * heads * seq * head_dim * BYTES_PER_ELEMENT * layers
    return flops, moved


def read(run):
    if run.trace is None or not hasattr(run.config_mod, "attention_shape"):
        return None
    shape = run.config_mod.attention_shape(run.config, run.traffic)
    seconds, events = trace.kernel_seconds(run.trace,
                                           kernel_events(*shape[:4]))
    steps = len(trace.step_starts(run.trace, run.traffic["step_program"]))
    if not events or not steps:
        return None
    flops, moved = work(*shape)
    least = max(flops / run.peaks["peak_flops_bf16"],
                moved / run.peaks["peak_hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
