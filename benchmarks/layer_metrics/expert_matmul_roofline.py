"""The grouped expert products' share of their roofline: the least time
the chip could take for the products of the rows the routing log
counted (the configuration's `expert_work`: FLOPs of the two grouped
products forward and backward, bytes of the held experts' weights and
of the rows in and out), over the device time a step of the grouped
products' own kernels (harness/op_names.py: GROUPED_PRODUCT; the
recomputed forward included: the share says what the step pays, the
work what it needs).  The SwiGLU passes between the two products are
`moe_ms`'s, not this share's.

The rows come from the program's routing log (`mxnet_tpu.models.
decoder_lm.moe_routing_stats`), read while the trainer is alive: the
NEWEST step's, the one that closed the untraced window, not the traced
steps' (the harness hands a reader no trainer to ask at the trace's
close).  A program without that accessor, or a configuration without
`expert_work`, gives None."""
from harness import op_names


def routed_rows():
    """Rows the held experts of each expert layer of the newest
    trainer's model got in its newest step, or None where the program
    keeps no routing log."""
    try:
        from mxnet_tpu.models import decoder_lm
    except ImportError:
        return None
    return list(decoder_lm.moe_routing_stats(newest=True)[
        "rows_here"].values()) or None


def read(run):
    if run.trace is None or not hasattr(run.config_mod, "expert_work"):
        return None
    rows = routed_rows()
    seconds = op_names.seconds_a_step(
        run, lambda name: op_names.GROUPED_PRODUCT in name)
    if not rows or seconds is None:
        return None
    flops, moved = run.config_mod.expert_work(run.config, rows)
    least = max(flops / run.peaks["peak_flops_bf16"],
                moved / run.peaks["peak_hbm_bytes_per_s"])
    return 100.0 * least / seconds
