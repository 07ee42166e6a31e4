"""The Mamba-2 SSD scan's share of its roofline: the least time the chip
could take for the scan a step needs in every Mamba-2 layer held (the
configuration's `ssd_work`: the recurrent form's two products a token
and head, forward and twice that backward, and the bytes of x, B, C,
dt, y and their gradients; nothing recomputed), over the device time a
step of the events under `/ssd/` (`jax.named_scope("ssd")` in
mxnet_tpu/ops/ssm.py: the chunked form's products and decays, the
recomputed forward included: the share says what the step pays, the
work what it needs).  A configuration without `ssd_work`, or a program
without the scope, gives None."""
from harness import op_names


def read(run):
    if run.trace is None or not hasattr(run.config_mod, "ssd_work"):
        return None
    seconds = op_names.seconds_a_step(run, lambda name: "/ssd/" in name)
    if seconds is None:
        return None
    flops, moved = run.config_mod.ssd_work(run.config, run.traffic)
    least = max(flops / run.peaks["peak_flops_bf16"],
                moved / run.peaks["peak_hbm_bytes_per_s"])
    return 100.0 * least / seconds
