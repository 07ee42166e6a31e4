"""The gated delta rule's share of its roofline: the least time the chip
could take for the rule a step needs in every linear-attention layer
held (the configuration's `delta_rule_work`: the recurrent form's
three products a token and value head, forward and twice that
backward, and the bytes of q, k, v, g, beta, o and their gradients;
nothing recomputed), over the device time a step of the events under
`/delta_rule/` (`jax.named_scope("delta_rule")` in
mxnet_tpu/ops/linear_attention.py: the chunked form's products, its
triangular inverse and its chunk scan, the recomputed forward
included: the share says what the step pays, the work what it needs).
A configuration without `delta_rule_work`, or a program without the
scope, gives None."""
from harness import op_names


def read(run):
    if run.trace is None or not hasattr(run.config_mod, "delta_rule_work"):
        return None
    seconds = op_names.seconds_a_step(
        run, lambda name: "/delta_rule/" in name)
    if seconds is None:
        return None
    flops, moved = run.config_mod.delta_rule_work(run.config, run.traffic)
    least = max(flops / run.peaks["peak_flops_bf16"],
                moved / run.peaks["peak_hbm_bytes_per_s"])
    return 100.0 * least / seconds
