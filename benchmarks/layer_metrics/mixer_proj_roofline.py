"""The mixers' dense projections' share of their roofline: the least
time the chip could take for every dense product of every mixer held
(the configuration's `projection_work`: attention's q, k, v, gate and
output projections, the linear mixer's `[q | k | v | z]`, `[b | a]` and
output projections, by the published shapes, forward and twice that
backward, each weight and each product's input and output once a pass;
nothing recomputed), over the device time a step of what a mixer runs
AROUND its attention kernels, its delta rule and its short convolution:
the events whose instruction's op_name holds `/attention_full/`,
`/attention_window/` or `/linear_attention/`
(`jax.named_scope` in mxnet_tpu/models/decoder_lm.py) and neither
`/delta_rule/` nor `/conv/`, less the attention kernels' Mosaic calls
(the configuration's `attention_kernel_events`, as
`attn_mixed_roofline` finds them); forward, the recomputed forward and
backward, containers left out (harness/op_names.py).

The norms, the rotary embedding, the l2 norms, the gates and the head
transposes are in the time and not in the work: the share says what the
step pays around its projections, the work what the projections need.
With `linear_attn_ms`' scope, `delta_rule_roofline`'s and
`attn_mixed_roofline`'s kernels it splits the mixers' device time into
parts that add up.  A configuration without `projection_work`, or a
program without the scopes, gives None."""
import re

from harness import op_names

MIXERS = ("/attention_full/", "/attention_window/", "/linear_attention/")
NOT_PROJECTIONS = ("/delta_rule/", "/conv/")


def around_the_kernels(op_name):
    return any(scope in op_name for scope in MIXERS) and not any(
        scope in op_name for scope in NOT_PROJECTIONS)


def read(run):
    if run.trace is None or not hasattr(run.config_mod, "projection_work"):
        return None
    seconds = op_names.seconds_a_step(
        run, around_the_kernels, unless=re.compile(
            run.config_mod.attention_kernel_events(run.config, run.traffic)))
    if seconds is None:
        return None
    flops, moved = run.config_mod.projection_work(run.config, run.traffic)
    least = max(flops / run.peaks["peak_flops_bf16"],
                moved / run.peaks["peak_hbm_bytes_per_s"])
    return 100.0 * least / seconds
