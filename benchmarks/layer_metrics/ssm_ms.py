"""Device milliseconds a step spends in the Mamba-2 mixers: every event
whose instruction's op_name holds `/mamba/` (the input projections, the
short convolution under `conv`, the SSD scan under `ssd`, the gated
group norm, the output projection); forward, the recomputed forward and
backward.  `jax.named_scope("mamba")` in mxnet_tpu/models/decoder_lm.py.
Containers (loops, branches, calls) are left out, their `.clone.N`
copies too (harness/op_names.py)."""
from harness import op_names


def read(run):
    seconds = op_names.seconds_a_step(run, lambda name: "/mamba/" in name)
    return None if seconds is None else 1000.0 * seconds
