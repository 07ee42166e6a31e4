"""Device milliseconds a step spends in the linear-attention mixers
(Gated DeltaNet): every event whose instruction's op_name holds
`/linear_attention/` (the packed projections, the short convolution
under `conv`, the gated delta rule under `delta_rule`, the gated norm,
the output projection); forward, the recomputed forward and backward.
`jax.named_scope("linear_attention")` in
mxnet_tpu/models/decoder_lm.py.  The rule's chunk scan is a `while` on
the device: containers are left out, their `.clone.N` copies too
(harness/op_names.py)."""
from harness import op_names


def read(run):
    seconds = op_names.seconds_a_step(
        run, lambda name: "/linear_attention/" in name)
    return None if seconds is None else 1000.0 * seconds
