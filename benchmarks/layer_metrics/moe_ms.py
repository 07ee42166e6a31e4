"""Device milliseconds a step spends in the expert layers' routed part:
every event whose instruction's op_name holds `/moe/` (the router, the
dispatch's sort and gather, the experts' SwiGLU passes, the combine)
or is one of the grouped products' own kernels, which the compiler
names outside every scope (harness/op_names.py: GROUPED_PRODUCT);
forward, the recomputed forward and backward.
`jax.named_scope("moe")` in mxnet_tpu/ops/moe.py."""
from harness import op_names


def read(run):
    seconds = op_names.seconds_a_step(
        run, lambda name: "/moe/" in name or op_names.GROUPED_PRODUCT in name)
    return None if seconds is None else 1000.0 * seconds
