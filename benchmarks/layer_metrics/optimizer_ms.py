"""Device milliseconds a step spends in the optimizer's update: the
events whose instruction's op_name holds `/optimizer/`
(`jax.named_scope("optimizer")` in the program; harness/scopes.py)."""
from harness import scopes


def read(run):
    return scopes.read(run, "optimizer")
