"""Device milliseconds a step spends in the forward pass: the events
whose instruction's op_name holds `forward` and no `transpose(`
(`jax.named_scope("forward")` in the program; harness/scopes.py)."""
from harness import scopes


def read(run):
    return scopes.read(run, "forward")
