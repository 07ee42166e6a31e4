"""Device milliseconds a step spends in the backward pass: the events
whose instruction's op_name holds `transpose(`, as JAX names the
transposed `jvp(forward)` (harness/scopes.py)."""
from harness import scopes


def read(run):
    return scopes.read(run, "backward")
