"""Programs compiled, or loaded from the persistent cache, between the
window's opening and its close (jax.monitoring's compile events, counted
by the harness).  Has to read 0."""


def read(run):
    return run.counters.get("compiles_in_window")
