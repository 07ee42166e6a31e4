"""Seconds of set-up in the trainer's `build()` (span `dp.build`:
`dp.build.params` places the parameters, `dp.build.opt_state` makes the
optimizer state) less what jax traced, lowered and compiled inside it,
from the trainer's `build_record` (harness/setup_log.py)."""
from harness import setup_log


def read(run):
    return setup_log.build_s(run)
