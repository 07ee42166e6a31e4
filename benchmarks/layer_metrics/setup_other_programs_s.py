"""Seconds of set-up jax spent making programs OUTSIDE the trainer's
set-up spans: the union of the trace, lower and compile events of
`profiler.compile_log()` that ended before set-up's end (weights made
from the seed, Gluon `initialize`, eager ops, the harness's first
readings; harness/setup_log.py)."""
from harness import setup_log


def read(run):
    return setup_log.other_programs_s(run)
