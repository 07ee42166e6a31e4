"""Seconds of set-up XLA spent compiling, or loading the executable
from the persistent cache, inside the trainer's set-up spans,
`dp.build` and the check steps' `dp.step` calls: the union the program
booked (`build_record`, step-log field `compile_ns`;
harness/setup_log.py)."""
from harness import setup_log


def read(run):
    return setup_log.booked_s(run, "compile")
