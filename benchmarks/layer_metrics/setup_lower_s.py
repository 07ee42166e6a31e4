"""Seconds of set-up jax spent lowering to MLIR (Mosaic kernels
included) inside the trainer's set-up spans, `dp.build` and the check
steps' `dp.step` calls: the union the program booked (`build_record`,
step-log field `lower_ns`; harness/setup_log.py)."""
from harness import setup_log


def read(run):
    return setup_log.booked_s(run, "lower")
