"""Test config: force an 8-device virtual CPU mesh BEFORE jax imports.

Ref test strategy (SURVEY.md §4): the reference fakes a cluster with the
dmlc 'local' launcher and uses CPU as the oracle device; the modern
analogue is xla_force_host_platform_device_count=8 on the CPU backend,
giving every test a multi-device SPMD environment without TPU hardware.
"""
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# the tests run on the virtual CPU mesh wherever they are started; the
# chip is exercised by chip_smoke.py, never by pytest
os.environ["JAX_PLATFORMS"] = "cpu"
# libtpu's AOT topology path (tests/test_aot_tpu.py) queries the GCP
# instance metadata server for every TPU env var; when that endpoint
# 403s, each variable retries for minutes and the first AOT test
# appears to hang.  Skipping the metadata query keeps
# get_topology_desc purely local (~4s) with no behavior change.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
os.environ.setdefault("MXTPU_TEST_SEED", "17")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import functools  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def interpret_pallas(monkeypatch):
    """Force every pl.pallas_call into interpret mode (CPU testing of
    TPU Pallas kernels) — shared by all pallas kernel suites."""
    from jax.experimental import pallas as pl

    orig = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(orig, interpret=True))


@pytest.fixture
def donation_on(monkeypatch):
    """Run the trainer tiers WITH buffer donation, as on a chip.  They
    switch it off on the CPU backend (optimizer._fused_donate_ok), so
    nothing in this suite otherwise meets a donated — deleted — buffer;
    jaxlib's CPU client does donate when asked.  Code that reads a
    holder's array after the step that donated it then fails here as
    it does on the chip."""
    from mxnet_tpu import optimizer

    monkeypatch.setattr(optimizer, "_donate_ok", True)
