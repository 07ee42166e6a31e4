"""The plain references against the Gluon models on seeded weights, at
a tiny size on the CPU (BERT 2 layers x 64, ResNet at 32x32), through
the same first steps the chip run compares; and the control, which has
to come out as not correct."""
from __future__ import annotations

import pytest

import tiny

CELLS = ["bert_tiny.seq32", "resnet_tiny.train32"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    run = tiny.load_run_module()
    from harness.manifest import Manifest

    manifest = Manifest(tiny.make_root(tmp_path_factory.mktemp("ref") / "r"))
    cache = {}

    def read(cell):
        if cell not in cache:
            job = run.make_job(manifest, cell, 2 ** 31 + 5, 0.0, 0,
                               tiny.CPU_DEVICE)
            cache[cell] = job.traffic_mod.read_seed(job, control=True,
                                                    faults=True)
        return cache[cell]

    return read


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_gluon_model(readings, cell):
    from harness import compare

    compared, correct = compare.judge(readings(cell)["program"],
                                      tiny.TINY_LIMITS)
    assert correct, compared


@pytest.mark.parametrize("cell", CELLS)
def test_control_precision_is_not_correct(readings, cell):
    from harness import compare

    compared, correct = compare.judge(readings(cell)["control"],
                                      tiny.TINY_LIMITS)
    assert not correct, compared
    assert any(c["value"] > 3 * c["limit"] for c in compared.values())


@pytest.mark.parametrize("cell", CELLS)
def test_half_batch_reference_is_not_correct(readings, cell):
    from harness import compare

    compared, correct = compare.judge(readings(cell)["half_batch"],
                                      tiny.TINY_LIMITS)
    assert not correct, compared
