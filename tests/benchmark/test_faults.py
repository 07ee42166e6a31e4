"""Drives a whole run past the look for a chip, at a tiny size on the
CPU, with the timed path sound and then broken underneath, and sees
`correct` come out true and then false: once for each fault a one-chip
training cell can have (a step that returns its state unchanged; half
of the batch left out, the mean taken over the rest)."""
from __future__ import annotations

import pytest

import tiny

CELLS = ["bert_tiny.seq32", "resnet_tiny.train32"]


@pytest.fixture(scope="module")
def driver(tmp_path_factory):
    run = tiny.load_run_module()
    from harness.manifest import Manifest

    manifest = Manifest(tiny.make_root(tmp_path_factory.mktemp("run") / "r"))

    def drive(cell, seed):
        return run.drive(run.make_job(manifest, cell, seed, 0.2, 0,
                                      tiny.CPU_DEVICE))

    return drive


def _state_unchanged(real_step):
    def step(self, x, y):
        import jax
        import jax.numpy as jnp

        self.build(x)
        # copies: the real step may donate the buffers it is given
        before = jax.tree.map(lambda a: jnp.array(a, copy=True),
                              (self._params, self._states))
        loss = real_step(self, x, y)
        self._params, self._states = before
        return loss
    return step


def _half_batch(real_step):
    def step(self, x, y):
        half = y.shape[0] // 2
        x = tuple(v[:half] for v in x) if isinstance(x, tuple) else x[:half]
        return real_step(self, x, y[:half])
    return step


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(driver, cell):
    result = driver(cell, 11)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert {"setup_s", "step_ms"} <= set(result["metrics"])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_broken_step_is_not_correct(driver, monkeypatch, cell, fault):
    from mxnet_tpu.parallel.data_parallel import DataParallelTrainer

    monkeypatch.setattr(DataParallelTrainer, "step",
                        fault(DataParallelTrainer.step))
    result = driver(cell, 12)
    assert result["correct"] is False, result["compared"]
