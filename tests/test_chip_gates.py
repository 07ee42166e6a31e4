"""The gates that keep a run from hiding the device (PR 22): the chip
smoke fails without a TPU instead of switching to the CPU, unknown
devices have no peak in the benchmark's table nor the health
monitor's, the compile cache has one rule, a native build that fails
says so, and arrays a model creates for itself follow its inputs rather
than the default (host) context."""
import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """No CPU switch, no fallback: non-zero exit, no phase, no result."""
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert "phase" not in r.stdout and '"ok"' not in r.stdout


def test_chip_smoke_has_no_cpu_option():
    r = _run("chip_smoke.py", "--cpu")
    assert r.returncode != 0 and '"ok"' not in r.stdout


def _benchmark_device():
    spec = importlib.util.spec_from_file_location(
        "benchmark_device",
        os.path.join(REPO, "benchmarks", "harness", "device.py"))
    device = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(device)
    return device


@pytest.mark.parametrize("kind", ["TPU v5", "TPU v7x", "Quantum9000"])
def test_benchmark_peaks_reject_unknown_device_kind(kind, monkeypatch):
    """The benchmark's table, the one the driver's numbers are divided
    by: a bare 'v5' must not inherit another chip's peak; an unknown
    device is an error, not a default."""
    device = _benchmark_device()
    table = device.peaks_table()
    assert table["TPU v5 lite"]["peak_flops_bf16"] == 197e12
    assert table["TPU v5 lite"]["peak_hbm_bytes_per_s"] == 819e9
    assert kind not in table

    class Dev:
        platform = "tpu"
        device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(device.NoChip, match=r"peaks\.json"):
        device.require_chips(1)
    Dev.device_kind = "TPU v5 lite"
    assert device.require_chips(1)["peaks"] == table["TPU v5 lite"]


def test_health_peak_rejects_unknown_accelerator(monkeypatch):
    from mxnet_tpu.telemetry import health

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.delenv("MXTPU_HEALTH_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    assert health._resolve_peak_flops() == 197e12
    Dev.device_kind = "TPU v5"      # used to match the v5e figure
    with pytest.raises(MXNetError, match="no published peak"):
        health._resolve_peak_flops()
    assert health._resolve_peak_flops(3e12) == 3e12   # explicit wins


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_has_one_rule(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory.
    Unset: <checkout>/.jax_cache — a fixed path, never a temp name."""
    from mxnet_tpu.utils import compile_cache

    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.enable()
    if env_dir is None:
        assert got == os.path.join(REPO, ".jax_cache")
        assert seen["jax_compilation_cache_dir"] == got
    else:
        assert got == env_dir
        assert "jax_compilation_cache_dir" not in seen


def test_one_setter_of_the_compile_cache_dir():
    paths = [os.path.join(REPO, f) for f in os.listdir(REPO)
             if f.endswith(".py")]
    for top in ("mxnet_tpu", "tools", "examples", "tests"):
        for root, _dirs, files in os.walk(os.path.join(REPO, top)):
            paths += [os.path.join(root, f) for f in files
                      if f.endswith(".py")]
    hits = []
    for path in paths:
        if path != os.path.abspath(__file__):
            with open(path) as fh:
                if '"jax_compilation_cache_dir"' in fh.read():
                    hits.append(os.path.relpath(path, REPO))
    assert hits == ["mxnet_tpu/utils/compile_cache.py"]


def test_failed_native_build_raises_with_the_tools_output(monkeypatch):
    """utils/libloader used to swallow a failed make and return None
    (the Python path, silently); MXTPU_NO_NATIVE is the one opt-out."""
    from mxnet_tpu.utils import libloader

    monkeypatch.delenv("MXTPU_NO_NATIVE", raising=False)
    with pytest.raises(MXNetError, match="make .* failed") as e:
        libloader.load_native_lib("libmxtpu_nope.so",
                                  "lib/libmxtpu_nope.so")
    assert "No rule to make target" in str(e.value)
    assert "MXTPU_NO_NATIVE=1" in str(e.value)
    monkeypatch.setenv("MXTPU_NO_NATIVE", "1")
    assert libloader.load_native_lib("libmxtpu_nope.so") is None


# -- arrays a model creates for itself follow its inputs ------------------
# On the virtual mesh mx.cpu() is device 0 and mx.xla(1) device 1: a
# model living on xla(1) meets the same two-device host a chip has.

def test_bert_forward_stays_on_its_inputs_context():
    from mxnet_tpu.models import bert

    ctx = mx.xla(1)
    net = bert.bert_tiny(vocab_size=50, dropout=0.0)
    net.initialize(ctx=ctx)
    rng = np.random.RandomState(0)
    tokens = nd.array(rng.randint(0, 50, (2, 8)), ctx=ctx, dtype="int32")
    types = nd.zeros((2, 8), ctx=ctx, dtype="int32")
    valid = nd.array([8, 5], ctx=ctx, dtype="int32")
    pos = nd.array([[1, 2], [0, 3]], ctx=ctx, dtype="int32")
    mlm, nsp = net(tokens, types, valid, pos)     # eager, not hybridized
    assert mlm.context == ctx and nsp.context == ctx
    assert mlm.shape == (2, 2, 50)


def test_transformer_mask_stays_on_its_inputs_context():
    from mxnet_tpu.models.transformer import TransformerModel

    ctx = mx.xla(1)
    net = TransformerModel(src_vocab=20, tgt_vocab=20, units=16,
                           hidden_size=32, num_layers=1, num_heads=2,
                           max_length=16, dropout=0.0)
    net.initialize(ctx=ctx)
    src = nd.array(np.arange(12).reshape(2, 6) % 20, ctx=ctx,
                   dtype="int32")
    tgt = nd.array(np.arange(8).reshape(2, 4) % 20, ctx=ctx,
                   dtype="int32")
    valid = nd.array([6, 3], ctx=ctx, dtype="int32")
    assert net(src, tgt, valid).context == ctx


@pytest.mark.parametrize("layer", ["LSTM", "GRU"])
def test_rnn_implicit_begin_state_follows_the_input(layer):
    from mxnet_tpu import gluon

    ctx = mx.xla(1)
    net = getattr(gluon.rnn, layer)(8)
    net.initialize(ctx=ctx)
    assert net(nd.ones((3, 2, 4), ctx=ctx)).context == ctx
    # explicit form: kwargs reach the creation function (ref API)
    assert all(s.context == ctx for s in net.begin_state(2, ctx=ctx))
