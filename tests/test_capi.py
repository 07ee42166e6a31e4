"""Flat C ABI (multi-frontend boundary) — compile and run a pure-C
frontend against lib/libmxtpu_capi.so.

Ref: include/mxnet/c_api.h + src/c_api/c_api.cc (the reference's ~400
MX* flat functions that Scala/R/Julia/cpp-package ride).  The TPU build
inverts the embedding (C hosts the Python orchestrator, which drives
XLA), but the frontend-facing contract is the same: opaque NDArray
handles, string-keyed imperative invoke against the op registry,
GetLastError error protocol, stateless flat calls.

The test builds the .so (make) and the C driver (gcc), then runs the
driver in a clean subprocess — a frontend with no Python of its own.
"""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    return shutil.which(name)


@pytest.mark.skipif(not _tool("g++") or not _tool("python3-config"),
                    reason="native toolchain unavailable")
def test_c_frontend_drives_the_framework(tmp_path):
    # 1. build the shared library
    r = subprocess.run(["make", "lib/libmxtpu_capi.so"], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]

    # 2. build the C driver (plain C, no python headers — the point)
    exe = str(tmp_path / "capi_driver")
    r = subprocess.run(
        ["gcc", os.path.join(REPO, "tests", "capi_driver.c"),
         "-o", exe, "-L" + os.path.join(REPO, "lib"), "-lmxtpu_capi",
         # the driver pthread_joins its own threads; toolchains that
         # don't link libpthread implicitly need it spelled out
         "-lpthread", "-Wl,-rpath," + os.path.join(REPO, "lib")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]

    # 3. run it: the embedded interpreter must find the venv + repo.
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in sys.path if "site-packages" in p])
    # the driver pins jax to cpu itself (MXTPUCAPIInit("cpu")); an
    # inherited JAX_PLATFORMS must not fight that in the subprocess
    env.pop("JAX_PLATFORMS", None)
    save_path = str(tmp_path / "capi_saved.params")
    r = subprocess.run([exe, save_path], capture_output=True, text=True,
                       timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "CAPI_DRIVER_OK" in r.stdout
    # the C frontend's save must be loadable by the python frontend
    # (backend/path setup already done by conftest)
    import numpy as np

    from mxnet_tpu.ndarray import ndarray as _nd

    loaded = _nd.load(save_path)
    assert set(loaded) == {"weight_a", "weight_b"}
    assert np.allclose(loaded["weight_a"].asnumpy(),
                       np.arange(1, 7).reshape(2, 3))


def _write_mnist_idx(tmp_path, n=640, seed=0):
    """Synthesize a learnable MNIST-format dataset: each class is a
    bright block at a class-dependent position plus noise (so LeNet can
    drive the loss down in a couple of epochs without the real files)."""
    import struct

    import numpy as np

    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 10, n).astype(np.uint8)
    images = (rng.rand(n, 28, 28) * 64).astype(np.uint8)
    for i, c in enumerate(labels):
        r, col = divmod(int(c), 5)
        images[i, 4 + r * 12:4 + r * 12 + 8,
               2 + col * 5:2 + col * 5 + 5] = 255
    img_path = str(tmp_path / "train-images.idx")
    lbl_path = str(tmp_path / "train-labels.idx")
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(images.tobytes())
    with open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    return img_path, lbl_path


@pytest.mark.skipif(not _tool("g++") or not _tool("python3-config"),
                    reason="native toolchain unavailable")
def test_c_frontend_trains_lenet(tmp_path):
    """VERDICT r3 #4: the trainable C ABI — a pure-C frontend composes
    LeNet symbolically, binds an executor, iterates MNISTIter batches,
    runs forward/backward, applies SGD updates, and the loss decreases;
    plus imperative autograd, kvstore push/pull, and CachedOp inference,
    all through the flat C surface (ref: cpp-package/example/lenet.cpp
    over include/mxnet/c_api.h)."""
    r = subprocess.run(["make", "lib/libmxtpu_capi.so"], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]

    exe = str(tmp_path / "capi_train_lenet")
    r = subprocess.run(
        ["gcc", os.path.join(REPO, "tests", "capi_train_lenet.c"),
         "-o", exe, "-L" + os.path.join(REPO, "lib"), "-lmxtpu_capi",
         "-lm", "-Wl,-rpath," + os.path.join(REPO, "lib")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]

    img, lbl = _write_mnist_idx(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in sys.path if "site-packages" in p])
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([exe, img, lbl], capture_output=True, text=True,
                       timeout=900, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "CAPI_TRAIN_OK" in r.stdout
    # the driver asserts the loss curve itself; sanity-check the print
    assert "epoch 2 loss" in r.stdout


@pytest.mark.skipif(not _tool("g++") or not _tool("python3-config"),
                    reason="native toolchain unavailable")
def test_cpp_frontend_header_only_api(tmp_path):
    """The cpp-package role: include/mxtpu_cpp.hpp (RAII + exceptions
    over the flat C ABI) trains an MLP from C++ — a SECOND non-Python
    frontend on the same boundary (ref: cpp-package/include/mxnet-cpp
    over include/mxnet/c_api.h)."""
    r = subprocess.run(["make", "lib/libmxtpu_capi.so"], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]

    exe = str(tmp_path / "capi_cpp_driver")
    r = subprocess.run(
        ["g++", "-std=c++17", "-I" + os.path.join(REPO, "include"),
         os.path.join(REPO, "tests", "capi_cpp_driver.cc"),
         "-o", exe, "-L" + os.path.join(REPO, "lib"), "-lmxtpu_capi",
         "-Wl,-rpath," + os.path.join(REPO, "lib")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in sys.path if "site-packages" in p])
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([exe], capture_output=True, text=True,
                       timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "CAPI_CPP_OK" in r.stdout
