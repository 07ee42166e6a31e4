"""Shared native-library loader (ref: python/mxnet/base.py _load_lib).

One cached find-so / auto-make / CDLL path for every native component
(libmxtpu_io, libmxtpu_engine, libmxtpu_storage)."""
from __future__ import annotations

import ctypes
import fcntl
import os
import shutil
import subprocess

from ..base import MXNetError, getenv

_cache = {}  # so_name -> CDLL


def repo_root():
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _stale(so, root):
    """True when any src/*.{cc,h} is newer than the built .so — a stale
    binary would silently run an OLD C ABI under new ctypes signatures
    (extra args are dropped by the calling convention, no error)."""
    if not os.path.exists(so):
        return True
    so_mtime = os.path.getmtime(so)
    src = os.path.join(root, "src")
    try:
        for f in os.listdir(src):
            if f.endswith((".cc", ".h", ".cpp")) and \
                    os.path.getmtime(os.path.join(src, f)) > so_mtime:
                return True
    except OSError:
        pass
    return False


def load_native_lib(so_name, make_target=None):
    """Return the CDLL for lib/<so_name>, building it via make when it
    is missing OR out of date vs src/ (``lib/`` is not tracked, so a
    fresh checkout builds at first use).  ``MXTPU_NO_NATIVE=1`` is the
    one way to run without the native tier (returns None); a build or
    a load that fails is an error with the tool's own output, never a
    silent switch to the Python path."""
    if getenv("NO_NATIVE", False, bool):
        return None  # env wins over the cache (tests toggle it)
    if so_name in _cache:
        return _cache[so_name]
    root = repo_root()
    so = os.path.join(root, "lib", so_name)
    # one builder at a time per checkout: test workers import in
    # parallel, and a .so another process is still writing is new
    # enough to pass the staleness check but does not load
    os.makedirs(os.path.dirname(so), exist_ok=True)
    with open(os.path.join(root, "lib", ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _stale(so, root):
            _build(root, so_name, make_target)
    try:
        _cache[so_name] = ctypes.CDLL(so)
    except OSError as e:
        raise MXNetError(
            f"native library {so} does not load: {e} (set "
            "MXTPU_NO_NATIVE=1 to run without the native tier)") from e
    return _cache[so_name]


def _build(root, so_name, make_target):
    opt_out = "set MXTPU_NO_NATIVE=1 to run without the native tier"
    for tool in ("make", "g++"):
        if not shutil.which(tool):
            raise MXNetError(
                f"lib/{so_name} is missing or older than src/ and there "
                f"is no {tool} to build it; {opt_out}")
    cmd = ["make", "-C", root]
    if make_target:
        cmd.append(make_target)
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=300)
    except subprocess.TimeoutExpired as e:
        raise MXNetError(
            f"{' '.join(cmd)} did not finish in {e.timeout:.0f}s; "
            f"{opt_out}") from e
    if p.returncode != 0:
        raise MXNetError(
            f"{' '.join(cmd)} failed (rc={p.returncode}); {opt_out}\n"
            f"{p.stdout[-2000:]}\n{p.stderr[-4000:]}")
