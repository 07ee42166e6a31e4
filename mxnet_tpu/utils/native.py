"""ctypes bindings for the native IO library (src/recordio.cc →
lib/libmxtpu_io.so).

Ref: python/mxnet/base.py _load_lib — the reference loads libmxnet.so
the same way.  Auto-builds with `make` on first use if the .so is
missing and g++ exists; everything degrades to the pure-Python path
when native is unavailable (MXTPU_NO_NATIVE=1 forces that).
"""
from __future__ import annotations

import ctypes
import os
import shutil
import subprocess

import numpy as np

from ..base import getenv

_lib = None
_tried = False


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def load():
    """Return the native lib handle or None."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    from .libloader import load_native_lib

    lib = load_native_lib("libmxtpu_io.so")
    if lib is None:
        return None
    # signatures
    lib.MXTPURecordIOWriterCreate.restype = ctypes.c_void_p
    lib.MXTPURecordIOWriterCreate.argtypes = [ctypes.c_char_p]
    lib.MXTPURecordIOWrite.restype = ctypes.c_int64
    lib.MXTPURecordIOWrite.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint64]
    lib.MXTPURecordIOWriterFree.argtypes = [ctypes.c_void_p]
    lib.MXTPURecordIOReaderCreate.restype = ctypes.c_void_p
    lib.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p]
    lib.MXTPURecordIORead.restype = ctypes.c_int64
    lib.MXTPURecordIORead.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_char_p)]
    lib.MXTPURecordIOSeek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.MXTPURecordIOTell.restype = ctypes.c_int64
    lib.MXTPURecordIOTell.argtypes = [ctypes.c_void_p]
    lib.MXTPURecordIOReaderFree.argtypes = [ctypes.c_void_p]
    lib.MXTPUImagePipelineCreate.restype = ctypes.c_void_p
    lib.MXTPUImagePipelineCreate.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float), ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_float)]
    lib.MXTPUImagePipelineReset.argtypes = [ctypes.c_void_p,
                                            ctypes.c_uint64]
    lib.MXTPUImagePipelineNext.restype = ctypes.c_int
    lib.MXTPUImagePipelineNext.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float)]
    lib.MXTPUImagePipelineNumBatches.restype = ctypes.c_uint64
    lib.MXTPUImagePipelineNumBatches.argtypes = [ctypes.c_void_p]
    lib.MXTPUImagePipelineDecodedBy.restype = ctypes.c_uint64
    lib.MXTPUImagePipelineDecodedBy.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
    lib.MXTPUImagePipelineFree.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


class NativeImagePipeline:
    """Wrapper over the C++ decode pipeline (ref: ImageRecordIOParser2)."""

    def __init__(self, rec_path, offsets, data_shape, batch_size,
                 num_threads=4, shuffle=False, rand_crop=False,
                 rand_mirror=False, resize_short=-1, mean=(0, 0, 0),
                 std=(1, 1, 1), seed=0, random_resized_crop=False,
                 min_random_area=1.0, max_random_area=1.0,
                 min_aspect_ratio=1.0, max_aspect_ratio=1.0,
                 brightness=0.0, contrast=0.0, saturation=0.0,
                 random_h=0.0, inter_method=1):
        lib = load()
        assert lib is not None, "native library unavailable"
        self._lib = lib
        self._shape = (batch_size,) + tuple(data_shape)
        offs = np.asarray(offsets, np.uint64)
        mean_arr = (ctypes.c_float * 3)(*[float(m) for m in mean])
        std_arr = (ctypes.c_float * 3)(*[float(s) for s in std])
        aug = (ctypes.c_float * 10)(
            float(bool(random_resized_crop)), float(min_random_area),
            float(max_random_area), float(min_aspect_ratio),
            float(max_aspect_ratio), float(brightness), float(contrast),
            float(saturation), float(random_h), float(inter_method))
        self._handle = lib.MXTPUImagePipelineCreate(
            rec_path.encode(), offs.ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint64)), len(offs),
            data_shape[0], data_shape[1], data_shape[2], batch_size,
            num_threads, int(shuffle), int(rand_crop), int(rand_mirror),
            int(resize_short), mean_arr, std_arr, seed, aug)
        assert self._handle, f"failed to open {rec_path}"
        self._epoch = 0
        self._num_threads = num_threads
        self._data_buf = np.empty(self._shape, np.float32)
        self._label_buf = np.empty(batch_size, np.float32)

    def reset(self):
        self._lib.MXTPUImagePipelineReset(self._handle, self._epoch)
        self._epoch += 1

    def next(self):
        n = self._lib.MXTPUImagePipelineNext(
            self._handle,
            self._data_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._label_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if n == 0:
            return None
        return self._data_buf.copy(), self._label_buf.copy()

    def decoded_by(self):
        """Records each worker of the decode pool has decoded so far."""
        return [int(self._lib.MXTPUImagePipelineDecodedBy(self._handle, t))
                for t in range(self._num_threads)]

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                self._lib.MXTPUImagePipelineFree(self._handle)
                self._handle = None
        except Exception:
            pass
