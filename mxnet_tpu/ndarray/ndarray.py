"""NDArray: the imperative n-dim array over XLA/PjRt buffers.

Ref: include/mxnet/ndarray.h + src/ndarray/ndarray.cc — ref-counted array
bound to a device context with an engine variable for async dependency
tracking; CopyFromTo; WaitToRead/WaitToWrite; Save/Load.

TPU-native design: ``NDArray`` wraps a ``jax.Array``.  The engine
variable IS the buffer — XLA dispatch is async and per-buffer ordering is
enforced by the runtime, so ``wait_to_read`` maps to
``block_until_ready``.  Device placement uses ``Context.jax_device()``;
cross-device copy is ``jax.device_put`` (ref: CopyFromTo).  Versioning
for autograd is handled by the tape pinning raw buffers at record time
(functional arrays never mutate, so WAR/WAW hazards cannot exist — the
reference needs ThreadedVar state machines precisely because CUDA
buffers mutate in place).
"""
from __future__ import annotations

import struct

import jax
import jax.numpy as jnp
import numpy as np

from .. import _imperative, autograd, engine
from .._imperative import invoke
from ..base import MXNetError
from ..context import Context, cpu, current_context

__all__ = ["NDArray", "array", "zeros", "ones", "full", "arange", "empty",
           "zeros_like", "ones_like", "eye", "linspace", "histogram",
           "concatenate", "waitall", "save", "load", "from_jax",
           "moveaxis"]


def waitall():
    engine.waitall()


def _wrap(jarr):
    nd = NDArray.__new__(NDArray)
    nd._data = jarr
    nd._grad = None
    nd._grad_req = "write"
    nd._in_graph = False
    return nd


def from_jax(jarr):
    """Zero-copy wrap of an existing jax.Array."""
    return _wrap(jarr)


def _to_jax_dtype(dtype):
    if dtype is None:
        return jnp.float32
    if dtype in (float, "float"):
        return jnp.float32
    if dtype in (int, "int"):
        return jnp.int32
    return jnp.dtype(dtype)


# --- pure op fns used by operators/methods (kept module-level so the
# jit/vjp caches in _imperative key them stably) -----------------------------

def _add(x, y): return jnp.add(x, y)
def _sub(x, y): return jnp.subtract(x, y)
def _rsub(x, y): return jnp.subtract(y, x)
def _mul(x, y): return jnp.multiply(x, y)
def _div(x, y): return jnp.divide(x, y)
def _rdiv(x, y): return jnp.divide(y, x)
def _mod(x, y): return jnp.mod(x, y)
def _pow(x, y): return jnp.power(x, y)
def _rpow(x, y): return jnp.power(y, x)
def _neg(x): return jnp.negative(x)
def _abs(x): return jnp.abs(x)

def _add_scalar(x, *, scalar): return x + scalar
def _sub_scalar(x, *, scalar): return x - scalar
def _rsub_scalar(x, *, scalar): return scalar - x
def _mul_scalar(x, *, scalar): return x * scalar
def _div_scalar(x, *, scalar): return x / scalar
def _rdiv_scalar(x, *, scalar): return scalar / x
def _mod_scalar(x, *, scalar): return x % scalar
def _pow_scalar(x, *, scalar): return x ** scalar
def _rpow_scalar(x, *, scalar): return scalar ** x

def _eq(x, y): return (x == y).astype(x.dtype)
def _ne(x, y): return (x != y).astype(x.dtype)
def _gt(x, y): return (x > y).astype(x.dtype)
def _ge(x, y): return (x >= y).astype(x.dtype)
def _lt(x, y): return (x < y).astype(x.dtype)
def _le(x, y): return (x <= y).astype(x.dtype)
def _eq_scalar(x, *, scalar): return (x == scalar).astype(x.dtype)
def _ne_scalar(x, *, scalar): return (x != scalar).astype(x.dtype)
def _gt_scalar(x, *, scalar): return (x > scalar).astype(x.dtype)
def _ge_scalar(x, *, scalar): return (x >= scalar).astype(x.dtype)
def _lt_scalar(x, *, scalar): return (x < scalar).astype(x.dtype)
def _le_scalar(x, *, scalar): return (x <= scalar).astype(x.dtype)

def _reshape(x, *, shape): return jnp.reshape(x, shape)
def _transpose(x, *, axes): return jnp.transpose(x, axes if axes else None)
def _astype(x, *, dtype): return x.astype(jnp.dtype(dtype))
def _sum(x, *, axis, keepdims): return jnp.sum(x, axis=axis, keepdims=keepdims)
def _mean(x, *, axis, keepdims): return jnp.mean(x, axis=axis, keepdims=keepdims)
def _max(x, *, axis, keepdims): return jnp.max(x, axis=axis, keepdims=keepdims)
def _min(x, *, axis, keepdims): return jnp.min(x, axis=axis, keepdims=keepdims)
def _prod(x, *, axis, keepdims): return jnp.prod(x, axis=axis, keepdims=keepdims)
def _argmax(x, *, axis): return jnp.argmax(x, axis=axis).astype(jnp.float32)
def _argmin(x, *, axis): return jnp.argmin(x, axis=axis).astype(jnp.float32)
def _clip(x, *, a_min, a_max): return jnp.clip(x, a_min, a_max)
def _dot(x, y): return jnp.dot(x, y)
def _getitem(x, *, index): return x[_decode_index(index)]
def _getitem_adv(x, *idx_arrays, index):
    it = iter(idx_arrays)
    full = tuple(next(it) if i is _ARRAY_SLOT else i
                 for i in _decode_index(index))
    return x[full]
def _take(x, indices, *, axis, mode):
    m = {"clip": "clip", "wrap": "wrap", "raise": "clip"}[mode]
    return jnp.take(x, indices.astype(jnp.int32), axis=axis, mode=m)
def _expand_dims(x, *, axis): return jnp.expand_dims(x, axis)
def _squeeze(x, *, axis): return jnp.squeeze(x, axis=axis)
def _broadcast_to(x, *, shape): return jnp.broadcast_to(x, shape)
def _swapaxes(x, *, dim1, dim2): return jnp.swapaxes(x, dim1, dim2)
def _flip(x, *, axis): return jnp.flip(x, axis)
def _tile(x, *, reps): return jnp.tile(x, reps)
def _repeat(x, *, repeats, axis): return jnp.repeat(x, repeats, axis=axis)
def _moveaxis(x, *, source, destination):
    return jnp.moveaxis(x, source, destination)
def _slice_op(x, *, begin, end, step):
    idx = tuple(slice(b, e, s) for b, s, e in
                zip(begin, step, end))
    return x[idx]
def _slice_axis(x, *, axis, begin, end):
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(begin, end)
    return x[tuple(idx)]
def _slice_like(x, y, *, axes):
    idx = [slice(None)] * x.ndim
    axes_ = axes if axes else range(min(x.ndim, y.ndim))
    for ax in axes_:
        idx[ax] = slice(0, y.shape[ax])
    return x[tuple(idx)]


# --- index encode/decode (hashable static attr for the jit cache) ----------


class _ArraySlot:
    """Sentinel marking where a traced index array goes (distinct from
    None, which means np.newaxis)."""

    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst


_ARRAY_SLOT = _ArraySlot()


def _encode_index(idx):
    """Convert an indexing expression to a hashable tree; array components
    are replaced by placeholders and passed as traced args."""
    arrays = []

    def enc(i):
        # NOTE: bool before int — bool is an int subclass
        if isinstance(i, bool):
            return ("b", i)
        if isinstance(i, slice):
            return ("s", i.start, i.stop, i.step)
        if i is Ellipsis:
            return ("e",)
        if i is None:
            return ("n",)
        if isinstance(i, (int, np.integer)):
            return ("i", int(i))
        if isinstance(i, NDArray):
            arrays.append(i)
            return ("a",)
        if isinstance(i, (np.ndarray, list)):
            arrays.append(array(i, dtype=np.asarray(i).dtype))
            return ("a",)
        if isinstance(i, tuple):
            return ("t",) + tuple(enc(j) for j in i)
        raise MXNetError(f"unsupported index component {i!r}")

    return enc(idx), arrays


def _decode_index(tree):
    def dec(t):
        tag = t[0]
        if tag == "s":
            return slice(t[1], t[2], t[3])
        if tag == "e":
            return Ellipsis
        if tag == "n":
            return None
        if tag in ("i", "b"):
            return t[1]
        if tag == "a":
            return _ARRAY_SLOT  # filled from traced args
        if tag == "t":
            return tuple(dec(j) for j in t[1:])
        raise AssertionError(t)

    out = dec(tree)
    if not isinstance(out, tuple) or tree[0] != "t":
        out = (out,)
    return out


class NDArray:
    """An n-dimensional array on a device (ref: include/mxnet/ndarray.h)."""

    __slots__ = ("_data", "_grad", "_grad_req", "_in_graph", "__weakref__")

    def __init__(self, data, ctx=None, dtype=None):
        if isinstance(data, NDArray):
            data = data._data
        jdt = _to_jax_dtype(dtype) if dtype is not None else None
        dev = (ctx or current_context()).jax_device() if ctx is not None else None
        arr = jnp.asarray(data, dtype=jdt)
        if dev is not None:
            arr = jax.device_put(arr, dev)
        self._data = engine.track(arr)
        self._grad = None
        self._grad_req = "write"
        self._in_graph = False

    # -- basic properties ---------------------------------------------------

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(self._data.size)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        dev = list(self._data.devices())[0]
        if dev.platform == "cpu":
            return Context("cpu", dev.id)
        return Context("xla", dev.id)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return self.transpose()

    @property
    def stype(self):
        return "default"

    # -- conversion ---------------------------------------------------------

    def asnumpy(self):
        """Blocking copy to host (ref: NDArray SyncCopyToCPU)."""
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of 0-d array")
        return self.shape[0]

    def astype(self, dtype, copy=True):
        return invoke(_astype, self, dtype=str(np.dtype(_to_jax_dtype(dtype))))

    def copy(self):
        return _wrap(engine.track(jnp.copy(self._data)))

    def copyto(self, other):
        """Ref: CopyFromTo."""
        if isinstance(other, NDArray):
            other._data = engine.track(
                jax.device_put(self._data, list(other._data.devices())[0]))
            return other
        if isinstance(other, Context):
            return _wrap(engine.track(jax.device_put(self._data, other.jax_device())))
        raise MXNetError(f"cannot copyto {type(other)}")

    def as_in_context(self, ctx):
        if self.context == ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse

        return sparse.cast_storage(self, stype)

    def detach(self):
        out = _wrap(self._data)
        return out

    # -- async control (ref: WaitToRead/WaitToWrite) ------------------------

    def wait_to_read(self):
        self._data.block_until_ready()

    def wait_to_write(self):
        self._data.block_until_ready()

    # -- autograd -----------------------------------------------------------

    def attach_grad(self, grad_req="write", stype=None):
        """Allocate a gradient buffer (ref: autograd.attach_grad)."""
        self._grad = _wrap(jnp.zeros(self.shape, self.dtype))
        self._grad_req = grad_req
        self._in_graph = True

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- arithmetic ---------------------------------------------------------

    def _binary(self, other, fn, scalar_fn):
        if isinstance(other, NDArray):
            return invoke(fn, self, other)
        if isinstance(other, (int, float, bool, np.generic)):
            return invoke(scalar_fn, self, scalar=float(other)
                          if isinstance(other, float) else other)
        if isinstance(other, (np.ndarray, list, tuple)):
            return invoke(fn, self, array(other, dtype=self.dtype))
        return NotImplemented

    def __add__(self, o): return self._binary(o, _add, _add_scalar)
    def __radd__(self, o): return self._binary(o, _add, _add_scalar)
    def __sub__(self, o): return self._binary(o, _sub, _sub_scalar)
    def __rsub__(self, o): return self._binary(o, _rsub, _rsub_scalar)
    def __mul__(self, o): return self._binary(o, _mul, _mul_scalar)
    def __rmul__(self, o): return self._binary(o, _mul, _mul_scalar)
    def __truediv__(self, o): return self._binary(o, _div, _div_scalar)
    def __rtruediv__(self, o): return self._binary(o, _rdiv, _rdiv_scalar)
    def __mod__(self, o): return self._binary(o, _mod, _mod_scalar)
    def __pow__(self, o): return self._binary(o, _pow, _pow_scalar)
    def __rpow__(self, o): return self._binary(o, _rpow, _rpow_scalar)
    def __neg__(self): return invoke(_neg, self)
    def __abs__(self): return invoke(_abs, self)
    def __matmul__(self, o): return invoke(_dot, self, o)

    def __iadd__(self, o): return self._inplace(self.__add__(o))
    def __isub__(self, o): return self._inplace(self.__sub__(o))
    def __imul__(self, o): return self._inplace(self.__mul__(o))
    def __itruediv__(self, o): return self._inplace(self.__truediv__(o))

    def _inplace(self, result):
        self._data = result._data
        return self

    def __eq__(self, o): return self._binary(o, _eq, _eq_scalar)
    def __ne__(self, o): return self._binary(o, _ne, _ne_scalar)
    def __gt__(self, o): return self._binary(o, _gt, _gt_scalar)
    def __ge__(self, o): return self._binary(o, _ge, _ge_scalar)
    def __lt__(self, o): return self._binary(o, _lt, _lt_scalar)
    def __le__(self, o): return self._binary(o, _le, _le_scalar)

    def __hash__(self):
        return id(self)

    # -- indexing -----------------------------------------------------------

    def __getitem__(self, idx):
        tree, arrays = _encode_index(idx)
        if arrays:
            return invoke(_getitem_adv, self, *arrays, index=tree)
        return invoke(_getitem, self, index=tree)

    def __setitem__(self, idx, value):
        if isinstance(value, NDArray):
            v = value._data
            # ref: a[:] = b is CopyFromTo — it crosses contexts (a
            # constant made on the default context, the host, assigned
            # into a parameter that lives on the chip)
            if not isinstance(v, jax.core.Tracer) and \
                    not isinstance(self._data, jax.core.Tracer):
                dst = self._data.devices()
                if len(dst) == 1 and v.devices() != dst:
                    v = jax.device_put(v, next(iter(dst)))
        else:
            v = jnp.asarray(value, self._data.dtype)
        tree, arrays = _encode_index(idx)
        if arrays:
            dec = _decode_index(tree)
            it = iter(a._data for a in arrays)
            full = tuple(next(it) if d is _ARRAY_SLOT else d for d in dec)
            self._data = engine.track(self._data.at[full].set(v))
        else:
            self._data = engine.track(
                self._data.at[_decode_index(tree)].set(v))

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- shape manipulation -------------------------------------------------

    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        reverse = bool(kwargs.get("reverse", False))
        # MXNet magic values (0 copy, -1 infer, -2 rest, -3 merge,
        # -4 split) resolved centrally — ref matrix_op-inl.h
        if any(int(s) <= 0 for s in shape):
            from ..ops.tensor import mx_reshape_target

            shape = mx_reshape_target(self.shape, shape, reverse)
        return invoke(_reshape, self, shape=tuple(int(s) for s in shape))

    def reshape_like(self, other):
        return invoke(_reshape, self, shape=other.shape)

    def transpose(self, *axes, **kwargs):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        axes = tuple(kwargs.get("axes", axes))
        return invoke(_transpose, self, axes=axes)

    def flatten(self):
        n = self.shape[0] if self.ndim else 1
        return invoke(_reshape, self, shape=(n, int(self.size // max(n, 1))))

    def expand_dims(self, axis):
        return invoke(_expand_dims, self, axis=axis)

    def squeeze(self, axis=None):
        return invoke(_squeeze, self, axis=axis)

    def broadcast_to(self, shape):
        return invoke(_broadcast_to, self, shape=tuple(shape))

    def broadcast_like(self, other):
        return invoke(_broadcast_to, self, shape=other.shape)

    def swapaxes(self, dim1, dim2):
        return invoke(_swapaxes, self, dim1=dim1, dim2=dim2)

    def split(self, num_outputs, axis=0):
        from . import ops as _ops

        return _ops.split(self, num_outputs=num_outputs, axis=axis)

    def slice(self, begin, end, step=None):
        step = step or tuple(1 for _ in begin)
        return invoke(_slice_op, self, begin=tuple(begin), end=tuple(end),
                      step=tuple(step))

    def slice_axis(self, axis, begin, end):
        return invoke(_slice_axis, self, axis=axis, begin=begin, end=end)

    def slice_like(self, other, axes=()):
        return invoke(_slice_like, self, other, axes=tuple(axes))

    def take(self, indices, axis=0, mode="clip"):
        return invoke(_take, self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False, mode="clip"):
        from . import ops as _ops

        return _ops.pick(self, index, axis=axis, keepdims=keepdims,
                         mode=mode)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from . import ops as _ops

        return _ops.one_hot(self, depth, on_value=on_value, off_value=off_value)

    def tile(self, reps):
        return invoke(_tile, self, reps=tuple(reps) if not isinstance(reps, int) else reps)

    def repeat(self, repeats, axis=None):
        return invoke(_repeat, self, repeats=repeats, axis=axis)

    def flip(self, axis):
        return invoke(_flip, self, axis=axis)

    def moveaxis(self, source, destination):
        return invoke(_moveaxis, self, source=source, destination=destination)

    # -- reductions & math --------------------------------------------------

    def _reduce(self, fn, axis, keepdims):
        axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis
        return invoke(fn, self, axis=axis, keepdims=keepdims)

    def sum(self, axis=None, keepdims=False, **kw):
        return self._reduce(_sum, axis, keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return self._reduce(_mean, axis, keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return self._reduce(_max, axis, keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return self._reduce(_min, axis, keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return self._reduce(_prod, axis, keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        from . import ops as _ops

        return _ops.norm(self, ord=ord, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, **kw):
        return invoke(_argmax, self, axis=axis)

    def argmin(self, axis=None, **kw):
        return invoke(_argmin, self, axis=axis)

    def clip(self, a_min=None, a_max=None):
        return invoke(_clip, self, a_min=a_min, a_max=a_max)

    def abs(self):
        return invoke(_abs, self)

    def sqrt(self):
        from . import ops as _ops

        return _ops.sqrt(self)

    def exp(self):
        from . import ops as _ops

        return _ops.exp(self)

    def log(self):
        from . import ops as _ops

        return _ops.log(self)

    def sigmoid(self):
        from . import ops as _ops

        return _ops.sigmoid(self)

    def relu(self):
        from . import ops as _ops

        return _ops.relu(self)

    def softmax(self, axis=-1):
        from . import ops as _ops

        return _ops.softmax(self, axis=axis)

    def log_softmax(self, axis=-1):
        from . import ops as _ops

        return _ops.log_softmax(self, axis=axis)

    def dot(self, other):
        return invoke(_dot, self, other)

    def square(self):
        from . import ops as _ops

        return _ops.square(self)

    def __repr__(self):
        return (f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))}"
                f" @{self.context}>")


# ---------------------------------------------------------------------------
# Creation functions (ref: python/mxnet/ndarray/utils.py + ndarray.py)


def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        src = source_array._data
        dtype = dtype or source_array.dtype
    else:
        src = np.asarray(source_array)
        if dtype is None:
            dtype = np.float32 if src.dtype == np.float64 else src.dtype
    return NDArray(src, ctx=ctx or current_context(), dtype=dtype)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jnp.zeros(shape, _to_jax_dtype(dtype)),
                   ctx=ctx or current_context())


def ones(shape, ctx=None, dtype=None, **kwargs):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jnp.ones(shape, _to_jax_dtype(dtype)),
                   ctx=ctx or current_context())


def full(shape, val, ctx=None, dtype=None):
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    return NDArray(jnp.full(shape, val, _to_jax_dtype(dtype)),
                   ctx=ctx or current_context())


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    arr = jnp.arange(start, stop, step, _to_jax_dtype(dtype))
    if repeat > 1:
        arr = jnp.repeat(arr, repeat)
    return NDArray(arr, ctx=ctx or current_context())


def zeros_like(other, **kw):
    return zeros(other.shape, dtype=other.dtype,
                 ctx=other.context if isinstance(other, NDArray) else None)


def ones_like(other, **kw):
    return ones(other.shape, dtype=other.dtype,
                ctx=other.context if isinstance(other, NDArray) else None)


def eye(N, M=0, k=0, ctx=None, dtype=None):
    return NDArray(jnp.eye(N, M if M else None, k, _to_jax_dtype(dtype)),
                   ctx=ctx or current_context())


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    return NDArray(jnp.linspace(start, stop, num, endpoint=endpoint,
                                dtype=_to_jax_dtype(dtype)),
                   ctx=ctx or current_context())


def histogram(a, bins=10, range=None):
    """(hist, bin_edges) like numpy (ref: mx.nd.histogram). `bins` may
    be an int (with optional `range`) or an NDArray/array of edges."""
    data = a._data if isinstance(a, NDArray) else jnp.asarray(a)
    if isinstance(bins, NDArray):
        bins = bins._data
    # range=None is handled lazily on-device by jnp.histogram (min/max
    # edges) — no host sync needed here
    h, edges = jnp.histogram(data, bins=bins, range=range)
    ctx = a.context if isinstance(a, NDArray) else None
    return NDArray(h, ctx=ctx), NDArray(edges, ctx=ctx)


def concatenate(arrays, axis=0):
    from . import ops as _ops

    return _ops.concat(*arrays, dim=axis)


def moveaxis(x, source, destination):
    return x.moveaxis(source, destination)


# ---------------------------------------------------------------------------
# Save/Load (ref: NDArray::Save/Load via dmlc::Stream; we keep the same
# user API — a single file holding a list or str->array dict — with .npz
# as the container; see utils/serialization for the legacy binary format)


def save(fname, data):
    from ..utils import serialization

    serialization.save_ndarrays(fname, data)


def load(fname):
    from ..utils import serialization

    return serialization.load_ndarrays(fname)
