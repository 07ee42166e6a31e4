"""Gluon Block / HybridBlock / CachedOp-equivalent.

Ref: python/mxnet/gluon/block.py (Block, HybridBlock, SymbolBlock) and
src/imperative/cached_op.{h,cc} (the hybridization backend).

TPU-native design (the BASELINE north star): ``hybridize()`` does NOT
build an nnvm graph + per-node engine pushes.  Instead the block's whole
forward is captured as a *pure JAX function* of (rng_key, params...,
inputs...) and compiled by XLA into ONE computation — the eager op
wrappers are themselves jax-traceable, so capture is simply re-running
the eager path under ``jax.jit`` tracing.  Backward of a hybridized call
is a single tape node whose VJP is the whole-graph XLA gradient (the
CachedOp::Backward equivalent).  static_alloc/static_shape/bulking knobs
are accepted for API parity and ignored: XLA's memory planner subsumes
them (SURVEY §3.2 "TPU translation").

Mutable aux state (BatchNorm moving stats) rides as extra outputs of the
compiled graph and is written back to the Parameters after each call.
"""
from __future__ import annotations

import re
import threading

from .. import autograd
from .. import profiler as _profiler
from .. import random as _random
from .._imperative import invoke
from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray import ndarray as _nd_mod
from ..ndarray.ndarray import NDArray, _wrap
from .parameter import (DeferredInitializationError, Parameter,
                        ParameterDict)

_naming = threading.local()


class _BlockScope:
    """Auto-naming: dense0_, conv1_, ... (ref: _BlockScope in block.py)."""

    _counters = {}
    _lock = threading.Lock()

    @classmethod
    def create_prefix(cls, hint):
        with cls._lock:
            i = cls._counters.get(hint, 0)
            cls._counters[hint] = i + 1
        return f"{hint}{i}_"


class HookHandle:
    """Removable handle for a registered hook (ref: gluon.utils.HookHandle)."""

    def __init__(self, hooks_list, hook):
        self._hooks_list = hooks_list
        self._hook = hook

    def detach(self):
        if self._hook is not None and self._hook in self._hooks_list:
            self._hooks_list.remove(self._hook)
        self._hook = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.detach()


def _int8_container_mismatch(params, loaded):
    """Detect an fp32 ↔ int8 .params container mismatch before the
    generic missing-parameter error hides it: loading an fp32 file into
    an INT8-quantized net (or vice versa) silently loads nothing and
    reconstructs garbage unless it fails HERE with a diagnosis."""
    def has(keys, suffix):
        return any(k == suffix or k.endswith("." + suffix)
                   or k.endswith("_" + suffix) for k in keys)

    net_q, file_q = has(params, "qweight"), has(loaded, "qweight")
    if net_q and not file_q and has(loaded, "weight"):
        return ("file holds fp32 parameters but this network is "
                "INT8-quantized — re-quantize them via contrib."
                "quantization.apply_fp32_params(net, nd.load(file)) "
                "(ModelServer/DecodeServer reload_weights() does this "
                "automatically), or save from the quantized net itself")
    if file_q and not net_q and has(params, "weight"):
        return ("file holds INT8-quantized parameters but this network "
                "is fp32 — rebuild the target with contrib.quantization"
                ".quantize_net (same architecture + calibration config) "
                "before loading, or load the fp32 training checkpoint "
                "instead")
    return None


class Block:
    """Base container for layers & parameters (ref: gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix = (prefix if prefix is not None
                        else _BlockScope.create_prefix(
                            type(self).__name__.lower()))
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    # -- attribute registration --------------------------------------------

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = getattr(self, "_children", None)
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = getattr(self, "_reg_params", None)
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self):
        return self._params

    def name_scope(self):
        class _NS:
            def __enter__(self_ns):
                return self_ns

            def __exit__(self_ns, *a):
                return False

        return _NS()

    # -- params -------------------------------------------------------------

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            ret.update({k: v for k, v in self._params.items()
                        if pat.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def _ordered_params(self):
        """Stable (name, Parameter) order for graph capture."""
        return list(self.collect_params().items())

    def register_child(self, block, name=None):
        """Register a child under an explicit structural name."""
        self._children[name if name is not None else
                       str(len(self._children))] = block
        return block

    def _collect_params_with_prefix(self, prefix=""):
        """Structural name -> Parameter (ref: Block._collect_params_with_
        prefix — the naming used by save_parameters so an identical
        architecture loads regardless of auto-prefix counters)."""
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._params.values():
            p.cast(dtype)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- save / load --------------------------------------------------------

    def save_parameters(self, filename, deduplicate=False):
        """Ref: Block.save_parameters — structural name->array dict, so an
        identically-built net loads regardless of auto-prefix counters."""
        params = self._collect_params_with_prefix()
        _nd_mod.save(filename, {k: v.data() for k, v in params.items()
                                if v._data is not None})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = _nd_mod.load(filename)
        params = self._collect_params_with_prefix()
        if loaded and params and not any(k in params for k in loaded):
            # fall back to full-prefix names (collect_params keys)
            params = dict(self.collect_params().items())
        mismatch = _int8_container_mismatch(params, loaded)
        if mismatch:
            raise MXNetError(f"{filename}: {mismatch}")
        for name, p in params.items():
            if name in loaded:
                p.shape = loaded[name].shape
                if p._data is None:
                    if p._deferred_init is not None:
                        p._finish_deferred_init()
                    else:
                        p.initialize(ctx=ctx or [current_context()])
                p.set_data(loaded[name])
            elif not allow_missing:
                raise MXNetError(f"missing parameter {name} in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"extra parameters in {filename}: {extra}")

    # legacy aliases (ref: save_params/load_params pre-1.4 names)
    save_params = save_parameters

    def load_params(self, filename, ctx=None, **kwargs):
        self.load_parameters(filename, ctx=ctx, **kwargs)

    # -- hooks --------------------------------------------------------------

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return HookHandle(self._forward_pre_hooks, hook)

    # -- call ---------------------------------------------------------------

    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        lines = [f"{type(self).__name__}("]
        for name, child in self._children.items():
            lines.append(f"  ({name}): {type(child).__name__}")
        lines.append(")")
        return "\n".join(lines)

    def __repr__(self):
        mods = "\n".join(f"  ({k}): {type(v).__name__}"
                         for k, v in self._children.items())
        return f"{type(self).__name__}(\n{mods}\n)"


# ---------------------------------------------------------------------------
# CachedOp equivalent


_tracing = threading.local()


def is_tracing():
    return getattr(_tracing, "active", False)


# Compiled-graph cache telemetry: a CachedOp call with an unseen input
# signature (shapes/dtypes/train-flag) is a new XLA compile; a seen one
# reuses the executable jax.jit already holds.  The serving tier's whole
# bucket design rests on "zero compiles after warmup", so the split is
# counted here — per CachedOp (ModelServer.stats()) and globally
# (profiler dumps / tests).
_graph_stats_lock = threading.Lock()
_graph_stats = {"compiles": 0, "reuses": 0}


def cached_graph_stats():
    """Global compiled-graph cache counters across every CachedOp:
    ``{"compiles": new-signature calls, "reuses": cache-hit calls}``."""
    with _graph_stats_lock:
        return dict(_graph_stats)


def reset_cached_graph_stats():
    with _graph_stats_lock:
        _graph_stats["compiles"] = 0
        _graph_stats["reuses"] = 0


_profiler.register_section(
    "cachedGraph", cached_graph_stats, reset_cached_graph_stats,
    _profiler.rows_table(
        "Compiled-Graph Cache (CachedOp)",
        (("graph compiles (new signature)", "compiles"),
         ("graph reuses (cache hit)", "reuses"))))


def traced_apply(block, param_raws, input_raws, key, train=True,
                 static_kwargs=None):
    """Run ``block.forward`` under graph capture: every Parameter's
    traced stand-in is bound to the matching entry of ``param_raws``
    (ordered like ``block._ordered_params()``), the trace RNG key is
    pushed, and the eager op wrappers re-trace the forward into whatever
    jax transformation is active (jit, vjp, shard_map, eval_shape).

    ``static_kwargs`` are compile-time keyword arguments forwarded
    verbatim to ``block.forward`` — shape-determining config (the
    speculative-verify unroll depth ``k``) that is part of the jit
    cache key rather than a traced input.

    Returns ``(out, aux)`` where ``out`` is the forward's return tree
    (NDArray leaves wrapping tracer buffers) and ``aux`` is a list of
    ``(param_name, new_raw)`` for parameters whose wrapper buffers were
    replaced in place during the forward (BatchNorm moving stats).

    This is the ONE capture body shared by the CachedOp graph fn and
    the whole-step trainer closure — forward semantics under trace have
    a single source.
    """
    params = [p for _, p in block._ordered_params()]
    wrappers = [_wrap(r) for r in param_raws]
    inputs = [_wrap(r) for r in input_raws]
    old_traced = [p._traced_value for p in params]
    prev_active = getattr(_tracing, "active", False)
    _tracing.active = True
    tok = _random.push_trace_key(key)
    try:
        for p, w in zip(params, wrappers):
            p._traced_value = w
        with autograd.pause(train_mode=train):
            out = block.forward(*inputs, **(static_kwargs or {}))
    finally:
        _random.pop_trace_key(tok)
        _tracing.active = prev_active
        for p, old in zip(params, old_traced):
            p._traced_value = old
    aux = []
    for (name, _p), w, r in zip(block._ordered_params(), wrappers,
                                param_raws):
        if w._data is not r:
            aux.append((name, w._data))
    return out, aux


class CachedOp:
    """Compiles a HybridBlock's forward to one XLA computation.

    Ref: src/imperative/cached_op.cc — but the node-loop + memory planner
    is replaced by jax.jit of the re-run eager path (SURVEY §3.2).
    """

    def __init__(self, block):
        self.block = block
        self._fns = {}   # train_flag -> pure graph fn
        self._meta = {}  # train_flag -> (n_outs, aux_param_names, multi)
        self._seen_sigs = set()  # (train, input shapes/dtypes) compiled
        self.stats = {"compiles": 0, "reuses": 0}

    def release(self):
        """Evict this op's compiled executables from the global caches."""
        from .. import _imperative

        for fn in self._fns.values():
            _imperative.evict(fn)
        self._fns.clear()
        self._meta.clear()  # stale meta must not outlive its graph fn
        # evicted executables recompile on the next call — the counters
        # must see those as fresh compiles, not reuses
        self._seen_sigs.clear()

    def __del__(self):
        try:
            self.release()
        except Exception:
            pass

    def _build_fn(self, train):
        block = self.block
        cached = self

        def _cached_graph_fn(key, *arrays, _n_params):
            out, aux = traced_apply(block, arrays[:_n_params],
                                    arrays[_n_params:], key, train=train)
            import jax

            # arbitrary nesting (e.g. RNN layers return (out, [h, c])):
            # flatten with NDArray leaves, remember the treedef
            leaves, treedef = jax.tree_util.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            outs = [o for o in leaves if isinstance(o, NDArray)]
            cached._meta[train] = (len(outs), [n for n, _ in aux], treedef)
            return tuple(o._data for o in outs) + tuple(r for _, r in aux)

        return _cached_graph_fn

    def __call__(self, *inputs):
        train = autograd.is_training()
        fn = self._fns.get(train)
        if fn is None:
            fn = self._build_fn(train)
            self._fns[train] = fn
        named = self.block._ordered_params()
        ctx = None
        for i in inputs:
            if isinstance(i, NDArray):
                ctx = i.context
                break
        param_nds = []
        for _, p in named:
            try:
                param_nds.append(p.data(ctx))
            except MXNetError:
                param_nds.append(p.data())
        # jax.jit specializes per committed device and per static value,
        # so the device and any non-NDArray inputs are part of what makes
        # a compile fresh — omitting them would count real compiles (e.g.
        # same shapes on a second ctx) as reuses
        sig = (train, str(ctx),
               tuple((i.shape, str(i.dtype)) if isinstance(i, NDArray)
                     else repr(i) for i in inputs))
        with _graph_stats_lock:
            fresh_compile = sig not in self._seen_sigs
            if fresh_compile:
                self._seen_sigs.add(sig)
                self.stats["compiles"] += 1
                _graph_stats["compiles"] += 1
            else:
                self.stats["reuses"] += 1
                _graph_stats["reuses"] += 1
        key_nd = _wrap(_random.next_key())
        if fresh_compile:
            with _profiler.op_scope(f"cached_op.compile.{self.block.name}",
                                    cat="cached_op"):
                res = invoke(fn, key_nd, *param_nds, *inputs,
                             _n_params=len(param_nds))
        else:
            res = invoke(fn, key_nd, *param_nds, *inputs,
                         _n_params=len(param_nds))
        if not isinstance(res, tuple):
            res = (res,)
        n_outs, aux_names, treedef = self._meta[train]
        outs, auxs = res[:n_outs], res[n_outs:]
        if aux_names:
            pdict = dict(named)
            for name, new in zip(aux_names, auxs):
                p = pdict[name]
                target = p.data(ctx) if ctx in (p._data or {}) else p.data()
                target._data = new._data
        import jax

        return jax.tree_util.tree_unflatten(treedef, list(outs))


class CachedStepOp:
    """Compile a block's forward as a fixed-shape, state-carrying step
    executable — the continuous-batching decode hot path
    (serve.DecodeServer).

    Differences from :class:`CachedOp`:

    - callers pass and receive RAW jax buffers (no NDArray wrap/unwrap
      on the per-token path — the caller owns the arena and replaces
      its buffers with the outputs every call);
    - ``donate_inputs`` names input positions (indices into the
      forward's argument list) whose buffers are DONATED to XLA, so the
      carried state (KV-cache arenas) is updated in place instead of
      allocating a second copy of the cache per token;
    - the forward must return a FLAT tuple/list of NDArrays (the caller
      knows the structure; there is no treedef round-trip);
    - every call books exactly one device dispatch on the honest
      ``_imperative`` counter, exactly like ``invoke()``.
    - ``static_kwargs`` bakes compile-time keyword arguments into the
      forward (and the jit cache key): the multi-token speculative
      VERIFY step passes its unroll depth ``k`` this way, so one
      executable verifies a whole k-token draft block per dispatch and
      a different ``k`` is a new warmup compile, not a silent retrace.

    Compile/reuse accounting rides the same global ``cached_graph_stats``
    the serving tier's zero-post-warmup-compile gates read.
    """

    def __init__(self, block, donate_inputs=(), static_kwargs=None):
        self.block = block
        self._donate = tuple(sorted(int(i) for i in donate_inputs))
        self._static = dict(static_kwargs or {})
        for k, v in self._static.items():
            hash(v)   # jit-cache key material; fail at construction
        self._fn = None
        self._params = None      # ordered Parameter list, cached: the
        # per-token path must not re-walk the block tree every call
        self._seen_sigs = set()
        self.stats = {"compiles": 0, "reuses": 0}

    def release(self):
        """Evict this op's compiled executables from the global caches."""
        from .. import _imperative

        if self._fn is not None:
            _imperative.evict(self._fn)
        self._fn = None
        self._seen_sigs.clear()

    def __del__(self):
        try:
            self.release()
        except Exception:
            pass

    def _build_fn(self):
        block = self.block

        def _step_graph_fn(key, *arrays, _n_params, **static):
            out, _aux = traced_apply(block, arrays[:_n_params],
                                     arrays[_n_params:], key, train=False,
                                     static_kwargs=static)
            outs = list(out) if isinstance(out, (list, tuple)) else [out]
            if not all(isinstance(o, NDArray) for o in outs):
                raise MXNetError(
                    "a CachedStepOp forward must return a flat "
                    "tuple/list of NDArrays")
            return tuple(o._data for o in outs)

        return _step_graph_fn

    def __call__(self, *input_raws):
        """Run one step on raw buffers; returns the flat raw-output
        tuple.  Parameters are fetched live (``p.data()``) each call, so
        a hot weight reload lands on the next step with no recompile."""
        from .. import _imperative

        if self._fn is None:
            self._fn = self._build_fn()
        if self._params is None:
            self._params = [p for _, p in self.block._ordered_params()]
        param_raws = [p.data()._data for p in self._params]
        n = len(param_raws)
        sig = tuple(
            (tuple(r.shape), str(r.dtype)) if hasattr(r, "shape")
            else repr(r) for r in input_raws)
        with _graph_stats_lock:
            fresh = sig not in self._seen_sigs
            if fresh:
                self._seen_sigs.add(sig)
                self.stats["compiles"] += 1
                _graph_stats["compiles"] += 1
            else:
                self.stats["reuses"] += 1
                _graph_stats["reuses"] += 1
        # +1 for the leading rng key arg of the graph fn
        donate = tuple(1 + n + i for i in self._donate) or None
        jitted = _imperative.get_jitted(
            self._fn, dict(self._static, _n_params=n),
            donate_argnums=donate)
        _imperative.count_dispatch()
        if fresh:
            with _profiler.op_scope(f"cached_op.compile.{self.block.name}",
                                    cat="cached_op"):
                outs = jitted(_random.next_key(), *param_raws, *input_raws)
        else:
            outs = jitted(_random.next_key(), *param_raws, *input_raws)
        return outs if isinstance(outs, tuple) else (outs,)


class HybridBlock(Block):
    """Block that can be hybridized into one compiled XLA computation
    (ref: gluon.HybridBlock)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._flags = {}

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs  # static_alloc/static_shape accepted, unused
        # only the outermost compiled graph matters; children run inside
        # the parent's trace (ref: inline_limit semantics)
        self._clear_cache()

    def _clear_cache(self):
        if self._cached_op is not None:
            self._cached_op.release()
        self._cached_op = None
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child._clear_cache()

    def cast(self, dtype):
        self._clear_cache()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Complete deferred param shapes from example inputs.  Built-in
        layers override; container blocks recurse via a dry eager run."""
        self._deferred_infer_shape(*args)

    def _deferred_infer_shape(self, *args):
        # generic fallback: run children eagerly until shapes resolve
        raise DeferredInitializationError(
            f"{type(self).__name__} has deferred-init parameters and no "
            "infer_shape; initialize with explicit in_units/in_channels")

    def forward(self, x, *args):
        from ..symbol.symbol import Symbol

        if isinstance(x, Symbol):
            # symbolic trace (export path, ref: _get_graph): params become
            # named variables
            from ..symbol import symbol as sym_ns

            params = {k: (p._traced_value if isinstance(p._traced_value,
                                                        Symbol)
                          else sym_ns.var(p.name))
                      for k, p in self._reg_params.items()}
            return self.hybrid_forward(sym_ns, x, *args, **params)
        if not isinstance(x, NDArray):
            raise MXNetError("HybridBlock.forward expects NDArray inputs")
        if self._active and not is_tracing():
            if self._cached_op is None:
                # finish any deferred init with one eager probe call
                try:
                    self._eager_forward(x, *args)
                except DeferredInitializationError:
                    self._try_infer_and_init(x, *args)
                self._cached_op = CachedOp(self)
            return self._cached_op(x, *args)
        return self._eager_forward(x, *args)

    def _eager_forward(self, x, *args):
        from .. import ndarray as F  # eager namespace (ops + creation fns)

        ctx = None
        if not is_tracing():  # tracers have no concrete device
            ctx = x.context
        try:
            params = {k: p.data(ctx) if (ctx is not None and p._data and
                                         ctx in p._data) else p.data()
                      for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._try_infer_and_init(x, *args)
            # same context-aware fetch as the first attempt: with
            # multi-context init and the input on a non-first context,
            # bare p.data() would mix parameter copies across devices
            params = {k: p.data(ctx) if (ctx is not None and p._data and
                                         ctx in p._data) else p.data()
                      for k, p in self._reg_params.items()}
        return self.hybrid_forward(F, x, *args, **params)

    def _try_infer_and_init(self, x, *args):
        self.infer_shape(x, *args)
        for p in self.collect_params().values():
            if p._deferred_init is not None:
                p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Ref: HybridBlock.export → model-symbol.json + .params."""
        from ..symbol import export as _export

        return _export.export_block(self, path, epoch)

    def optimize_for(self, x, *args, backend=None, **kwargs):
        self.hybridize(True)
        return self(x, *args)
