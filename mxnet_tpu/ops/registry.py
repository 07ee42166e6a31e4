"""Operator registry.

Ref: the nnvm op registry (NNVM_REGISTER_OP in src/operator/*; attrs
FCompute/FInferShape/FInferType, dmlc parameter structs) and the
frontend codegen that builds ``mx.nd.*`` / ``mx.sym.*`` from
MXListAllOpNames (python/mxnet/ndarray/register.py).

TPU-native design: one entry per op holding a *pure JAX function*
(positional array inputs, keyword-only static attrs).  ``FCompute``
becomes "jit the fn" (see _imperative), ``FInferShape/Type`` become
``jax.eval_shape`` of the same fn, and ``FGradient`` becomes
``jax.vjp``.  The same entry powers the eager namespace (mx.nd), the
symbolic namespace (mx.sym), and hybrid tracing — so the three fronts
can never drift apart.
"""
from __future__ import annotations

import inspect

from ..base import MXNetError

_ops = {}

# the outputs ops name (`jax.ad_checkpoint.checkpoint_name`) for a
# `jax.checkpoint` policy to keep, by the op that names them:
# `DataParallelTrainer(remat=True)` saves every name here, and the op
# is not run again in the recomputation of what follows it.  An op
# that names a residual lists it here and nowhere else.
RESIDUAL_NAMES = {
    "flash_attention": ("flash_out", "flash_lse"),
    "gated_delta_rule": ("delta_rule_out",),
}


class Param:
    """Self-documenting op parameter descriptor.

    Ref: dmlc::Parameter / DMLC_DECLARE_FIELD (3rdparty/dmlc-core/
    include/dmlc/parameter.h) — defaults, ranges and docs surfaced as
    typed keyword args in generated docstrings, plus host-side
    validation. The signature feature that makes
    ``help(mx.nd.Convolution)`` useful.
    """

    __slots__ = ("name", "type", "default", "doc", "choices", "low",
                 "high", "required")

    def __init__(self, name, type=None, default=None, doc="",
                 choices=None, low=None, high=None, required=False):
        self.name = name
        self.type = type
        self.default = default
        self.doc = doc
        self.choices = tuple(choices) if choices else None
        self.low = low
        self.high = high
        self.required = required

    def describe(self):
        tname = getattr(self.type, "__name__", str(self.type)) \
            if self.type else "any"
        bits = [tname]
        if self.choices:
            bits.append("one of " + ", ".join(map(repr, self.choices)))
        if self.low is not None or self.high is not None:
            bits.append(f"range [{self.low}, {self.high}]")
        if self.required:
            bits.append("required")
        else:
            bits.append(f"default={self.default!r}")
        head = f"{self.name} : " + ", ".join(bits)
        return head + (f"\n    {self.doc}" if self.doc else "")

    def validate(self, op_name, value):
        if self.choices is not None and value not in self.choices:
            raise MXNetError(
                f"{op_name}: {self.name}={value!r} not in "
                f"{self.choices}")
        if self.low is not None and value is not None and value < self.low:
            raise MXNetError(
                f"{op_name}: {self.name}={value!r} below min {self.low}")
        if self.high is not None and value is not None \
                and value > self.high:
            raise MXNetError(
                f"{op_name}: {self.name}={value!r} above max {self.high}")


class OpEntry:
    __slots__ = ("name", "fn", "arg_names", "aliases", "needs_rng",
                 "train_aware", "nondiff", "variadic", "num_outputs",
                 "jit_compile", "wrapper", "mutate_aux", "validator",
                 "doc", "params", "_doc_cache")

    def __init__(self, name, fn, arg_names=("data",), aliases=(),
                 needs_rng=False, train_aware=False, nondiff=False,
                 variadic=False, num_outputs=1, jit_compile=True,
                 wrapper=None, mutate_aux=None, validator=None, doc=None,
                 params=None):
        self.name = name
        self.fn = fn
        self.arg_names = tuple(arg_names)
        self.aliases = tuple(aliases)
        self.needs_rng = needs_rng
        self.train_aware = train_aware
        self.nondiff = nondiff
        self.variadic = variadic
        self.num_outputs = num_outputs
        self.jit_compile = jit_compile
        self.wrapper = wrapper  # fully custom python-level wrapper
        self.mutate_aux = mutate_aux  # (aux_arg_indices, out_indices) pairs
        self.validator = validator  # host-side (arrays, attrs) precheck
        self.doc = doc or (fn.__doc__ if fn else None)
        # explicit descriptors win; otherwise derived from fn signature
        self.params = {p.name: p for p in params} if params else None
        self._doc_cache = None

    def param_descriptors(self):
        """Explicit Params, or introspected from the kernel signature
        (keyword-only args with defaults) so EVERY op self-documents."""
        if self.params is not None:
            return self.params
        derived = {}
        if self.fn is not None:
            try:
                sig = inspect.signature(self.fn)
            except (TypeError, ValueError):
                return {}
            for p in sig.parameters.values():
                if p.kind is not inspect.Parameter.KEYWORD_ONLY \
                        or p.name.startswith("_"):
                    continue
                default = None if p.default is inspect.Parameter.empty \
                    else p.default
                ptype = type(default) if default is not None else None
                derived[p.name] = Param(
                    p.name, type=ptype, default=default,
                    required=p.default is inspect.Parameter.empty)
        return derived

    def build_doc(self):
        """Numpy-style docstring: summary + typed inputs + typed params
        (the dmlc parameter.h auto-doc equivalent)."""
        if self._doc_cache is not None:
            return self._doc_cache
        lines = []
        if self.doc:
            lines.append(inspect.cleandoc(self.doc))
            lines.append("")
        if self.arg_names:
            lines.append("Inputs")
            lines.append("------")
            for a in self.arg_names:
                lines.append(f"{a} : NDArray")
            lines.append("")
        descs = self.param_descriptors()
        if descs:
            lines.append("Parameters")
            lines.append("----------")
            for p in descs.values():
                lines.append(p.describe())
            lines.append("")
        self._doc_cache = "\n".join(lines).rstrip() or None
        return self._doc_cache

    def validate_attrs(self, attrs):
        """Choice/range checks from descriptors (explicit only — derived
        descriptors carry no constraints)."""
        if not self.params:
            return
        for k, v in attrs.items():
            if k.startswith("_"):
                continue
            p = self.params.get(k)
            if p is not None:
                p.validate(self.name, v)


def register(name, fn=None, **kwargs):
    """Register an op (decorator or direct)."""

    def _do(f):
        if name in _ops:
            raise MXNetError(f"op '{name}' already registered")
        entry = OpEntry(name, f, **kwargs)
        _ops[name] = entry
        for a in entry.aliases:
            if a in _ops:
                raise MXNetError(f"op alias '{a}' already registered")
            _ops[a] = entry
        return f

    if fn is not None:
        return _do(fn)
    return _do


def get(name):
    if name not in _ops:
        raise MXNetError(f"unknown operator '{name}'")
    return _ops[name]


def exists(name):
    return name in _ops


def list_ops():
    return sorted(_ops)


def canonical_items():
    """(name, entry) pairs excluding alias duplicates."""
    seen = set()
    for k, v in _ops.items():
        if id(v) not in seen:
            seen.add(id(v))
            yield v.name, v
