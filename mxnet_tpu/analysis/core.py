"""mxtpu-analyze core: the framework the pass families plug into.

The analyses here are *framework-aware* lints, not a general type
system: every pass works from one shared :class:`Index` — parsed module
ASTs, a per-module import map, a class-attribute type sketch (only
``self.x = ClassName(...)`` in methods), and the package-internal call
graph those resolutions support.  Resolution is deliberately heuristic
(``self.m()`` → same-class method, ``mod.f()`` → imported module's
``f``, bare ``f()`` → same-module or package-unique); what it cannot
resolve it drops rather than guesses, so passes err toward missed
findings, never toward unresolvable noise.  The runtime lock-order
checker (:mod:`mxnet_tpu.analysis.runtime`) covers the dynamic residue.

Findings carry stable keys — ``CODE:path:symbol`` — so the checked-in
baseline file survives unrelated line churn.  See
docs/static-analysis.md for the pass catalog and suppression workflow.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import os


# ---------------------------------------------------------------------------
# Findings + baseline


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str      # e.g. "MXA101"
    path: str      # repo-relative file
    line: int
    symbol: str    # enclosing qualname / stable detail anchor
    message: str

    @property
    def key(self):
        """Line-insensitive identity the baseline file matches on."""
        return f"{self.code}:{self.path}:{self.symbol}"

    def to_dict(self):
        return {"code": self.code, "path": self.path, "line": self.line,
                "symbol": self.symbol, "message": self.message,
                "key": self.key}

    def sort_key(self):
        return (self.code, self.path, self.line, self.symbol)


def load_baseline(path):
    """Baseline file -> {finding key: justification}.  Every entry MUST
    carry a non-empty justification — an unexplained suppression is a
    bug magnet, so it fails loudly here."""
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        data = json.load(f)
    out = {}
    for entry in data.get("suppressions", []):
        just = entry.get("justification", "").strip()
        if not just:
            raise ValueError(
                f"baseline entry {entry.get('key')!r} has no justification "
                f"({path}); every suppression must say why")
        out[entry["key"]] = just
    return out


def apply_baseline(findings, baseline):
    """Partition into (new, suppressed, unused_suppression_keys)."""
    new, suppressed = [], []
    seen = set()
    for f in findings:
        if f.key in baseline:
            suppressed.append(f)
            seen.add(f.key)
        else:
            new.append(f)
    unused = sorted(k for k in baseline if k not in seen)
    return new, suppressed, unused


# ---------------------------------------------------------------------------
# Configuration: what parts of the tree each pass family targets.  The
# defaults describe the real repo; tests override them to point the
# framework at small synthetic fixture packages.


@dataclasses.dataclass
class AnalysisConfig:
    package: str = "mxnet_tpu"
    env_doc: str = "docs/ENV_VARS.md"
    resilience_doc: str = "docs/resilience.md"
    # module (package-relative dotted) holding dumps()/_aggregate_table
    profiler_module: str = "profiler"
    # the seeded-replay surface: batch sequences here must be pure
    # functions of (seed, state) — wallclock/global-RNG leaks break the
    # bit-identical-resume contract chaos_smoke proves
    seeded_modules: tuple = ("pipeline", "pipeline.stages",
                             "resilience.faults", "resilience.retry")
    # (module, qualname) host-side hot paths where an implicit device
    # sync is a latency hazard worth an explicit justification
    hotpath_roots: tuple = (("serve.server", "ModelServer._run_batch"),)
    # naming convention for jit-traced kernels
    traced_prefixes: tuple = ("_k_", "_fk_")
    # extra traced roots by exact function name (nested defs included):
    # the CachedOp graph fn, the whole-step trainer closure, and the
    # ZeRO-1 sharded update it lowers into — host syncs anywhere inside
    # any of them are lint errors (MXA201)
    # also the quantized-block forward bodies: they run inside CachedOp/
    # CachedStepOp captures, so a host sync there stalls every int8
    # serve batch
    traced_names: tuple = ("_cached_graph_fn", "_whole_step_fn",
                           "apply_zero_step_plan", "_step_graph_fn",
                           "_quantized_dense_forward",
                           "_quantized_conv_forward",
                           "_finish_quantized")
    getenv_fns: tuple = ("getenv",)
    fault_point_fns: tuple = ("fault_point",)
    # telemetry catalog (MXA403/MXA405): how sections register, which
    # helpers the output paths read them through, where span/metric
    # names must be documented, and which call names define them
    section_register_fns: tuple = ("register_section",)
    section_iter_fns: tuple = ("sections", "_section_tables")
    observability_doc: str = "docs/observability.md"
    span_site_fns: tuple = ("op_scope", "span_begin", "instant",
                            "request_begin")
    metric_def_fns: tuple = ("counter", "gauge", "histogram")
    metric_name_prefix: str = "mxtpu_"
    # knob-registry invariants (MXA501/502): the module whose literal
    # Knob(...) constructor calls define the autotuner's control
    # surface, and the constructor names to look for
    tune_knobs_module: str = "tune.knobs"
    knob_ctor_names: tuple = ("Knob",)
    # modules allowed to touch os.environ directly (the config tier)
    env_exempt_modules: tuple = ("base",)
    # raw env names allowed outside base.getenv (launcher wire protocol,
    # documented as raw-read in docs/ENV_VARS.md) — still must be
    # documented or MXA402 fires
    raw_env_allowed_prefixes: tuple = ("DMLC_",)


# ---------------------------------------------------------------------------
# Module / function / class index


class ModuleInfo:
    __slots__ = ("modname", "relpath", "tree", "is_pkg", "module_aliases",
                 "func_imports", "ext_aliases", "ext_from", "globals_")

    def __init__(self, modname, relpath, tree, is_pkg):
        self.modname = modname        # package-relative dotted ("" = root)
        self.relpath = relpath        # repo-relative file path
        self.tree = tree
        self.is_pkg = is_pkg
        self.module_aliases = {}      # local name -> internal modname
        self.ext_aliases = {}         # local name -> external dotted module
        self.func_imports = {}        # local name -> (modname, attr)
        self.ext_from = {}            # local name -> (ext module, attr)
        self.globals_ = set()         # module-level assigned names


class FuncInfo:
    __slots__ = ("key", "node", "cls", "module")

    def __init__(self, key, node, cls, module):
        self.key = key                # (modname, qualname)
        self.node = node
        self.cls = cls                # enclosing class name or None
        self.module = module

    @property
    def name(self):
        return self.key[1].rsplit(".", 1)[-1]


class ClassInfo:
    __slots__ = ("key", "node", "module", "methods", "attr_types")

    def __init__(self, key, node, module):
        self.key = key                # (modname, clsname)
        self.node = node
        self.module = module
        self.methods = {}             # name -> FuncInfo
        self.attr_types = {}          # self-attr name -> class key


def _module_name(rel, is_pkg):
    parts = rel[:-3].split("/")      # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class Index:
    """Everything a pass needs: parsed modules, symbol tables, imports,
    the attribute-type sketch, and the package-internal call graph."""

    def __init__(self, root, cfg=None):
        self.root = root
        self.cfg = cfg or AnalysisConfig()
        self.modules = {}             # modname -> ModuleInfo
        self.funcs = {}               # (modname, qualname) -> FuncInfo
        self.classes = {}             # (modname, clsname) -> ClassInfo
        self._by_name = {}            # bare top-level func name -> [keys]
        self._calls = None            # funckey -> set(funckey)
        self._parse_package()
        for mod in self.modules.values():
            self._index_imports(mod)
            self._index_defs(mod)
        for mod in self.modules.values():
            self._index_attr_types(mod)

    # -- parsing ------------------------------------------------------------

    def _parse_package(self):
        pkg_dir = os.path.join(self.root, self.cfg.package)
        if not os.path.isdir(pkg_dir):
            # a missing tree must not masquerade as a clean one
            raise RuntimeError(
                f"analysis root has no package dir: {pkg_dir}")
        for dirpath, _dirnames, filenames in os.walk(pkg_dir):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                rel_in_pkg = os.path.relpath(full, pkg_dir).replace(
                    os.sep, "/")
                relpath = os.path.relpath(full, self.root).replace(
                    os.sep, "/")
                with open(full) as f:
                    src = f.read()
                try:
                    tree = ast.parse(src, filename=relpath)
                except SyntaxError as e:
                    raise RuntimeError(
                        f"cannot analyze {relpath}: {e}") from e
                modname = _module_name(rel_in_pkg, None)
                info = ModuleInfo(modname, relpath, tree,
                                  fn == "__init__.py")
                self.modules[modname] = info
        if not self.modules:
            raise RuntimeError(
                f"no Python modules under {pkg_dir} — wrong root or "
                f"package name?")

    # -- imports ------------------------------------------------------------

    def _rel_base(self, mod, level):
        """Dotted base module a level-N relative import resolves
        against (packages resolve level 1 to themselves)."""
        parts = mod.modname.split(".") if mod.modname else []
        if not mod.is_pkg:
            parts = parts[:-1] if parts else []
        drop = level - 1
        if drop:
            parts = parts[:-drop] if drop <= len(parts) else []
        return ".".join(parts)

    def _index_imports(self, mod):
        pkg = self.cfg.package
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.name
                    if name == pkg or name.startswith(pkg + "."):
                        internal = name[len(pkg):].lstrip(".")
                        if alias.asname:
                            mod.module_aliases[alias.asname] = internal
                        else:
                            # `import pkg.sub` binds the ROOT name
                            # `pkg`, not `sub`
                            mod.module_aliases[pkg] = ""
                    else:
                        # `import a.b` binds `a`; `import a.b as c` binds c
                        local = alias.asname or name.split(".")[0]
                        mod.ext_aliases[local] = (name if alias.asname
                                                  else name.split(".")[0])
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = self._rel_base(mod, node.level)
                    target = (base + "." + node.module if base and
                              node.module else (node.module or base or ""))
                elif node.module and (node.module == pkg
                                      or node.module.startswith(pkg + ".")):
                    target = node.module[len(pkg):].lstrip(".")
                else:
                    for alias in node.names:
                        mod.ext_from[alias.asname or alias.name] = (
                            node.module or "", alias.name)
                    continue
                for alias in node.names:
                    local = alias.asname or alias.name
                    full = (target + "." + alias.name if target
                            else alias.name)
                    if full in self.modules:
                        mod.module_aliases[local] = full
                    else:
                        mod.func_imports[local] = (target, alias.name)

    # -- definitions --------------------------------------------------------

    def _index_defs(self, mod):
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                key = (mod.modname, node.name)
                self.funcs[key] = FuncInfo(key, node, None, mod)
                self._by_name.setdefault(node.name, []).append(key)
            elif isinstance(node, ast.ClassDef):
                ckey = (mod.modname, node.name)
                cinfo = ClassInfo(ckey, node, mod)
                self.classes[ckey] = cinfo
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        fkey = (mod.modname, f"{node.name}.{item.name}")
                        finfo = FuncInfo(fkey, item, node.name, mod)
                        self.funcs[fkey] = finfo
                        cinfo.methods[item.name] = finfo
            elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            mod.globals_.add(n.id)

    def _index_attr_types(self, mod):
        """Sketch: self.x = ClassName(...) in any method records the
        attribute's class so self.x.m() calls resolve."""
        for ckey, cinfo in self.classes.items():
            if cinfo.module is not mod:
                continue
            for meth in cinfo.methods.values():
                for node in ast.walk(meth.node):
                    if not (isinstance(node, ast.Assign)
                            and isinstance(node.value, ast.Call)):
                        continue
                    target_cls = self.resolve_class(mod, node.value.func)
                    if target_cls is None:
                        continue
                    for t in node.targets:
                        if (isinstance(t, ast.Attribute)
                                and isinstance(t.value, ast.Name)
                                and t.value.id == "self"):
                            cinfo.attr_types[t.attr] = target_cls

    # -- resolution ---------------------------------------------------------

    def resolve_class(self, mod, expr):
        """Class key for an expression used as a constructor, or None."""
        if isinstance(expr, ast.Name):
            n = expr.id
            if (mod.modname, n) in self.classes:
                return (mod.modname, n)
            if n in mod.func_imports:
                tgt = mod.func_imports[n]
                if tgt in self.classes:
                    return tgt
        elif (isinstance(expr, ast.Attribute)
              and isinstance(expr.value, ast.Name)):
            m = mod.module_aliases.get(expr.value.id)
            if m is not None and (m, expr.attr) in self.classes:
                return (m, expr.attr)
        return None

    def resolve_call(self, func, call_func):
        """Function keys a call expression may dispatch to ([] when the
        receiver is not statically resolvable)."""
        mod, cls = func.module, func.cls
        f = call_func
        if isinstance(f, ast.Name):
            n = f.id
            if n in mod.func_imports:
                tgt = mod.func_imports[n]
                if tgt in self.funcs:
                    return [tgt]
                if tgt in self.classes:
                    init = (tgt[0], f"{tgt[1]}.__init__")
                    return [init] if init in self.funcs else []
            if (mod.modname, n) in self.funcs:
                return [(mod.modname, n)]
            if (mod.modname, n) in self.classes:
                init = (mod.modname, f"{n}.__init__")
                return [init] if init in self.funcs else []
            hits = self._by_name.get(n, [])
            return [hits[0]] if len(hits) == 1 else []
        if isinstance(f, ast.Attribute):
            base = f.value
            if isinstance(base, ast.Name):
                if base.id in ("self", "cls") and cls is not None:
                    k = (mod.modname, f"{cls}.{f.attr}")
                    return [k] if k in self.funcs else []
                m = mod.module_aliases.get(base.id)
                if m is not None:
                    k = (m, f.attr)
                    if k in self.funcs:
                        return [k]
                    if k in self.classes:
                        init = (m, f"{f.attr}.__init__")
                        return [init] if init in self.funcs else []
            elif (isinstance(base, ast.Attribute)
                  and isinstance(base.value, ast.Name)
                  and base.value.id == "self" and cls is not None):
                cinfo = self.classes.get((mod.modname, cls))
                tgt = cinfo.attr_types.get(base.attr) if cinfo else None
                if tgt is not None:
                    k = (tgt[0], f"{tgt[1]}.{f.attr}")
                    return [k] if k in self.funcs else []
        return []

    # -- call graph ---------------------------------------------------------

    def call_graph(self):
        if self._calls is None:
            self._calls = {}
            for key, func in self.funcs.items():
                edges = set()
                for node in ast.walk(func.node):
                    if isinstance(node, ast.Call):
                        edges.update(self.resolve_call(func, node.func))
                edges.discard(key)
                self._calls[key] = edges
        return self._calls

    def reachable(self, roots):
        """Transitive closure over the package-internal call graph."""
        graph = self.call_graph()
        seen, stack = set(), [r for r in roots if r in self.funcs]
        while stack:
            k = stack.pop()
            if k in seen:
                continue
            seen.add(k)
            stack.extend(graph.get(k, ()))
        return seen

    # -- misc helpers -------------------------------------------------------

    def doc_text(self, relpath):
        full = os.path.join(self.root, relpath)
        if not os.path.exists(full):
            return None
        with open(full) as f:
            return f.read()

    def enclosing(self, mod, lineno):
        """Qualname of the innermost top-level def/class member
        containing `lineno` (for finding symbols)."""
        best = "<module>"
        for key, func in self.funcs.items():
            if func.module is not mod:
                continue
            node = func.node
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= lineno <= end:
                best = key[1]
        return best

    def ext_call_target(self, mod, call_func):
        """Dotted external name for a call like np.random.seed(...) /
        time.monotonic() / random.random(), following import aliases;
        None when the receiver isn't an external import."""
        parts = []
        node = call_func
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            base = mod.ext_aliases.get(node.id)
            if base is not None:
                return ".".join([base] + list(reversed(parts)))
            if not parts and node.id in mod.ext_from:
                emod, attr = mod.ext_from[node.id]
                return f"{emod}.{attr}"
        return None


# ---------------------------------------------------------------------------
# Running passes


def run_passes(root, cfg=None, passes=None):
    """Build the index once, run every registered pass, return the
    sorted finding list.  `passes` limits to a subset by name; an
    unknown name raises — a typo'd CI config must not silently green
    the gate with zero analysis run."""
    from . import PASSES

    if passes is not None:
        known = {name for name, _ in PASSES}
        unknown = sorted(set(passes) - known)
        if unknown:
            raise ValueError(
                f"unknown pass(es) {unknown}; known: {sorted(known)}")
    index = Index(root, cfg)
    findings = []
    for name, fn in PASSES:
        if passes is not None and name not in passes:
            continue
        findings.extend(fn(index))
    findings.sort(key=Finding.sort_key)
    return findings, index
