"""Pass family 4: repo-invariant lints (MXA4xx).

These encode, mechanically, the invariants past PRs fixed by hand in
review passes — so the next violation is a CI failure, not a reviewer
catch.

MXA401  raw environment read — ``os.environ``/``os.getenv`` outside
        ``base.py``.  Every knob goes through ``base.getenv`` so both
        the ``MXTPU_``/``MXNET_`` spellings work; the documented
        exception is the raw launcher wire protocol (``DMLC_*``), which
        is allowed by prefix but still must be documented.
MXA402  undocumented env knob — a ``base.getenv("NAME")`` read whose
        ``MXTPU_NAME`` spelling (or a raw read whose literal name) does
        not appear in docs/ENV_VARS.md.
MXA403  profiler output path outside the section registry —
        ``dumps()`` or ``_aggregate_table()`` not reading the registry
        at all, or reading it without forwarding ``reset`` (the
        registry zeroes every section it reads under ``reset``; an
        output path that drops the flag window-scopes none).  What a
        section registers (``register_section(name, stats, reset)``)
        needs no check: the registry, not the owner, calls ``reset``.
MXA404  uncataloged fault point — an ``engine.fault_point("site")``
        whose site name is missing from the docs/resilience.md catalog
        (chaos plans target sites by name; an uncataloged site is
        untestable by reading the docs).
MXA405  uncataloged telemetry name — a registered profiler section, a
        literal span site (``op_scope``/``span_begin``/``instant``/
        ``request_begin``), or a literal ``mxtpu_*`` metric name that
        does not appear in docs/observability.md (dashboards and trace
        queries target these names; an uncataloged one is invisible to
        anyone reading the docs — the fault-point rule, applied to
        observability).
"""
from __future__ import annotations

import ast
import re

from .core import Finding


# -- env reads --------------------------------------------------------------


def _literal(node):
    return node.value if isinstance(node, ast.Constant) and \
        isinstance(node.value, str) else None


def _raw_env_reads(index, mod):
    """(node, name_or_None) for os.environ/os.getenv touches."""
    out = []
    for node in ast.walk(mod.tree):
        # os.environ.get("X") / os.environ["X"] / os.getenv("X")
        if isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Attribute)
                    and isinstance(f.value.value, ast.Name)
                    and mod.ext_aliases.get(f.value.value.id) == "os"
                    and f.value.attr == "environ"
                    and f.attr in ("get", "setdefault", "pop")):
                out.append((node, _literal(node.args[0])
                            if node.args else None))
            elif (isinstance(f, ast.Attribute)
                  and isinstance(f.value, ast.Name)
                  and mod.ext_aliases.get(f.value.id) == "os"
                  and f.attr == "getenv"):
                out.append((node, _literal(node.args[0])
                            if node.args else None))
        elif isinstance(node, ast.Subscript):
            v = node.value
            if (isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name)
                    and mod.ext_aliases.get(v.value.id) == "os"
                    and v.attr == "environ"):
                out.append((node, _literal(node.slice)))
        elif isinstance(node, ast.Compare):
            # "X" in os.environ / "X" not in os.environ
            if (len(node.ops) == 1
                    and isinstance(node.ops[0], (ast.In, ast.NotIn))):
                cmp = node.comparators[0]
                if (isinstance(cmp, ast.Attribute)
                        and isinstance(cmp.value, ast.Name)
                        and mod.ext_aliases.get(cmp.value.id) == "os"
                        and cmp.attr == "environ"):
                    out.append((node, _literal(node.left)))
    return out


def _env_findings(index, findings):
    cfg = index.cfg
    doc = index.doc_text(cfg.env_doc) or ""
    documented = set(re.findall(r"[A-Z][A-Z0-9_]{2,}", doc))
    exempt = set(cfg.env_exempt_modules)
    seen_doc_checks = set()

    for name, mod in sorted(index.modules.items()):
        raw = _raw_env_reads(index, mod)
        for node, env_name in raw:
            sym = index.enclosing(mod, node.lineno)
            allowed = (name in exempt
                       or (env_name is not None
                           and env_name.startswith(
                               tuple(cfg.raw_env_allowed_prefixes))))
            if not allowed:
                findings.append(Finding(
                    "MXA401", mod.relpath, node.lineno,
                    f"{sym}:{env_name or '<dynamic>'}",
                    f"raw environment read of "
                    f"{env_name or 'a computed name'} in {sym} — route "
                    f"through base.getenv so MXTPU_/MXNET_ spellings "
                    f"both work"))
            if (env_name is not None and name not in exempt
                    and env_name not in documented):
                k = (mod.relpath, env_name)
                if k not in seen_doc_checks:
                    seen_doc_checks.add(k)
                    findings.append(Finding(
                        "MXA402", mod.relpath, node.lineno,
                        f"{sym}:{env_name}",
                        f"env var {env_name} is read here but not "
                        f"documented in {cfg.env_doc}"))

        # base.getenv("NAME") reads: NAME must be documented as
        # MXTPU_NAME (the canonical spelling)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            if fname not in cfg.getenv_fns or not node.args:
                continue
            env_name = _literal(node.args[0])
            if env_name is None:
                continue
            if "MXTPU_" + env_name not in documented:
                sym = index.enclosing(mod, node.lineno)
                k = (mod.relpath, env_name)
                if k in seen_doc_checks:
                    continue
                seen_doc_checks.add(k)
                findings.append(Finding(
                    "MXA402", mod.relpath, node.lineno,
                    f"{sym}:{env_name}",
                    f"env knob MXTPU_{env_name} (base.getenv "
                    f"{env_name!r}) is not documented in "
                    f"{cfg.env_doc}"))


# -- profiler window scoping ------------------------------------------------


def _fname(call_func):
    if isinstance(call_func, ast.Name):
        return call_func.id
    if isinstance(call_func, ast.Attribute):
        return call_func.attr
    return None


def _passes_reset(node):
    return any(isinstance(a, ast.Name) and a.id == "reset"
               for a in list(node.args)
               + [kw.value for kw in node.keywords])


def _profiler_findings(index, findings):
    """MXA403: both output paths of the profiler module read the
    section registry through a ``section_iter_fns`` helper, and hand it
    their ``reset``."""
    cfg = index.cfg
    mod = index.modules.get(cfg.profiler_module)
    if mod is None:
        return
    for caller_name in ("dumps", "_aggregate_table"):
        caller = index.funcs.get((mod.modname, caller_name))
        if caller is None:
            continue
        touched = False
        for node in ast.walk(caller.node):
            if not isinstance(node, ast.Call):
                continue
            fn = _fname(node.func)
            if fn not in cfg.section_iter_fns:
                continue
            touched = True
            if not _passes_reset(node):
                findings.append(Finding(
                    "MXA403", mod.relpath, node.lineno,
                    f"{caller_name}:{fn}",
                    f"{caller_name}() iterates the section "
                    f"registry via {fn} without forwarding reset — "
                    f"this output path would not window-scope ANY "
                    f"section"))
        if not touched:
            findings.append(Finding(
                "MXA403", mod.relpath, caller.node.lineno,
                f"{caller_name}:<no-sections>",
                f"{caller_name}() does not iterate the section "
                f"registry — counter sections are missing from this "
                f"output path"))


# -- fault-point catalog ----------------------------------------------------


def _fault_point_findings(index, findings):
    cfg = index.cfg
    doc = index.doc_text(cfg.resilience_doc) or ""
    for name, mod in sorted(index.modules.items()):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            if fname not in cfg.fault_point_fns or not node.args:
                continue
            site = _literal(node.args[0])
            if site is None:
                continue   # dispatcher plumbing forwards a variable
            if f"`{site}`" not in doc and site not in doc:
                sym = index.enclosing(mod, node.lineno)
                findings.append(Finding(
                    "MXA404", mod.relpath, node.lineno,
                    f"{sym}:{site}",
                    f"fault point '{site}' is not cataloged in "
                    f"{cfg.resilience_doc} — chaos plans target sites "
                    f"by name"))


# -- telemetry catalog ------------------------------------------------------


def _telemetry_catalog_findings(index, findings):
    """MXA405: registered section names, literal span sites, and
    literal ``mxtpu_*`` metric names must appear in the observability
    doc — dashboards, scrape configs, and Perfetto queries target
    telemetry by name, so an undocumented name is unfindable."""
    cfg = index.cfg
    doc = index.doc_text(cfg.observability_doc) or ""

    def _check(mod, node, kind, name):
        if name in doc:
            return
        sym = index.enclosing(mod, node.lineno)
        findings.append(Finding(
            "MXA405", mod.relpath, node.lineno, f"{sym}:{name}",
            f"{kind} '{name}' is not cataloged in "
            f"{cfg.observability_doc} — telemetry consumers target "
            f"these names by reading the docs"))

    for _name, mod in sorted(index.modules.items()):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = _fname(node.func)
            lit = _literal(node.args[0])
            if lit is None:
                continue   # dynamic names (f-string buckets) are
                # documented as families, not checked per-site
            if fn in cfg.section_register_fns:
                _check(mod, node, "profiler section", lit)
            elif fn in cfg.span_site_fns:
                _check(mod, node, "span site", lit)
            elif fn in cfg.metric_def_fns and \
                    lit.startswith(cfg.metric_name_prefix):
                _check(mod, node, "metric", lit)


def run(index):
    findings = []
    _env_findings(index, findings)
    _profiler_findings(index, findings)
    _fault_point_findings(index, findings)
    _telemetry_catalog_findings(index, findings)
    return findings
