"""mxtpu-analyze: per-pass unit tests on synthetic fixture packages, a
"repo is clean modulo baseline" acceptance test, baseline mechanics,
and the runtime lock-order checker (docs/static-analysis.md)."""
import os
import threading
import time

import pytest

from mxnet_tpu import analysis
from mxnet_tpu.analysis import AnalysisConfig, runtime as lock_order
from mxnet_tpu.analysis.core import (Finding, apply_baseline,
                                     load_baseline, run_passes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "analysis_baseline.json")


def _fixture_cfg(**over):
    base = dict(
        package="pkg",
        env_doc="docs/ENV_VARS.md",
        resilience_doc="docs/resilience.md",
        profiler_module="profiler",
        seeded_modules=("seeded",),
        hotpath_roots=(("hot", "Server._run_batch"),),
    )
    base.update(over)
    return AnalysisConfig(**base)


def _run(tmp_path, files, docs=None, cfg=None, passes=None):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    docdir = tmp_path / "docs"
    docdir.mkdir(exist_ok=True)
    for name, text in {"ENV_VARS.md": "", "resilience.md": "",
                       **(docs or {})}.items():
        (docdir / name).write_text(text)
    findings, _ = run_passes(str(tmp_path), cfg or _fixture_cfg(), passes)
    return findings


def _codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# MXA1xx: lock order


def test_lock_cycle_direct(tmp_path):
    findings = _run(tmp_path, {"m.py": (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def f():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
        "def g():\n"
        "    with B:\n"
        "        with A:\n"
        "            pass\n")}, passes=["locks"])
    assert _codes(findings) == ["MXA101"]
    assert "m.A" in findings[0].message and "m.B" in findings[0].message


def test_lock_cycle_interprocedural_with_condition_alias(tmp_path):
    """f holds the Condition's underlying lock while CALLING a method
    that takes _mu; g nests them the other way round — the pass must
    see through both the call and the Condition alias."""
    findings = _run(tmp_path, {"q.py": (
        "import threading\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cv = threading.Condition(self._lock)\n"
        "        self._mu = threading.Lock()\n"
        "    def h(self):\n"
        "        with self._mu:\n"
        "            pass\n"
        "    def f(self):\n"
        "        with self._cv:\n"
        "            self.h()\n"
        "    def g(self):\n"
        "        with self._mu:\n"
        "            with self._lock:\n"
        "                pass\n")}, passes=["locks"])
    assert _codes(findings) == ["MXA101"]
    assert "Q._mu" in findings[0].symbol and "Q._lock" in findings[0].symbol


def test_lock_ordered_nesting_is_clean(tmp_path):
    findings = _run(tmp_path, {"m.py": (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def f():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
        "def g():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n")}, passes=["locks"])
    assert findings == []


def test_lock_self_reacquire(tmp_path):
    findings = _run(tmp_path, {"c.py": (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self.b()\n"
        "    def b(self):\n"
        "        with self._lock:\n"
        "            pass\n")}, passes=["locks"])
    assert _codes(findings) == ["MXA103"]
    # the same shape over an RLock is legal
    findings = _run(tmp_path, {"c.py": (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self.b()\n"
        "    def b(self):\n"
        "        with self._lock:\n"
        "            pass\n")}, passes=["locks"])
    assert findings == []


def test_unguarded_shared_global_from_thread(tmp_path):
    findings = _run(tmp_path, {"w.py": (
        "import threading\n"
        "_shared = []\n"
        "_guard = threading.Lock()\n"
        "def worker():\n"
        "    _shared.append(1)\n"
        "def ok_worker():\n"
        "    with _guard:\n"
        "        _shared.append(2)\n"
        "def start():\n"
        "    threading.Thread(target=worker).start()\n"
        "    threading.Thread(target=ok_worker).start()\n")},
        passes=["locks"])
    assert _codes(findings) == ["MXA102"]
    assert findings[0].symbol == "worker:_shared"


# ---------------------------------------------------------------------------
# MXA2xx: trace safety


def test_host_sync_in_jitted_kernel(tmp_path):
    findings = _run(tmp_path, {"k.py": (
        "def _k_bad(x):\n"
        "    return x.asnumpy()\n")}, passes=["trace"])
    assert _codes(findings) == ["MXA201"]
    assert findings[0].symbol == "_k_bad:asnumpy"


def test_host_sync_in_kernel_callee(tmp_path):
    findings = _run(tmp_path, {"k.py": (
        "def _k_outer(x):\n"
        "    return _helper(x)\n"
        "def _helper(x):\n"
        "    return x.item()\n")}, passes=["trace"])
    assert _codes(findings) == ["MXA201"]
    assert findings[0].symbol == "_helper:item"


def test_telemetry_hook_inside_kernel_is_trace_unsafe(tmp_path):
    """A telemetry hook that reads a traced value back to host inside
    a jitted kernel is exactly the host-sync hazard MXA201 exists for
    — recording span attrs must never force a device sync."""
    findings = _run(tmp_path, {"k.py": (
        "def _k_loss(x, tracer):\n"
        "    tracer.instant('pipeline.wait', val=x.asnumpy())\n"
        "    return x * 2\n"
        "def _k_clean(x, tracer):\n"
        "    tracer.instant('pipeline.wait', n=x.shape[0])\n"
        "    return x * 2\n")}, passes=["trace"])
    assert "MXA201" in _codes(findings)
    syms = {f.symbol.split(":")[0] for f in findings
            if f.code == "MXA201"}
    assert syms == {"_k_loss"}


def test_concretizer_and_control_flow_on_traced_param(tmp_path):
    findings = _run(tmp_path, {"k.py": (
        "def _k_conc(x):\n"
        "    return float(x)\n"
        "def _k_flow(x, *, n):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n"
        "def _k_static_ok(x, *, mode):\n"
        "    if mode == 'a':\n"    # kw-only attr: static, not flagged
        "        return x\n"
        "    if x.shape[0] > 1:\n"  # shape: static accessor, fine
        "        return x\n"
        "    return -x\n")}, passes=["trace"])
    assert _codes(findings) == ["MXA201", "MXA202"]
    syms = {f.symbol.split(":")[0] for f in findings}
    assert syms == {"_k_conc", "_k_flow"}


def test_unhashable_jit_signature(tmp_path):
    findings = _run(tmp_path, {"j.py": (
        "def get_jitted(fn, attrs):\n"
        "    return fn\n"
        "def go(x):\n"
        "    return get_jitted(_k_f, {'shapes': [1, 2]})(x)\n"
        "def ok(x):\n"
        "    return get_jitted(_k_f, {'shapes': (1, 2)})(x)\n"
        "def _k_f(x, *, shapes):\n"
        "    return x\n")}, passes=["trace"])
    assert [f.code for f in findings] == ["MXA203"]
    assert findings[0].symbol == "go:shapes"


def test_host_sync_on_hot_path(tmp_path):
    findings = _run(tmp_path, {"hot.py": (
        "class Server:\n"
        "    def _run_batch(self, group):\n"
        "        return [g.asnumpy() for g in group]\n")},
        passes=["trace"])
    assert _codes(findings) == ["MXA204"]


# ---------------------------------------------------------------------------
# MXA3xx: determinism of the seeded surface


def test_wallclock_and_global_rng_in_seeded_module(tmp_path):
    findings = _run(tmp_path, {"seeded.py": (
        "import random\n"
        "import time\n"
        "import numpy as np\n"
        "class Shuffle:\n"
        "    def __init__(self, seed):\n"
        "        self._rng = np.random.RandomState(seed)\n"   # sanctioned
        "        self._t0 = time.time()\n"                    # MXA301
        "    def draw(self):\n"
        "        return random.random()\n"                    # MXA302
        "    def draw2(self):\n"
        "        return np.random.rand(3)\n"                  # MXA302
        "    def telemetry_ok(self):\n"
        "        t0 = time.perf_counter()\n"                  # local: fine
        "        return self._rng.rand(), t0\n")},
        passes=["determinism"])
    assert _codes(findings) == ["MXA301", "MXA302", "MXA302"]
    m301 = [f for f in findings if f.code == "MXA301"][0]
    assert "time.time" in m301.symbol
    # the same code OUTSIDE the seeded surface is nobody's business
    cfg = _fixture_cfg(seeded_modules=("elsewhere",))
    assert _run(tmp_path, {}, cfg=cfg, passes=["determinism"]) == []


def test_wallclock_seeding_rng_flagged(tmp_path):
    findings = _run(tmp_path, {"seeded.py": (
        "import time\n"
        "import numpy as np\n"
        "def make_rng():\n"
        "    return np.random.RandomState(int(time.time()))\n")},
        passes=["determinism"])
    assert "MXA301" in _codes(findings)


# ---------------------------------------------------------------------------
# MXA4xx: repo invariants


def test_env_lints(tmp_path):
    files = {
        "base.py": (
            "import os\n"
            "def getenv(name, default=None, dtype=str):\n"
            "    return os.environ.get('MXTPU_' + name, default)\n"),
        "knobs.py": (
            "import os\n"
            "from .base import getenv\n"
            "def raw():\n"
            "    return os.environ.get('MXTPU_RAW')\n"
            "def documented():\n"
            "    return getenv('DOCUMENTED')\n"
            "def missing():\n"
            "    return getenv('MISSING')\n"
            "def protocol():\n"
            "    return os.environ.get('DMLC_THING')\n"),
    }
    docs = {"ENV_VARS.md": "| `MXTPU_DOCUMENTED` | documented knob |\n"}
    findings = _run(tmp_path, files, docs=docs, passes=["invariants"])
    by_code = {}
    for f in findings:
        by_code.setdefault(f.code, []).append(f.symbol)
    # raw read outside base.py (DMLC_* protocol reads exempt by prefix)
    assert by_code["MXA401"] == ["raw:MXTPU_RAW"]
    # undocumented: the raw name, the getenv miss, and the DMLC read
    assert sorted(by_code["MXA402"]) == [
        "missing:MISSING", "protocol:DMLC_THING", "raw:MXTPU_RAW"]


def test_profiler_output_path_without_sections_flagged(tmp_path):
    """MXA403: dumps() that never reads the registry has silently lost
    every counter section, and an output path that reads it without
    forwarding reset window-scopes none; the clean shape is silent."""
    findings = _run(tmp_path, {"profiler.py": (
        "_sections = {}\n"
        "def register_section(name, stats, reset, table=None):\n"
        "    _sections[name] = (stats, reset, table)\n"
        "def sections(reset=False):\n"
        "    return {}\n"
        "def _section_tables(reset=False):\n"
        "    return []\n"
        "def dumps(reset=False):\n"
        "    return '{}'\n"
        "def _aggregate_table(reset=False):\n"
        "    return (_section_tables(True), sections(reset))\n")},
        passes=["invariants"])
    assert _codes(findings) == ["MXA403", "MXA403"]
    assert sorted(f.symbol for f in findings) == [
        "_aggregate_table:_section_tables", "dumps:<no-sections>"]


def test_fault_point_catalog_lint(tmp_path):
    files = {"eng.py": (
        "def fault_point(site, /, **ctx):\n"
        "    return None\n"
        "def go():\n"
        "    fault_point('known.site')\n"
        "    fault_point('unknown.site', step=3)\n")}
    docs = {"resilience.md": "| `known.site` | somewhere | — |\n"}
    findings = _run(tmp_path, files, docs=docs, passes=["invariants"])
    assert _codes(findings) == ["MXA404"]
    assert findings[0].symbol == "go:unknown.site"


def test_telemetry_catalog_lint(tmp_path):
    """MXA405: literal span sites and mxtpu_* metric names must be in
    the observability doc; dynamic names and unprefixed metrics are
    out of scope."""
    files = {"t.py": (
        "def op_scope(name, cat='op'):\n"
        "    return None\n"
        "def go(reg, tracer, key):\n"
        "    op_scope('known.span')\n"
        "    op_scope('unknown.span')\n"
        "    op_scope(f'dynamic.{key}')\n"
        "    tracer.instant('resilience.retry')\n"
        "    tracer.request_begin('lost.request')\n"
        "    reg.counter('mxtpu_known_total')\n"
        "    reg.counter('mxtpu_unknown_total')\n"
        "    reg.gauge('unprefixed_name')\n")}
    docs = {"observability.md": (
        "| `known.span` | `resilience.retry` | `mxtpu_known_total` |\n")}
    findings = _run(tmp_path, files, docs=docs, passes=["invariants"])
    assert _codes(findings) == ["MXA405", "MXA405", "MXA405"]
    syms = sorted(f.symbol for f in findings)
    assert syms == ["go:lost.request", "go:mxtpu_unknown_total",
                    "go:unknown.span"]


def test_section_registration_catalog_lint(tmp_path):
    """MXA405 finds a section where its owner registers it, in
    whichever module of the package that is."""
    files = {"profiler.py": (
        "def register_section(name, stats, reset, table=None):\n"
        "    pass\n"
        "def dumps(reset=False):\n"
        "    return sections(reset)\n"
        "def sections(reset=False):\n"
        "    return {}\n"),
        "owner.py": (
        "from . import profiler\n"
        "def owner_stats():\n"
        "    return {}\n"
        "def reset_owner_stats():\n"
        "    pass\n"
        "profiler.register_section('knownSection', owner_stats,\n"
        "                          reset_owner_stats)\n"
        "profiler.register_section('unknownSection', owner_stats,\n"
        "                          reset_owner_stats)\n")}
    docs = {"observability.md": "the `knownSection` section\n"}
    findings = _run(tmp_path, files, docs=docs, passes=["invariants"])
    assert _codes(findings) == ["MXA405"]
    assert findings[0].symbol == "<module>:unknownSection"
    assert findings[0].path.endswith("owner.py")


# ---------------------------------------------------------------------------
# MXA5xx: knob-registry invariants


_KNOB_FIXTURE = (
    "class Knob:\n"
    "    def __init__(self, name, **kw):\n"
    "        pass\n"
    "def build():\n"
    "    Knob('good', env='GOOD_KNOB', domain=(1, 2, 4))\n"
    "    Knob('undocumented', env='NOT_IN_DOCS', bounds=(1, 8))\n"
    "    Knob('no_env', domain=(1, 2))\n"
    "    Knob('unbounded', env='OTHER_KNOB')\n"
    "    Knob('flag', env='FLAG_KNOB', kind='bool')\n"
    "    Knob('bad_bounds', env='RANGE_KNOB', bounds=(8, 1))\n")

_KNOB_DOCS = ("| `MXTPU_GOOD_KNOB` | 1 | a knob |\n"
              "| `MXTPU_OTHER_KNOB` | 2 | another |\n"
              "| `MXTPU_FLAG_KNOB` | 0 | a flag |\n"
              "| `MXTPU_RANGE_KNOB` | 4 | ranged |\n")


def test_tune_registry_lints(tmp_path):
    """MXA501: missing/undocumented env=; MXA502: no literal
    domain=/bounds= (bool exempt, lo >= hi rejected)."""
    findings = _run(tmp_path,
                    {"tune/__init__.py": "", "tune/knobs.py":
                     _KNOB_FIXTURE},
                    docs={"ENV_VARS.md": _KNOB_DOCS},
                    passes=["tune"])
    assert _codes(findings) == ["MXA501", "MXA501", "MXA502",
                                "MXA502"]
    syms = sorted(f.symbol for f in findings)
    assert syms == ["build:bad_bounds", "build:no_env",
                    "build:unbounded", "build:undocumented"]


def test_tune_registry_docs_drift_is_a_finding(tmp_path):
    """The same registry goes clean <-> dirty purely on the docs: drop
    one documented var and exactly that knob fires."""
    clean_src = ("class Knob:\n"
                 "    def __init__(self, name, **kw):\n"
                 "        pass\n"
                 "Knob('a', env='A_KNOB', domain=(1, 2))\n"
                 "Knob('b', env='B_KNOB', bounds=(0, 10))\n")
    both = "`MXTPU_A_KNOB` and `MXTPU_B_KNOB`\n"
    findings = _run(tmp_path, {"tune/knobs.py": clean_src},
                    docs={"ENV_VARS.md": both}, passes=["tune"])
    assert findings == []
    findings = _run(tmp_path, {"tune/knobs.py": clean_src},
                    docs={"ENV_VARS.md": "`MXTPU_A_KNOB` only\n"},
                    passes=["tune"])
    assert _codes(findings) == ["MXA501"]
    assert findings[0].symbol == "<module>:b"


def test_tune_pass_noop_without_knobs_module(tmp_path):
    """Fixture packages with no tune tier stay clean (the pass must
    not invent findings about a module that does not exist)."""
    findings = _run(tmp_path, {"m.py": "x = 1\n"}, passes=["tune"])
    assert findings == []


# ---------------------------------------------------------------------------
# baseline mechanics


def test_plain_internal_import_binds_root_package(tmp_path):
    """`import pkg.sub` binds the local name `pkg` (the root), not
    `sub` — `pkg.helper()` must resolve against the root __init__."""
    from mxnet_tpu.analysis.core import Index

    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("def helper():\n    pass\n")
    (pkg / "other.py").write_text("")
    (pkg / "m.py").write_text(
        "import pkg.other\n"
        "def f():\n"
        "    pkg.helper()\n")
    idx = Index(str(tmp_path), _fixture_cfg())
    assert ("", "helper") in idx.call_graph()[("m", "f")]


def test_unknown_pass_name_rejected(tmp_path):
    """A typo'd --passes must fail the gate, not green it with zero
    analysis run."""
    with pytest.raises(ValueError, match="unknown pass"):
        _run(tmp_path, {}, passes=["lokcs"])


def test_baseline_requires_justification(tmp_path):
    p = tmp_path / "b.json"
    p.write_text('{"suppressions": [{"key": "MXA101:x.py:f"}]}')
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(p))


def test_baseline_partition_and_stale_detection():
    f1 = Finding("MXA101", "a.py", 3, "f", "msg")
    f2 = Finding("MXA402", "b.py", 9, "g:KNOB", "msg")
    baseline = {f1.key: "why", "MXA999:gone.py:h": "stale"}
    new, suppressed, unused = apply_baseline([f1, f2], baseline)
    assert new == [f2]
    assert suppressed == [f1]
    assert unused == ["MXA999:gone.py:h"]
    # keys are line-insensitive: moving the finding keeps the match
    f1_moved = Finding("MXA101", "a.py", 57, "f", "msg")
    assert f1_moved.key == f1.key


# ---------------------------------------------------------------------------
# acceptance: the real repo is clean modulo the checked-in baseline


def test_repo_clean_modulo_baseline():
    t0 = time.perf_counter()
    result = analysis.analyze(REPO, baseline_path=BASELINE)
    runtime_s = time.perf_counter() - t0
    new = result["new"]
    assert not new, "non-baselined findings:\n" + "\n".join(
        f"  {f.key} (line {f.line}): {f.message}" for f in new)
    assert not result["unused"], (
        f"stale baseline suppressions: {result['unused']}")
    # the baseline documents real, justified designs — it must not rot
    # into an empty file silently (keys above) or grow unreviewed
    assert len(result["suppressed"]) >= 2
    # the `make verify` latency budget on this box
    assert runtime_s < 30, f"analyzer took {runtime_s:.1f}s"


def test_every_pass_ran_on_repo():
    """Each pass family produces SOMETHING over the repo when its
    specific suppressed findings are included — guards against a pass
    silently short-circuiting to zero coverage."""
    result = analysis.analyze(REPO, baseline_path=None)
    codes = {f.code for f in result["findings"]}
    # locks: the engine's documented lock-free hot path
    assert "MXA102" in codes
    # trace: the serve readback on the hot path
    assert "MXA204" in codes
    index = result["index"]
    # the other two families prove coverage structurally: the seeded
    # surface and the profiler providers were actually found
    assert any(m in index.modules for m in ("pipeline.stages",))
    assert (index.cfg.profiler_module in index.modules)


# ---------------------------------------------------------------------------
# runtime lock-order checker


def _fresh(enabled=False, raise_on_inversion=False):
    lock_order.disable()
    lock_order.reset()
    if enabled:
        assert lock_order.enable(raise_on_inversion=raise_on_inversion)


def test_runtime_inversion_recorded():
    _fresh(enabled=True)
    try:
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        with b:
            with a:
                pass
    finally:
        lock_order.disable()
    inv = lock_order.inversions()
    assert len(inv) == 1
    assert inv[0]["acquiring"] != inv[0]["while_holding"]
    with pytest.raises(AssertionError, match="inversion"):
        lock_order.assert_clean()
    lock_order.reset()
    lock_order.assert_clean()


def test_runtime_inversion_raises_and_unwinds():
    _fresh(enabled=True, raise_on_inversion=True)
    try:
        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        with pytest.raises(lock_order.LockInversionError):
            with b:
                with a:
                    pass
        # the failed acquire unwound: both locks are free again
        assert a.acquire(False)
        a.release()
        assert b.acquire(False)
        b.release()
    finally:
        lock_order.disable()
        lock_order.reset()


def test_runtime_ordered_nesting_clean_and_disable_restores():
    _fresh(enabled=True)
    try:
        a = threading.Lock()
        b = threading.Lock()
        for _ in range(3):
            with a:
                with b:
                    pass
        assert lock_order.inversions() == []
        st = lock_order.stats()
        assert st["edges"] == 1
        # liveness telemetry: wrapped creations + every acquisition
        # count even when nothing nests (sites/edges only see pairs)
        assert st["locks_wrapped"] >= 2
        assert st["acquires"] >= 6
    finally:
        lock_order.disable()
        lock_order.reset()
    assert threading.Lock is lock_order._orig_Lock
    assert threading.RLock is lock_order._orig_RLock


def test_runtime_condition_wait_notify_compat():
    """Condition over a checked lock must keep wait/notify working and
    the held-stack bookkeeping symmetric (via _release_save/_acquire_
    restore delegation)."""
    _fresh(enabled=True)
    try:
        lk = threading.Lock()
        cv = threading.Condition(lk)
        hits = []

        def waiter():
            with cv:
                while not hits:
                    cv.wait(timeout=5)
                hits.append("seen")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            hits.append("go")
            cv.notify_all()
        t.join(5)
        assert not t.is_alive()
        assert hits == ["go", "seen"]
        assert lock_order.inversions() == []
    finally:
        lock_order.disable()
        lock_order.reset()


def test_runtime_wrap_existing_rebinds_module_globals():
    _fresh(enabled=True)
    try:
        import mxnet_tpu.pipeline.stats as pstats

        lock_order.wrap_existing()
        # wrapped either in place by wrap_existing (module.attr site)
        # or at creation if the module first imported under an enabled
        # checker (file:line site) — both are checked locks
        assert isinstance(pstats._lock, lock_order._CheckedLock)
        # the wrapped global still does its job
        pstats.reset_pipeline_stats()
    finally:
        # restore raw locks so later tests see pristine module state
        n = lock_order.unwrap_existing()
        lock_order.disable()
        lock_order.reset()
    assert n > 0
    assert not isinstance(pstats._lock, lock_order._CheckedLock)
