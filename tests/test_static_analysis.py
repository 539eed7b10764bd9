"""Static analyzer + runtime sanitizers — seeded defects, the package
gate, and the transfer-guard proof for the warm scoring path.

Three tiers of assurance, mirroring how the reference gates its Java tree
with findbugs/error-prone:
  1. each rule R001-R006 detects a seeded defect (the rule works);
  2. the whole package reports zero unsuppressed findings against
     analysis_baseline.json (the codebase is clean, and stays clean:
     a new finding fails tier-1);
  3. the warm-cache scoring path runs under
     jax.transfer_guard("disallow") — every transfer it performs is
     explicit, so the recompile-free fast path is also stray-sync-free.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from h2o3_tpu.analysis import engine
from h2o3_tpu.analysis import sanitizers

REPO = engine.repo_root()
BASELINE = os.path.join(REPO, "analysis_baseline.json")


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# 1. seeded defects — one per rule
def test_r001_detects_jit_lambda_in_function_body():
    src = (
        "import jax\n"
        "def hot(x):\n"
        "    return jax.jit(lambda a: a + 1)(x)\n")
    found = engine.analyze_source(src)
    assert "R001" in _rules_of(found)
    assert any(f.line == 3 for f in found if f.rule == "R001")


def test_r001_detects_per_call_jit_of_nested_def():
    src = (
        "import jax\n"
        "def hot(x):\n"
        "    def body(a):\n"
        "        return a * 2\n"
        "    return jax.jit(body)(x)\n")
    assert "R001" in _rules_of(engine.analyze_source(src))


def test_r001_clean_on_module_level_jit_and_cached_jit():
    src = (
        "import jax\n"
        "from h2o3_tpu.parallel.mrtask import cached_jit\n"
        "@jax.jit\n"
        "def fine(a):\n"
        "    return a + 1\n"
        "def also_fine(x):\n"
        "    return cached_jit(lambda a: a + 1)(x)\n")
    assert "R001" not in _rules_of(engine.analyze_source(src))


def test_r002_detects_host_sync_inside_traced_fn():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.asarray(x).sum()\n")
    assert "R002" in _rules_of(engine.analyze_source(src))


def test_r002_detects_barrier_inside_span_block():
    src = (
        "import jax\n"
        "from h2o3_tpu.obs.timeline import span\n"
        "def hot(x):\n"
        "    with span('score.dispatch'):\n"
        "        jax.block_until_ready(x)\n"
        "    return x\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R002"]
    assert found and found[0].line == 5


def test_r003_detects_bare_mutation_of_locked_attr():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []\n"
        "    def safe(self, v):\n"
        "        with self._lock:\n"
        "            self._items.append(v)\n"
        "    def racy(self, v):\n"
        "        self._items.append(v)\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R003"]
    assert len(found) == 1 and found[0].line == 10
    assert "racy" in found[0].message


def test_r004_detects_impurity_under_trace():
    src = (
        "import jax\n"
        "import time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x * time.time()\n")
    assert "R004" in _rules_of(engine.analyze_source(src))
    src2 = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x + np.random.normal()\n")
    assert "R004" in _rules_of(engine.analyze_source(src2))


def test_r005_detects_duplicate_and_nonliteral_declarations():
    src = (
        "from h2o3_tpu.obs import metrics as _om\n"
        "A = _om.counter('h2o3_fixture_dup_total', 'first')\n"
        "B = _om.counter('h2o3_fixture_dup_total', 'second')\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R005"]
    assert len(found) == 1 and found[0].line == 3
    src2 = (
        "from h2o3_tpu.obs import metrics as _om\n"
        "def make(suffix):\n"
        "    return _om.counter('h2o3_' + suffix)\n")
    assert "R005" in _rules_of(engine.analyze_source(src2))


def test_r005_detects_inconsistent_label_sets():
    src = (
        "from h2o3_tpu.obs import metrics as _om\n"
        "C = _om.counter('h2o3_fixture_labels_total', 'x')\n"
        "def a():\n"
        "    C.inc(reason='x')\n"
        "def b():\n"
        "    C.inc(reason='x')\n"
        "def c():\n"
        "    C.inc()\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R005"]
    assert len(found) == 1 and found[0].line == 8


def test_r005_sees_instance_attribute_emissions():
    """Metrics bound to self.<attr> at declaration (the SLO engine's
    pattern) must be tracked through self.<attr>.set(...) emission
    sites — both for the label-consistency gate and the census."""
    src = (
        "from h2o3_tpu.obs import metrics as _om\n"
        "class Eng:\n"
        "    def __init__(self):\n"
        "        self._g = _om.REGISTRY.gauge('h2o3_fixture_attr', 'x')\n"
        "    def a(self):\n"
        "        self._g.set(1.0, slo='s')\n"
        "    def b(self):\n"
        "        self._g.set(0.0)\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R005"]
    assert len(found) == 1 and found[0].line == 8
    # census records the labels seen at the attribute emission sites
    import ast as _ast
    from h2o3_tpu.analysis import rules_metrics
    mod = engine.Module("<fixture>", "<fixture>", src, _ast.parse(src))
    decls, _ = rules_metrics.collect([mod])
    emis = [e for en in decls["h2o3_fixture_attr"]
            for e in en.get("emissions", [])]
    assert {lb for _, _, ls in emis for lb in ls} == {"slo"}


def test_r006_detects_group_signature_drift():
    src = (
        "import re\n"
        "def _h_one(h, a):\n"
        "    pass\n"
        "ROUTES = [\n"
        "    (re.compile(r'/3/Thing/([^/]+)/([^/]+)'), 'GET', _h_one),\n"
        "]\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/api/fixture_routes.py")
        if f.rule == "R006"]
    assert len(found) == 1 and "captures 2 group" in found[0].message


def test_r006_detects_duplicate_and_missing_handler():
    src = (
        "import re\n"
        "def _h_ok(h):\n"
        "    pass\n"
        "ROUTES = [\n"
        "    (re.compile(r'/3/Same'), 'GET', _h_ok),\n"
        "    (re.compile(r'/3/Same'), 'GET', _h_ok),\n"
        "    (re.compile(r'/3/Gone'), 'GET', _h_missing),\n"
        "]\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/api/fixture_routes.py")
        if f.rule == "R006"]
    msgs = " | ".join(f.message for f in found)
    assert "duplicate route" in msgs and "not defined" in msgs


# ---------------------------------------------------------------------------
# suppression + baseline mechanics
def test_inline_suppression_waives_finding():
    src = (
        "import jax\n"
        "def hot(x):\n"
        "    # h2o3-ok: R001 fixture: intentionally waived\n"
        "    return jax.jit(lambda a: a + 1)(x)\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R001"]
    assert found and all(f.suppressed for f in found)
    assert not engine.unsuppressed(found)


def test_baseline_grandfathers_by_fingerprint(tmp_path):
    src = (
        "import jax\n"
        "def hot(x):\n"
        "    return jax.jit(lambda a: a + 1)(x)\n")
    found = engine.analyze_source(src)
    bl = tmp_path / "bl.json"
    engine.write_baseline(found, str(bl))
    again = engine.analyze_source(src)
    engine.apply_baseline(again, engine.load_baseline(str(bl)))
    assert not engine.unsuppressed(again)
    data = json.loads(bl.read_text())
    assert data["findings"] and all("fingerprint" in e
                                    for e in data["findings"])


# ---------------------------------------------------------------------------
# 2. the package + tests gate (tier-1): zero unsuppressed findings.
# tests/ rides along under the relaxed profile (R001/R004 waived — test
# code jits lambdas and calls time() on purpose; every other rule,
# including the R007-R010 concurrency pass, applies in full: a racy
# harness or leaked test thread flakes the suite like any product bug).
def test_package_and_tests_have_zero_unsuppressed_findings():
    findings = engine.run(paths=[engine.package_root(),
                                 engine.tests_root()],
                          baseline_path=BASELINE)
    bad = engine.unsuppressed(findings)
    assert not bad, (
        "static analysis found new defects (fix them, or suppress with "
        "`# h2o3-ok: Rnnn <reason>` / baseline via --write-baseline):\n"
        + "\n".join(str(f) for f in bad))


def test_cli_entry_point_exit_codes():
    out = subprocess.run(
        [sys.executable, "-m", "h2o3_tpu.analysis",
         "--baseline", BASELINE, "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["unsuppressed"] == 0


def test_metric_census_is_committed_and_current():
    """obs/METRICS.md must match a fresh census — renaming or adding a
    metric without regenerating fails here, keeping dashboards honest."""
    from h2o3_tpu.analysis import rules_metrics
    mods = engine.load_modules([engine.package_root()])
    want = rules_metrics.census_markdown(mods)
    path = os.path.join(engine.package_root(), "obs", "METRICS.md")
    assert os.path.exists(path), \
        "run: python -m h2o3_tpu.analysis --write-census"
    with open(path, encoding="utf-8") as fh:
        have = fh.read()
    assert have == want, \
        "stale metric census — run: python -m h2o3_tpu.analysis " \
        "--write-census"


def test_check_census_checks_committed_files_despite_explicit_write(
        tmp_path):
    """`--write-census <path> --check-census` must still gate the
    COMMITTED censuses: writing to an explicit side path and then
    comparing the gate against that same fresh file would let a stale
    obs/METRICS.md or SPANS.md sail through exit 0."""
    spans_path = os.path.join(engine.package_root(), "obs", "SPANS.md")
    with open(spans_path, encoding="utf-8") as fh:
        committed = fh.read()
    try:
        with open(spans_path, "a", encoding="utf-8") as fh:
            fh.write("\nstale marker\n")
        out = subprocess.run(
            [sys.executable, "-m", "h2o3_tpu.analysis",
             "--write-census", str(tmp_path / "side.md"),
             "--check-census"],
            capture_output=True, text=True, cwd=REPO, timeout=300)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "stale" in out.stderr and "census" in out.stderr
    finally:
        with open(spans_path, "w", encoding="utf-8") as fh:
            fh.write(committed)


def test_r005_ignores_exemplar_kwarg():
    """`exemplar=` on Histogram.observe is the OpenMetrics exemplar, not
    a label — mixed presence across sites must not split the series."""
    src = (
        "from h2o3_tpu.obs import metrics as _om\n"
        "H = _om.histogram('h2o3_fixture_ex_seconds', 'x')\n"
        "def a(tid):\n"
        "    H.observe(0.1, exemplar=tid, route='/3/X')\n"
        "def b():\n"
        "    H.observe(0.2, route='/3/X')\n")
    assert not [f for f in engine.analyze_source(src) if f.rule == "R005"]


def test_r005_flags_exemplar_kwarg_on_counter():
    """Counter.inc has no exemplar parameter — the kwarg lands in
    **labels and mints a series per trace id, so R005 must keep seeing
    it as a label (the observe/time carve-out must not leak here)."""
    src = (
        "from h2o3_tpu.obs import metrics as _om\n"
        "C = _om.counter('h2o3_fixture_ex_total', 'x')\n"
        "def a(tid):\n"
        "    C.inc(exemplar=tid, route='/3/X')\n"
        "def b():\n"
        "    C.inc(route='/3/X')\n")
    found = [f for f in engine.analyze_source(src) if f.rule == "R005"]
    assert found and "label" in found[0].message.lower(), found


# ---------------------------------------------------------------------------
# R011: span-name drift (ISSUE 7)
def test_r011_detects_duplicate_span_declarations():
    src = (
        "from h2o3_tpu.obs.timeline import span as _span\n"
        "def a():\n"
        "    with _span('fixture.phase'):\n"
        "        pass\n"
        "def b():\n"
        "    with _span('fixture.phase'):\n"
        "        pass\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/fixture_spans.py") if f.rule == "R011"]
    assert len(found) == 1 and "more than one call site" in found[0].message


def test_r011_detects_nonliteral_span_name():
    src = (
        "from h2o3_tpu.obs.timeline import span\n"
        "def a(key):\n"
        "    with span('fixture.' + key):\n"
        "        pass\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/fixture_spans.py") if f.rule == "R011"]
    assert len(found) == 1 and "non-literal" in found[0].message


def test_r011_clean_shapes():
    """Pass-through wrappers, conditional literals (nested too), receiver-
    style calls and the ring's add_finished are all legitimate; wrapper
    call sites are censused."""
    from h2o3_tpu.analysis import rules_spans
    src = (
        "from h2o3_tpu.obs import timeline\n"
        "from h2o3_tpu.obs.timeline import span as _span\n"
        "def wrapper(name, fn):\n"
        "    with _span(name):\n"
        "        return fn()\n"
        "def a(warm, fn):\n"
        "    with _span('fixture.warm' if warm else 'fixture.cold'):\n"
        "        pass\n"
        "    with timeline.span('fixture.receiver'):\n"
        "        pass\n"
        "    return wrapper('fixture.wrapped', fn)\n"
        "def b(SPANS, st):\n"
        "    SPANS.add_finished('fixture.t' if st == 't' else 'fixture.l'\n"
        "                       if st == 'l' else 'fixture.c', 0.0, 1.0)\n")
    mods = [engine.Module("h2o3_tpu/fx.py", "h2o3_tpu/fx.py", src,
                          __import__('ast').parse(src))]
    mods[0].lines = src.splitlines()
    decls, findings = rules_spans.collect(mods)
    assert not findings and not rules_spans.check(mods)
    assert set(decls) == {"fixture.warm", "fixture.cold",
                          "fixture.receiver", "fixture.wrapped",
                          "fixture.t", "fixture.l", "fixture.c"}


def test_r011_relaxed_for_tests():
    src = (
        "from h2o3_tpu.obs.timeline import span\n"
        "def test_x(n):\n"
        "    with span('t.' + str(n)):\n"
        "        pass\n")
    found = engine.analyze_source(src, filename="tests/test_fixture.py")
    assert "R011" not in _rules_of(found)


def test_span_census_is_committed_and_current():
    """obs/SPANS.md must match a fresh census — renaming or adding a
    span without regenerating fails here, keeping trace search honest."""
    from h2o3_tpu.analysis import rules_spans
    mods = engine.load_modules([engine.package_root()])
    want = rules_spans.census_markdown(mods)
    path = os.path.join(engine.package_root(), "obs", "SPANS.md")
    assert os.path.exists(path), \
        "run: python -m h2o3_tpu.analysis --write-census"
    with open(path, encoding="utf-8") as fh:
        have = fh.read()
    assert have == want, \
        "stale span census — run: python -m h2o3_tpu.analysis " \
        "--write-census"
    # the census knows the load-bearing production spans
    assert "`rest.request`" in have and "`slo.alert`" in have


# ---------------------------------------------------------------------------
# 3. runtime sanitizers on the real serving path
RNG = np.random.default_rng(77)


def _frame(n, resp=False):
    from h2o3_tpu.core.frame import Frame
    cols = {"a": RNG.normal(size=n), "b": RNG.normal(size=n)}
    if resp:
        cols["y"] = RNG.normal(size=n)
    return Frame.from_dict(cols)


@pytest.fixture(scope="module")
def glm():
    from h2o3_tpu.core.kvstore import DKV
    from h2o3_tpu.models import ESTIMATORS
    tr = _frame(220, resp=True)
    m = ESTIMATORS["glm"]()
    m.train(x=["a", "b"], y="y", training_frame=tr)
    yield m
    DKV.remove(tr.key)
    DKV.remove(m.key)


def test_warm_scoring_path_is_transfer_guard_clean(glm):
    """The ISSUE 2 fast path does no stray transfers: after warming a
    bucket, scoring under jax.transfer_guard('disallow') — which rejects
    every IMPLICIT transfer — must succeed without falling back, because
    staging uses device_put and readback uses device_get (explicit)."""
    from h2o3_tpu.core.kvstore import DKV
    from h2o3_tpu.serving import scorer_cache as sc
    warm = _frame(64)
    p0 = glm.predict(warm)                    # compile + warm the bucket
    trace_errors0 = sc.FALLBACKS.value(reason="trace-error")
    f = _frame(57)                            # same bucket, new row count
    with sanitizers.transfer_guard("disallow"):
        p = glm.predict(f)
    assert p.nrows == 57
    assert sc.FALLBACKS.value(reason="trace-error") == trace_errors0, \
        "warm scoring fell back under transfer_guard('disallow') — an " \
        "implicit host↔device transfer crept into the fast path"
    for k in (warm.key, p0.key, f.key, p.key):
        DKV.remove(k)


def test_debug_nans_scoped_toggle():
    import jax
    prev = jax.config.jax_debug_nans
    with sanitizers.debug_nans(True):
        assert jax.config.jax_debug_nans is True
    assert jax.config.jax_debug_nans == prev


def test_install_from_env_is_gated(monkeypatch):
    monkeypatch.delenv("H2O3_DEBUG_NANS", raising=False)
    monkeypatch.delenv("H2O3_TRANSFER_GUARD", raising=False)
    monkeypatch.delenv("H2O3_LOCKDEP", raising=False)
    monkeypatch.delenv("H2O3_DIVERGENCE", raising=False)
    assert sanitizers.install_from_env() == {}
    # explicit "off" spellings must DISABLE, not fall through to raise
    from h2o3_tpu.analysis import lockdep
    for off in ("0", "false", "off", "no"):
        monkeypatch.setenv("H2O3_LOCKDEP", off)
        assert sanitizers.install_from_env() == {}, off
        assert lockdep._mode_from_env(off) == ""


def test_install_from_env_enables_lockdep(monkeypatch):
    from h2o3_tpu.analysis import lockdep
    monkeypatch.setenv("H2O3_DEBUG_NANS", "")
    monkeypatch.setenv("H2O3_TRANSFER_GUARD", "")
    monkeypatch.setenv("H2O3_LOCKDEP", "log")
    try:
        out = sanitizers.install_from_env()
        assert out.get("lockdep") == "log"
        assert lockdep.enabled()
    finally:
        lockdep.disable()


# ---------------------------------------------------------------------------
# micro-batch backpressure (bounded queue depth → 503 + Retry-After)
def test_cached_jit_key_hardening():
    """Bound methods and cyclic closures must fall back to an uncached
    jit (never share a key); hash-equal captures of different types must
    key apart (1 vs 1.0 traces different programs)."""
    from h2o3_tpu.parallel import mrtask as mrt

    class M:
        def __init__(self, k):
            self.k = k

        def score(self, x):
            return x * self.k

    a, b = M(2.0), M(3.0)
    assert float(mrt.cached_jit(a.score)(np.float32(1.0))) == 2.0
    assert float(mrt.cached_jit(b.score)(np.float32(1.0))) == 3.0

    def outer():
        def g(x):
            return g(x)
        return g

    mrt.cached_jit(outer())        # cyclic closure: must not recurse

    def mk(c):
        return lambda x: x + c

    one = mrt._fn_key(mk(1))
    one_f = mrt._fn_key(mk(1.0))
    assert one != one_f            # int vs float capture → distinct keys
    assert mrt._fn_key(mk(1)) == one


def test_queue_full_rejects_before_staging(glm, monkeypatch):
    """check_capacity sheds at the entry point — before payload decode /
    frame staging burns CPU on a request that will be 503'd anyway."""
    from h2o3_tpu import serving
    from h2o3_tpu.serving import microbatch as mb
    monkeypatch.setenv("H2O3_SCORE_QUEUE_DEPTH", "1")
    monkeypatch.setattr(mb.BATCHER, "_depth", 1)
    called = []
    monkeypatch.setattr(serving, "payload_to_raw",
                        lambda *a, **k: called.append(1) or (_ for _ in ()).throw(
                            AssertionError("staged a doomed request")))
    with pytest.raises(serving.QueueFull):
        serving.score_payload(glm, [{"a": 0.1, "b": 0.2}])
    assert not called


def test_queue_full_rejects_and_recovers(glm, monkeypatch):
    from h2o3_tpu import serving
    from h2o3_tpu.serving import microbatch as mb
    monkeypatch.setenv("H2O3_SCORE_QUEUE_DEPTH", "1")
    rejected0 = mb.REJECTED.value()
    monkeypatch.setattr(mb.BATCHER, "_depth", 1)
    with pytest.raises(serving.QueueFull) as ei:
        serving.score_payload(glm, [{"a": 0.1, "b": 0.2}])
    assert ei.value.retry_after_s >= 1
    assert mb.REJECTED.value() == rejected0 + 1
    monkeypatch.setattr(mb.BATCHER, "_depth", 0)
    out = serving.score_payload(glm, [{"a": 0.1, "b": 0.2}])
    assert len(out) == 1 and "predict" in out[0]


def test_queue_depth_tracks_inflight_requests(glm, monkeypatch):
    """_depth rises while a request lingers in the queue and returns to
    zero afterwards (the gauge the 503 decision reads)."""
    from h2o3_tpu import serving
    from h2o3_tpu.serving import microbatch as mb
    monkeypatch.setenv("H2O3_SCORE_LINGER_MS", "30")
    seen = []
    t = threading.Thread(target=lambda: seen.append(
        serving.score_payload(glm, [{"a": 0.3, "b": 0.4}])))
    t.start()
    t.join(timeout=30)
    assert seen and len(seen[0]) == 1
    assert mb.BATCHER._depth == 0


def test_rest_returns_503_with_retry_after(glm, monkeypatch):
    """Full REST stack: queue-full answers 503 + Retry-After, not 500."""
    import urllib.error
    import urllib.request
    from h2o3_tpu.api.server import H2OServer
    from h2o3_tpu.serving import microbatch as mb
    s = H2OServer(port=0).start()
    try:
        monkeypatch.setenv("H2O3_SCORE_QUEUE_DEPTH", "1")
        monkeypatch.setattr(mb.BATCHER, "_depth", 1)
        body = json.dumps({"rows": [{"a": 0.1, "b": 0.2}]}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{s.port}/3/Predictions/models/{glm.key}",
            data=body, method="POST",
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") == "1"
        monkeypatch.setattr(mb.BATCHER, "_depth", 0)
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["row_count"] == 1
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# R012: logging discipline (ISSUE 8)
def test_r012_detects_print_and_bare_getlogger():
    src = (
        "import logging\n"
        "def work():\n"
        "    print('done')\n"
        "    lg = logging.getLogger('mine')\n"
        "    lg.info('x')\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/fixture_prints.py") if f.rule == "R012"]
    assert len(found) == 2
    assert any("print()" in f.message for f in found)
    assert any("getLogger" in f.message for f in found)


def test_r012_clean_on_structured_logger():
    src = (
        "from h2o3_tpu.utils import log as _log\n"
        "def work():\n"
        "    _log.info('done %s', 1)\n"
        "    _log.get_logger('sub').warning('x')\n")
    assert "R012" not in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/fixture_prints.py"))


def test_r012_exempts_cli_main_modules_and_tests():
    src = "def main():\n    print('usage: ...')\n"
    assert "R012" not in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/analysis/__main__.py"))
    assert "R012" not in _rules_of(engine.analyze_source(
        src, filename="tests/test_fixture.py"))
    # a non-CLI library module IS flagged
    assert "R012" in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/core/fixture.py"))


def test_r012_inline_suppression():
    src = ("def main():\n"
           "    print('report')   # h2o3-ok: R012 CLI output\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/fixture_prints.py") if f.rule == "R012"]
    assert len(found) == 1 and found[0].suppressed


# ---------------------------------------------------------------------------
# R013: timeout-less socket waits (ISSUE 10)
def test_r013_detects_unbounded_socket_waits():
    src = (
        "import socket\n"
        "def serve(port):\n"
        "    srv = socket.socket()\n"
        "    srv.bind(('0.0.0.0', port))\n"
        "    srv.listen(1)\n"
        "    conn, addr = srv.accept()\n"
        "    data = conn.recv(4096)\n"
        "def dial(host):\n"
        "    s = socket.create_connection((host, 80))\n"
        "    s.recv(1)\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/fixture_socks.py") if f.rule == "R013"]
    # srv.accept (local socket, no settimeout), create_connection without
    # timeout=, and s.recv on the connection made here; conn.recv is NOT
    # flagged (conn came from accept, not a tracked ctor — scope limit)
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 3, found
    assert "create_connection" in msgs and ".accept()" in msgs \
        and ".recv()" in msgs


def test_r013_clean_when_bounded():
    src = (
        "import socket\n"
        "def serve(port):\n"
        "    srv = socket.socket()\n"
        "    srv.settimeout(1.0)\n"
        "    conn, addr = srv.accept()\n"
        "def dial(host):\n"
        "    s = socket.create_connection((host, 80), timeout=5.0)\n"
        "    return s.recv(1)\n"
        "def helper(sock):\n"
        "    return sock.recv(64)\n")   # parameter socket: creator owns it
    assert "R013" not in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/fixture_socks.py"))


def test_r013_suppression_and_test_relaxation():
    src = ("import socket\n"
           "def dial(host):\n"
           "    s = socket.create_connection((host, 80))   # h2o3-ok: R013 formation wait is bounded by the caller\n"
           "    s.settimeout(1.0)\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/fixture_socks.py") if f.rule == "R013"]
    assert len(found) == 1 and found[0].suppressed
    # tests are relaxed: loopback fixtures own their own bounds
    assert "R013" not in _rules_of(engine.analyze_source(
        "import socket\ndef t():\n    s = socket.create_connection(('h', 1))\n",
        filename="tests/test_fixture.py"))


def test_r013_package_is_clean():
    """The bug class is fixed in-tree: formation accept, worker connect
    and reconnect all carry deadlines — R013 runs at zero findings."""
    found = [f for f in engine.run(rules=["R013"])
             if not f.suppressed and not f.baselined]
    assert found == [], [str(f) for f in found]


# ---------------------------------------------------------------------------
# R014: unguarded pjit/jit dispatch in serving/ and parallel/ (ISSUE 11)
def test_r014_detects_raw_jit_in_serving_layers():
    src = (
        "import jax\n"
        "from jax.experimental.pjit import pjit\n"
        "def build(fn):\n"
        "    return jax.jit(fn)\n"
        "def build2(fn):\n"
        "    return pjit(fn)\n")
    for path in ("h2o3_tpu/serving/fixture_cache.py",
                 "h2o3_tpu/parallel/fixture_disp.py"):
        found = [f for f in engine.analyze_source(src, filename=path)
                 if f.rule == "R014"]
        assert len(found) == 2, (path, found)
        assert "rendezvous" in found[0].message


def test_r014_detects_unguarded_jit_decorator():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def scorer(x):\n"
        "    return x * 2\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/serving/fixture_deco.py")
        if f.rule == "R014"]
    assert len(found) == 1
    # a guard_collective decorator above it makes the same site clean
    guarded = ("import jax\n"
               "from h2o3_tpu.parallel import compat as _compat\n"
               "@_compat.guard_collective\n"
               "@jax.jit\n"
               "def scorer(x):\n"
               "    return x * 2\n")
    assert "R014" not in _rules_of(engine.analyze_source(
        guarded, filename="h2o3_tpu/serving/fixture_deco.py"))


def test_r014_detects_partial_jit_spelling():
    """@functools.partial(jax.jit, static_argnames=...) — the repo's
    dominant static-args idiom — is a jit-maker too; the jit rides as an
    ARGUMENT of the partial, not the callee."""
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('depth',))\n"
        "def scorer(x, *, depth):\n"
        "    return x * depth\n"
        "def build(fn):\n"
        "    return functools.partial(jax.jit, donate_argnums=(0,))(fn)\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/serving/fixture_partial.py")
        if f.rule == "R014"]
    assert len(found) == 2, found
    # guard-stacked decorator and guard-wrapped call are clean
    clean = (
        "import functools\n"
        "import jax\n"
        "from h2o3_tpu.parallel import compat as _compat\n"
        "@_compat.guard_collective\n"
        "@functools.partial(jax.jit, static_argnames=('depth',))\n"
        "def scorer(x, *, depth):\n"
        "    return x * depth\n"
        "def build(fn):\n"
        "    return _compat.guard_collective(\n"
        "        functools.partial(jax.jit, donate_argnums=(0,))(fn))\n")
    assert "R014" not in _rules_of(engine.analyze_source(
        clean, filename="h2o3_tpu/serving/fixture_partial.py"))


def test_r014_clean_when_routed_through_the_guard():
    src = (
        "import jax\n"
        "from h2o3_tpu.parallel import compat as _compat\n"
        "def build(fn):\n"
        "    return _compat.guard_collective(jax.jit(fn))\n"
        "def build2(fn):\n"
        "    return _compat.guarded_jit(fn, donate_argnums=(0,))\n")
    assert "R014" not in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/serving/fixture_cache.py"))


def test_r014_scope_is_serving_and_parallel_only():
    """Model modules own their guards via guard_collective wrapping at
    module level (ISSUE 10); R014's path scope keeps it surgical."""
    src = "import jax\ndef b(fn):\n    return jax.jit(fn)\n"
    assert "R014" not in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/models/fixture_algo.py"))
    # compat.py defines the guard — its inner jits ARE the guarded impl
    assert "R014" not in _rules_of(engine.analyze_source(
        src, filename="h2o3_tpu/parallel/compat.py"))


def test_r014_suppression():
    src = ("import jax\n"
           "def host_only(fn):\n"
           "    return jax.jit(fn)   # h2o3-ok: R014 host-side scalar probe, no collectives\n")
    found = [f for f in engine.analyze_source(
        src, filename="h2o3_tpu/serving/fixture_cache.py")
        if f.rule == "R014"]
    assert len(found) == 1 and found[0].suppressed


def test_r014_package_is_clean():
    """The mesh-sharded scorer rebuild routed every serving/parallel
    dispatch through the guard funnel — R014 runs at zero findings."""
    found = [f for f in engine.run(rules=["R014"])
             if not f.suppressed and not f.baselined]
    assert found == [], [str(f) for f in found]
