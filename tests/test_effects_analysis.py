"""Effect-lattice analyzer (R018–R021), the PROTOCOL.md census, SARIF
emission, content-hash fingerprints, and the wall-time budget.

Mirrors tests/test_analysis_v2.py: each rule (a) fires on a seeded
defect reproducing its bug class, (b) stays quiet on the sanctioned fix
shape, and (c) reports zero unsuppressed findings over the real
package + tests tree."""

import ast
import json
import os
import subprocess
import sys
import time

from h2o3_tpu.analysis import engine

REPO = engine.repo_root()
BASELINE = os.path.join(REPO, "analysis_baseline.json")


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# R018 — coordinator-only mutation through replay-exempt routes.
# The exempt set is EXTRACTED from the fixture's own predicate (the
# `_is_static_path` shape server.py uses), never hand-listed in the rule.
R018_SEED = {
    "h2o3_tpu/fx18/srv.py": (
        "import re\n"
        "from h2o3_tpu.core.kvstore import DKV\n"
        "def _is_static_path(path):\n"
        "    return path.startswith('/flow') or path == '/ping'\n"
        "def _h_flow_asset(req):\n"
        "    DKV.put('asset_meta', req)\n"
        "def _h_models(req):\n"
        "    DKV.put('m', req)\n"
        "ROUTES = [\n"
        "    (re.compile(r'/flow/index\\.html'), 'GET', _h_flow_asset),\n"
        "    (re.compile(r'/3/Models'), 'GET', _h_models),\n"
        "]\n"),
}


def test_r018_flags_exempt_route_mutating_replicated_state():
    found = [f for f in engine.analyze_sources(R018_SEED)
             if f.rule == "R018"]
    assert len(found) == 1, [str(f) for f in found]
    # the static-asset handler is flagged; the broadcast route is not
    assert found[0].line == 5
    assert "replay-EXEMPT" in found[0].message
    assert "DKV.put()" in found[0].message
    assert "forking" in found[0].message


def test_r018_reaches_through_helper_calls():
    srcs = {
        "h2o3_tpu/fx18b/store.py": (
            "from h2o3_tpu.core.kvstore import DKV\n"
            "def stash(key, v):\n"
            "    DKV.put(key, v)\n"),
        "h2o3_tpu/fx18b/srv.py": (
            "import re\n"
            "from h2o3_tpu.fx18b.store import stash\n"
            "def _is_obs_path(path):\n"
            "    return path in ('/metrics', '/3/Timeline')\n"
            "def _h_metrics(req):\n"
            "    stash('scrape', req)\n"
            "ROUTES = [(re.compile(r'/metrics'), 'GET', _h_metrics)]\n"),
    }
    found = [f for f in engine.analyze_sources(srcs) if f.rule == "R018"]
    assert len(found) == 1
    assert found[0].file == "h2o3_tpu/fx18b/srv.py"


def test_r018_clean_when_route_is_broadcast():
    srcs = {"h2o3_tpu/fx18c/srv.py": R018_SEED[
        "h2o3_tpu/fx18/srv.py"].replace(
        "(re.compile(r'/flow/index\\.html'), 'GET', _h_flow_asset),\n",
        "(re.compile(r'/3/Assets'), 'POST', _h_flow_asset),\n")}
    assert "R018" not in _rules_of(engine.analyze_sources(srcs))


def test_r018_suppression_and_test_relaxation():
    srcs = {"h2o3_tpu/fx18d/srv.py": R018_SEED[
        "h2o3_tpu/fx18/srv.py"].replace(
        "def _h_flow_asset(req):\n",
        "# h2o3-ok: R018 fixture: coordinator-owned asset metadata\n"
        "def _h_flow_asset(req):\n")}
    found = [f for f in engine.analyze_sources(srcs) if f.rule == "R018"]
    assert len(found) == 1 and found[0].suppressed
    relaxed = {"tests/test_fx18.py": R018_SEED["h2o3_tpu/fx18/srv.py"]}
    assert "R018" not in _rules_of(engine.analyze_sources(relaxed))


def test_r018_package_is_clean():
    found = engine.unsuppressed(engine.run(rules=["R018"]))
    assert found == [], [str(f) for f in found]


# ---------------------------------------------------------------------------
# R019 — host-divergence sources feeding replicated state,
# INTERPROCEDURALLY: the source call lives a module away.
R019_SEED = {
    "h2o3_tpu/fx19/ident.py": (
        "import os\n"
        "def node_tag():\n"
        "    return 'node-%d' % os.getpid()\n"),
    "h2o3_tpu/fx19/bcast.py": (
        "from h2o3_tpu.fx19.ident import node_tag\n"
        "class FixtureBroadcaster:\n"
        "    def __init__(self):\n"
        "        self._state = {}\n"
        "    def handle(self, req):\n"
        "        self._state[req['k']] = node_tag()\n"),
}


def test_r019_interprocedural_pid_through_helper_module():
    found = [f for f in engine.analyze_sources(R019_SEED)
             if f.rule == "R019"]
    assert len(found) == 1, [str(f) for f in found]
    assert found[0].file == "h2o3_tpu/fx19/bcast.py"
    assert "node_tag" in found[0].message
    assert "os.getpid" in found[0].message
    assert "OWN host identity" in found[0].message


def test_r019_direct_hostname_store():
    src = (
        "import socket\n"
        "class FixtureBroadcaster:\n"
        "    def __init__(self):\n"
        "        self._state = {}\n"
        "    def handle(self, req):\n"
        "        self._state['host'] = socket.gethostname()\n")
    found = [f for f in engine.analyze_source(
        src, "h2o3_tpu/fx19b.py") if f.rule == "R019"]
    assert len(found) == 1 and "socket.gethostname()" in found[0].message


def test_r019_environ_read_is_divergence_but_census_accessor_is_not():
    dirty = (
        "import os\n"
        "class FixtureBroadcaster:\n"
        "    def __init__(self):\n"
        "        self._state = {}\n"
        "    def handle(self, req):\n"
        "        self._state['r'] = os.environ.get('SOME_ROLE')\n")
    found = [f for f in engine.analyze_source(
        dirty, "h2o3_tpu/fx19c.py") if f.rule == "R019"]
    assert len(found) == 1
    clean = dirty.replace(
        "import os\n", "from h2o3_tpu.utils.env import env_str\n").replace(
        "os.environ.get('SOME_ROLE')", "env_str('H2O3_ROLE', '')")
    assert "R019" not in _rules_of(engine.analyze_source(
        clean, "h2o3_tpu/fx19d.py"))


def test_r019_host_local_sinks_are_not_flagged():
    # per-host telemetry keeping its own pid is the POINT of obs/
    srcs = {"h2o3_tpu/obs/fx19e.py": (
        "import os\n"
        "class FixtureBroadcaster:\n"
        "    def __init__(self):\n"
        "        self._state = {}\n"
        "    def handle(self, req):\n"
        "        self._state['pid'] = os.getpid()\n")}
    assert "R019" not in _rules_of(engine.analyze_sources(srcs))


def test_r019_suppression_and_test_relaxation():
    srcs = dict(R019_SEED)
    srcs["h2o3_tpu/fx19/bcast.py"] = srcs["h2o3_tpu/fx19/bcast.py"].replace(
        "        self._state[req['k']] = node_tag()\n",
        "        # h2o3-ok: R019 fixture: per-host diagnostic tag\n"
        "        self._state[req['k']] = node_tag()\n")
    found = [f for f in engine.analyze_sources(srcs) if f.rule == "R019"]
    assert len(found) == 1 and found[0].suppressed
    relaxed = {"tests/fx19/ident.py": R019_SEED["h2o3_tpu/fx19/ident.py"],
               "tests/fx19/bcast.py": R019_SEED["h2o3_tpu/fx19/bcast.py"]}
    assert "R019" not in _rules_of(engine.analyze_sources(relaxed))


def test_r019_package_is_clean():
    found = engine.unsuppressed(engine.run(rules=["R019"]))
    assert found == [], [str(f) for f in found]


# ---------------------------------------------------------------------------
# R020 — replay-channel protocol drift
R020_SEED = {
    "h2o3_tpu/fx20/chan.py": (
        "def poll(bc):\n"
        "    bc.collect('metricz')\n"
        "    bc.collect('ping')\n"
        "def _collect_local(op):\n"
        "    if op == 'ping':\n"
        "        return 1\n"
        "    if op == 'stats':\n"
        "        return 2\n"
        "    return {'error': 'unknown'}\n"),
}


def test_r020_flags_unhandled_send_and_dead_handler_arm():
    found = sorted([f for f in engine.analyze_sources(R020_SEED)
                    if f.rule == "R020"], key=lambda f: f.line)
    assert len(found) == 2, [str(f) for f in found]
    assert "'metricz'" in found[0].message
    assert "no worker-side handler arm" in found[0].message
    assert "'stats'" in found[1].message
    assert "dead protocol" in found[1].message


def test_r020_prefix_families_and_variable_ops_pair():
    srcs = {"h2o3_tpu/fx20b/chan.py": (
        "import json\n"
        "def poll(bc, tid, q):\n"
        "    bc.collect(f'trace:{tid}')\n"
        "    op = 'logs:search:' + json.dumps(q)\n"
        "    bc.collect(op)\n"
        "def _collect_local(op):\n"
        "    if op.startswith(('trace:', 'logs:search:')):\n"
        "        return 1\n"
        "    return {'error': 'unknown'}\n")}
    assert "R020" not in _rules_of(engine.analyze_sources(srcs))


def test_r020_scoped_run_with_one_endpoint_stays_quiet():
    srcs = {"h2o3_tpu/fx20c/send_only.py": (
        "def poll(bc):\n"
        "    bc.collect('orphan_op')\n")}
    assert "R020" not in _rules_of(engine.analyze_sources(srcs))


def test_r020_package_is_clean():
    found = engine.unsuppressed(engine.run(rules=["R020"]))
    assert found == [], [str(f) for f in found]


def test_protocol_census_is_committed_and_current():
    from h2o3_tpu.analysis import rules_protocol
    mods = engine.load_modules([engine.package_root()])
    want = rules_protocol.census_markdown(mods)
    path = os.path.join(engine.package_root(), "deploy", "PROTOCOL.md")
    assert os.path.exists(path), \
        "run: python -m h2o3_tpu.analysis --write-census"
    with open(path, encoding="utf-8") as fh:
        have = fh.read()
    assert have == want, \
        "stale protocol census — run: python -m h2o3_tpu.analysis " \
        "--write-census"
    # the census knows the live protocol surface
    for op in ("`ping`", "`leave`", "`trace:`", "`metrics`"):
        assert op in have, op


def test_check_census_gates_protocol_md():
    path = os.path.join(engine.package_root(), "deploy", "PROTOCOL.md")
    with open(path, encoding="utf-8") as fh:
        committed = fh.read()
    try:
        with open(path, "a", encoding="utf-8") as fh:
            fh.write("\nstale marker\n")
        out = subprocess.run(
            [sys.executable, "-m", "h2o3_tpu.analysis",
             "--check-census", "--rules", "R020"],
            capture_output=True, text=True, cwd=REPO, timeout=300)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "stale protocol census" in out.stderr
    finally:
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(committed)


# ---------------------------------------------------------------------------
# R021 — npz wire-format pairing
R021_SEED = (
    "import numpy as np\n"
    "def save(path, d, m):\n"
    "    np.savez(path, data=d, mask=m)\n"
    "def load(path):\n"
    "    z = np.load(path)\n"
    "    return z['data'], z['extra']\n")


def test_r021_flags_phantom_read_and_orphan_write():
    found = sorted([f for f in engine.analyze_source(
        R021_SEED, "h2o3_tpu/fx21.py") if f.rule == "R021"],
        key=lambda f: f.line)
    assert len(found) == 2, [str(f) for f in found]
    assert "'mask'" in found[0].message and "no reader" in found[0].message
    assert "'extra'" in found[1].message and "no writer" in found[1].message


def test_r021_membership_guard_and_dict_payload_pair_clean():
    src = (
        "import numpy as np\n"
        "def save(path, d, m):\n"
        "    arrays = {'data': d}\n"
        "    arrays['mask'] = m\n"
        "    np.savez(path, **arrays)\n"
        "def load(path):\n"
        "    z = np.load(path)\n"
        "    m = z['mask'] if 'mask' in z.files else None\n"
        "    return z['data'], m\n")
    assert "R021" not in _rules_of(engine.analyze_source(
        src, "h2o3_tpu/fx21b.py"))


def test_r021_dynamic_keys_make_the_format_open():
    src = (
        "import numpy as np\n"
        "def save(path, cols):\n"
        "    np.savez(path, **{f'd{i}': c for i, c in enumerate(cols)})\n"
        "def load(path, j):\n"
        "    z = np.load(path)\n"
        "    return z[f'd{j}']\n")
    assert "R021" not in _rules_of(engine.analyze_source(
        src, "h2o3_tpu/fx21c.py"))


def test_r021_suppression_and_test_relaxation():
    src = R021_SEED.replace(
        "    return z['data'], z['extra']\n",
        "    # h2o3-ok: R021 fixture: forward-compat probe\n"
        "    return z['data'], z['extra']\n")
    found = [f for f in engine.analyze_source(
        src, "h2o3_tpu/fx21d.py") if f.rule == "R021"]
    # the guarded read is waived; the orphan 'mask' write still fires
    assert any(f.suppressed and "'extra'" in f.message for f in found)
    assert "R021" not in _rules_of(engine.analyze_source(
        R021_SEED, "tests/test_fx21.py"))


def test_r021_package_is_clean():
    found = engine.unsuppressed(engine.run(rules=["R021"]))
    assert found == [], [str(f) for f in found]


# ---------------------------------------------------------------------------
# content-hash fingerprints: line drift must not dirty baselines/censuses
def test_finding_fingerprints_survive_whitespace_shift():
    base = [f for f in engine.analyze_sources(R019_SEED)
            if f.rule == "R019"]
    shifted = {rel: "\n\n\n" + src.replace(
        "def handle(self, req):", "def handle(self, req):  ")
        for rel, src in R019_SEED.items()}
    moved = [f for f in engine.analyze_sources(shifted)
             if f.rule == "R019"]
    assert len(base) == len(moved) == 1
    assert base[0].line != moved[0].line          # the line DID move
    assert base[0].fingerprint == moved[0].fingerprint


def _mods_from(sources: dict):
    mods = []
    for rel, src in sources.items():
        m = engine.Module(rel, rel, src, ast.parse(src, filename=rel))
        m.lines = src.splitlines()
        mods.append(m)
    return mods


def test_census_rows_are_line_free_under_whitespace_shift():
    """A pure line-shift upstream of a declaration leaves every committed
    census byte-identical — the review-noise class this PR kills."""
    from h2o3_tpu.analysis import (rules_env, rules_metrics,
                                   rules_protocol, rules_spans)
    srcs = {
        "h2o3_tpu/fxc/m.py": (
            "from h2o3_tpu.obs.metrics import counter\n"
            "from h2o3_tpu.obs.timeline import span\n"
            "from h2o3_tpu.utils.env import env_int\n"
            "C = counter('h2o3_fxc_total', 'fixture counter')\n"
            "N = env_int('H2O3_FXC_N', 4)\n"
            "def work(bc):\n"
            "    with span('fxc.work'):\n"
            "        bc.collect('ping')\n"
            "def _collect_local(op):\n"
            "    if op == 'ping':\n"
            "        return 1\n"),
    }
    shifted = {rel: "# leading comment\n\n\n" + src
               for rel, src in srcs.items()}
    for census in (rules_metrics.census_markdown,
                   rules_spans.census_markdown,
                   rules_env.census_markdown,
                   rules_protocol.census_markdown):
        a = census(_mods_from(srcs))
        b = census(_mods_from(shifted))
        assert a == b, census.__module__


# ---------------------------------------------------------------------------
# SARIF 2.1.0 emission
def test_sarif_golden_file():
    from h2o3_tpu.analysis import sarif
    f1 = engine.Finding("R019", "h2o3_tpu/deploy/fx.py", 12,
                        "seeded message one")
    f1.snippet = "self._state['k'] = os.getpid()"
    f2 = engine.Finding("R021", "h2o3_tpu/io/fx.py", 30,
                        "seeded message two", suppressed=True)
    f2.snippet = "z['extra']"
    f3 = engine.Finding("R005", "h2o3_tpu/obs/fx.py", 7,
                        "seeded message three")
    f3.snippet = "counter(name)"
    f3.baselined = True
    got = json.dumps(sarif.to_sarif([f1, f2, f3]), indent=2,
                     sort_keys=True) + "\n"
    golden = os.path.join(os.path.dirname(__file__), "data",
                          "sarif_golden.json")
    with open(golden, encoding="utf-8") as fh:
        want = fh.read()
    assert got == want, \
        "SARIF output drifted from tests/data/sarif_golden.json"


def test_sarif_covers_every_rule_and_tracks_fingerprints():
    from h2o3_tpu.analysis import sarif
    assert set(sarif.RULE_SUMMARIES) == \
        {f"R{i:03d}" for i in range(1, 26)}
    f = engine.Finding("R018", "h2o3_tpu/x.py", 3, "m")
    f.snippet = "DKV.put('k', v)"
    log = sarif.to_sarif([f])
    res = log["runs"][0]["results"][0]
    assert res["partialFingerprints"]["h2o3ContentHash/v1"] == \
        f.fingerprint
    assert res["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 3


def test_sarif_cli_writes_file(tmp_path):
    seed = tmp_path / "h2o3_tpu" / "fx_sarif.py"
    seed.parent.mkdir()
    seed.write_text(
        "import numpy as np\n"
        "def save(p, d):\n"
        "    np.savez(p, data=d)\n"
        "def load(p):\n"
        "    z = np.load(p)\n"
        "    return z['other']\n")
    out_path = tmp_path / "out.sarif"
    out = subprocess.run(
        [sys.executable, "-m", "h2o3_tpu.analysis", str(seed),
         "--rules", "R021", "--sarif", str(out_path)],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert out.returncode == 1, out.stdout + out.stderr
    log = json.loads(out_path.read_text())
    assert log["version"] == "2.1.0"
    results = log["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == {"R021"}


# ---------------------------------------------------------------------------
# per-rule self-timing + the wall-time budget
def test_json_reports_per_rule_timings():
    out = subprocess.run(
        [sys.executable, "-m", "h2o3_tpu.analysis",
         os.path.join(engine.package_root(), "deploy"), "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    payload = json.loads(out.stdout)
    t = payload["rule_timings_s"]
    for key in ("callgraph:index", "effects:closure", "R018", "R019",
                "R020", "R021", "lifecycle:index", "R022+R024", "R023",
                "R025"):
        assert key in t and t[key] >= 0, (key, sorted(t))


def test_json_reports_per_rule_finding_counts():
    """--json carries a by_rule histogram next to rule_timings_s, so a
    CI trend line can watch per-rule volume without re-parsing the
    findings array."""
    seed = ("import jax\n"
            "def hot(x):\n"
            "    return jax.jit(lambda a: a + 1)(x)\n")
    fixture = os.path.join(REPO, "h2o3_tpu", "_fx_by_rule_tmp.py")
    try:
        with open(fixture, "w", encoding="utf-8") as fh:
            fh.write(seed)
        out = subprocess.run(
            [sys.executable, "-m", "h2o3_tpu.analysis", fixture,
             "--rules", "R001", "--json"],
            capture_output=True, text=True, cwd=REPO, timeout=300)
        payload = json.loads(out.stdout)
        assert payload["by_rule"].get("R001", 0) >= 1
        assert sum(payload["by_rule"].values()) == payload["total"]
    finally:
        os.unlink(fixture)


def test_full_package_wall_time_budget():
    """All 25 rules over the package stay under 2x the pre-effects
    analyzer baseline (~5.3s full-package) — the effect rules ride the
    ONE interprocedural index, and the lifecycle rules (R022-R025) build
    their exception-edge CFGs lazily per flagged-candidate function
    behind terminal-name prefilters, so the CFG pass adds ~1s, not a
    second whole-tree walk. The budget is on the analyzer's OWN CPU
    time (it runs in this one thread): wall-clock under six xdist workers
    measures the neighbours. A second reading only if the first is over —
    contention inflates CPU time too (shared caches), a regression
    inflates both readings."""
    budget, spent = 10.6, []
    while len(spent) < 2 and min(spent, default=budget) >= budget:
        t0 = time.thread_time()
        engine.run(paths=[engine.package_root()], baseline_path=BASELINE)
        spent.append(time.thread_time() - t0)
    assert min(spent) < budget, \
        f"analyzer took {min(spent):.1f}s of CPU (budget {budget}s): {spent}"


# ---------------------------------------------------------------------------
# the PR gate: everything at zero unsuppressed over package + tests
def test_package_and_tests_zero_unsuppressed_for_effect_rules():
    findings = engine.run(paths=[engine.package_root(),
                                 engine.tests_root()],
                          baseline_path=BASELINE,
                          rules=["R018", "R019", "R020", "R021"])
    bad = engine.unsuppressed(findings)
    assert not bad, "\n".join(str(f) for f in bad)
