"""Observability subsystem (h2o3_tpu/obs): metrics registry semantics,
Prometheus exposition, span timeline nesting/bounds, and the /metrics +
/3/Timeline + /3/WaterMeter REST surface fed by a real model build."""

import json
import re
import time
import urllib.parse
import urllib.request

import jax
import numpy as np
import pytest

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.obs.metrics import (MetricsRegistry, REGISTRY)
from h2o3_tpu.obs.timeline import SpanTimeline, SPANS, span


# ---------------------------------------------------------------------------
# registry semantics
def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    c.inc(1, algo="gbm")
    assert c.value() == 3.5
    assert c.value(algo="gbm") == 1
    assert c.value(algo="drf") == 0
    with pytest.raises(ValueError):
        c.inc(-1)
    # re-registration returns the same object; kind conflicts raise
    assert reg.counter("c_total") is c
    with pytest.raises(TypeError):
        reg.gauge("c_total")


def test_gauge_semantics():
    reg = MetricsRegistry()
    g = reg.gauge("g", "a gauge")
    g.set(5.0, host="0")
    g.set(7.0, host="0")          # set overwrites
    g.inc(1.0, host="1")
    assert g.value(host="0") == 7.0
    assert g.value(host="1") == 1.0
    # callback gauge evaluated at scrape time
    state = {"v": 1.0}
    cb = reg.gauge("g_cb", fn=lambda: state["v"])
    assert cb.value() == 1.0
    state["v"] = 42.0
    assert cb.value() == 42.0
    # a raising callback yields no series, not a scrape error
    bad = reg.gauge("g_bad", fn=lambda: 1 / 0)
    assert bad._expose() == []
    assert "g_bad" in reg.prometheus_text()


def test_histogram_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", "latencies", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(55.55)
    # per-bucket (non-cumulative) internal counts: one observation each
    assert snap["counts"] == [1, 1, 1, 1]
    with h.time():
        time.sleep(0.01)
    assert h.snapshot()["count"] == 5


def test_prometheus_exposition_parses():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3, route="/3/Frames")
    reg.gauge("hbm_bytes").set(2 ** 20, device="0")
    hist = reg.histogram("lat_seconds", buckets=(0.5, 1.0))
    hist.observe(0.2)
    hist.observe(2.0)
    text = reg.prometheus_text()
    # exposition-format invariants: HELP/TYPE pairs, sample lines match
    # the grammar, histogram buckets are cumulative and end at +Inf
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.e+]+(inf)?$',
        re.IGNORECASE)
    seen_types = {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            seen_types[name] = kind
        elif not line.startswith("#"):
            assert sample_re.match(line), line
    assert seen_types["req_total"] == "counter"
    assert seen_types["hbm_bytes"] == "gauge"
    assert seen_types["lat_seconds"] == "histogram"
    assert 'req_total{route="/3/Frames"} 3' in text
    buckets = [l for l in text.splitlines()
               if l.startswith("lat_seconds_bucket")]
    assert [b.split()[-1] for b in buckets] == ["1", "1", "2"]  # cumulative
    assert buckets[-1].startswith('lat_seconds_bucket{le="+Inf"}')
    assert "lat_seconds_count 2" in text
    # label values with quotes/backslashes/newlines are escaped
    reg.counter("esc_total").inc(1, k='a"b\\c\nd')
    assert 'k="a\\"b\\\\c\\nd"' in reg.prometheus_text()


def test_registry_json_exposition():
    reg = MetricsRegistry()
    reg.counter("c_total").inc(2, algo="glm")
    d = reg.to_dict()
    assert d["c_total"]["kind"] == "counter"
    assert d["c_total"]["series"] == [
        {"labels": {"algo": "glm"}, "value": 2.0}]


# ---------------------------------------------------------------------------
# span timeline
def test_span_nesting_and_ring_bounds():
    tl = SpanTimeline(capacity=8)
    with_span = tl.begin("outer", job="j1")
    inner = tl.begin("inner")
    assert inner.parent_id == with_span.span_id
    tl.end(inner)
    tl.end(with_span)
    snap = tl.snapshot()
    assert [s["name"] for s in snap] == ["inner", "outer"]  # end order
    assert snap[0]["parent"] == snap[1]["id"]
    assert snap[1]["parent"] == 0
    assert snap[0]["duration_ms"] >= 0
    # ring stays bounded
    for i in range(20):
        tl.end(tl.begin(f"s{i}"))
    assert len(tl.snapshot()) == 8
    assert tl.snapshot(limit=3)[-1]["name"] == "s19"


def test_span_context_manager_records_attrs():
    before = len(SPANS.snapshot())
    with span("t.outer", a=1):
        with span("t.inner") as sp:
            assert SPANS.current() is sp
    snap = SPANS.snapshot()
    # the ring is bounded: late in a long suite it may already be at
    # capacity, where appends evict instead of growing
    assert len(snap) == min(before + 2, SPANS.capacity)
    inner, outer = snap[-2], snap[-1]
    assert inner["name"] == "t.inner" and outer["name"] == "t.outer"
    assert inner["parent"] == outer["id"]
    assert outer["attrs"] == {"a": 1}


def test_span_survives_exceptions():
    with pytest.raises(RuntimeError):
        with span("t.fail"):
            raise RuntimeError("boom")
    assert SPANS.snapshot()[-1]["name"] == "t.fail"
    assert SPANS.current() is None


def test_xprof_bridge_is_env_gated(monkeypatch, tmp_path):
    # without both env vars no capture starts and attrs stay clean
    monkeypatch.delenv("H2O3_OBS_TRACE_DIR", raising=False)
    monkeypatch.delenv("H2O3_OBS_TRACE_SPAN", raising=False)
    with span("gbm.histogram") as sp:
        pass
    assert "xprof" not in sp.attrs
    # dir set but name prefix not matching → still no capture
    monkeypatch.setenv("H2O3_OBS_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("H2O3_OBS_TRACE_SPAN", "glm.")
    with span("gbm.histogram") as sp:
        pass
    assert "xprof" not in sp.attrs


def test_worker_collect_snapshot():
    """deploy/multihost worker side of the /3/Timeline cloud merge."""
    from h2o3_tpu.deploy.multihost import _collect_local
    with span("t.collect"):
        pass
    out = _collect_local("timeline")
    assert out["host"] == 0
    assert any(s["name"] == "t.collect" for s in out["spans"])
    m = _collect_local("metrics")
    assert "h2o3_dkv_objects" in m["metrics"]
    assert _collect_local("nonsense") is None


# ---------------------------------------------------------------------------
# REST surface
@pytest.fixture(scope="module")
def server():
    from h2o3_tpu.api.server import H2OServer
    s = H2OServer(port=0).start()
    yield s
    s.stop()


def _get_raw(s, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{s.port}{path}") as r:
        return r.read(), r.headers.get("Content-Type", "")


def _get(s, path):
    return json.loads(_get_raw(s, path)[0])


def _post(s, path, **data):
    body = urllib.parse.urlencode(data).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{s.port}{path}",
                                 data=body, method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _wait(s, key, timeout=120):
    for _ in range(timeout * 10):
        j = _get(s, f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            return j
        time.sleep(0.1)
    raise TimeoutError


@pytest.fixture(scope="module")
def gbm_via_rest(server):
    """One GBM fit through the REST API; everything below asserts on the
    telemetry it left behind."""
    rng = np.random.default_rng(7)
    n = 200
    Frame.from_dict({"x1": rng.normal(size=n), "x2": rng.normal(size=n),
                     "y": rng.normal(size=n)}, "obs_train")
    r = _post(server, "/3/ModelBuilders/gbm", training_frame="obs_train",
              response_column="y", ntrees=3, max_depth=3,
              histogram_type="UniformAdaptive", model_id="obs_gbm")
    j = _wait(server, r["job"]["key"])
    assert j["status"] == "DONE", j
    yield j
    for k in ("obs_train", "obs_gbm"):
        DKV.remove(k)


def test_metrics_endpoint_prometheus(server, gbm_via_rest):
    body, ctype = _get_raw(server, "/metrics")
    assert ctype.startswith("text/plain")
    text = body.decode()
    # at least one populated counter, gauge and histogram from the fit
    m = re.search(r'^h2o3_gbm_row_trees_total\{engine="adaptive"\} (\d+)$',
                  text, re.M)
    assert m and int(m.group(1)) >= 3 * 200, "rows*trees counter"
    m = re.search(r'^h2o3_dkv_objects\{what="keys"\} (\d+)$', text, re.M)
    assert m and int(m.group(1)) >= 1, "dkv gauge"
    # the REST fit itself: the model-build POST and the job polls
    counts = [int(v) for v in re.findall(
        r'^h2o3_rest_request_seconds_count\{[^}]*\} (\d+)$', text, re.M)]
    assert counts and sum(counts) >= 2, "request histogram"


def test_timeline_endpoint_spans_and_nesting(server, gbm_via_rest):
    tl = _get(server, "/3/Timeline")
    spans = tl["spans"]
    assert spans, "no spans recorded"
    byid = {s["id"]: s for s in spans}
    grows = [s for s in spans if s["name"] == "tree.grow"]
    levels = [s for s in spans if s["name"] == "tree.level"]
    assert len(grows) >= 3 and len(levels) >= 9
    assert all(s["duration_ms"] > 0 for s in grows)
    # correct parent/child nesting: each level's parent is a tree.grow
    # span whose time window contains it
    for lv in levels:
        parent = byid.get(lv["parent"])
        assert parent is not None and parent["name"] == "tree.grow"
        assert parent["start"] <= lv["start"] and lv["end"] <= parent["end"]
    # cloud shape: single host here, but the merged-host envelope exists
    assert tl["hosts"][0]["n_spans"] == len(spans)


def test_jobs_phase_timings(server, gbm_via_rest):
    jobs = _get(server, "/3/Jobs")["jobs"]
    phased = [j for j in jobs if j.get("phases", {}).get("grow")]
    assert phased, "no job carries phase timings"
    ph = phased[0]["phases"]
    assert ph["grow"] > 0
    assert ph["grow"] <= phased[0]["msec"] + 1


def test_watermeter_json(server, gbm_via_rest):
    wm = _get(server, "/3/WaterMeter")["metrics"]
    assert wm["h2o3_gbm_row_trees_total"]["kind"] == "counter"
    series = wm["h2o3_gbm_row_trees_total"]["series"]
    assert any(s["value"] > 0 for s in series)
    assert "h2o3_device_memory_bytes" in wm


def test_parse_counters_populate():
    import os
    import tempfile
    from h2o3_tpu.io import parser as P
    before = P.PARSE_BYTES.value(type="CSV")
    fd, path = tempfile.mkstemp(suffix=".csv")
    try:
        with os.fdopen(fd, "w") as fh:
            fh.write("a,b\n")
            for i in range(50):
                fh.write(f"{i},{i * 2}\n")
        f = P.import_file(path, destination_frame="obs_parse")
        sz = os.path.getsize(path)
    finally:
        os.unlink(path)
    assert P.PARSE_BYTES.value(type="CSV") == before + sz
    names = [s["name"] for s in SPANS.snapshot()]
    assert "parse.file" in names
    DKV.remove(f.key)


def test_glm_irlsm_spans():
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator, \
        _IRLSM_ITERS
    rng = np.random.default_rng(3)
    n = 120
    x = rng.normal(size=n)
    yb = (rng.random(n) < 1 / (1 + np.exp(-x))).astype(float)
    f = Frame.from_dict({
        "x": x, "y": np.array(["n", "p"], object)[yb.astype(int)]})
    before = _IRLSM_ITERS.value()
    m = H2OGeneralizedLinearEstimator(family="binomial", max_iterations=5)
    m.train(y="y", training_frame=f)
    assert _IRLSM_ITERS.value() > before
    names = [s["name"] for s in SPANS.snapshot()]
    assert "glm.irlsm" in names
    DKV.remove(f.key)
    DKV.remove(m.key)


# ---------------------------------------------------------------------------
# spans inside predict() and train(), one clock with the profiler (ISSUE 27)
@pytest.fixture(scope="module")
def small_gbm():
    """A small binned GBM and the spans and job its train() left."""
    from h2o3_tpu.core.jobs import jobs_list
    from h2o3_tpu.models import H2OGradientBoostingEstimator
    rng = np.random.default_rng(11)
    n = 400
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    f = Frame.from_dict({
        "x1": x1, "x2": x2,
        "y": np.array(["n", "p"], object)[(x1 + x2 > 0).astype(int)]})
    # the second train() of the process is the one read: in the first,
    # one-off compiles of eager ops between the phases weigh seconds
    for _ in range(2):
        SPANS.clear()
        m = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=5,
                                         model_id="obs_small_gbm")
        m.train(y="y", training_frame=f)
    spans = SPANS.snapshot()
    job = [j for j in jobs_list() if j["dest"] == m.key][-1]
    yield m, f, spans, job
    DKV.remove(f.key)
    DKV.remove(m.key)


def _predict_rows(path):
    return REGISTRY.get("h2o3_predict_rows_total").value(algo="gbm",
                                                         path=path)


@pytest.mark.parametrize("path,children", [
    ("frame", ["predict.matrix", "predict.dispatch", "predict.wait",
               "predict.frame"]),
    ("bucket", ["predict.frame"])])
def test_predict_leaves_one_root_span_with_its_stages(
        small_gbm, monkeypatch, path, children):
    m, f, _, _ = small_gbm
    if path == "frame":      # the large-frame walk, at a test's size
        monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    other = "bucket" if path == "frame" else "frame"
    rows0, other0 = _predict_rows(path), _predict_rows(other)
    calls0 = REGISTRY.get("h2o3_predict_calls_total").value(algo="gbm",
                                                            path=path)
    # where the prediction frame's columns are made: the large-frame path
    # leaves the scores on the device, the bucket path answers on the host
    where = "device" if path == "frame" else "host"
    made = REGISTRY.get("h2o3_predict_frame_columns_total")
    made0 = {w: made.value(algo="gbm", columns=w) for w in ("device", "host")}
    SPANS.clear()
    pred = m.predict(f)
    spans = SPANS.snapshot()
    planes = [type(v.data) for v in pred.vecs]
    DKV.remove(pred.key)
    roots = [s for s in spans if s["name"] == "predict"]
    assert len(roots) == 1
    root = roots[0]
    assert root["parent"] == 0
    # a tree model's root also says what the walk matches: the level rows
    # of its categorical columns and its SET-split nodes (none here)
    assert root["attrs"] == {"model": m.key, "algo": "gbm", "frame": f.key,
                             "rows": f.nrows, "cols": f.ncols, "path": path,
                             "cat_levels": 0, "set_nodes": 0}
    kids = [s for s in spans if s["parent"] == root["id"]
            and s["name"].startswith("predict.")]
    assert [s["name"] for s in kids] == children
    assert all(s["name"].startswith(("predict.", "scorer."))
               for s in spans if s["parent"] == root["id"])
    assert sum(s["duration_ms"] for s in kids) <= root["duration_ms"]
    assert all(root["start"] <= s["start"] and s["end"] <= root["end"]
               for s in kids)
    # nothing of frame size crosses the host link on the large-frame path
    assert not [s for s in spans
                if s["name"] in ("predict.fetch", "mrtask.host_fetch")]
    assert len(planes) == 3 and all(issubclass(t, jax.Array) for t in planes)
    assert kids[-1]["attrs"] == {"cols": 3,    # predict, pn, pp
                                 "columns": where}
    assert {w: made.value(algo="gbm", columns=w) for w in made0} == \
        {**made0, where: made0[where] + 1}
    assert _predict_rows(path) == rows0 + f.nrows
    assert _predict_rows(other) == other0
    assert REGISTRY.get("h2o3_predict_calls_total").value(
        algo="gbm", path=path) == calls0 + 1


def test_a_predict_override_gets_the_root_span_only():
    from h2o3_tpu.models import H2OKMeansEstimator
    from h2o3_tpu.models.model import ModelBase
    assert H2OKMeansEstimator.predict is not ModelBase.predict
    rng = np.random.default_rng(2)
    f = Frame.from_dict({"a": rng.normal(size=60), "b": rng.normal(size=60)})
    m = H2OKMeansEstimator(k=2, seed=1)
    m.train(training_frame=f)
    SPANS.clear()
    pred = m.predict(f)
    spans = SPANS.snapshot()
    roots = [s for s in spans if s["name"] == "predict"]
    assert len(roots) == 1 and roots[0]["attrs"]["algo"] == "kmeans"
    assert roots[0]["attrs"]["rows"] == 60
    assert not [s for s in spans if s["name"] == "predict.frame"]
    for k in (pred.key, f.key, m.key):
        DKV.remove(k)


def test_train_phases_cover_the_job_and_setup_waits_for_binning(small_gbm):
    _, _, spans, job = small_gbm
    ph = job["phases"]
    assert {"setup", "grow", "score", "finish", "metrics"} <= set(ph)
    run = next(s for s in spans if s["name"] == "job.run")
    assert sum(ph.values()) >= 0.90 * run["duration_ms"]
    assert sum(ph.values()) <= run["duration_ms"] + 1
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert ph["setup"] >= by["gbm.bin.codes"][0]["duration_ms"]
    # setup holds both binning spans; each chunk its build and its wait
    setup = by["job.setup"][0]
    for name in ("gbm.bin.spec", "gbm.bin.codes"):
        assert by[name][0]["parent"] == setup["id"]
    chunks = {s["id"] for s in by["gbm.chunk"]}
    for name in ("gbm.chunk.build", "gbm.chunk.wait"):
        assert len(by[name]) == len(chunks)
        assert {s["parent"] for s in by[name]} == chunks
    # the metrics walk and the publish have a name of their own
    assert by["job.metrics"][0]["parent"] == run["id"]
    assert by["model.publish"][0]["start"] >= run["end"]


def test_span_without_a_capture_initialises_no_backend():
    """span() enters a profiler TraceAnnotation: with no capture running
    that must neither raise nor touch a device — the REST edge opens spans
    before a cloud is formed, and a process that has not chosen its
    platform yet must not have it chosen by a span."""
    import subprocess
    import sys
    code = ("from h2o3_tpu.obs.timeline import SPANS, span\n"
            "with span('t.cold', a=1):\n"
            "    pass\n"
            "assert SPANS.snapshot()[-1]['name'] == 't.cold'\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_span_is_an_event_of_the_host_plane_under_a_capture(tmp_path):
    """One clock: under ANY jax.profiler capture the program's spans lie
    in the same .xplane.pb as the device ops."""
    import glob
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t0 = time.time()
        with span("t.on_the_host_plane"):
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    found = [(pl.name, e.duration_ns) for pl in pd.planes for ln in pl.lines
             for e in ln.events if e.name == "t.on_the_host_plane"]
    assert len(found) == 1, [pl.name for pl in pd.planes]
    plane, dur_ns = found[0]
    assert "host" in plane.lower() and not plane.startswith("/device:")
    # by name: other threads of the suite append to the ring too
    ring, = [s for s in SPANS.snapshot()
             if s["name"] == "t.on_the_host_plane" and s["start"] >= t0]
    # the event and the ring's span time the same block: they differ by
    # what the host does between their clock reads, a share of the span
    assert ring["duration_ms"] >= 50.0
    assert abs(dur_ns / 1e6 - ring["duration_ms"]) \
        < 0.25 * ring["duration_ms"]
