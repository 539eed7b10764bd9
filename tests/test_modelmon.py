"""Model & data drift observability (ISSUE 20).

Covers the training-baseline profile stamped at fit (per-feature
quantile-edge histograms, top-K categoricals, prediction distribution,
npz round trip through DKV), the score_rows serving tap folding live
sketches, PSI/JS drift evaluation and its gauges, the merge's
associativity/commutativity (host count and merge order never change a
drift score bit-for-bit), the cluster merge over the REAL replay
channel with a lagging host absorbed in-deadline, per-model
metric-series hygiene on model churn, the drift SLI kind in the SLO
engine, and the seeded covariate-shift e2e: in-distribution traffic
stays quiet, a shifted stream crosses the threshold, the drift SLO
fires at GET /3/Alerts with a pinned trace, and a hot-swap retrain
makes the generation-skew gauge reflect the new-vs-old delta.
"""

import os
import sys
import threading
import time
import urllib.error

import numpy as np
import pytest

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.deploy import membership as MB
from h2o3_tpu.models import ESTIMATORS
from h2o3_tpu.obs import modelmon, slo, usage
from h2o3_tpu import serving

from test_membership import FakeWorker, _free_port

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "clients", "py"))
from h2o3_client import H2OClient  # noqa: E402

RNG = np.random.default_rng(20)


@pytest.fixture(autouse=True)
def _fresh_modelmon(monkeypatch):
    # background evaluators stay off: tests drive evaluate() explicitly.
    # The tap's duty-cycle throttle and stride cap are disabled so the
    # sketches see every row deterministically (the throttle has its own
    # unit tests below).
    monkeypatch.setenv("H2O3_MODELMON_EVAL_S", "0")
    monkeypatch.setenv("H2O3_MODELMON_TAP_PCT", "100")
    monkeypatch.setenv("H2O3_MODELMON_TAP_ROWS", "0")
    modelmon.reset()
    usage.reset()
    yield
    modelmon.reset()
    usage.reset()
    slo.ENGINE.configure([])


def _train_frame(n=400, seed=7):
    rng = np.random.default_rng(seed)
    return Frame.from_dict(
        {"a": rng.normal(size=n), "b": rng.normal(2, 1, size=n),
         "c": rng.choice(["u", "v", "w"], size=n).tolist(),
         "resp": rng.choice(["no", "yes"], size=n).tolist()})


def _traffic(n=600, seed=11, shift=False):
    rng = np.random.default_rng(seed)
    if shift:
        return Frame.from_dict(
            {"a": rng.normal(6, 1, size=n), "b": rng.normal(-5, 1, size=n),
             "c": rng.choice(["w"], size=n).tolist()})
    return Frame.from_dict(
        {"a": rng.normal(size=n), "b": rng.normal(2, 1, size=n),
         "c": rng.choice(["u", "v", "w"], size=n).tolist()})


def _mk_gbm(model_id=None, seed=1):
    fr = _train_frame()
    m = ESTIMATORS["gbm"](ntrees=3, max_depth=3, seed=seed,
                          model_id=model_id)
    m.train(x=["a", "b", "c"], y="resp", training_frame=fr)
    return fr, m


# Train once per module (GBM fit is ~4s); the autouse reset wipes the
# monitoring state between tests, so the fixture re-installs the baseline
# (a sub-second re-score) to hand every test a freshly-monitored model.
_CACHE: dict = {}


@pytest.fixture()
def gbm(_fresh_modelmon):
    if "m" not in _CACHE:
        _CACHE["m"] = _mk_gbm()
    fr, m = _CACHE["m"]
    if not modelmon.monitored(m.key):
        modelmon.install_baseline(m, fr)
    return m


@pytest.fixture(scope="module", autouse=True)
def _module_cleanup():
    yield
    if "m" in _CACHE:
        fr, m = _CACHE.pop("m")
        DKV.remove(m.key)
        DKV.remove(fr.key)


# ---------------------------------------------------------------------------
# baseline capture at fit
def test_baseline_installed_on_train(gbm):
    assert modelmon.monitored(gbm.key)
    prof = DKV.get(modelmon.monitor_key(gbm.key))
    assert isinstance(prof, modelmon.BaselineProfile)
    di = gbm._dinfo
    assert [f["name"] for f in prof.features] == di.raw_columns()
    kinds = {f["name"]: f["kind"] for f in prof.features}
    assert kinds["a"] == "numeric" and kinds["c"] == "categorical"
    # numeric bins over quantile edges: counts cover every training row
    j = [f["name"] for f in prof.features].index("a")
    assert int(prof.counts[j].sum()) + int(prof.na[j]) == prof.n_rows
    edges = prof.features[j]["edges"]
    assert list(edges) == sorted(edges)
    # categorical top-K + other slot, level names resolved
    jc = [f["name"] for f in prof.features].index("c")
    fc = prof.features[jc]
    assert set(fc["levels"]) <= {"u", "v", "w"}
    assert len(prof.counts[jc]) == len(fc["codes"]) + 1   # + other
    # the binomial GBM's prediction distribution is a class histogram
    assert prof.pred_kind == "class"
    assert int(prof.pred_counts.sum()) == prof.n_rows
    # response distribution rides along for supervised models
    assert prof.resp_counts is not None
    assert int(prof.resp_counts.sum()) == prof.n_rows


def test_baseline_npz_round_trip(gbm):
    prof = DKV.get(modelmon.monitor_key(gbm.key))
    clone = modelmon.BaselineProfile.from_npz_bytes(prof.to_npz_bytes())
    assert clone.n_rows == prof.n_rows
    assert clone.pred_kind == prof.pred_kind
    np.testing.assert_array_equal(clone.pred_counts, prof.pred_counts)
    np.testing.assert_array_equal(clone.na, prof.na)
    for a, b in zip(clone.counts, prof.counts):
        np.testing.assert_array_equal(a, b)
    for fa, fb in zip(clone.features, prof.features):
        assert fa["name"] == fb["name"] and fa["kind"] == fb["kind"]
        if fa["kind"] == "numeric":
            np.testing.assert_allclose(fa["edges"], fb["edges"])
        else:
            assert fa["codes"] == list(fb["codes"])


def test_unmonitored_when_disabled(monkeypatch):
    monkeypatch.setenv("H2O3_MODELMON", "0")
    fr, m = _mk_gbm(seed=3)
    try:
        assert not modelmon.monitored(m.key)
        assert DKV.get(modelmon.monitor_key(m.key)) is None
        serving.score_frame(m, _traffic(64))
        assert modelmon.SCORED.value(model=m.key) == 0.0
    finally:
        DKV.remove(fr.key)
        DKV.remove(m.key)


def test_model_cardinality_cap(monkeypatch, gbm):
    monkeypatch.setenv("H2O3_MODELMON_MAX_MODELS", "1")
    skipped0 = modelmon.SKIPPED.value()
    fr, m = _mk_gbm(seed=4)        # gbm fixture already holds the slot
    try:
        assert not modelmon.monitored(m.key)
        assert modelmon.SKIPPED.value() == skipped0 + 1
    finally:
        DKV.remove(fr.key)
        DKV.remove(m.key)


# ---------------------------------------------------------------------------
# the serving tap + drift evaluation
def test_tap_folds_and_drift_separates(gbm):
    serving.score_frame(gbm, _traffic(600, seed=21))
    assert modelmon.SCORED.value(model=gbm.key) == 600.0
    doc = modelmon.evaluate()[gbm.key]
    assert doc["rows"] == 600
    # in-distribution traffic: every drift score stays under threshold
    assert doc["drift"]["numeric"] < 0.2, doc["drift"]
    assert doc["drift"]["categorical"] < 0.2
    assert doc["prediction_drift"] < 0.05
    assert modelmon.DRIFT.value(model=gbm.key, feature_kind="numeric") \
        == doc["drift"]["numeric"]
    # covariate shift: numeric AND categorical cross decisively
    serving.score_frame(gbm, _traffic(600, seed=22, shift=True))
    doc = modelmon.evaluate()[gbm.key]
    assert doc["drift"]["numeric"] > 0.5, doc["drift"]
    assert doc["drift"]["categorical"] > 0.2
    assert modelmon.PRED_DRIFT.value(model=gbm.key) \
        == doc["prediction_drift"]
    # the pressure dimension reads the evaluation and saturates
    p, detail = modelmon.pressure()
    assert p == 1.0 and detail["worst_model"] == gbm.key
    assert usage.evaluate_pressure()["dimensions"]["drift"] == 1.0


def test_tap_stride_cap_bounds_one_fold(monkeypatch, gbm):
    """Batches above H2O3_MODELMON_TAP_ROWS fold a deterministic stride
    sample — the scored-rows counter still counts every row."""
    monkeypatch.setenv("H2O3_MODELMON_TAP_ROWS", "100")
    serving.score_frame(gbm, _traffic(600, seed=25))
    assert modelmon.SCORED.value(model=gbm.key) == 600.0
    doc = modelmon.evaluate()[gbm.key]
    # ceil(600/100)=6 -> every 6th row -> exactly 100 rows folded
    assert doc["rows"] == 100
    # the sample is still the same distribution: drift stays quiet
    assert doc["drift"]["numeric"] < 0.2


def test_tap_duty_cycle_throttle(monkeypatch, gbm):
    """At a tiny duty-cycle budget the first batch folds and the
    immediate next one lands inside the deferral window — counted, not
    folded. Overhead is bounded by construction."""
    monkeypatch.setenv("H2O3_MODELMON_TAP_PCT", "0.001")
    serving.score_frame(gbm, _traffic(200, seed=26))
    serving.score_frame(gbm, _traffic(200, seed=27))
    assert modelmon.SCORED.value(model=gbm.key) == 400.0
    doc = modelmon.evaluate()[gbm.key]
    assert doc["rows"] == 200 and doc["batches"] == 1


def test_na_rate_drift_tracked(gbm):
    f = _traffic(200, seed=31)
    nas = Frame.from_dict({
        "a": np.where(np.arange(200) % 2 == 0, np.nan,
                      RNG.normal(size=200)),
        "b": RNG.normal(2, 1, size=200),
        "c": RNG.choice(["u", "v", "w"], size=200).tolist()})
    serving.score_frame(gbm, f)
    serving.score_frame(gbm, nas)
    doc = modelmon.evaluate()[gbm.key]
    fa = [x for x in doc["features"] if x["name"] == "a"][0]
    assert fa["na_rate_baseline"] == 0.0
    assert fa["na_rate_live"] == pytest.approx(0.25, abs=0.02)
    assert doc["drift"]["na"] == pytest.approx(0.25, abs=0.02)


# ---------------------------------------------------------------------------
# merge algebra: order and host count never change a drift score
def _synthetic_profile(nbins=8):
    edges = np.linspace(-2.0, 2.0, nbins - 1)
    feats = [{"name": "x", "kind": "numeric", "edges": edges},
             {"name": "g", "kind": "categorical",
              "codes": [0, 1, 2], "card": 5, "levels": ["a", "b", "c"]}]
    counts = [np.full(nbins, 50, np.int64), np.array([40, 30, 20, 10],
                                                     np.int64)]
    return modelmon.BaselineProfile(
        feats, counts, np.array([0, 0], np.int64), "reg",
        np.linspace(0.0, 1.0, nbins - 1), np.full(nbins, 50, np.int64),
        None, nbins * 50)


def test_merge_associative_commutative_property_sweep():
    """Fold the same batches on K simulated hosts, then merge the host
    snapshots in every order and several groupings: the drift scores
    must be IDENTICAL bit-for-bit, because the merge is int64 count
    addition and scoring happens once over the sums."""
    import itertools
    prof = _synthetic_profile()
    rng = np.random.default_rng(99)
    hosts = []
    for h in range(4):
        sk = modelmon.LiveSketch(prof)
        for _ in range(3):
            n = int(rng.integers(5, 60))
            raw = np.column_stack([
                rng.normal(0.5, 1.5, size=n),
                rng.integers(0, 5, size=n).astype(np.float64)])
            raw[rng.random(n) < 0.1, 0] = np.nan
            preds = rng.random(n)
            sk.fold(prof, raw.astype(np.float32), preds, n)
        hosts.append(sk.to_doc())

    def score(docs):
        merged = modelmon.LiveSketch(prof)
        for d in docs:
            merged.merge_doc(d)
        doc = modelmon.drift_from_sketches("m", prof, merged, None, 1)
        return (doc["drift"], doc["prediction_drift"], doc["rows"])

    ref = score(hosts)
    assert ref[2] > 0
    for perm in itertools.permutations(hosts):
        assert score(list(perm)) == ref
    # grouping sweep (associativity): pre-merge subsets into partial
    # sketches, then merge the partials
    for split in (1, 2, 3):
        partial = modelmon.LiveSketch(prof)
        for d in hosts[:split]:
            partial.merge_doc(d)
        rest = modelmon.LiveSketch(prof)
        for d in hosts[split:]:
            rest.merge_doc(d)
        assert score([partial.to_doc(), rest.to_doc()]) == ref
    # shape-mismatched (foreign-generation) docs are rejected wholesale,
    # not partially folded
    bad = {"counts": [[1, 2], [3]], "na": [0, 0], "pred_counts": [1],
           "rows": 7, "batches": 1}
    assert score(hosts + [bad])[:2] == ref[:2]


# ---------------------------------------------------------------------------
# cluster merge over the real replay channel
class _ModelmonWorker(FakeWorker):
    """Answers the `modelmon:{key}` collect op with a canned snapshot —
    what a live worker's _collect_local ships."""

    def __init__(self, port, pid, snap=None):
        self._snap = snap
        super().__init__(port, pid)

    def _answer(self, msg):
        op = str(msg.get("op") or "")
        if op.startswith("modelmon:"):
            return self._snap
        return super()._answer(msg)


@pytest.fixture()
def cluster_env(monkeypatch):
    monkeypatch.setenv("H2O3_CLUSTER_SECRET", "modelmon-test-secret")
    monkeypatch.setenv("H2O3_HEARTBEAT_S", "0")
    monkeypatch.setenv("H2O3_REPLAY_ACK_TIMEOUT_S", "1")
    MB.MEMBERSHIP.reset()
    yield
    MB.MEMBERSHIP.reset()


def test_cluster_merge_with_lagging_host(cluster_env, gbm):
    """Two protocol-faithful workers answer the modelmon collect; a
    third is muted (wedged) and absorbed within the collect deadline:
    the merged report sums the answering hosts' integer counts and the
    drift equals scoring the summed counts — bit-for-bit."""
    serving.score_frame(gbm, _traffic(256, seed=41))
    local = modelmon.snapshot(gbm.key)
    remote1 = dict(local, host=101)
    remote2 = dict(local, host=102)
    port = _free_port()
    out = {}

    def _mk():
        out["bc"] = MB.ElasticBroadcaster(3, port)

    t = threading.Thread(target=_mk, daemon=True)
    t.start()
    workers = [_ModelmonWorker(port, 1, snap=remote1),
               _ModelmonWorker(port, 2, snap=remote2),
               _ModelmonWorker(port, 3, snap=None)]
    t.join(timeout=15)
    assert not t.is_alive() and "bc" in out
    bc = out["bc"]
    try:
        workers[2].muted = True
        t0 = time.monotonic()
        remote = bc.collect(f"modelmon:{gbm.key}", timeout=2.0)
        elapsed = time.monotonic() - t0
    finally:
        bc.close()
        for w in workers:
            w.kill()
    assert len(remote) == 3
    answered = [r for r in remote if isinstance(r, dict)]
    assert len(answered) == 2          # the muted host's slot is None
    assert elapsed < 10.0              # absorbed in-deadline, not hung
    rep = modelmon.merged_report(gbm.key, [local] + answered)
    assert rep["monitored"]
    assert rep["rows"] == 3 * 256      # local + two remote copies
    assert {101, 102} <= {h["host"] for h in rep["hosts"]}
    # bit-for-bit: the cluster merge must equal folding the same three
    # count docs into one sketch locally and scoring the sums once
    prof = DKV.get(modelmon.monitor_key(gbm.key))
    summed = modelmon.LiveSketch(prof)
    for s in (local, remote1, remote2):
        summed.merge_doc(s["live"])
    ref = modelmon.drift_from_sketches(gbm.key, prof, summed, None, 1)
    assert rep["drift"] == ref["drift"]
    assert rep["prediction_drift"] == ref["prediction_drift"]


# ---------------------------------------------------------------------------
# per-model metric-series hygiene on churn
def _model_series(metric, key):
    return [e for e in metric._json()
            if (e["labels"] or {}).get("model") == key]


def test_series_hygiene_on_model_churn():
    """Train → score → delete, three times over: every {model=…} series
    (drift gauges, scored-rows counter, usage device-seconds counter,
    ledger rows) must be removed exactly once per delete — the registry
    must not accumulate dead series across churn."""
    from h2o3_tpu.obs import metrics as om
    deleted = []
    for i in range(3):
        fr, m = _mk_gbm(seed=50 + i)
        deleted.append(m.key)
        serving.score_frame(m, _traffic(128, seed=60 + i))
        modelmon.evaluate()
        assert _model_series(modelmon.DRIFT, m.key)
        assert _model_series(modelmon.SCORED, m.key)
        assert _model_series(usage.MODEL_DEVICE_SECONDS, m.key)
        assert any(r["model"] == m.key
                   for r in usage.usage_snapshot()["ledger"])
        DKV.remove(m.key)
        DKV.remove(fr.key)
        for metric in (modelmon.DRIFT, modelmon.PRED_DRIFT,
                       modelmon.GEN_SKEW, modelmon.SCORED,
                       usage.MODEL_DEVICE_SECONDS):
            assert not _model_series(metric, m.key), metric.name
        assert not any(r["model"] == m.key
                       for r in usage.usage_snapshot()["ledger"])
        assert DKV.get(modelmon.monitor_key(m.key)) is None
        # forget() is idempotent: the second call is a no-op
        assert modelmon.forget(m.key) is False
    # the exposition as a whole carries no dead model series
    text = om.REGISTRY.prometheus_text()
    for key in deleted:
        assert f'model="{key}"' not in text


def test_counter_remove_drops_one_series():
    from h2o3_tpu.obs import metrics as om
    c = om.Counter("t_counter")
    c.inc(3, model="m1", kind="score")
    c.inc(5, model="m2", kind="score")
    c.remove(model="m1", kind="score")
    assert c.value(model="m1", kind="score") == 0.0
    assert c.value(model="m2", kind="score") == 5.0
    c.remove(model="nope")                 # absent series: no-op


# ---------------------------------------------------------------------------
# the drift SLI kind
def test_drift_slo_spec_parsing():
    s = slo.SLOSpec({"name": "drift-all", "kind": "drift",
                     "objective": 0.9})
    assert s.metric == "h2o3_model_drift"
    assert s.threshold == 0.2
    assert s.to_dict()["kind"] == "drift"
    lat = slo.SLOSpec({"name": "lat", "objective": 0.99,
                       "threshold_ms": 250})
    assert lat.to_dict()["kind"] == "latency"
    assert lat.threshold is None
    with pytest.raises(ValueError):
        slo.SLOSpec({"name": "x", "kind": "latency99", "objective": 0.9})


def test_drift_totals_tick_against_gauge():
    from h2o3_tpu.obs import metrics as om
    reg = om.MetricsRegistry()
    g = reg.gauge("h2o3_model_drift", "t")  # h2o3-ok: R005 isolated
    # registry standing in for the process gauge — the engine under test
    # resolves the metric by name
    g.set(0.5, model="hot", feature_kind="numeric")
    g.set(0.01, model="hot", feature_kind="na")
    g.set(0.01, model="cold", feature_kind="numeric")
    eng = slo.SLOEngine(
        [slo.SLOSpec({"name": "d", "kind": "drift", "objective": 0.5,
                      "model": "^hot$"})], registry=reg)
    spec = eng.specs()[0]
    assert eng._totals(spec) == (2, 1)     # cold filtered by model regex
    assert eng._totals(spec) == (4, 2)     # cumulative, monotone
    g.set(0.05, model="hot", feature_kind="numeric")
    assert eng._totals(spec) == (6, 2)     # recovered: ticks stay good


# ---------------------------------------------------------------------------
# the seeded covariate-shift e2e (acceptance criteria)
def test_covariate_shift_fires_drift_slo_and_generation_skew():
    from h2o3_tpu.api.server import H2OServer
    fr, m = _mk_gbm(model_id="drift_e2e_gbm")
    old_model = m
    s = H2OServer(port=0).start()
    try:
        c = H2OClient(f"http://127.0.0.1:{s.port}")
        # phase 1: in-distribution traffic — near-zero drift
        serving.score_frame(m, _traffic(600, seed=71))
        modelmon.evaluate()
        assert modelmon.DRIFT.value(model=m.key,
                                    feature_kind="numeric") < 0.2
        doc = c.model_monitor(m.key)
        assert doc["__meta"]["schema_type"] == "ModelMonitorV3"
        assert doc["monitored"] and doc["rows"] == 600
        assert doc["drift"]["numeric"] < 0.2
        # phase 2: covariate-shifted stream crosses the threshold
        serving.score_frame(m, _traffic(600, seed=72, shift=True))
        modelmon.evaluate()
        assert modelmon.DRIFT.value(model=m.key,
                                    feature_kind="numeric") > 0.5
        # phase 3: the drift SLO fires at GET /3/Alerts with a pinned
        # trace — history pre-ticked through the engine's sample ring
        slo.ENGINE.configure([slo.SLOSpec(
            {"name": "model-drift", "kind": "drift", "objective": 0.9,
             "model": "^drift_e2e_gbm$", "threshold": 0.2,
             "windows": [[2, 4, 2.0]]})])
        now = time.time()
        for dt in (10, 8, 6, 4, 2):
            slo.ENGINE.evaluate(now=now - dt)
        body = c.alerts()
        firing = [a for a in body["alerts"] if a["slo"] == "model-drift"]
        assert firing and firing[0]["firing"], body
        tid = firing[0]["trace"]
        assert tid
        trace = c.get(f"/3/Trace/{tid}")
        spans = [sp for sp in trace["spans"]
                 if sp.get("name") == "slo.alert"]
        assert spans, "alert episode trace not pinned"
        assert spans[0]["attrs"]["slo"] == "model-drift"
        # the drift dimension reaches /3/CloudHealth
        health = c.get("/3/CloudHealth")
        assert health["dimensions"]["drift"] == 1.0
        # phase 4: hot-swap retrain rotates generations; the previous
        # generation's sketch is retained and traffic still scoring the
        # OLD model object shadow-folds into it
        fr2, m2 = _mk_gbm(model_id="drift_e2e_gbm", seed=5)
        assert modelmon.monitored(m2.key)
        serving.score_frame(m2, _traffic(400, seed=73))        # new gen
        serving.score_frame(old_model, _traffic(400, seed=73))  # shadow
        docs = modelmon.evaluate()
        skew = docs[m2.key]["generation_skew"]
        assert skew is not None
        assert modelmon.GEN_SKEW.value(model=m2.key) == skew
        mon = c.model_monitor(m2.key)
        assert mon["generation"] == 2
        assert mon["rows"] == 400 and mon["prev_rows"] >= 400
        # fresh generation against in-distribution traffic: low drift
        assert mon["drift"]["numeric"] < 0.2
        DKV.remove(fr2.key)
    finally:
        s.stop()
        DKV.remove(m.key)
        DKV.remove(fr.key)


def test_model_monitor_unknown_model_404():
    from h2o3_tpu.api.server import H2OServer
    s = H2OServer(port=0).start()
    try:
        c = H2OClient(f"http://127.0.0.1:{s.port}")
        with pytest.raises(urllib.error.HTTPError) as ei:
            c.model_monitor("no_such_model")
        assert ei.value.code == 404
    finally:
        s.stop()
