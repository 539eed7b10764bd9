"""Usage attribution & capacity observability (ISSUE 16).

Covers the device-time ledger behind GET /3/Usage (dispatch-funnel
attribution to (principal, model, kind), cardinality folds), the
per-request Server-Timing stage waterfall (stages sum to the measured
wall, the Python client parses the header), the /3/CloudHealth pressure
document (a seeded queue flood raises it, recovery drops it), and the
cluster merge of both over the REAL replay channel — protocol-faithful
fake workers answering the `usage` collect op."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.deploy import membership as MB
from h2o3_tpu.models import ESTIMATORS
from h2o3_tpu.obs import tracing, usage
from h2o3_tpu.serving import qos
from h2o3_tpu import serving

from test_membership import FakeWorker, _free_port

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "clients", "py"))
from h2o3_client import H2OClient, _parse_server_timing  # noqa: E402

RNG = np.random.default_rng(16)


@pytest.fixture(autouse=True)
def _fresh_usage():
    qos.reset()
    usage.reset()
    yield
    qos.reset()
    usage.reset()


def _mk_glm():
    fr = Frame.from_dict(
        {"a": RNG.normal(size=240), "b": RNG.normal(size=240),
         "resp": RNG.choice(["no", "yes"], size=240)})
    m = ESTIMATORS["glm"](family="binomial")
    m.train(x=["a", "b"], y="resp", training_frame=fr)
    return fr, m


@pytest.fixture(scope="module")
def glm_model():
    fr, m = _mk_glm()
    yield m
    DKV.remove(fr.key)
    DKV.remove(m.key)


ROW = [{"a": 0.1, "b": 0.2}]


# ---------------------------------------------------------------------------
# the ledger: charge/meter semantics
def test_meter_charges_principal_model_kind():
    with tracing.request_context("alice"):
        with usage.meter("score", model="m_test", rows=4):
            with usage.meter("jit"):    # nested: outermost owns the wall
                time.sleep(0.01)
    snap = usage.usage_snapshot()
    assert len(snap["ledger"]) == 1, snap["ledger"]
    row = snap["ledger"][0]
    assert row["principal"] == "alice"
    assert row["model"] == "m_test"
    assert row["kind"] == "score"
    assert row["rows"] == 4 and row["calls"] == 1
    assert row["device_seconds"] >= 0.01
    assert snap["device_seconds_total"] == row["device_seconds"]
    # outside any request context the charge lands on `anonymous`
    with usage.meter("jit"):
        pass
    principals = {r["principal"] for r in usage.usage_snapshot()["ledger"]}
    assert principals == {"alice", "anonymous"}


def test_device_rate_nonzero_under_sustained_charging():
    """Regression: charges arriving <50ms apart coalesce into the newest
    rate sample in place; the retained sample's timestamp must not
    advance, or the ring degenerates to one ever-fresh sample and
    device_rate reads 0 exactly when the host is busiest."""
    t_end = time.monotonic() + 0.2
    while time.monotonic() < t_end:
        usage.charge("score", 0.001)
        time.sleep(0.002)
    assert usage.device_seconds_total() > 0.0
    assert usage.device_rate(window_s=1.0) > 0.0


def test_ledger_disabled_is_free(monkeypatch):
    monkeypatch.setenv("H2O3_USAGE", "0")
    with usage.meter("score", model="m", rows=1):
        time.sleep(0.001)
    usage.begin_request()
    with usage.stage("decode"):
        pass
    assert usage.finish_request(0.5) is None
    assert usage.device_seconds_total() == 0.0
    assert usage.usage_snapshot()["ledger"] == []


def test_principal_cardinality_fold(monkeypatch):
    """Past H2O3_QOS_MAX_PRINCIPALS the ledger reuses the QoS overflow
    fold — hostile principal churn cannot mint unbounded series."""
    monkeypatch.setenv("H2O3_QOS_MAX_PRINCIPALS", "2")
    qos.reset()
    for i in range(6):
        usage.charge("score", 0.01, model="m", principal=f"tenant_{i}")
    principals = {r["principal"] for r in usage.usage_snapshot()["ledger"]}
    assert principals == {"tenant_0", "tenant_1", qos.OVERFLOW}
    folded = [r for r in usage.usage_snapshot()["ledger"]
              if r["principal"] == qos.OVERFLOW]
    assert len(folded) == 1
    assert folded[0]["device_seconds"] == pytest.approx(0.04)


def test_model_cardinality_fold(monkeypatch):
    monkeypatch.setenv("H2O3_USAGE_MAX_MODELS", "3")
    for i in range(8):
        usage.charge("score", 0.001, model=f"model_{i}")
    models = {r["model"] for r in usage.usage_snapshot()["ledger"]}
    assert usage.OTHER_MODEL in models
    assert len(models) <= 4          # 3 named + the fold


# ---------------------------------------------------------------------------
# attribution correctness under concurrent 2-tenant load
def test_two_tenant_concurrent_split(glm_model):
    """Two tenants score concurrently at a 3:1 request rate; the ledger
    must split the device seconds in proportion to dispatched rows (the
    micro-batch key carries the principal, so tenants never share a
    coalesced dispatch and every chunk charges exactly one tenant)."""
    serving.score_payload(glm_model, ROW)      # warm: compile off the clock
    usage.reset()
    n_a, n_b = 24, 8

    def run(principal, n):
        with tracing.request_context(principal):
            for _ in range(n):
                serving.score_payload(glm_model, ROW)

    ta = threading.Thread(target=run, args=("alice", n_a))
    tb = threading.Thread(target=run, args=("bob", n_b))
    ta.start(); tb.start()
    ta.join(timeout=120); tb.join(timeout=120)
    assert not ta.is_alive() and not tb.is_alive()

    per_s, per_rows = {}, {}
    snap = usage.usage_snapshot()
    for r in snap["ledger"]:
        if r["kind"] != "score":
            continue
        per_s[r["principal"]] = \
            per_s.get(r["principal"], 0.0) + r["device_seconds"]
        per_rows[r["principal"]] = per_rows.get(r["principal"], 0) + r["rows"]
    # every dispatched row is attributed to the tenant that sent it
    assert per_rows == {"alice": n_a, "bob": n_b}
    assert per_s["alice"] > 0.0 and per_s["bob"] > 0.0
    # device seconds follow the 3:1 row split (wide slack: scheduler
    # jitter on small dispatches, but the ordering must be decisive)
    ratio = per_s["alice"] / per_s["bob"]
    assert 1.3 <= ratio <= 8.0, (ratio, per_s)
    # internal consistency: the ledger rows sum to the cumulative total
    assert sum(r["device_seconds"] for r in snap["ledger"]) == \
        pytest.approx(usage.device_seconds_total(), abs=1e-6)


# ---------------------------------------------------------------------------
# per-request latency decomposition
def test_stage_recorder_folds_remainder_into_app():
    usage.begin_request()
    usage.add_stage("decode", 0.010)
    usage.add_stage("device", 0.030)
    st = usage.finish_request(wall=0.050)
    assert st["decode"] == pytest.approx(0.010)
    assert st["device"] == pytest.approx(0.030)
    assert st["app"] == pytest.approx(0.010)        # the remainder
    assert sum(st.values()) == pytest.approx(0.050)
    hdr = usage.server_timing(st)
    # waterfall order, milliseconds on the wire
    assert hdr == "decode;dur=10.000, device;dur=30.000, app;dur=10.000"
    assert _parse_server_timing(hdr) == {
        "decode": pytest.approx(0.010), "device": pytest.approx(0.030),
        "app": pytest.approx(0.010)}


def test_parse_server_timing_tolerates_junk():
    parsed = _parse_server_timing(
        "edge;dur=1.5, junk, cache;desc=hit, device;desc=x;dur=10,;dur=3")
    assert parsed == {"edge": pytest.approx(0.0015),
                      "device": pytest.approx(0.010)}


def test_server_timing_sums_to_wall(glm_model):
    """A traced REST scoring request's Server-Timing stages must sum to
    within 10% of the request's measured wall time (the app stage folds
    in whatever no other stage claimed, so the server-side sum is exact;
    the client-side slack covers loopback + urllib overhead)."""
    import json
    import urllib.request
    from h2o3_tpu.api.server import H2OServer
    s = H2OServer(port=0).start()
    try:
        c = H2OClient(f"http://127.0.0.1:{s.port}")
        rows = [{"a": float(i) / 97.0, "b": 0.2} for i in range(2048)]
        path = f"/3/Predictions/models/{glm_model.key}"
        c.post(path, rows=rows)                 # warm: compile off the clock
        st = dict(c.last_timings)
        assert st, "Server-Timing header missing"
        assert set(st) <= set(usage.STAGE_ORDER), st
        assert "device" in st and "decode" in st and "queue" in st
        # measured pass: prebuilt body, bare urlopen — the wall is the
        # request round trip, not the client's JSON encode/decode
        body = json.dumps({"rows": rows}).encode()
        url = f"http://127.0.0.1:{s.port}{path}"
        best = None
        for _ in range(5):
            req = urllib.request.Request(
                url, data=body, method="POST",
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
                hdr = r.headers.get("Server-Timing")
            wall = time.perf_counter() - t0
            st = _parse_server_timing(hdr)
            err = abs(sum(st.values()) - wall) / wall
            best = err if best is None else min(best, err)
            if best <= 0.10:
                break
        assert best <= 0.10, (best, st, wall)
    finally:
        s.stop()


def test_usage_endpoint_reports_rest_scoring(glm_model):
    from h2o3_tpu.api.server import H2OServer
    s = H2OServer(port=0).start()
    try:
        c = H2OClient(f"http://127.0.0.1:{s.port}")
        c.post(f"/3/Predictions/models/{glm_model.key}", rows=ROW)
        doc = c.get("/3/Usage")
        assert doc["__meta"]["schema_type"] == "UsageV3"
        assert doc["device_seconds_total"] > 0.0
        scored = [r for r in doc["ledger"]
                  if r["kind"] == "score" and r["model"] == glm_model.key]
        assert scored and scored[0]["principal"] == "anonymous"
        assert scored[0]["rows"] >= 1
        # ledger is sorted by device seconds, biggest spender first
        costs = [r["device_seconds"] for r in doc["ledger"]]
        assert costs == sorted(costs, reverse=True)
        assert glm_model.key in doc["hbm"]["params_by_model"]
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# /3/CloudHealth: the pressure signal
def test_cloudhealth_rises_under_flood_and_recovers(glm_model,
                                                    monkeypatch):
    """Seeded overload: with the micro-batch queue driven to its depth
    bound the queue pressure dimension saturates (→ the HPA-shaped
    overall follows); restoring the queue recovers the signal."""
    from h2o3_tpu.api.server import H2OServer
    from h2o3_tpu.serving import microbatch as mb
    s = H2OServer(port=0).start()
    try:
        c = H2OClient(f"http://127.0.0.1:{s.port}")
        calm = c.get("/3/CloudHealth")
        assert calm["__meta"]["schema_type"] == "CloudHealthV3"
        assert calm["dimensions"]["queue"] <= 0.1
        assert calm["overall"] == pytest.approx(
            max(calm["dimensions"].values()), abs=1e-4)
        limit = mb._queue_depth_limit()
        monkeypatch.setattr(mb.BATCHER, "_depth", limit)
        hot = c.get("/3/CloudHealth")
        assert hot["dimensions"]["queue"] >= 0.99
        assert hot["overall"] >= 0.99
        monkeypatch.setattr(mb.BATCHER, "_depth", 0)
        cool = c.get("/3/CloudHealth")
        assert cool["dimensions"]["queue"] <= 0.1
        # the gauge feed mirrors the LAST evaluation (cached, lock-free)
        series = dict()
        for lbl, v in usage._pressure_series():
            series[lbl["dimension"]] = v
        assert series["queue"] <= 0.1
        assert "overall" in series
    finally:
        s.stop()


def test_pressure_queue_dimension_direct(monkeypatch):
    """evaluate_pressure() without a server: per-tenant share pressure
    counts too — one tenant holding its whole queue share saturates the
    queue dimension even when the global depth is low."""
    from h2o3_tpu.serving import microbatch as mb
    limit = mb._queue_depth_limit()
    share = qos.tenant_share_cap(limit)
    monkeypatch.setattr(mb.BATCHER, "_depth", 2)
    monkeypatch.setattr(mb.BATCHER, "_queued", {"flood": share})
    doc = usage.evaluate_pressure()
    assert doc["dimensions"]["queue"] >= 0.99
    assert doc["detail"]["queue"]["by_principal"] == {"flood": share}
    assert usage.last_pressure() is doc


# ---------------------------------------------------------------------------
# cluster merge through the real replay channel
class _UsageWorker(FakeWorker):
    """Protocol-faithful fake worker that answers the `usage` and
    `cloudhealth` collect ops with canned snapshots — what a live
    worker's _collect_local returns."""

    def __init__(self, port, pid, snapshot=None, pressure=None):
        self._snapshot = snapshot
        self._pressure = pressure
        super().__init__(port, pid)

    def _answer(self, msg):
        if msg.get("op") == "usage":
            return self._snapshot
        if msg.get("op") == "cloudhealth":
            return self._pressure
        return super()._answer(msg)


@pytest.fixture()
def cluster_env(monkeypatch):
    monkeypatch.setenv("H2O3_CLUSTER_SECRET", "usage-test-secret")
    monkeypatch.setenv("H2O3_HEARTBEAT_S", "0")
    monkeypatch.setenv("H2O3_REPLAY_ACK_TIMEOUT_S", "1")
    MB.MEMBERSHIP.reset()
    yield
    MB.MEMBERSHIP.reset()


def _worker_snap(host, seconds, model="remote_model"):
    return {"host": host, "device_seconds_total": seconds,
            "ledger": [{"principal": "alice", "model": model,
                        "kind": "score", "device_seconds": seconds,
                        "calls": 3, "rows": 30}],
            "hbm": {"params_by_model": {model: 1024},
                    "params_total_bytes": 1024,
                    "tier": {"faults": 0}}}


def test_cluster_usage_merge_over_replay_channel(cluster_env):
    """GET /3/Usage on a formed cloud: the coordinator's broadcaster
    collects every worker's snapshot over the real framed channel and
    the merge sums ledgers and HBM maps across hosts."""
    usage.charge("score", 1.0, model="local_model", principal="alice")
    port = _free_port()
    out = {}

    def _mk():
        out["bc"] = MB.ElasticBroadcaster(2, port)

    t = threading.Thread(target=_mk, daemon=True)
    t.start()
    workers = [_UsageWorker(port, 1, snapshot=_worker_snap("w1", 2.0)),
               _UsageWorker(port, 2, snapshot=_worker_snap("w2", 3.0))]
    t.join(timeout=15)
    assert not t.is_alive() and "bc" in out
    bc = out["bc"]
    try:
        remote = bc.collect("usage", timeout=5.0)
        assert len(remote) == 2
        merged = usage.merge_usage([usage.usage_snapshot()] + remote)
    finally:
        bc.close()
        for w in workers:
            w.kill()
    assert len(merged["hosts"]) == 3
    assert {"w1", "w2"} <= set(merged["hosts"])
    assert merged["device_seconds_total"] == pytest.approx(6.0)
    # same (principal, model, kind) across hosts sums into one row
    alice = [r for r in merged["ledger"]
             if r["principal"] == "alice" and r["model"] == "remote_model"]
    assert len(alice) == 1
    assert alice[0]["device_seconds"] == pytest.approx(5.0)
    assert alice[0]["calls"] == 6 and alice[0]["rows"] == 60
    assert merged["ledger"][0]["device_seconds"] == pytest.approx(5.0)
    assert merged["hbm"]["params_by_model"]["remote_model"] == 2048
    # the coordinator's own tier stats ride along with the workers'
    assert {"w1", "w2"} <= set(merged["hbm"]["tier_by_host"])


def test_cloudhealth_merge_is_max_per_dimension():
    """Pressure is a weakest-link signal: the cloud doc takes each
    dimension's max across hosts, and overall tracks the merged max."""
    a = {"host": "h0", "epoch": 3, "overall": 0.2,
         "dimensions": {"queue": 0.2, "utilization": 0.1}, "detail": {}}
    b = {"host": "h1", "epoch": 4, "overall": 0.9,
         "dimensions": {"queue": 0.05, "utilization": 0.9,
                        "stalls": 1.0}, "detail": {}}
    merged = usage.merge_cloudhealth([a, b, None, "lagging"])
    assert merged["dimensions"] == {"queue": 0.2, "utilization": 0.9,
                                    "stalls": 1.0}
    assert merged["overall"] == pytest.approx(1.0)
    assert merged["epoch"] == 4
    assert [h["host"] for h in merged["hosts"]] == ["h0", "h1"]
