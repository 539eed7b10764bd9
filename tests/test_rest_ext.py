"""Extended REST surface + bindings codegen tests (RequestServer long-tail
routes: diagnostics, frame munging, artifacts, validation, codegen)."""

import importlib.util
import json
import sys
import urllib.request
import urllib.parse

import numpy as np
import pytest

from h2o3_tpu.api.server import H2OServer, ROUTES
from h2o3_tpu.core.frame import Frame
from h2o3_tpu.core.kvstore import DKV


@pytest.fixture(scope="module")
def server():
    s = H2OServer(port=0).start()
    yield s
    s.stop()


def _get(s, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{s.port}{path}") as r:
        return json.loads(r.read())


def _get_raw(s, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{s.port}{path}") as r:
        return r.read()


def _post(s, path, **data):
    body = urllib.parse.urlencode(data).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{s.port}{path}",
                                 data=body, method="POST")
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read())


def _wait(s, key, timeout=60):
    import time
    for _ in range(timeout * 10):
        j = _get(s, f"/3/Jobs/{key}")["jobs"][0]
        if j["status"] in ("DONE", "FAILED", "CANCELLED"):
            return j
        time.sleep(0.1)
    raise TimeoutError


def test_route_count_at_least_60(server):
    assert len(ROUTES) >= 60, len(ROUTES)
    eps = _get(server, "/3/Metadata/endpoints")
    assert eps["num_routes"] >= 60


def test_diagnostics_routes(server):
    assert _get(server, "/3/Ping")["cloud_healthy"]
    caps = _get(server, "/3/Capabilities")["capabilities"]
    assert any(c["name"] == "Algos" for c in caps)
    js = _get(server, "/3/JStack")["traces"]
    # cluster schema: one entry per node, each with its thread dump
    assert js and js[0]["node"].startswith("h2o3-")
    assert any("h2o3-rest" in t["name"] for t in js[0]["thread_traces"])
    nt = _get(server, "/3/NetworkTest")
    assert nt["results"] and nt["results"][0]["micros"] > 0
    _post(server, "/3/LogAndEcho", message="hello from test")
    _post(server, "/3/GarbageCollect")


def test_create_split_missing_download(server):
    r = _post(server, "/3/CreateFrame", rows=200, cols=5, seed=42,
              categorical_fraction=0.2, missing_fraction=0.0,
              dest="cf_test")
    _wait(server, r["job"]["key"])
    fr = _get(server, "/3/Frames/cf_test")["frames"][0]
    assert fr["rows"] == 200 and fr["column_count"] == 5

    r = _post(server, "/3/SplitFrame", dataset="cf_test",
              ratios="[0.7]",
              destination_frames='["cf_tr", "cf_te"]', seed=1)
    tr = _get(server, "/3/Frames/cf_tr")["frames"][0]
    te = _get(server, "/3/Frames/cf_te")["frames"][0]
    assert tr["rows"] + te["rows"] == 200
    assert abs(tr["rows"] - 140) < 30            # ~70/30 split

    _post(server, "/3/MissingInserter", dataset="cf_tr", fraction=0.2,
          seed=1)
    tr2 = _get(server, "/3/Frames/cf_tr")["frames"][0]
    assert sum(c["missing_count"] for c in tr2["columns"]) > 0

    csv = _get_raw(server, "/3/DownloadDataset?frame_id=cf_te")
    lines = csv.decode().strip().split("\n")
    assert len(lines) == te["rows"] + 1          # header + rows


def test_interaction_route(server):
    a = np.array(["x", "y"], object)[
        np.random.default_rng(0).integers(0, 2, 100)]
    b = np.array(["u", "v"], object)[
        np.random.default_rng(1).integers(0, 2, 100)]
    Frame.from_dict({"a": a, "b": b}, key="inter_src")
    r = _post(server, "/3/Interaction", source_frame="inter_src",
              factor_columns='["a", "b"]', dest="inter_out")
    _wait(server, r["job"]["key"])
    out = _get(server, "/3/Frames/inter_out")["frames"][0]
    assert out["rows"] == 100
    assert set(out["columns"][0]["domain"]) <= {"x_u", "x_v", "y_u", "y_v"}


def test_builder_info_and_validation(server):
    info = _get(server, "/3/ModelBuilders/gbm")["model_builders"]["gbm"]
    pnames = {p["name"] for p in info["parameters"]}
    assert {"ntrees", "max_depth", "learn_rate"} <= pnames

    ok = _post(server, "/3/ModelBuilders/gbm/parameters",
               ntrees="10", max_depth="3")
    assert ok["error_count"] == 0
    bad = _post(server, "/3/ModelBuilders/gbm/parameters",
                ntrees="10", not_a_param="1", training_frame="missing_f")
    assert bad["error_count"] == 2
    fields = {m["field_name"] for m in bad["messages"]}
    assert {"not_a_param", "training_frame"} <= fields


@pytest.fixture(scope="module")
def small_model(server):
    rng = np.random.default_rng(0)
    X = rng.normal(0, 1, (200, 3))
    y = (X[:, 0] > 0).astype(int)
    cols = {f"x{j}": X[:, j] for j in range(3)}
    cols["y"] = np.array(["n", "p"], object)[y]
    Frame.from_dict(cols, key="ext_train")
    r = _post(server, "/3/ModelBuilders/gbm", training_frame="ext_train",
              response_column="y", ntrees="3", max_depth="3",
              model_id="ext_gbm", seed="7")
    j = _wait(server, r["job"]["key"])
    assert j["status"] == "DONE", j
    return "ext_gbm"


def test_tree_and_artifact_routes(server, small_model):
    t = _get(server, f"/3/Tree?model={small_model}&tree_number=0")
    assert len(t["thresholds"]) == len(t["predictions"])
    assert any(c >= 0 for c in t["left_children"])

    mojo = _get_raw(server, f"/3/Models/{small_model}/mojo")
    assert mojo[:2] == b"PK"                     # a genuine zip

    pojo = _get_raw(server, f"/3/Models.java/{small_model}")
    assert b"class" in pojo and b"score0" in pojo


def test_a_three_class_models_trees_by_class_and_its_artifacts(server):
    """A K-class GBM holds ONE ensemble; the tree route and the artifact
    writers read a class at a time through its host view (`_trees_k`)."""
    rng = np.random.default_rng(1)
    X = rng.normal(0, 1, (300, 3))
    y = np.digitize(X[:, 0] + 0.3 * X[:, 1], [-0.5, 0.5])
    cols = {f"x{j}": X[:, j] for j in range(3)}
    cols["y"] = np.array(["a", "b", "c"], object)[y]
    Frame.from_dict(cols, key="ext_train3")
    r = _post(server, "/3/ModelBuilders/gbm", training_frame="ext_train3",
              response_column="y", ntrees="2", max_depth="3",
              model_id="ext_gbm3", seed="7")
    assert _wait(server, r["job"]["key"])["status"] == "DONE"
    from h2o3_tpu.core.kvstore import DKV
    m = DKV.get("ext_gbm3")
    assert m._trees.ntrees == 6 and list(m._trees.tree_class) == [0, 1, 2] * 2
    for ci, name in enumerate("abc"):
        t = _get(server, f"/3/Tree?model=ext_gbm3&tree_number=1"
                         f"&tree_class={name}")
        want = np.asarray(m._trees.value)[3 + ci]     # iteration 1, class ci
        assert np.allclose(t["predictions"], want)
    assert _get_raw(server, "/3/Models/ext_gbm3/mojo")[:2] == b"PK"
    assert b"score0" in _get_raw(server, "/3/Models.java/ext_gbm3")


def test_typeahead_sessions_dkv(server, tmp_path):
    (tmp_path / "data_a.csv").write_text("x\n1\n")
    (tmp_path / "data_b.csv").write_text("x\n2\n")
    m = _get(server, "/99/Typeahead/files?src="
             + urllib.parse.quote(str(tmp_path / "data")))
    assert len(m["matches"]) == 2

    sid = _post(server, "/4/sessions")["session_key"]
    assert sid.startswith("_sid")

    Frame.from_dict({"v": [1.0]}, key="dkv_kill_me")
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/3/DKV/dkv_kill_me",
        method="DELETE")
    urllib.request.urlopen(req).read()
    assert DKV.get("dkv_kill_me") is None


def test_import_sql_fails_loudly(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(server, "/86/ImportSQLTable", table="t")
    assert ei.value.code == 501


def test_bindings_codegen_end_to_end(server, tmp_path, small_model):
    """gen_python against the live server; the generated class must train
    a model over plain HTTP (no h2o3_tpu import in the generated code)."""
    from h2o3_tpu.bindings import gen_python
    url = f"http://127.0.0.1:{server.port}"
    names = gen_python(url, str(tmp_path / "gen"))
    assert "H2OGradientBoostingEstimator" in names
    assert "H2OGeneralizedLinearEstimator" in names

    spec = importlib.util.spec_from_file_location(
        "genest", tmp_path / "gen" / "estimators.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.NUM_SERVER_ROUTES >= 60

    conn = mod.H2OConnection(url)
    est = mod.H2OGeneralizedLinearEstimator(conn, family="binomial",
                                            model_id="gen_glm")
    est.train(y="y", training_frame="ext_train")
    metrics = est.metrics()
    assert metrics.get("auc", 0) > 0.7
    dest = est.predict("ext_train")
    pf = _get(server, f"/3/Frames/{dest}")["frames"][0]
    assert pf["rows"] == 200

    # unknown parameters are rejected client-side (generated param list)
    with pytest.raises(TypeError):
        mod.H2OGradientBoostingEstimator(conn, bogus_param=1)


def test_flow_ui_served(server):
    """Flow-lite (h2o-web analog): the operations UI serves at / and
    drives only public REST routes."""
    html = _get_raw(server, "/").decode()
    assert "<title>h2o3-tpu Flow</title>" in html
    assert "/3/ModelBuilders" in html and "/99/Rapids" in html
    html2 = _get_raw(server, "/flow/index.html").decode()
    assert html2 == html
