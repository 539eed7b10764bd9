"""The prediction frame built from scores left on the device (ISSUE 31).

`ModelBase._prediction_columns` adapts on where its input lives: a
`jax.Array` becomes device planes out of one program, a NumPy array the
float64 host columns it always was. The two frames must read the same
through `Vec.to_numpy()`, `Vec.type` and `Vec.domain` — bits, dtype and NA
positions — for every family shape (binomial, multinomial, regression, a
GLM), on a frame whose rows are not a multiple of the padding and whose
features hold NaN; and for the planted rows no model produces: an all-NaN
score row and a tie, where NumPy's `argmax` rules decide the label.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.core.frame import DevicePlanes, Frame, T_CAT, T_NUM
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.models.model import Margins
from h2o3_tpu.obs.metrics import REGISTRY
from h2o3_tpu.obs.timeline import SPANS

N = 403                     # 8 shards x 8 sublanes pad this to 448


def _features(rng):
    x1, x2, x3 = (rng.normal(size=N) for _ in range(3))
    x1[::7] = np.nan
    x3[5::11] = np.nan
    return x1, x2, x3


def _train(kind):
    from h2o3_tpu.models import (H2OGeneralizedLinearEstimator,
                                 H2OGradientBoostingEstimator)
    rng = np.random.default_rng(31)
    x1, x2, x3 = _features(rng)
    s = np.nan_to_num(x1) + x2 - 0.5 * np.nan_to_num(x3)
    if kind == "gbm_regression":
        y = s + 0.1 * rng.normal(size=N)
    elif kind == "gbm_multinomial":
        y = np.array(["a", "b", "c"], object)[np.digitize(s, [-0.7, 0.7])]
    else:
        y = np.array(["n", "p"], object)[(s > 0).astype(int)]
    f = Frame.from_dict({"x1": x1, "x2": x2, "x3": x3, "y": y})
    if kind == "glm_binomial":
        m = H2OGeneralizedLinearEstimator(family="binomial",
                                          max_iterations=8)
    else:
        m = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=5)
    m.train(y="y", training_frame=f)
    return m, f


@pytest.fixture(scope="module")
def models(cloud8):
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = _train(kind)
        return made[kind]
    yield get
    for m, f in made.values():
        DKV.remove(m.key)
        DKV.remove(f.key)


def _same_frame(dev, host):
    """Column by column: names, type, domain, and to_numpy() to the bit."""
    assert dev.names == host.names and dev.nrows == host.nrows
    for name in dev.names:
        a, b = dev.vec(name), host.vec(name)
        assert isinstance(a.data, jax.Array) and a.padded_len == b.padded_len
        assert a.type == b.type, name
        assert (a.domain is None) == (b.domain is None), name
        if a.domain is not None:
            assert list(a.domain) == list(b.domain)
        x, y = a.to_numpy(), b.to_numpy()
        assert x.dtype == y.dtype and x.shape == y.shape == (dev.nrows,)
        assert np.array_equal(np.isnan(x), np.isnan(y)), name
        assert np.array_equal(x, y, equal_nan=True), name
        # decoded planes agree on the padding too: NA past the last row
        assert np.isnan(np.asarray(a.as_f32())[dev.nrows:]).all()


def _both_frames(m, out, n):
    """(frame from the device array, frame from its fetched copy)."""
    assert isinstance(out, jax.Array)
    dev = m._prediction_frame(out, n)
    host = m._prediction_frame(np.asarray(out), n)
    return dev, host


def _planted(out, what):
    """A score row no model produces, at rows 1 and 2 of a (rows, K)
    array: all-NaN / a tie between the last two classes (and, at K > 2, a
    row whose FIRST NaN is not in column 0)."""
    k = out.shape[1]
    if what == "nan_row":
        out = out.at[1].set(jnp.nan)
        return out.at[2, k - 1].set(jnp.nan)
    tie = jnp.zeros(k, out.dtype).at[k - 2:].set(1.0 / 2)
    return out.at[1].set(tie).at[2].set(jnp.full(k, 1.0 / k, out.dtype))


@pytest.mark.parametrize("kind,planted", [
    ("gbm_binomial", None), ("gbm_multinomial", None),
    ("gbm_regression", None), ("glm_binomial", None),
    ("gbm_binomial", "nan_row"), ("gbm_multinomial", "nan_row"),
    ("gbm_binomial", "tie"), ("gbm_multinomial", "tie")])
def test_device_frame_equals_host_frame(models, kind, planted):
    m, f = models(kind)
    assert f.nrows == N and f.padded_len > N
    out = m._score_matrix(m._dinfo.matrix(f))
    if planted:
        out = _planted(out, planted)
    dev, host = _both_frames(m, out, N)
    try:
        _same_frame(dev, host)
        lab = dev.vec("predict")
        if kind == "gbm_regression":
            assert dev.names == ["predict"] and lab.type == T_NUM
            assert lab.codec.kind == "f32"
        else:
            assert lab.type == T_CAT and lab.codec.kind == "i8"
            assert lab.data.dtype == jnp.int8           # no wider than today
            assert dev.ncols == 1 + m.nclasses
        got = lab.to_numpy()
        if planted == "nan_row":
            # NumPy's argmax: a NaN is the maximum, the first one wins; the
            # label is a level (not NA), the NaN scores are NA
            assert got[1] == 0 and got[2] == m.nclasses - 1
            p_last = dev.vec(dev.names[-1]).to_numpy()
            assert np.isnan(p_last[1]) and np.isnan(p_last[2])
            assert np.isnan(dev.vec(dev.names[1]).to_numpy()[1])
        if planted == "tie":
            assert got[1] == m.nclasses - 2 and got[2] == 0   # first maximum
    finally:
        DKV.remove(dev.key)
        DKV.remove(host.key)


@pytest.mark.parametrize("kind", ["gbm_binomial", "gbm_regression"])
def test_predict_on_the_large_path_returns_the_host_frame_values(
        models, monkeypatch, kind):
    """predict() end to end: the large-frame path (device columns, the link
    in their program) against the host frame of the scores it was handed,
    against the frame of _score_host's fetched scores, and against the
    bucket path of the same model. _score_host's eager ops round lr x sums
    before the add, which XLA's CPU backend contracts into one
    multiply-add inside the one program: an ulp
    (tests/test_predict_epilogue.py bounds the margins)."""
    m, f = models(kind)
    bucket = m.predict(f)
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    dev = m.predict(f)
    same = m._prediction_frame(
        np.asarray(m._score_linkable(m._dinfo.matrix(f))), N)
    host = m._prediction_frame(m._score_host(f), N)
    try:
        _same_frame(dev, same)
        for name in dev.names:
            x, y = dev.vec(name).to_numpy(), host.vec(name).to_numpy()
            assert np.array_equal(np.isnan(x), np.isnan(y)), name
            assert (np.abs(x - y) <= np.spacing(np.abs(y))).all(), name
        for name in dev.names[1:]:
            np.testing.assert_allclose(dev.vec(name).to_numpy(),
                                       bucket.vec(name).to_numpy(),
                                       atol=1e-6, rtol=0)
    finally:
        for fr in (bucket, dev, same, host):
            DKV.remove(fr.key)


def test_the_label_plane_widens_past_127_levels(models):
    m, _ = models("gbm_binomial")
    dom = [f"l{i}" for i in range(130)]
    out = jnp.asarray(np.random.default_rng(0).random((448, 130)),
                      jnp.float32)
    cols = m._device_columns(out, N, dom)
    assert len(cols) == 131 and all(isinstance(v, DevicePlanes)
                                    for _, v, _ in cols)
    lab = cols[0][1]
    assert lab.codec.kind == "f32" and lab.data.dtype == jnp.float32
    assert np.array_equal(np.asarray(lab.data)[:N],
                          np.asarray(out).argmax(axis=1)[:N])


def test_score_host_still_fetches_for_the_host_callers(models, monkeypatch):
    """KMeans / IsolationForest / GLRM call _score_host: a NumPy array,
    with the device→host fetch under its own span."""
    m, f = models("gbm_binomial")
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    SPANS.clear()
    out = m._score_host(f)
    names = [s["name"] for s in SPANS.snapshot()]
    assert isinstance(out, np.ndarray) and out.shape == (f.padded_len, 2)
    # (spans are listed as they END: the tables' placement lies inside
    # the dispatch)
    assert names == ["predict.matrix", "predict.tables", "predict.dispatch",
                     "predict.wait", "predict.fetch"]
    fetch = SPANS.snapshot()[-1]
    assert fetch["attrs"]["bytes"] == out.nbytes
    monkeypatch.delenv("H2O3_SCORE_FASTPATH_MAX_ROWS")
    assert isinstance(m._score_host(f), np.ndarray)     # the bucket branch


@pytest.mark.parametrize("fault", ["altered", "half_unscored"])
def test_a_patched_prediction_columns_on_numpy_yields_a_host_frame(
        models, monkeypatch, fault):
    """benchmark/tools/controls.py plants its faults by patching
    _prediction_columns with a function that converts `out` to NumPy: the
    large-frame predict() must still build a correct (host) frame of what
    the patch hands on."""
    from h2o3_tpu.models.model import ModelBase
    m, f = models("gbm_binomial")
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    clean = m.predict(f)
    orig = ModelBase._prediction_columns
    seen = []

    def cols(self, out, n):
        seen.append(type(out))
        out = np.array(out, np.float64)
        if fault == "altered":
            out[:n:97, 1] += 1e-3
            out[:n:97, 0] -= 1e-3
        else:
            out[n // 2:n] = out[0]
        return orig(self, out, n)
    monkeypatch.setattr(ModelBase, "_prediction_columns", cols)
    counter = REGISTRY.get("h2o3_predict_frame_columns_total")
    host0 = counter.value(algo="gbm", columns="host")
    SPANS.clear()
    pred = m.predict(f)
    spans = SPANS.snapshot()
    try:
        # the walk's sums with the model's link: np.array gives the
        # probabilities
        assert issubclass(seen[0], (jax.Array, Margins))
        frame = next(s for s in spans if s["name"] == "predict.frame")
        assert frame["attrs"]["columns"] == "host"
        assert counter.value(algo="gbm", columns="host") == host0 + 1
        p1, c1 = pred.vec("pp").to_numpy(), clean.vec("pp").to_numpy()
        if fault == "altered":
            hit = np.zeros(N, bool)
            hit[::97] = True
            np.testing.assert_allclose(p1[hit], c1[hit] + 1e-3, atol=1e-6)
            assert np.array_equal(p1[~hit], c1[~hit])
        else:
            assert np.array_equal(p1[:N // 2], c1[:N // 2])
            assert (p1[N // 2:] == c1[0]).all()
        assert pred.vec("predict").type == T_CAT
    finally:
        DKV.remove(pred.key)
        DKV.remove(clean.key)
