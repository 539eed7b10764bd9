"""The link and the prediction planes as ONE program behind the walk.

A GBM's large-frame predict() hands `_prediction_columns` the walk's sums
with their link (`model.Margins`), and ONE program, `_linked_planes`, makes
the frame's planes from them: F = f0 + lr x sums, the inverse link, the
label and a (data, mask) pair a class. Two classes never form the (rows, 2)
matrix; K classes on the TPU are one kernel (`ops/planes_pallas.py`,
interpreted here). What stays: the walk's program, `_score_matrix` as the
probabilities function, today's planes program for every family that does
not supply its link (a GLM, a user distribution, XGBoost's own
`_score_matrix`), and `np.asarray` of what
`_prediction_columns` is handed (the benchmark's controls patch it).

Bit for bit against today's ops, with one reason for a difference: XLA's
CPU backend contracts f0 + lr x sums into one multiply-add when both are in
one program, where today's eager ops round the product first. So the fused
planes are checked against today's link and planes of the margin a program
of its own computes, to the bit, and that margin against today's eager one
within the rounding the contraction skips: half an ulp of lr x sums plus
an ulp of F. A K-class link was one program already: no difference there.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.core.frame import DevicePlanes, Frame
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.models import model as MB
from h2o3_tpu.models.model import Margins, _prediction_planes
from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.models.tree import shared_tree as ST
from h2o3_tpu.obs.metrics import REGISTRY
from h2o3_tpu.obs.timeline import SPANS

N = 403                     # 8 shards x 8 sublanes pad this to 448
LINK = {"bernoulli": "logistic", "quasibinomial": "logistic",
        "multinomial": "softmax", "gaussian": "identity", "poisson": "exp",
        "laplace": "identity"}


class _Huber:
    """A user distribution (water/udf CDistributionFunc): its link is
    user code."""

    def grad_hess(self, F, y):
        r = y - F
        return jnp.clip(r, -1.0, 1.0), jnp.ones_like(F)

    def init_f0(self, ybar):
        return ybar


def _train(kind):
    from h2o3_tpu.models import (H2OGeneralizedLinearEstimator,
                                 H2OGradientBoostingEstimator,
                                 H2OXGBoostEstimator)
    from h2o3_tpu.udf import CustomDistribution, register_udf
    rng = np.random.default_rng(39)
    x1, x2, x3 = (rng.normal(size=N) for _ in range(3))
    x1[::7] = np.nan
    x3[5::11] = np.nan
    s = np.nan_to_num(x1) + x2 - 0.5 * np.nan_to_num(x3)
    if kind == "multinomial":
        y = np.array(["a", "b", "c"], object)[np.digitize(s, [-0.7, 0.7])]
    elif kind in ("bernoulli", "quasibinomial", "glm_binomial", "xgboost"):
        y = np.array(["n", "p"], object)[(s > 0).astype(int)]
    elif kind == "poisson":
        y = np.exp(0.3 * s + 0.1 * rng.normal(size=N))
    else:
        y = s + 0.1 * rng.normal(size=N)
    f = Frame.from_dict({"x1": x1, "x2": x2, "x3": x3, "y": y})
    if kind == "glm_binomial":
        m = H2OGeneralizedLinearEstimator(family="binomial", max_iterations=8)
    elif kind == "xgboost":
        m = H2OXGBoostEstimator(ntrees=3, max_depth=3, seed=5)
    elif kind == "custom":
        ref = register_udf("epilogue_huber",
                           type("Huber", (_Huber, CustomDistribution), {})())
        m = H2OGradientBoostingEstimator(
            ntrees=3, max_depth=3, seed=5, distribution="custom",
            custom_distribution_func=ref)
    else:
        m = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=5,
                                         distribution=kind)
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
    from h2o3_tpu.udf import remove_udf
    remove_udf("epilogue_huber")
    for m, f in made.values():
        DKV.remove(m.key)
        DKV.remove(f.key)


def _dom(m):
    return m._dinfo.response_domain if m._is_classifier else None


def _same_planes(got, want):
    """Column by column: names, codec, and data and mask to the bit."""
    assert [c[0] for c in got] == [c[0] for c in want]
    for (name, a, da), (_, b, db) in zip(got, want):
        assert isinstance(a, DevicePlanes) and da == db, name
        assert a.codec.kind == b.codec.kind and a.nrows == b.nrows, name
        for x, y in ((a.data, b.data), (a.mask, b.mask)):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape, name
            assert np.array_equal(x, y, equal_nan=True), \
                (name, int((x != y).sum()))


def _plant_nan(sums):
    """NaN margins no walk produces: a whole row, and (of K classes) one
    class of another row."""
    sums = sums.at[1].set(jnp.nan)
    return sums.at[2, -1].set(jnp.nan) if sums.ndim == 2 else sums


@functools.partial(jax.jit, static_argnums=2)
def _class_link(sums, f0, lr):
    """A K-class link as one program, the parent's `_class_link` as it
    was written."""
    return jax.nn.softmax(f0[None, :] + lr * sums, axis=1)


def _counts():
    c = REGISTRY.get("h2o3_predict_epilogue_total")
    return {w: c.value(algo="gbm", link=w) for w in ("fused", "apart")}


@pytest.mark.parametrize("dist,planted", [
    ("bernoulli", False), ("quasibinomial", False), ("multinomial", False),
    ("gaussian", False), ("poisson", False), ("laplace", False),
    ("bernoulli", True), ("multinomial", True), ("poisson", True)])
def test_fused_planes_are_todays_planes_of_the_margin(models, dist, planted):
    m, f = models(dist)
    assert f.nrows == N and f.padded_len > N
    X = m._dinfo.matrix(f)
    mg = m._score_linkable(X)
    assert isinstance(mg, Margins) and mg.link == LINK[dist]
    assert mg.lr == m.params["learn_rate"]
    if planted:
        mg.sums = _plant_nan(mg.sums)
    before = _counts()
    fused = m._device_columns(mg, N, _dom(m))
    assert _counts() == {"fused": before["fused"] + 1,
                         "apart": before["apart"]}
    lr = mg.lr
    # today's link and planes, of the margin a program of its own makes
    F = jax.jit(lambda s, f0: f0 + lr * s)(mg.sums, mg.f0)
    if dist == "multinomial":
        today = _class_link(mg.sums, mg.f0, lr)
    else:
        today = ST._link_inv_dist(dist, F)
    _same_planes(fused, m._device_columns(today, N, _dom(m)))
    # ... and that margin is today's eager one but for the one rounding
    eager = np.asarray(m._f0 + lr * mg.sums)
    F = np.asarray(F)
    skipped = np.spacing(np.abs(np.asarray(lr * mg.sums))) / 2
    ok = np.abs(F - eager) <= skipped + np.spacing(np.abs(eager))
    assert (ok | (np.isnan(F) & np.isnan(eager))).all()
    if planted:
        scores = fused
        if m._is_classifier:
            lab = np.asarray(fused[0][1].data)
            assert lab.dtype == np.int8 and lab[1] == 0   # a NaN row: class 0
            scores = fused[1:]
        for name, p, _ in scores:
            assert np.asarray(p.mask)[1] == 1, name       # ... its scores NA


def _synthetic(link, K, rng):
    """(sums (448, K) or (448,), f0) with planted rows: 1 all NaN, 2 NaN in
    its last class, 3 a tie between the last two classes (between p and
    1 - p of two classes: a margin of 0)."""
    if link == "logistic":
        s = rng.normal(size=448).astype(np.float32) * 9
        s[1], s[3] = np.nan, 0.0
        return jnp.asarray(s), jnp.asarray(0.0, jnp.float32)
    s = rng.normal(size=(448, K)).astype(np.float32) * 9
    s[1], s[2, K - 1] = np.nan, np.nan
    s[3] = -50.0
    s[3, K - 2:] = 50.0
    f0 = np.log(rng.dirichlet(np.ones(K))).astype(np.float32)
    f0[K - 1] = f0[K - 2]
    return jnp.asarray(s), jnp.asarray(f0)


def _today(link, sums, f0, lr):
    """Today's probabilities: the eager logistic (f0 = 0: no product to
    contract) or the one-program softmax."""
    if link == "logistic":
        return ST._link_inv_dist("bernoulli", f0 + lr * sums)
    return _class_link(sums, f0, lr)


@pytest.mark.parametrize("K", [2, 3, 23, 130])
def test_planted_rows_take_argmax_rules(models, K):
    """NumPy's argmax: the first maximum wins, a NaN counts as one; two
    classes from p alone (p > 1 - p). Past 127 classes the label is f32."""
    m, _ = models("bernoulli")
    link = "logistic" if K == 2 else "softmax"
    sums, f0 = _synthetic(link, K, np.random.default_rng(K))
    dom = [f"l{i}" for i in range(K)]
    got = m._device_columns(Margins(sums, f0, 0.1, link), N, dom)
    want = m._device_columns(_today(link, sums, f0, 0.1), N, dom)
    _same_planes(got, want)
    lab = np.asarray(got[0][1].data)
    assert got[0][1].codec.kind == ("i8" if K <= 127 else "f32")
    assert lab[1] == 0                      # a NaN row: its first class
    if K > 2:
        assert lab[2] == 0                  # one NaN margin: the row's NaN
    assert lab[3] == (0 if K == 2 else K - 2)   # the first of a tie
    assert (np.asarray(got[0][1].mask)[N:] == 1).all()
    assert (lab[N:] == 0).all()


@pytest.mark.parametrize("K,sharded", [(3, True), (23, True), (23, False)])
def test_the_kernel_interpreted_makes_the_twins_planes(models, monkeypatch,
                                                       K, sharded):
    """`class_planes` (the TPU's one kernel) against the XLA twin, to the
    bit, in tiles of 16 rows: whole and partial tiles, the planted rows,
    the padding — row-sharded over the cloud's eight shards (each counts
    its padding from its first row's place) and unsharded."""
    from jax.experimental.pallas import tpu as pltpu
    from h2o3_tpu.ops import hist_pallas as HP
    from h2o3_tpu.ops import planes_pallas as PP
    from h2o3_tpu.parallel import mesh as _mesh
    m, _ = models("bernoulli")
    sums, f0 = _synthetic("softmax", K, np.random.default_rng(K + 1))
    c = _mesh.cloud()
    if sharded:
        sums = jax.device_put(sums, c.rows_sharding(2))
        assert E._rows_mesh(sums) is not None
    mesh = E._rows_mesh(sums)
    twin = jax.jit(MB._linked_planes(N, "i8", 0.1, "softmax", mesh))(sums, f0)
    monkeypatch.setattr(PP, "use_pallas", lambda: True)
    monkeypatch.setattr(PP, "TILE_ROWS", 16)
    before = HP.kernel_traces().get(("class_planes", K), 0)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(MB._linked_planes(N, "i8", 0.1, "softmax", mesh))(
            sums, f0)
    assert HP.kernel_traces()[("class_planes", K)] > before
    assert len(got) == len(twin) == K + 1
    for k, ((a, am), (b, bm)) in enumerate(zip(got, twin)):
        assert a.dtype == b.dtype and a.shape == b.shape == (448,), k
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
        assert np.array_equal(np.asarray(am), np.asarray(bm)), k
    assert np.asarray(got[0][0])[3] == K - 2


def _planes_program(monkeypatch):
    """Record which planes program `_device_columns` builds."""
    seen = []
    for name in ("_prediction_planes", "_linked_planes"):
        orig = getattr(MB, name)

        def rec(*a, _orig=orig, _name=name):
            fn = _orig(*a)
            seen.append((_name, a, fn))
            return fn
        monkeypatch.setattr(MB, name, rec)
    return seen


@pytest.mark.parametrize("kind", ["custom", "glm_binomial", "xgboost"])
def test_families_that_keep_their_link_take_todays_program(
        models, monkeypatch, kind):
    m, f = models(kind)
    X = m._dinfo.matrix(f)
    assert not isinstance(m._score_linkable(X), Margins)
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    seen = _planes_program(monkeypatch)
    c = REGISTRY.get("h2o3_predict_epilogue_total")
    apart0 = c.value(algo=m.algo, link="apart")
    fused0 = c.value(algo=m.algo, link="fused")
    SPANS.clear()
    pred = m.predict(f)
    names = [s["name"] for s in SPANS.snapshot()]
    try:
        assert [s[0] for s in seen] == ["_prediction_planes"]
        (n, label), fn = seen[0][1], seen[0][2]
        assert n == N and label == ("i8" if m._is_classifier else None)
        out = m._score_matrix(X)
        assert str(jax.make_jaxpr(fn)(out)) == \
            str(jax.make_jaxpr(_prediction_planes(N, label))(out))
        assert c.value(algo=m.algo, link="apart") == apart0 + 1
        assert c.value(algo=m.algo, link="fused") == fused0
        assert "predict.link" not in names
    finally:
        DKV.remove(pred.key)


@pytest.mark.parametrize("dist", ["bernoulli", "multinomial"])
def test_predict_runs_todays_walk(models, monkeypatch, dist):
    """The walk is the program `_score_matrix` runs: the same arguments,
    the same jaxpr, the sums to the bit."""
    m, f = models(dist)
    X = m._dinfo.matrix(f)
    calls = []
    orig = E._ensemble_walk

    def walk(*a, **kw):
        calls.append((a, kw))
        return orig(*a, **kw)
    monkeypatch.setattr(E, "_ensemble_walk", walk)
    m._score_matrix(X)
    mg = m._score_linkable(X)
    assert len(calls) == 2
    (a1, k1), (a2, k2) = calls
    assert k1 == k2 and all(x is y for x, y in zip(a1[1:], a2[1:]))

    def jaxpr(a, kw):
        return str(jax.make_jaxpr(functools.partial(orig, **kw))(*a))
    assert jaxpr(a1, k1) == jaxpr(a2, k2)
    assert np.array_equal(np.asarray(mg.sums),
                          np.asarray(E.predict_ensemble(X, m._trees)))


@pytest.mark.parametrize("dist", ["bernoulli", "multinomial", "gaussian"])
def test_one_link_span_and_one_fused_count_a_predict_call(models,
                                                          monkeypatch, dist):
    m, f = models(dist)
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    h2o3_tpu_predict_warm = m.predict(f)           # every program compiled
    DKV.remove(h2o3_tpu_predict_warm.key)
    before = _counts()
    SPANS.clear()
    pred = m.predict(f)
    spans = SPANS.snapshot()
    try:
        ids = {s["id"]: s for s in spans}
        links = [s for s in spans if s["name"] == "predict.link"]
        assert len(links) == 1
        assert ids[links[0]["parent"]]["name"] == "predict.dispatch"
        assert _counts() == {"fused": before["fused"] + 1,
                             "apart": before["apart"]}
        root = next(s for s in spans if s["name"] == "predict")
        assert root["attrs"]["path"] == "frame"
        assert not [s for s in spans if s["name"].startswith("xla.")]
        assert pred.ncols == (1 + m.nclasses if m._is_classifier else 1)
    finally:
        DKV.remove(pred.key)


@pytest.mark.parametrize("dist", ["bernoulli", "multinomial"])
def test_the_controls_patch_still_receives_the_probabilities(
        models, monkeypatch, dist):
    """benchmark/tools/controls.py patches `_prediction_columns` with a
    function that takes `np.array(out, np.float64)`: it gets the (rows, K)
    probabilities, those of `_score_matrix`."""
    m, f = models(dist)
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    orig = MB.ModelBase._prediction_columns
    seen = []

    def cols(self, out, n):
        seen.append(np.array(out, np.float64))
        return orig(self, seen[-1], n)
    monkeypatch.setattr(MB.ModelBase, "_prediction_columns", cols)
    pred = m.predict(f)
    try:
        got = seen[0]
        assert got.shape == (f.padded_len, m.nclasses)
        np.testing.assert_allclose(got[:N].sum(axis=1), 1.0, atol=1e-6)
        want = np.asarray(m._score_matrix(m._dinfo.matrix(f)), np.float64)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        lab = pred.vec("predict").to_numpy()
        assert np.array_equal(lab, got[:N].argmax(axis=1))
    finally:
        DKV.remove(pred.key)
