"""A multinomial GBM through the normal path — `train()` publishes ONE
ensemble (`m._trees`, `tree_class` iteration-major), `predict()` scores it
by one walk and one link — against the benchmark's plain reference
(benchmark/reference/gbm_classes_plain.py) and through its check
(benchmark/checks/gbm_classes.py): the sound program is correct, the
bfloat16 control and both planted faults are not; the KDD Cup 1999 data
generator keeps its promises; and the cell `gbm_kddcup99.score` rehearses.
"""

import json
import pickle

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import h2o3_tpu
from h2o3_tpu import models
from h2o3_tpu.core.frame import Frame, T_CAT, Vec
from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.obs.timeline import SPANS

from benchmark import checks, run
from benchmark.checks import gbm_classes as check
from benchmark.datasets import kddcup99_like as data
from benchmark.reference import gbm_classes_plain as ref

K, N = 5, 3000
NAMES = ["x0", "x1", "x2", "x3", "c1", "c2"]
LIMITS = {"score_gap": 2e-6, "score_bad": 0, "classes_unscored": 0}


@pytest.fixture(scope="module")
def trained(cloud8):
    """K = 5 classes, four numeric and two categorical columns (6 and 40
    levels) that carry signal, 6 iterations of depth 4."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((N, 6)).astype(np.float32)
    X[:, 4], X[:, 5] = rng.integers(0, 6, N), rng.integers(0, 40, N)
    X[rng.random(X.shape) < 0.01] = np.nan
    s = np.stack([X[:, 0], X[:, 1] + (X[:, 4] == 2), (X[:, 5] % 5 == 0) * 2.0,
                  X[:, 2] * X[:, 3], -X[:, 0]], 1)
    y = (np.nan_to_num(s) + rng.gumbel(size=s.shape)).argmax(1)
    doms = {4: [f"a{i}" for i in range(6)], 5: [f"b{i}" for i in range(40)]}
    vecs = [Vec.from_numpy(X[:, j], type=T_CAT, domain=doms[j]) if j in doms
            else Vec.from_numpy(X[:, j]) for j in range(6)]
    vecs.append(Vec.from_numpy(y.astype(np.float64), type=T_CAT,
                               domain=list("vwxyz")))
    fr = Frame(NAMES + ["y"], vecs)
    m = models.H2OGradientBoostingEstimator(ntrees=6, max_depth=4, seed=1)
    m.train(y="y", training_frame=fr)
    yield m, fr, X
    for k in (m.key, fr.key):
        h2o3_tpu.remove(k)


def _scores(m, fr, ids):
    """What predict_loop_classes' finish reads of one prediction frame."""
    pred = m.predict(fr)
    P = np.stack([pred.vec("p" + c).to_numpy()[ids] for c in "vwxyz"], 1)
    lab = pred.vec("predict").to_numpy()[ids]
    h2o3_tpu.remove(pred.key)
    return ids, P, lab


def test_train_publishes_one_ensemble_iteration_major(trained):
    m = trained[0]
    tr = m._trees
    assert isinstance(tr, E.TreeArrays) and tr.ntrees == 6 * K
    assert np.array_equal(tr.tree_class, np.tile(np.arange(K), 6))
    assert tr.n_classes == K and isinstance(tr.col, jax.Array)
    assert m._output.model_summary["engine"] == "binned_pallas"
    assert m._output.model_summary["number_of_trees"] == 6 * K
    # the harness's train_once ends on the model's trees
    jax.block_until_ready(jax.tree_util.tree_leaves(m._trees))
    # the serving scorer takes the ONE ensemble; the view is no parameter
    assert list(m._serving_params()) == ["_trees"]
    assert "_trees_k" not in m.__dict__
    back = pickle.loads(pickle.dumps(m._trees))
    assert np.array_equal(back.tree_class, tr.tree_class)


def test_predict_is_one_walk_and_one_link(trained):
    m, fr, X = trained
    Xd = m._dinfo.matrix(fr)
    SPANS.clear()
    P = np.asarray(m._score_matrix(Xd))[:N]
    spans = SPANS.snapshot()
    names = [s["name"] for s in spans]
    assert names.count("predict.link") == 1
    assert names.count("predict.tables") == 1
    t = next(s for s in spans if s["name"] == "predict.tables")
    assert (t["attrs"]["path"], t["attrs"]["block"]) == \
        ("dense", E._block_label(4))
    want = ref.predict_proba(X, check.read_model(m))
    assert np.abs(P - want).max() <= 2e-6
    # the initial margins are placed once a model
    assert m._link_f0() is m._link_f0()


def test_the_classes_view_scores_the_same(trained):
    m, fr, _ = trained
    Xd = m._dinfo.matrix(fr)
    sums = np.asarray(E.predict_ensemble(Xd, m._trees))
    views = m._trees_k
    assert len(views) == K and all(v.ntrees == 6 for v in views)
    assert all(isinstance(v.col, np.ndarray) for v in views)
    for c, v in enumerate(views):
        assert np.array_equal(np.asarray(E.predict_ensemble(Xd, v)),
                              sums[:, c])


def test_the_sound_program_is_correct_through_the_check(trained):
    m, fr, X = trained
    model = check.read_model(m)
    assert model["predictors"] == NAMES and model["f0"].shape == (K,)
    got = check.compare("scores", X=X, y=None, params=m.params, model=model,
                        produced=[_scores(m, fr, np.arange(0, N, 3))],
                        opts={"names": NAMES})
    assert got["score_rows"] == N // 3 and got["cat_levels_lost"] == 0
    rows = checks.verdict(got, LIMITS)
    assert all(ok for *_, ok in rows), rows


@pytest.mark.parametrize("control", [{"precision": "bf16"},
                                     {"fault": "shift_class"},
                                     {"fault": "drop_f0"}],
                         ids=["bf16", "shift_class", "drop_f0"])
def test_the_control_and_the_planted_faults_are_not_correct(trained, control):
    """The reference in the program's place, computed in bfloat16 or with a
    fault planted: NOT correct by at least one of the limits."""
    m, _, X = trained
    model = check.read_model(m)
    ids = np.arange(0, N, 3)
    P = ref.predict_proba(X[ids], model, **control)
    got = check.check_scores([(ids, P, P.argmax(1).astype(np.float64))], X,
                             model)
    rows = checks.verdict(got, LIMITS)
    assert not all(ok for *_, ok in rows), rows


def test_a_lost_column_a_lost_frame_and_a_wrong_label_are_counted(trained):
    m, fr, X = trained
    model = check.read_model(m)
    ids, P, lab = _scores(m, fr, np.arange(0, N, 3))
    gone = P.copy()
    gone[:, 3] = np.nan                        # no `py` column in the frame
    got = check.check_scores([(ids, gone, lab)], X, model)
    assert got["classes_unscored"] == 1 and got["score_bad"] == len(ids)
    assert check.check_scores([(ids, None, None)], X, model)["score_bad"] \
        == len(ids)
    wrong = lab.copy()
    wrong[:7] = (lab[:7] + 1) % K
    assert check.check_scores([(ids, P, wrong)], X, model)["score_bad"] == 7
    no_tree = dict(model, tree_class=np.where(model["tree_class"] == 2, 0,
                                              model["tree_class"]))
    assert check.check_scores([(ids, P, lab)], X,
                              no_tree)["classes_unscored"] == 1
    assert check.check_scores([], X, model)["score_bad"] == 1


def test_the_adaptive_engine_publishes_one_ensemble_too(trained):
    _, fr, _ = trained
    m = models.H2OGradientBoostingEstimator(
        ntrees=2, max_depth=3, seed=1, histogram_type="UniformAdaptive")
    m.train(y="y", training_frame=fr)
    try:
        assert m._trees.ntrees == 2 * K
        assert np.array_equal(m._trees.tree_class, np.tile(np.arange(K), 2))
        p = m.predict(fr)
        P = np.stack([p.vec("p" + c).to_numpy() for c in "vwxyz"], 1)
        assert np.abs(P.sum(1) - 1).max() < 1e-6
        h2o3_tpu.remove(p.key)
    finally:
        h2o3_tpu.remove(m.key)


def test_a_checkpoint_restart_appends_whole_iterations(trained):
    m, fr, _ = trained
    more = models.H2OGradientBoostingEstimator(
        ntrees=8, max_depth=4, seed=1, checkpoint=m.key)
    more.train(y="y", training_frame=fr)
    try:
        tr = more._trees
        assert tr.ntrees == 8 * K
        assert np.array_equal(tr.tree_class, np.tile(np.arange(K), 8))
        assert np.array_equal(np.asarray(tr.col[:6 * K]),
                              np.asarray(m._trees.col))
    finally:
        h2o3_tpu.remove(more.key)


# ---- the KDD Cup 1999 data generator ---------------------------------------
def test_the_tables_first_rows_are_the_same_whatever_the_total():
    seed = 4100000007                      # past 32 signed bits
    X, y = data.host_arrays(300_000, 41, seed)
    Xs, ys = data.host_arrays(1_000, 41, seed)
    assert np.array_equal(X[:1_000], Xs) and np.array_equal(y[:1_000], ys)
    Xo, _ = data.host_arrays(1_000, 41, seed + 1)
    assert not np.array_equal(Xo, Xs)
    assert X.dtype == np.float32 and y.dtype == np.int8
    for j, k in enumerate(data.LEVELS):
        if k:
            assert X[:, j].min() == 0 and X[:, j].max() == k - 1
            assert np.array_equal(X[:, j], np.floor(X[:, j]))
    assert not np.isnan(X).any()
    assert X[:, data.NAMES.index("num_outbound_cmds")].max() == 0
    rates = [data.NAMES.index(n) for n in data.RATES]
    assert len(rates) == 15 and X[:, rates].min() >= 0 \
        and X[:, rates].max() <= 1
    assert X[:, data.NAMES.index("count")].max() == 511


def test_the_class_frequencies_are_the_configurations():
    cfg = run.load_json("configs", "gbm_kddcup99.json")
    table = cfg["table"]
    assert table["class_counts"] == data.COUNTS
    assert sum(data.COUNTS.values()) == cfg["published"]["rows"] == 4_898_431
    assert table["domain"] == data.DOMAIN == sorted(data.COUNTS)
    assert len(data.DOMAIN) == table["classes"] == 23
    assert table["levels"] == data.LEVELS and table["names"] == data.NAMES \
        == cfg["check"]["names"]
    assert cfg["sizes"] == {"table_rows": 4_898_430, "train_rows": 2_449_215}
    _, y = data.host_arrays(500_000, 41, 11)
    got = np.bincount(y, minlength=23) / y.size
    want = np.array([data.COUNTS[c] for c in data.DOMAIN]) / 4_898_431
    big = want > 1e-3                      # smurf, neptune, normal, ...
    assert big.sum() == 6 and np.abs(got[big] / want[big] - 1).max() < 0.05
    assert got[~big].sum() < 0.002


# ---- the benchmark's cell, rehearsed ----------------------------------------
def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]


def test_the_kddcup99_cell_rehearses_correct(cloud8, capsys):
    rc = run.main(["--workload", "gbm_kddcup99.score", "--seed", "4100000007",
                   "--seconds", "1", "--trace", "0", "--rehearse"])
    line = _json_lines(capsys.readouterr().out)[-1]
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {
        "score_gap", "score_bad", "classes_unscored", "cat_levels_lost",
        "failed_operations"}
    assert line["compared"]["score_gap"]["value"] < 2e-6
    assert set(line["metrics"]) == {"score_rows_per_s", "setup_s"}


def test_the_controls_turn_the_kddcup99_check_incorrect(cloud8, capsys):
    from benchmark.tools import controls_classes
    rc = controls_classes.main(["--config", "gbm_kddcup99", "--seeds",
                                "3100000019", "--rehearse"])
    recs = _json_lines(capsys.readouterr().out)
    assert rc == 0 and [r["variant"] for r in recs] \
        == ["sound", "bf16", "shift_class", "drop_f0"]
    sound, *controls = recs
    assert sound["within_limits"] and sound["score_gap"] < 2e-6
    assert sound["engine"] == "binned_pallas" and sound["trees"] == 230
    assert sound["columns"] == 40              # the constant column dropped
    for r in controls:
        assert not r["within_limits"] and r["gap_all_rows"] > 1e-3


def test_the_dense_work_model_counts_blocks_and_passes():
    from benchmark import work_model_dense as wd
    # 230 trees of depth 5: 58 blocks of four; 2 select + 1 path + half a
    # set pass each
    assert wd.walk_passes(230, 5, 41, 84) == 58 * 3.5
    assert wd.walk_passes(20, 5, 28, 0) == 5 * 3
    assert wd.walk_passes(3, 7, 100, 759) == 3 * (4 + 1 + 3)
    assert wd.walk_flops(1000, 230, 5, 41, 84) == 1000 * 32768 * 58 * 3.5
    with pytest.raises(ValueError):
        wd.walk_passes(10, 8, 28, 0)


def test_the_two_readers_read_what_there_is_and_nothing_where_there_is_none():
    from benchmark.layer_metrics import score_link_pct, score_walk_mxu_pct
    cfg = run.load_json("configs", "gbm_kddcup99.json")
    peak = run.load_json("peaks.json")["TPU v5 lite"]
    rec = {"peak": peak, "params": cfg["params"], "config": cfg,
           "rehearse": False,
           "window": {"call_walls": [0.12] * 4, "call_rows": 2_449_215,
                      "trace": {"module_s": {"jit__ensemble_walk": 0.42}}}}
    # 58 blocks x 3.5 passes x 4 frames of 2,449,215 rows x 32,768 flop at
    # 197 TF/s = 0.3308 s of 0.42
    assert score_walk_mxu_pct.read(rec) == pytest.approx(78.76, abs=0.01)
    fast = dict(rec, window=dict(rec["window"], trace={
        "module_s": {"jit__ensemble_walk": 0.30}}))
    with pytest.raises(ValueError):            # over 100 %: never clipped
        score_walk_mxu_pct.read(fast)
    for none in (dict(rec, peak=None),
                 dict(rec, window=dict(rec["window"], trace=None)),
                 dict(rec, window=dict(rec["window"],
                                       trace={"module_s": {"jit_x": 1.0}}))):
        assert score_walk_mxu_pct.read(none) is None
    # no `predict` root in the ring, or a rehearsed run: nothing to read
    SPANS.clear()
    assert score_link_pct.read(rec) is None
    assert score_link_pct.read(dict(rec, rehearse=True)) is None
