"""Categorical columns past a code byte, through train() and predict():
every level of a column its own bin up to `nbins_cats` (byte planes under
the histogram kernels, models/tree/binned.py `Planes`), the trained sets
walked densely, and the model's answers against the benchmark's plain
reference (benchmark/reference/gbm_sets_plain.py: NumPy, imports nothing
of the program).
"""

import json

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import models
from h2o3_tpu.core.frame import Frame, T_CAT, Vec
from h2o3_tpu.core.jobs import jobs_list
from h2o3_tpu.models.tree import binned as BN
from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.obs import metrics as om
from h2o3_tpu.obs.timeline import SPANS

from benchmark.checks import gbm_sets as check
from benchmark.reference import gbm_sets_plain as ref


def _frame(levels, n, seed, hidden=None):
    """One wide categorical column `a`, a numeric `b`, a five-level `c`;
    the label depends ONLY on `a`'s level: on its parity among the levels
    from `hidden` on (a code byte holds 0..254), else on its parity."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, levels, size=n)
    b = rng.standard_normal(n).astype(np.float32)
    c = rng.integers(0, 5, size=n)
    y = (a % 2 == 0) & (a >= (hidden or 0))
    fr = Frame(["a", "b", "c", "y"], [
        Vec.from_numpy(a.astype(np.float64), type=T_CAT,
                       domain=[f"L{i}" for i in range(levels)]),
        Vec.from_numpy(b),
        Vec.from_numpy(c.astype(np.float64), type=T_CAT,
                       domain=list("vwxyz")),
        Vec.from_numpy(y.astype(np.float64), type=T_CAT, domain=["N", "Y"])])
    X = np.stack([a, b, c], axis=1).astype(np.float32)
    return fr, X, y


def _gbm(**kw):
    return models.H2OGradientBoostingEstimator(
        ntrees=10, max_depth=5, min_rows=1, seed=1, **kw)


def _scores(m, fr):
    pred = m.predict(fr)
    out = [pred.vec(c).to_numpy() for c in ("pN", "pY", "predict")]
    h2o3_tpu.remove(pred.key)
    return out


# the test that showed the disagreement (ISSUE 34 §2b): on the parent every
# level from 254 on shared bin 254, so a target that depends only on the
# levels past a code byte could not be learned (accuracy 0.927, the share
# of N)
@pytest.mark.parametrize("levels", [300, 700])
def test_every_level_past_a_code_byte_keeps_its_own_bin(cloud8, levels):
    fr, X, y = _frame(levels, 20_000, levels, hidden=256)
    m = _gbm().train(y="y", training_frame=fr)
    p0, p1, lab = _scores(m, fr)
    assert np.array_equal(lab == 1, y), \
        f"{(lab != y).sum()} training rows mislabelled"
    model = check.read_model(m)
    assert check.levels_lost(model) == 0
    assert m._output.model_summary["nbins_effective"] == levels
    assert "categorical_levels_grouped" not in m._output.model_summary
    tr = m._trees
    assert list(tr.cat_levels[:3]) == [levels, 0, 5]
    assert 32 * tr.catbits.shape[-1] >= levels
    # and the answers are the plain reference's, from the raw table
    want = ref.predict_proba(X, model)
    assert np.abs(p1 - want).max() < 2e-6
    sets, splits = check.set_nodes(model)
    assert 0 < sets <= splits
    h2o3_tpu.remove(m.key)
    h2o3_tpu.remove(fr.key)


def test_past_nbins_cats_levels_share_bins_and_the_model_says_so(cloud8):
    fr, X, y = _frame(700, 20_000, 7)
    m = _gbm(nbins_cats=256).train(y="y", training_frame=fr)
    said = m._output.model_summary["categorical_levels_grouped"]
    assert said == {"a": {"levels": 700, "bins": 256}}
    model = check.read_model(m)
    assert check.levels_lost(model) == 700 - 256
    # consecutive levels share a bin (DHistogram's step): scoring sends a
    # bin's levels one way, and agrees with the reference walking the sets
    p0, p1, lab = _scores(m, fr)
    assert np.abs(p1 - ref.predict_proba(X, model)).max() < 2e-6
    lv = np.arange(700)
    bins = lv * 256 // 700
    byl = {int(l): p1[X[:, 0] == l] for l in lv[:40]}
    for l in range(39):
        if bins[l] == bins[l + 1] and byl[l].size and byl[l + 1].size:
            # same bin, and `b`, `c` carry no signal: the same few answers
            assert set(np.round(byl[l], 6)) & set(np.round(byl[l + 1], 6))
    h2o3_tpu.remove(m.key)
    h2o3_tpu.remove(fr.key)


def test_a_frame_that_fits_a_code_byte_bins_as_it_did():
    """No column past a byte, none past nbins_cats: no planes, the spec
    the parent made (all-numeric training keeps its code plane)."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((2000, 5)).astype(np.float32)
    is_cat = np.array([False, True, False, False, True])
    X[:, 1], X[:, 4] = rng.integers(0, 200, 2000), rng.integers(0, 3, 2000)
    old = BN.make_bins(X, is_cat, 200)
    new = BN.make_bins(X, is_cat, 200, cat_levels=[0, 200, 0, 0, 3],
                       nbins_cats=1024)
    assert new.planes is None and old.planes is None
    assert (new.b_val, new.n_bins, new.c_pad) == (old.b_val, old.n_bins,
                                                  old.c_pad) == (200, 256, 8)
    assert np.array_equal(new.edges, old.edges)


def test_plane_layout_of_the_airline_columns():
    levels = np.array([12, 31, 7, 0, 29, 340, 340, 0])
    X = np.zeros((64, 8), np.float32)
    spec = BN.make_bins(X, levels > 0, 255, cat_levels=levels)
    pl = spec.planes
    assert (spec.b_val, spec.n_bins, pl.n_search, pl.per, pl.cp_pad) \
        == (340, 256, 384, 2, 16)
    assert list(pl.nb) == [12, 31, 7, 255, 29, 340, 340, 255]
    assert list(pl.src[:10]) == [0, 1, 2, 3, 4, 5, 5, 6, 6, 7]
    assert list(pl.first[:8]) == [0, 1, 2, 3, 4, 5, 7, 9]
    # a wide column's code c is byte c % 255 of its plane c // 255; its NA
    # code (340) lies in the second plane and lands on the search's NA bin
    assert pl.hist_src[5, 254] == 5 * 256 + 254
    assert pl.hist_src[5, 255] == 6 * 256 + 0
    assert pl.hist_src[5, 340] == 6 * 256 + (340 - 255)
    assert pl.route_dst[5, 256 + (340 - 255)] == 340       # NA -> NA bin
    assert pl.route_dst[5, 255] == pl.n_search             # byte 255: nowhere
    assert pl.route_dst[0, 12] == 340 and pl.route_dst[0, 13] == pl.n_search
    assert not pl.grouped


def test_random_trees_with_sets_against_the_plain_reference(cloud8,
                                                            monkeypatch):
    """`model.predict` on seeded random trees (numeric and SET splits) put
    in a trained model's place, against the reference walking them from
    the raw table (the large-frame path: it walks `_trees` as they are)."""
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "1024")
    fr, X, y = _frame(300, 6000, 11)
    m = _gbm().train(y="y", training_frame=fr)
    rng = np.random.default_rng(3)
    tr = m._trees
    col = np.asarray(tr.col).copy()
    inner = 2 ** tr.depth - 1
    col[:, :inner] = rng.integers(0, 3, size=col[:, :inner].shape)
    col[:, 1:inner][rng.random((col.shape[0], inner - 1)) < 0.15] = -1
    col[:, inner:] = -1
    m._trees = E.TreeArrays(
        col=col, thr=rng.standard_normal(col.shape).astype(np.float32),
        na_left=rng.random(col.shape) < 0.5,
        value=rng.standard_normal(col.shape).astype(np.float32) * 0.3,
        depth=tr.depth, cover=tr.cover,
        catbits=rng.integers(0, 2 ** 32, size=np.asarray(tr.catbits).shape,
                             dtype=np.uint64).astype(np.uint32),
        col_is_cat=tr.col_is_cat, cat_levels=tr.cat_levels)
    model = check.read_model(m)
    p0, p1, lab = _scores(m, fr)
    want = ref.predict_proba(X, model)
    assert np.abs(p1 - want).max() < 2e-6
    assert np.unique(np.round(want, 5)).size > 50
    # the two controls read far from it on the same trees and rows
    for control in ({"precision": "bf16"}, {"clip_codes": 255}):
        assert np.abs(ref.predict_proba(X, model, **control)
                      - want).max() > 1e-3
    h2o3_tpu.remove(m.key)
    h2o3_tpu.remove(fr.key)


def test_spans_nest_and_counters_count(cloud8):
    fr, X, y = _frame(300, 9000, 5)
    counter = om.REGISTRY.get("h2o3_tree_set_split_nodes_total")
    before = counter.value(algo="gbm")
    SPANS.clear()
    m = _gbm().train(y="y", training_frame=fr)
    pred = m.predict(fr)
    spans = SPANS.snapshot()
    name = {s["id"]: s["name"] for s in spans}
    parent = {s["id"]: s["parent"] for s in spans}

    def ancestors(s):
        p, out = s["parent"], []
        while p is not None and p in name:
            out.append(name[p])
            p = parent[p]
        return out
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    # gbm.bin.cats: inside the `setup` phase, inside gbm.bin.codes
    cats = by["gbm.bin.cats"][0]
    assert ancestors(cats)[:3] == ["job.setup.cats", "gbm.bin.codes",
                                   "job.setup"]
    assert cats["attrs"]["columns"] == 2 and cats["attrs"]["levels"] == 300
    assert cats["attrs"]["bins"] == 300
    phases = next(j["phases"] for j in jobs_list() if j["dest"] == m.key)
    assert 0 < phases["setup.cats"] <= phases["setup"]
    # the published model's SET nodes, counted once
    sets, _ = check.set_nodes(check.read_model(m))
    assert counter.value(algo="gbm") == before + sets > before
    root = by["predict"][-1]
    assert root["attrs"]["set_nodes"] == sets
    assert root["attrs"]["cat_levels"] == 305
    for k in (pred.key, m.key, fr.key):
        h2o3_tpu.remove(k)


def test_the_large_frame_path_puts_tables_inside_dispatch(cloud8,
                                                          monkeypatch):
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "1024")
    fr, X, y = _frame(300, 5000, 6)
    m = _gbm().train(y="y", training_frame=fr)
    walks = E.WALKS.value(path="dense", block="4x32")
    SPANS.clear()
    pred = m.predict(fr)
    spans = SPANS.snapshot()
    ids = {s["id"]: s for s in spans}
    root = next(s for s in spans if s["name"] == "predict")
    assert root["attrs"]["path"] == "frame"
    t = next(s for s in spans if s["name"] == "predict.tables")
    assert ids[t["parent"]]["name"] == "predict.dispatch"
    assert ids[t["parent"]]["parent"] == root["id"]
    assert E.WALKS.value(path="dense", block="4x32") == walks + 1
    for k in (pred.key, m.key, fr.key):
        h2o3_tpu.remove(k)


# ---- the benchmark's cell, rehearsed ----------------------------------------
def _last_json(text):
    return json.loads([ln for ln in text.splitlines()
                       if ln.startswith("{")][-1])


def test_the_airline_cell_rehearses_correct(cloud8, capsys):
    from benchmark import run
    rc = run.main(["--workload", "gbm_airline.score", "--seed", "3100000019",
                   "--seconds", "1", "--trace", "0", "--rehearse"])
    line = _last_json(capsys.readouterr().out)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {"score_gap", "score_bad",
                                     "cat_levels_lost", "failed_operations"}
    assert line["compared"]["score_gap"]["value"] < 2e-6
    assert set(line["metrics"]) == {"score_rows_per_s", "setup_s"}


def test_both_controls_turn_the_airline_check_incorrect(cloud8, capsys):
    from benchmark.tools import controls_sets
    rc = controls_sets.main(["--config", "gbm_airline", "--seeds",
                             "3100000019", "--rehearse"])
    recs = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rc == 0 and [r["variant"] for r in recs] \
        == ["sound", "bf16", "clip255"]
    sound, bf16, clip = recs
    assert sound["within_limits"] and sound["cat_levels_lost"] == 0
    assert not bf16["within_limits"] and bf16["score_gap"] > 1e-3
    assert not clip["within_limits"] and clip["score_gap"] > 1e-3
    # the compared rows reach the levels past a code byte
    assert min(sound["rows_past_a_byte"][-2:]) > 0
    assert 0 < sound["set_nodes"] <= sound["split_nodes"]
