"""Binned (pre-quantized) tree engine: kernels, categorical SET splits,
monotone constraints.

Reference behaviors under test: hex/tree/DTree.java categorical group
splits (water/util/IcedBitSet.java), hex/tree/Constraints.java monotone
constraints, hex/tree/GlobalQuantilesCalc.java global binning,
hex/tree/ScoreBuildHistogram2.java histogram semantics.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from h2o3_tpu.core.frame import Frame, Vec
from h2o3_tpu.models.tree import binned as BN
from h2o3_tpu.ops import hist_pallas as HP


def _auc(y, p):
    order = np.argsort(p)
    r = np.empty(len(y))
    r[order] = np.arange(1, len(y) + 1)
    npos = y.sum()
    nneg = len(y) - npos
    return (r[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)


# ===========================================================================
def test_sbh_hist_xla_matches_numpy():
    rng = np.random.default_rng(0)
    n, C, nb, L, base = 5000, 8, 128, 8, 7
    n_pad = -(-n // HP.BLOCK_ROWS) * HP.BLOCK_ROWS
    codesT = np.zeros((C, n_pad), np.int32)
    codesT[:, :n] = rng.integers(0, nb, (C, n))
    heap = np.full(n_pad, 10 ** 6, np.int32)
    heap[:n] = rng.integers(base, base + L, n)
    stats = np.zeros((4, n_pad), np.float32)
    stats[:, :n] = rng.normal(0, 1, (4, n))
    h = np.asarray(HP.sbh_hist_xla(jnp.asarray(codesT), jnp.asarray(heap),
                                   jnp.asarray(stats), base=base, L=L,
                                   n_bins=nb))
    ref = np.zeros((L, C, 4, nb), np.float32)
    for c in range(C):
        for s in range(4):
            np.add.at(ref[:, c, s, :],
                      (heap[:n] - base, codesT[c, :n]), stats[s, :n])
    assert np.allclose(h[:L, :C], ref, atol=1e-3)


def test_sbh_route_xla_semantics():
    # two leaves at level 1 (base=1): leaf 0 splits on col 0 at bin 5,
    # NA goes left; leaf 1 is terminal
    nb = 128
    n_pad = HP.BLOCK_ROWS
    codesT = np.zeros((8, n_pad), np.int32)
    codesT[0, :6] = [3, 5, 6, 127, 0, 9]   # row 3 = NA code (b_val=127)
    heap = np.array([1, 1, 1, 1, 2, 2] + [0] * (n_pad - 6), np.int32)
    tbl = np.zeros((8, 8), np.float32)
    tbl[0, 0] = 0      # split col
    tbl[1, 0] = 1      # did
    tbl[2, 0] = 5      # bin
    tbl[3, 0] = 1      # na goes left
    route = np.zeros((8, nb), np.float32)
    route[0, 6:] = 1.0          # code > 5 goes right
    route[0, 127] = 0.0         # NA left
    valtab = np.zeros((8, 640), np.float32)
    F = np.zeros(n_pad, np.float32)
    nh, _ = HP.sbh_route_xla(jnp.asarray(codesT), jnp.asarray(heap),
                             jnp.asarray(tbl), jnp.asarray(route),
                             jnp.asarray(valtab), jnp.asarray(F),
                             base=1, L=2, na_code=127)
    nh = np.asarray(nh)
    # leaf 0 (heap 1): children 3 (left) / 4 (right)
    assert nh[0] == 3          # code 3 <= 5 -> left
    assert nh[1] == 3          # code 5 <= 5 -> left
    assert nh[2] == 4          # code 6 > 5 -> right
    assert nh[3] == 3          # NA -> left
    assert nh[4] == 2 and nh[5] == 2   # terminal leaf keeps its node


# ===========================================================================
def _frame_with_cat(n, k, rng):
    """Categorical column whose per-level response means are NON-monotone in
    the level id — a SET split separates good/bad levels in one cut, while
    label-encoded numeric splits need many."""
    lv = rng.integers(0, k, n)
    good = rng.permutation(k) < k // 2        # random half of levels "good"
    logit = np.where(good[lv], 1.6, -1.6)
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    x2 = rng.normal(0, 1, n).astype(np.float32)
    domain = [f"lv{i}" for i in range(k)]
    fr = Frame(["cat", "x2", "y"],
               [Vec.from_numpy(lv.astype(np.float32), domain=domain),
                Vec.from_numpy(x2),
                Vec.from_numpy(y.astype(np.float32),
                               domain=["no", "yes"])])
    return fr, y


def test_categorical_set_splits_beat_label_encoding():
    from h2o3_tpu.models.tree.shared_tree import H2OGradientBoostingEstimator
    rng = np.random.default_rng(7)
    fr, y = _frame_with_cat(8000, 32, rng)
    common = dict(ntrees=2, max_depth=2, learn_rate=0.5, seed=1,
                  score_tree_interval=100)
    m_set = H2OGradientBoostingEstimator(**common)       # binned: SET splits
    m_set.train(x=["cat", "x2"], y="y", training_frame=fr)
    m_lab = H2OGradientBoostingEstimator(
        histogram_type="UniformAdaptive", **common)      # label-order splits
    m_lab.train(x=["cat", "x2"], y="y", training_frame=fr)
    pf1 = m_set.predict(fr)
    pf2 = m_lab.predict(fr)
    p_set = np.asarray(pf1.matrix([pf1.names[-1]]))[: fr.nrows, 0]
    p_lab = np.asarray(pf2.matrix([pf2.names[-1]]))[: fr.nrows, 0]
    auc_set = _auc(y, p_set)
    auc_lab = _auc(y, p_lab)
    # the SET split should capture the good-level subset far faster
    assert auc_set > auc_lab + 0.02, (auc_set, auc_lab)
    assert auc_set > 0.70, auc_set


def test_monotone_constraints_enforced():
    from h2o3_tpu.models.tree.shared_tree import H2OGradientBoostingEstimator
    rng = np.random.default_rng(3)
    n = 6000
    x0 = rng.normal(0, 1, n).astype(np.float32)
    x1 = rng.normal(0, 1, n).astype(np.float32)
    # monotone signal + strong non-monotone noise component
    yv = (0.8 * x0 + 1.2 * np.sin(3 * x0) + 0.5 * x1
          + rng.normal(0, 0.3, n)).astype(np.float32)
    fr = Frame(["x0", "x1", "y"],
               [Vec.from_numpy(x0), Vec.from_numpy(x1), Vec.from_numpy(yv)])
    m = H2OGradientBoostingEstimator(
        ntrees=20, max_depth=4, learn_rate=0.2, seed=1,
        monotone_constraints={"x0": 1}, score_tree_interval=100)
    m.train(x=["x0", "x1"], y="y", training_frame=fr)
    # partial dependence over x0 with x1 fixed: must be non-decreasing
    grid = np.linspace(-2.5, 2.5, 41, dtype=np.float32)
    test = Frame(["x0", "x1"],
                 [Vec.from_numpy(grid),
                  Vec.from_numpy(np.zeros_like(grid))])
    pd = np.asarray(m.predict(test).matrix(["predict"]))[: len(grid), 0]
    viol = np.diff(pd) < -1e-5
    assert not viol.any(), pd
    # sanity: the unconstrained model DOES violate monotonicity on this data
    m2 = H2OGradientBoostingEstimator(
        ntrees=20, max_depth=4, learn_rate=0.2, seed=1,
        score_tree_interval=100)
    m2.train(x=["x0", "x1"], y="y", training_frame=fr)
    pd2 = np.asarray(m2.predict(test).matrix(["predict"]))[: len(grid), 0]
    assert (np.diff(pd2) < -1e-5).any()


def test_binned_matches_adaptive_quality():
    """The default (binned) engine reaches the same training AUC class as
    the H2O-exact adaptive engine on numeric data."""
    from h2o3_tpu.models.tree.shared_tree import H2OGradientBoostingEstimator
    rng = np.random.default_rng(0)
    n, C = 6000, 6
    X = rng.normal(0, 1, (n, C)).astype(np.float32)
    logit = 1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    cols = [f"c{i}" for i in range(C)]
    fr = Frame(cols + ["y"],
               [Vec.from_numpy(X[:, i]) for i in range(C)]
               + [Vec.from_numpy(y, domain=["n", "yes"])])
    aucs = {}
    for ht in ("AUTO", "UniformAdaptive"):
        m = H2OGradientBoostingEstimator(ntrees=20, max_depth=4, seed=1,
                                         histogram_type=ht,
                                         score_tree_interval=100)
        m.train(x=cols, y="y", training_frame=fr)
        pf = m.predict(fr)
        p = np.asarray(pf.matrix([pf.names[-1]]))[: fr.nrows, 0]
        aucs[ht] = _auc(y, p)
    assert abs(aucs["AUTO"] - aucs["UniformAdaptive"]) < 0.03, aucs
    assert aucs["AUTO"] > 0.8, aucs


# ===========================================================================
# Round-4 gates: uint8 code planes end-to-end, packed-plane round trip,
# fused route+hist.
def test_quantize_emits_uint8_codes():
    rng = np.random.default_rng(5)
    X = rng.normal(0, 1, (700, 5)).astype(np.float32)
    X[rng.random(X.shape) < 0.07] = np.nan
    spec = BN.make_bins(X, np.zeros(5, bool), 64)
    codes = BN.quantize(jnp.asarray(X), spec)
    assert codes.dtype == jnp.uint8
    cn = np.asarray(codes)
    # NA rows carry the NA code; values stay below it
    assert (cn[:5, :700].T[np.isnan(X)] == spec.na_code).all()
    assert (cn <= spec.na_code).all()


def test_pack_codes_roundtrip_and_layout():
    rng = np.random.default_rng(6)
    for c_pad in (8, 16, 40):          # one sub-tile + two tiled planes
        u8 = rng.integers(0, 256, (c_pad, 512)).astype(np.uint8)
        packed = HP.pack_codes(jnp.asarray(u8))
        assert packed.dtype == jnp.int32
        assert packed.shape == (HP.packed_words(c_pad), 512)
        back = np.asarray(HP.unpack_codes(packed, c_pad=c_pad))
        np.testing.assert_array_equal(back, u8)
    # 1 byte/code in HBM: the packed plane never exceeds ceil-to-tile of
    # the uint8 plane's bytes (vs 4x for the old i32 layout)
    assert HP.packed_words(32) * 4 == 32


def test_uint8_vs_i32_code_planes_bit_exact():
    """The XLA kernels must be dtype-agnostic: the uint8 plane produces
    bit-identical histograms and routing to the legacy i32 plane,
    plane-for-plane (ISSUE 14 acceptance)."""
    rng = np.random.default_rng(7)
    n_pad, c_pad, L, base, nb, b_val = 2048, 8, 8, 7, 128, 100
    u8 = rng.integers(0, b_val + 1, (c_pad, n_pad)).astype(np.uint8)
    i32 = u8.astype(np.int32)
    heap = jnp.asarray(rng.integers(base, base + L, n_pad), jnp.int32)
    stats = jnp.asarray(rng.normal(0, 1, (4, n_pad)), jnp.float32)
    for half in (False, True):
        h_u8 = HP.sbh_hist_xla(jnp.asarray(u8), heap, stats, base=base,
                               L=L, n_bins=nb, half=half)
        h_i32 = HP.sbh_hist_xla(jnp.asarray(i32), heap, stats, base=base,
                                L=L, n_bins=nb, half=half)
        np.testing.assert_array_equal(np.asarray(h_u8), np.asarray(h_i32))
    tbl = np.zeros((8, 8), np.float32)
    tbl[0, :L] = rng.integers(0, c_pad, L)
    tbl[1, :L] = 1
    route_f = jnp.asarray((rng.random((8, nb)) < 0.5).astype(np.float32))
    args = dict(base=base, L=L, na_code=b_val)
    h1, _ = HP.sbh_route_xla(jnp.asarray(u8), heap, jnp.asarray(tbl),
                             route_f, **args)
    h2, _ = HP.sbh_route_xla(jnp.asarray(i32), heap, jnp.asarray(tbl),
                             route_f, **args)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


def test_fused_route_hist_matches_sequential():
    """sbh_route_hist (fused dispatcher) == explicit route then half-hist,
    heaps and histograms (ISSUE 14 acceptance: fused vs unfused identical;
    on CPU both ride the XLA reference pair — the on-chip fused Pallas
    program is held to the same contract by ops/parity.py)."""
    rng = np.random.default_rng(8)
    n_pad, c_pad, nb, b_val = 2048, 8, 128, 100
    L_h = 8
    L_r = L_h >> 1
    base_r, base_h = L_r - 1, L_h - 1
    u8 = jnp.asarray(rng.integers(0, b_val + 1, (c_pad, n_pad)), jnp.uint8)
    heap = jnp.asarray(rng.integers(base_r, base_r + L_r, n_pad), jnp.int32)
    stats = jnp.asarray(rng.normal(0, 1, (4, n_pad)), jnp.float32)
    tbl = np.zeros((8, 8), np.float32)
    tbl[0, :L_r] = rng.integers(0, c_pad, L_r)
    tbl[1, :L_r] = rng.random(L_r) < 0.8
    tbl = jnp.asarray(tbl)
    route_f = jnp.asarray((rng.random((8, nb)) < 0.5).astype(np.float32))
    nh, hist = HP.sbh_route_hist(
        u8, heap, tbl, route_f, stats, base_r=base_r, L_r=L_r,
        base_h=base_h, L_h=L_h, n_bins=nb, na_code=b_val)
    nh_ref, _ = HP.sbh_route_xla(u8, heap, tbl, route_f,
                                 base=base_r, L=L_r, na_code=b_val)
    hist_ref = HP.sbh_hist_xla(u8, nh_ref, stats, base=base_h,
                               L=L_h, n_bins=nb, half=True)
    np.testing.assert_array_equal(np.asarray(nh), np.asarray(nh_ref))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_ref))


@pytest.mark.parametrize("flag", ["int8_hist", "radix_shallow",
                                  "fused_level"])
def test_kernel_selecting_parameters_are_gone(flag):
    """ISSUE 32: no option selects a histogram kernel — the three former
    estimator parameters are unknown, like any other misspelling."""
    import h2o3_tpu.models  # noqa: F401 — registers every tree estimator
    from h2o3_tpu.models.tree.shared_tree import (
        H2OGradientBoostingEstimator, SharedTreeEstimator)
    with pytest.raises(ValueError, match="unknown parameters"):
        H2OGradientBoostingEstimator(**{flag: True})
    assert flag not in SharedTreeEstimator._tree_defaults
    todo, seen = [SharedTreeEstimator], []
    while todo:
        cls = todo.pop()
        seen.append(cls)
        todo += cls.__subclasses__()
        assert flag not in getattr(cls, "_defaults", {}), cls.__name__
    assert len(seen) >= 6, seen     # GBM, DRF, XGBoost, IF, EIF + the base


def test_tree_codes_plane_registered_with_pager(monkeypatch):
    """With tiering active, the training code plane is registered with
    the DKV pager — pinned (never an LRU victim mid-build) and at uint8
    size (1 byte/code), so HBM budget accounting finally sees the tree
    engine's biggest resident plane."""
    from h2o3_tpu.core.tiering import PAGER
    from h2o3_tpu.models.tree.shared_tree import H2OGradientBoostingEstimator
    monkeypatch.setenv("H2O3_TPU_TIERING", "1")
    seen = []
    orig = PAGER.new_chunk

    def spy(data, mask, host=None, label="", pinned=0):
        ch = orig(data, mask, host=host, label=label, pinned=pinned)
        if label == "tree_codes":
            seen.append(ch)
        return ch

    monkeypatch.setattr(PAGER, "new_chunk", spy)
    rng = np.random.default_rng(11)
    n = 600
    fr = Frame(["a", "b", "y"],
               [Vec.from_numpy(rng.normal(size=n).astype(np.float32)),
                Vec.from_numpy(rng.normal(size=n).astype(np.float32)),
                Vec.from_numpy((rng.random(n) < 0.5).astype(np.float32),
                               domain=["no", "yes"])])
    m = H2OGradientBoostingEstimator(ntrees=2, max_depth=3, seed=1,
                                     score_tree_interval=100)
    m.train(x=["a", "b"], y="y", training_frame=fr)
    assert seen, "code plane was not registered with the tier pager"
    ch = seen[0]
    assert ch.pinned >= 1
    data, mask = ch._dev
    assert mask is None
    # 1 byte/code either way: uint8 plane on CPU, packed i32 words on TPU
    want = jnp.int32 if HP.use_pallas() else jnp.uint8
    assert data.dtype == want
