"""Reference-format MOJO interop (hex/genmodel zip layout).

Validation strategy:
  1. Round-trip: our GBM -> reference-format zip -> import -> identical
     predictions (exact: adjacent-float threshold conversion).
  2. A GENUINE H2O-produced MOJO (the reference repo's test fixture
     h2o-genmodel/src/test/resources/hex/genmodel/mojo.zip) imports and
     scores identically to an independent in-test byte-walker that ports
     SharedTreeMojoModel.scoreTree line by line.
"""

import os
import struct
import zipfile

import numpy as np
import pytest

from h2o3_tpu.core.frame import Frame, Vec
from h2o3_tpu.genmodel import h2o_mojo as HM

# an H2O-trained GBM MOJO from the reference repo's own test resources —
# it lives OUTSIDE this checkout, so its tests skip where it is absent
# instead of standing permanently red
FIXTURE = ("/root/reference/h2o-genmodel/src/test/resources/"
           "hex/genmodel/mojo.zip")
needs_fixture = pytest.mark.skipif(
    not os.path.exists(FIXTURE),
    reason=f"reference MOJO fixture not present: {FIXTURE}")


# ---------------------------------------------------------------------------
def _score_tree_reference(tree: bytes, row: np.ndarray) -> float:
    """Line-by-line port of SharedTreeMojoModel.scoreTree (the official
    scoring walk) used as an independent oracle."""
    pos = 0

    def u1():
        nonlocal pos
        v = tree[pos]
        pos += 1
        return v

    def u2():
        nonlocal pos
        v = struct.unpack_from("<H", tree, pos)[0]
        pos += 2
        return v

    def i4():
        nonlocal pos
        v = struct.unpack_from("<i", tree, pos)[0]
        pos += 4
        return v

    def f4():
        nonlocal pos
        v = struct.unpack_from("<f", tree, pos)[0]
        pos += 4
        return v

    while True:
        node_type = u1()
        col_id = u2()
        if col_id == 0xFFFF:
            return f4()
        na_sd = u1()
        na_vs_rest = na_sd == 1
        leftward = na_sd in (2, 4)
        lmask = node_type & 51
        equal = node_type & 12
        split_val = None
        bits = None
        bitoff = 0
        nbits = 32
        if not na_vs_rest:
            if equal == 0:
                split_val = f4()
            elif equal == 8:
                bits = tree[pos: pos + 4]
                pos += 4
            else:
                bitoff = u2()
                nbits = i4()
                nb = (nbits + 7) // 8
                bits = tree[pos: pos + nb]
                pos += nb
        d = row[col_id]
        if np.isnan(d) or (equal != 0 and not
                           (0 <= int(d) - bitoff < nbits)):
            go_right = not leftward
        elif na_vs_rest:
            go_right = False
        elif equal == 0:
            go_right = d >= split_val
        else:
            idx = int(d) - bitoff
            go_right = bool(bits[idx >> 3] & (1 << (idx & 7)))
        if go_right:
            if lmask <= 3:
                n = int.from_bytes(tree[pos: pos + lmask + 1], "little")
                pos += lmask + 1 + n
            elif lmask == 48:
                pos += 4
            lmask = (node_type & 0xC0) >> 2
        else:
            if lmask <= 3:
                pos += lmask + 1
        if lmask & 16:
            return f4()


# ---------------------------------------------------------------------------
def _make_frame(rng, n=3000, with_cat=False):
    x0 = rng.normal(0, 1, n).astype(np.float32)
    x1 = rng.normal(0, 1, n).astype(np.float32)
    cols = {"x0": x0, "x1": x1}
    yv = 1.5 * x0 - x1 + rng.normal(0, 0.2, n)
    vecs, names = [], []
    if with_cat:
        lv = rng.integers(0, 12, n)
        good = np.array([1, 0] * 6)
        yv += 2.0 * good[lv]
        names.append("cat")
        vecs.append(Vec.from_numpy(lv.astype(np.float32),
                                   domain=[f"L{i}" for i in range(12)]))
    for k, v in cols.items():
        names.append(k)
        vecs.append(Vec.from_numpy(v))
    names.append("y")
    vecs.append(Vec.from_numpy(yv.astype(np.float32)))
    return Frame(names, vecs), names[:-1]


def test_roundtrip_regression(tmp_path):
    from h2o3_tpu.models.tree.shared_tree import H2OGradientBoostingEstimator
    rng = np.random.default_rng(0)
    fr, xs = _make_frame(rng)
    m = H2OGradientBoostingEstimator(ntrees=10, max_depth=4, seed=1,
                                     score_tree_interval=100)
    m.train(x=xs, y="y", training_frame=fr)
    p_orig = np.asarray(m.predict(fr).matrix(["predict"]))[: fr.nrows, 0]

    path = str(tmp_path / "m.zip")
    HM.export_h2o_mojo(m, path)
    mm = HM.import_h2o_mojo(path)
    X = np.asarray(m._dinfo.matrix(fr))[: fr.nrows]
    p_im = mm.predict_raw(X)
    assert np.allclose(p_im, p_orig, atol=1e-5), \
        np.abs(p_im - p_orig).max()
    # and the official byte-walk agrees with the import on every tree
    with zipfile.ZipFile(path) as z:
        for t in range(5):
            tb = z.read(f"trees/t00_{t:03d}.bin")
            for r in range(10):
                ref = _score_tree_reference(tb, X[r].astype(np.float64))
                import jax.numpy as jnp
                from h2o3_tpu.models.tree import engine as E
                one = E.predict_ensemble(
                    jnp.asarray(X[r: r + 1]),
                    _slice_tree(mm.trees_k[0], t))
                assert abs(float(one[0]) - ref) < 1e-6


def _slice_tree(ta, t):
    from h2o3_tpu.models.tree.engine import TreeArrays
    return TreeArrays(
        col=ta.col[t: t + 1], thr=ta.thr[t: t + 1],
        na_left=ta.na_left[t: t + 1], value=ta.value[t: t + 1],
        depth=ta.depth,
        catbits=None if ta.catbits is None else ta.catbits[t: t + 1],
        col_is_cat=ta.col_is_cat)


def test_roundtrip_binomial_with_categoricals(tmp_path):
    from h2o3_tpu.models.tree.shared_tree import H2OGradientBoostingEstimator
    rng = np.random.default_rng(1)
    fr, xs = _make_frame(rng, with_cat=True)
    # binarize the response
    yv = np.asarray(fr.vec("y").to_numpy())
    fr2 = Frame(fr.names[:-1] + ["yb"],
                [fr.vec(c) for c in fr.names[:-1]]
                + [Vec.from_numpy((yv > np.median(yv)).astype(np.float32),
                                  domain=["no", "yes"])])
    m = H2OGradientBoostingEstimator(ntrees=8, max_depth=4, seed=1,
                                     score_tree_interval=100)
    m.train(x=xs, y="yb", training_frame=fr2)
    pf = m.predict(fr2)
    p_orig = np.asarray(pf.matrix([pf.names[-1]]))[: fr2.nrows, 0]

    path = str(tmp_path / "mb.zip")
    HM.export_h2o_mojo(m, path)
    mm = HM.import_h2o_mojo(path)
    assert mm.n_classes == 2
    X = np.asarray(m._dinfo.matrix(fr2))[: fr2.nrows]
    P = mm.predict_raw(X)
    assert np.allclose(P[:, 1], p_orig, atol=1e-5), \
        np.abs(P[:, 1] - p_orig).max()
    # oracle check incl. the categorical bitset nodes
    with zipfile.ZipFile(path) as z:
        tb = z.read("trees/t00_000.bin")
    for r in range(20):
        ref = _score_tree_reference(tb, X[r].astype(np.float64))
        import jax.numpy as jnp
        from h2o3_tpu.models.tree import engine as E
        one = E.predict_ensemble(jnp.asarray(X[r: r + 1]),
                                 _slice_tree(mm.trees_k[0], 0))
        assert abs(float(one[0]) - ref) < 1e-6


@needs_fixture
def test_import_genuine_h2o_fixture():
    """The reference repo's own H2O-trained GBM MOJO imports and our
    batch scorer matches the official scoreTree byte-walk exactly."""
    mm = HM.import_h2o_mojo(FIXTURE)
    assert mm.info["algo"] == "gbm"
    ntrees = int(mm.info["n_trees"])
    assert ntrees == 20
    nfeat = mm.n_features
    rng = np.random.default_rng(0)
    X = rng.normal(0, 50, (32, nfeat)).astype(np.float32)
    X[rng.random(X.shape) < 0.05] = np.nan

    with zipfile.ZipFile(FIXTURE) as z:
        total = np.zeros(32)
        for t in range(ntrees):
            tb = z.read(f"trees/t00_{t:03d}.bin")
            for r in range(32):
                total[r] += _score_tree_reference(
                    tb, X[r].astype(np.float64))
    expected = mm.f0 + total
    got = mm.predict_raw(X)
    assert np.allclose(got, expected, atol=1e-4), \
        np.abs(got - expected).max()


@needs_fixture
def test_generic_estimator_loads_reference_mojo():
    """H2OGenericEstimator imports a genuine H2O-3 MOJO zip (the VERDICT's
    ecosystem-parity gate) and scores through the normal predict path."""
    from h2o3_tpu.models.generic import H2OGenericEstimator
    g = H2OGenericEstimator(path=FIXTURE)
    assert g.original_algo == "gbm"
    mm = g._ref
    rng = np.random.default_rng(1)
    n = 16
    cols, vecs = [], []
    for name in mm.columns[: mm.n_features]:
        cols.append(name)
        vecs.append(Vec.from_numpy(
            rng.normal(0, 10, n).astype(np.float32)))
    fr = Frame(cols, vecs)
    out = g.predict(fr)
    p = np.asarray(out.matrix(["predict"]))[:n, 0]
    assert np.isfinite(p).all()
    # must not be the bare intercept — trees contribute
    assert np.std(p) > 0


@needs_fixture
def test_export_structural_conformance_with_genuine_mojo(tmp_path):
    """Export-side format check against the genuine H2O artifact: every
    zip entry class and model.ini key the reference genmodel scorer reads
    from its own MOJO must exist in OUR export with the same layout.
    (The Java scorer itself cannot run in this image — no JVM — so
    conformance is held to the fixture's structure plus the byte-walk
    round-trip tests above.)"""
    import h2o3_tpu.models as models
    from h2o3_tpu.core.frame import Frame
    rng = np.random.default_rng(3)
    n = 300
    X = rng.normal(0, 1, (n, 4))
    yv = X[:, 0] * 2 + np.sin(X[:, 1]) + rng.normal(0, 0.1, n)
    f = Frame.from_dict({**{f"x{j}": X[:, j] for j in range(4)}, "y": yv})
    m = models.H2OGradientBoostingEstimator(ntrees=5, max_depth=3, seed=1)
    m.train(y="y", training_frame=f)
    out = str(tmp_path / "exp.zip")
    HM.export_h2o_mojo(m, out)

    def entry_classes(path):
        with zipfile.ZipFile(path) as z:
            names = z.namelist()
        classes = set()
        for nm in names:
            if nm.startswith("trees/"):
                classes.add("trees/t.bin")
            elif nm.startswith("domains/"):
                classes.add("domains/")
            else:
                classes.add(nm)
        return classes

    def ini_keys(path):
        with zipfile.ZipFile(path) as z:
            txt = z.read("model.ini").decode()
        keys = set()
        for line in txt.splitlines():
            if "=" in line and not line.startswith("["):
                keys.add(line.split("=")[0].strip())
        return keys

    genuine_cls = entry_classes(FIXTURE)
    ours_cls = entry_classes(out)
    # the genuine artifact's entry classes the scorer reads must all be
    # present (domains/ only when categorical columns exist)
    # experimental/* is diagnostic-only — the scorer never reads it
    required = {c for c in genuine_cls
                if c != "domains/" and not c.startswith("experimental/")}
    missing = {c for c in required if c not in ours_cls}
    assert not missing, missing

    need_keys = {"algorithm", "category", "n_features", "n_classes",
                 "n_columns", "n_domains", "n_trees", "mojo_version"}
    gk = ini_keys(FIXTURE)
    ok = ini_keys(out)
    assert need_keys <= gk       # sanity: the fixture really has them
    assert need_keys <= ok, need_keys - ok
