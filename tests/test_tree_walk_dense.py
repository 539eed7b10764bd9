"""The scoring walk's two bodies (models/tree/engine.py): `_walk_dense`
(every node of a tree for a tile of rows, no per-row index) against
`_walk_gather` (a chain of gathers per tree), bit for bit — `==`, never
allclose — and the rule that picks between them.

The CPU's matmul is exact whatever its operands; that the chip's bfloat16
products select a feature's bytes bit for bit is chip_smoke.py's
`walk_exact` phase.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.models.tree import engine as E

C = 6
GATHER, DENSE = E._walk_gather, E._walk_dense
NO_BITS = (jnp.zeros((1, 1, 1), jnp.uint32), jnp.zeros(1, bool))


def _ulp(x, up):
    return np.nextafter(x, np.float32(np.inf if up else -np.inf),
                        dtype=np.float32)


def _ensemble(rng, ntrees, depth, n_cols=C):
    """Heap arrays with early leaves at EVERY level (tree 0 is a root
    leaf, tree t < depth cuts a node of level t, the others a tenth of
    their nodes anywhere), both na_left directions, ±inf and NaN
    thresholds."""
    nodes = 2 ** (depth + 1) - 1
    inner = 2 ** depth - 1
    col = rng.integers(0, n_cols, size=(ntrees, nodes)).astype(np.int32)
    col[:, inner:] = -1
    col[:, 1:inner][rng.random((ntrees, inner - 1)) < 0.1] = -1
    col[0, 0] = -1
    for t in range(1, min(depth, ntrees)):
        col[t, 2 ** t - 1 + rng.integers(0, 2 ** t)] = -1
    thr = rng.standard_normal((ntrees, nodes)).astype(np.float32)
    for v in (np.inf, -np.inf, np.nan):
        thr[rng.random(thr.shape) < 0.02] = v
    nal = rng.random((ntrees, nodes)) < 0.5
    val = rng.standard_normal((ntrees, nodes)).astype(np.float32)
    tw = (rng.random(ntrees) * 2 + 0.25).astype(np.float32)   # non-unit
    return col, thr, nal, val, tw


def _rows(rng, n, col, thr):
    """Features that sit ON the thresholds they will meet, one ulp either
    side of them, NaN, ±inf, ±0 and subnormals among ordinary values."""
    X = rng.standard_normal((n, C)).astype(np.float32)
    split = np.argwhere(col >= 0)
    pick = split[rng.integers(0, len(split), size=n)]
    at = thr[pick[:, 0], pick[:, 1]]
    kind = rng.integers(0, 4, size=n)           # 3: leave the row as drawn
    edge = np.where(kind == 0, at, np.where(kind == 1, _ulp(at, True),
                                            _ulp(at, False)))
    r = np.flatnonzero(kind < 3)
    X[r, col[pick[r, 0], pick[r, 1]]] = edge[r]
    for v in (np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-40, -3e-39, 3.4e38):
        X[rng.random(X.shape) < 0.01] = v
    return X


# row counts: one row; whole tiles and ragged tails of the tile the depth
# gives (32768 rows at depth 8; 262144 and over at depths 5 and 1: one
# tile); odd counts and multiples of the 8-row granule
@pytest.mark.parametrize("n", [1, 4097, 70_001, 32_768, 65_576])
@pytest.mark.parametrize("depth", [1, 5, 8])
def test_dense_walk_is_the_gather_walk_bit_for_bit(depth, n):
    rng = np.random.default_rng(1000 * depth + n)
    col, thr, nal, val, tw = _ensemble(rng, 2 * depth + 1, depth)
    X = _rows(rng, n, col, thr)
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    want = np.asarray(GATHER(*args, *NO_BITS, depth=depth, has_cat=False))
    got = np.asarray(DENSE(*args, depth=depth))
    assert got.dtype == want.dtype == np.float32 and got.shape == (n,)
    assert np.array_equal(got, want), \
        f"{(got != want).sum()} of {n} rows differ"
    assert n == 1 or np.unique(want).size > 1     # rows went several ways


@pytest.mark.parametrize("depth", [9, 11])
def test_levels_under_the_path_matrix(depth, monkeypatch):
    """Depths over _PATH_LEVELS walk their lower levels by the position
    one-hot; small tiles so that several tiles and a tail run."""
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 1 << (depth + 8))
    rng = np.random.default_rng(depth)
    col, thr, nal, val, tw = _ensemble(rng, 3, depth)
    X = _rows(rng, 1000, col, thr)
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    want = np.asarray(GATHER(*args, *NO_BITS, depth=depth, has_cat=False))
    got = np.asarray(DENSE(*args, depth=depth))
    assert np.array_equal(got, want)


def test_a_row_sharded_frame_stays_sharded(cloud8, monkeypatch):
    """Each shard walks its own rows, tile by tile: the program made of
    the dense walk over a row-sharded X holds no collective, and its
    output is sharded as X is."""
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 32 * 24)    # 24-row tiles
    rng = np.random.default_rng(3)
    col, thr, nal, val, tw = _ensemble(rng, 4, 5)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=5)
    Xh = _rows(rng, 8 * 8 * 40, col, thr)
    X = jax.device_put(Xh, cloud8.rows_sharding(2))
    assert E._rows_mesh(X) is cloud8.mesh and E._rows_mesh(Xh) is None
    args = [jnp.asarray(a) for a in (col, thr, nal, val, tw)]
    text = E._ensemble_walk.__wrapped__.lower(
        X, *args, *NO_BITS, depth=5, has_cat=False,
        mesh=cloud8.mesh).compile().as_text()
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
    got = E.predict_ensemble(X, ta, weights=tw)
    assert got.sharding.is_equivalent_to(cloud8.rows_sharding(1), 1)
    want = np.asarray(GATHER(jnp.asarray(Xh), *args, *NO_BITS, depth=5,
                             has_cat=False))
    assert np.array_equal(np.asarray(got), want)


def _walks():
    return {p: E.WALKS.value(path=p) for p in ("dense", "gather")}


def test_the_shape_picks_the_body_and_the_counter_says_which():
    rng = np.random.default_rng(5)
    X28 = jnp.asarray(rng.standard_normal((64, 28)).astype(np.float32))

    def trees(depth, cat=False):
        col, thr, nal, val, _ = _ensemble(rng, 2, depth, n_cols=28)
        ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val,
                          depth=depth)
        if cat:
            ta.catbits = np.zeros(col.shape + (1,), np.uint32)
            ta.col_is_cat = np.arange(28) == 3
        return ta

    for ta, path in ((trees(8), "dense"), (trees(12), "dense"),
                     (trees(16), "gather"), (trees(5, cat=True), "gather")):
        before = _walks()
        out = E.predict_ensemble(X28, ta)
        assert out.shape == (64,)
        after = _walks()
        other = "gather" if path == "dense" else "dense"
        assert after[path] == before[path] + 1, (ta.depth, path)
        assert after[other] == before[other]
    assert E._walk_path(8, 28, False) == E._walk_path(5, 28, False) == "dense"
    assert E._walk_path(8, 28, True) == "gather"
    # depth 14 at HIGGS width is the deepest shape measured on the chip
    # (dense 5.9 x the faster); wider or deeper than that walks by gathers
    assert E._walk_path(14, 28, False) == "dense"
    assert E._walk_path(14, 40, False) == E._walk_path(15, 28, False) \
        == "gather"
    assert E._walk_path(20, 28, False) == "gather"     # default DRF
    assert E._walk_path(0, 28, False) == "gather"      # a root alone
