"""The scoring walk's two bodies (models/tree/engine.py): `_walk_dense`
(every node of a tree for a tile of rows, a 128-slot node block at a time,
no per-row index) against `_walk_gather` (a chain of gathers per tree), bit
for bit — `==`, never allclose — in every block regime (128 / 2^depth
trees a block, one tree a block, two blocks a tree's top), for the XLA body
and for the TPU kernel (ops/walk_pallas.py, interpreted here), and the rule
that picks between the bodies.

The CPU's matmul is exact whatever its operands; that the chip's bfloat16
products select a feature's bytes bit for bit is chip_smoke.py's
`walk_exact` phase.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.ops import walk_pallas as WP

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
    if len(split):                              # not a lone root leaf
        pick = split[rng.integers(0, len(split), size=n)]
        at = thr[pick[:, 0], pick[:, 1]]
        kind = rng.integers(0, 4, size=n)       # 3: leave the row as drawn
        edge = np.where(kind == 0, at, np.where(kind == 1, _ulp(at, True),
                                                _ulp(at, False)))
        r = np.flatnonzero(kind < 3)
        X[r, col[pick[r, 0], pick[r, 1]]] = edge[r]
    for v in (np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-40, -3e-39, 3.4e38):
        X[rng.random(X.shape) < 0.01] = v
    return X


def _same(depth, ntrees, n, seed, body="xla"):
    rng = np.random.default_rng(seed)
    col, thr, nal, val, tw = _ensemble(rng, ntrees, depth)
    X = _rows(rng, n, col, thr)
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    want = np.asarray(GATHER(*args, *NO_BITS, depth=depth, has_cat=False))
    if body == "xla":
        got = np.asarray(DENSE(*args, depth=depth))
    else:       # the TPU kernel, interpreted: the program has no such option
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(E._walk_dense.__wrapped__(*args, depth=depth))
    assert got.dtype == want.dtype == np.float32 and got.shape == (n,)
    assert np.array_equal(got, want), \
        f"{(got != want).sum()} of {n} rows differ"
    return want


# row counts: one row; whole tiles and ragged tails of the tile the depth
# gives (32768 rows at depth 8; 65536 at depths 5 and 1: two tiles at
# 70,001); odd counts and multiples of the 8-row granule
@pytest.mark.parametrize("n", [1, 4097, 70_001, 32_768, 65_576])
@pytest.mark.parametrize("depth", [1, 5, 8])
def test_dense_walk_is_the_gather_walk_bit_for_bit(depth, n):
    want = _same(depth, 2 * depth + 1, n, 1000 * depth + n)
    assert n == 1 or np.unique(want).size > 1     # rows went several ways


# every block regime: 16 trees a block (depths 1-3 are scored as 3 levels),
# 8, 4, 2, one tree a block at depth 7, two blocks a tree's top at depth 8;
# tree counts that are no multiple of a block's trees (one short group, its
# tail trees of weight 0), one tree, many groups; _ensemble cuts early
# leaves at levels 0, 1, 2, ...: their values cross a sub-tree split
@pytest.mark.parametrize("n", [1, 127, 4097])
@pytest.mark.parametrize("ntrees", [1, 3, 20, 50])
@pytest.mark.parametrize("depth", [1, 2, 3, 4, 5, 6, 7, 8])
def test_every_block_regime_is_the_gather_walk(depth, ntrees, n):
    _same(depth, ntrees, n, 7919 * depth + 31 * ntrees + n)


def _take_the_kernel(monkeypatch, tile_rows):
    """`_walk_dense` takes the kernel where `use_pallas()` says TPU; small
    tiles so that several tiles and a tail run."""
    monkeypatch.setattr(WP, "use_pallas", lambda: True)
    monkeypatch.setattr(WP, "TILE_ROWS", tile_rows)


@pytest.mark.parametrize("depth,ntrees,n", [
    (1, 3, 127), (3, 20, 1), (4, 50, 4097), (5, 20, 70_001), (5, 3, 1),
    (6, 3, 127), (7, 3, 4097), (8, 1, 127), (8, 10, 4097), (8, 3, 1)])
def test_the_kernel_is_the_gather_walk_bit_for_bit(depth, ntrees, n,
                                                   monkeypatch):
    _take_the_kernel(monkeypatch, 512)
    before = WP.KERNEL_TRACES.value(kernel="walk_dense_tile",
                                    L=str(1 << max(depth, 3)))
    _same(depth, ntrees, n, 104_729 * depth + 31 * ntrees + n, body="kernel")
    assert WP.KERNEL_TRACES.value(kernel="walk_dense_tile",
                                  L=str(1 << max(depth, 3))) == before + 1


def test_block_layout():
    """What `_perfect_tree` lays out: G trees side by side down the path
    matrix's diagonal; a deep tree's top as [root, left subtree], [root,
    right subtree]."""
    assert [E._block_regime(d) for d in (1, 3, 5, 7, 8, 12)] == \
        [(3, 16, 1), (3, 16, 1), (5, 4, 1), (7, 1, 1), (8, 1, 2), (12, 1, 2)]
    assert [E._block_label(d) for d in (1, 5, 7, 8, 12)] == \
        ["16x8", "4x32", "1x128", "0.5x256", "0.5x256"]
    P5, P8 = E._block_paths(5), E._block_paths(8)
    assert P5.shape == (1, 128, 128) and P8.shape == (2, 128, 128)
    one = E._path_matrix(5)
    for g in range(4):
        assert np.array_equal(P5[0, 32 * g:32 * g + 32, 32 * g:32 * g + 32],
                              one)
    assert np.abs(P5).sum() == 4 * np.abs(one).sum()
    # a block's column p is the path to position p (left) / 128 + p
    # (right) of level 8: the whole matrix's, the root's row in slot 0
    whole = E._path_matrix(8)
    slots = np.asarray(E._split_top(jnp.arange(256)))
    for k in (0, 1):
        assert np.array_equal(P8[k], whole[slots[128 * k:128 * k + 128],
                                           128 * k:128 * k + 128])
    assert slots[0] == slots[128] == 1
    assert sorted([*slots[1:128], *slots[129:]]) == list(range(2, 256))


@pytest.mark.parametrize("body", ["xla", "kernel"])
@pytest.mark.parametrize("depth", [9, 11])
def test_levels_under_the_path_matrix(depth, body, monkeypatch):
    """Depths over _PATH_LEVELS walk their lower levels by the position
    one-hot, a 128-slot block of the level at a time in the kernel; small
    tiles so that several tiles and a tail run."""
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 1 << (depth + 8))
    if body == "kernel":
        _take_the_kernel(monkeypatch, 256)
    _same(depth, 3, 1000, depth, body=body)


def test_a_row_sharded_frame_stays_sharded(cloud8, monkeypatch):
    """Each shard walks its own rows, tile by tile: the program made of
    the dense walk over a row-sharded X holds no collective, and its
    output is sharded as X is."""
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 128 * 24)   # 24-row tiles
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


def _walks(block):
    return {"dense": E.WALKS.value(path="dense", block=block),
            "gather": E.WALKS.value(path="gather", block="")}


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

    # the dense body's `block` says which regime the call took: trees a
    # 128-slot block x slots a tree
    for ta, path, block in (
            (trees(8), "dense", "0.5x256"), (trees(12), "dense", "0.5x256"),
            (trees(5), "dense", "4x32"), (trees(7), "dense", "1x128"),
            (trees(2), "dense", "16x8"), (trees(16), "gather", ""),
            # a categorical SET split no longer means gather: its 32 level
            # rows count as columns (tests/test_tree_walk_sets.py)
            (trees(5, cat=True), "dense", "4x32")):
        before = _walks(block)
        out = E.predict_ensemble(X28, ta)
        assert out.shape == (64,)
        after = _walks(block)
        other = "gather" if path == "dense" else "dense"
        assert after[path] == before[path] + 1, (ta.depth, path)
        assert after[other] == before[other]
    assert E._walk_path(8, 28, False) == E._walk_path(5, 28, False) == "dense"
    assert E._walk_path(8, 28, 32) == "dense"
    # depth 14 at HIGGS width is the deepest shape measured on the chip
    # (dense 5.9 x the faster); wider or deeper than that walks by gathers
    assert E._walk_path(14, 28, False) == "dense"
    assert E._walk_path(14, 40, False) == E._walk_path(15, 28, False) \
        == "gather"
    assert E._walk_path(20, 28, False) == "gather"     # default DRF
    assert E._walk_path(0, 28, False) == "gather"      # a root alone
