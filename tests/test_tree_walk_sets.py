"""Categorical SET splits on the scoring walk's dense body
(models/tree/engine.py): a node's go-right set matched densely — the
rows' level one-hots times the blocks' bit matrices — against
`_walk_gather`'s per-row bit look-up, bit for bit (`==`, never allclose),
on ensembles that mix numeric and SET splits, in every block regime, with
the columns' levels known (`TreeArrays.cat_levels`) and not (a MOJO: all
32 W bits of a set); the same for the TPU kernel's set variant
(ops/walk_pallas.py: the level one-hot a VMEM tile, one int8 product more a
node block), interpreted here; and the rules that pick the body and its
form.

The CPU's matmul is exact whatever its operands; that the chip's int8 and
bfloat16 products of {0, 1} are, is chip_smoke.py's `walk_sets` phase.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.ops import walk_pallas as WP
from test_tree_walk_dense import _take_the_kernel

C = 6
IS_CAT = np.array([True, False, True, False, False, True])


def _ensemble(rng, ntrees, depth, levels):
    """Heap arrays mixing numeric and SET splits over C columns, early
    leaves at every level, random sets W words wide — wider than the
    widest column's levels where 32 does not divide them."""
    nodes, inner = 2 ** (depth + 1) - 1, 2 ** depth - 1
    col = rng.integers(0, C, size=(ntrees, nodes)).astype(np.int32)
    col[:, inner:] = -1
    col[:, 1:inner][rng.random((ntrees, inner - 1)) < 0.1] = -1
    for t in range(1, min(depth, ntrees)):
        col[t, 2 ** t - 1 + rng.integers(0, 2 ** t)] = -1
    thr = rng.standard_normal((ntrees, nodes)).astype(np.float32)
    nal = rng.random((ntrees, nodes)) < 0.5
    val = rng.standard_normal((ntrees, nodes)).astype(np.float32)
    tw = (rng.random(ntrees) * 2 + 0.25).astype(np.float32)
    W = -(-int(levels.max()) // 32) + 1
    sets = rng.integers(0, 2 ** 32, size=(ntrees, nodes, W),
                        dtype=np.uint64).astype(np.uint32)
    return col, thr, nal, val, tw, sets


def _rows(rng, n, levels):
    """Level ids over every level and past them (past the bitset too),
    negative and fractional ones, NaN and ±inf, beside numeric values."""
    X = rng.standard_normal((n, C)).astype(np.float32)
    for c in np.flatnonzero(levels):
        X[:, c] = rng.integers(-3, levels[c] + 70, size=n) \
            + rng.choice([0.0, 0.5, 0.99], size=n)
    for v in (np.nan, np.inf, -np.inf, 1e12, -0.0):
        X[rng.random(X.shape) < 0.01] = v
    return X


def _both(depth, ntrees, n, widest, seed, known=True, body="xla",
          levels=None):
    """`levels`: a column's levels, 0 for a numeric one (by default three
    categorical columns of `widest`, 7 and 31); `body` "kernel": the TPU
    kernel, interpreted — the program has no such option."""
    rng = np.random.default_rng(seed)
    levels = np.array([widest, 0, 7, 0, 0, 31] if levels is None else levels)
    col, thr, nal, val, tw, sets = _ensemble(rng, ntrees, depth, levels)
    X = _rows(rng, n, levels)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=depth,
                      catbits=sets, col_is_cat=levels > 0,
                      cat_levels=levels if known else None)
    cats = E._cat_layout(ta, C)
    hold = np.zeros(C, np.int32)
    hold[[c for c, _ in cats]] = [k for _, k in cats]
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    want = np.asarray(E._walk_gather(
        *args, jnp.asarray(sets), jnp.asarray(levels > 0), jnp.asarray(hold),
        depth=depth, has_cat=True))
    if body == "xla":
        got = np.asarray(E._walk_dense(*args, jnp.asarray(sets), depth=depth,
                                       cats=cats))
    else:
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(E._walk_dense.__wrapped__(
                *args, jnp.asarray(sets), depth=depth, cats=cats))
    assert got.dtype == want.dtype == np.float32 and got.shape == (n,)
    assert np.array_equal(got, want), \
        f"{(got != want).sum()} of {n} rows differ"
    return ta, X, tw, want, cats


# every block regime (16 / 4 trees a block, one tree a block, two blocks a
# tree's top, a level under the path-matched eight) x a column of few
# levels, of some, past a code byte, and past two; 1,037 rows: off every
# tile's edge
@pytest.mark.parametrize("widest", [5, 40, 300, 700])
@pytest.mark.parametrize("depth", [1, 3, 5, 7, 8, 9])
def test_dense_set_walk_is_the_gather_walk_bit_for_bit(depth, widest):
    ta, X, tw, want, cats = _both(depth, 7, 1037, widest,
                                  1000 * depth + widest)
    assert dict(cats) == {0: widest, 2: 7, 5: 31}
    assert np.unique(want).size > 1               # rows went several ways
    # the entry point takes the dense body and counts it so
    before = E.WALKS.value(path="dense", block=E._block_label(depth))
    got = np.asarray(E.predict_ensemble(jnp.asarray(X), ta, weights=tw))
    assert np.array_equal(got, want)
    assert E.WALKS.value(path="dense", block=E._block_label(depth)) \
        == before + 1


@pytest.mark.parametrize("depth,widest", [(3, 40), (5, 300), (8, 5)])
def test_levels_not_known_match_every_bit_of_a_set(depth, widest):
    """A TreeArrays without `cat_levels` (a MOJO): every categorical column
    takes all 32 W bits, and a level id is held to the bitset as the gather
    body always held it."""
    ta, X, tw, want, cats = _both(depth, 5, 517, widest, depth + widest,
                                  known=False)
    bits = 32 * ta.catbits.shape[-1]
    assert dict(cats) == {0: bits, 2: bits, 5: bits}
    no_hold = np.asarray(E._walk_gather(
        *[jnp.asarray(a) for a in (X, ta.col, ta.thr, ta.na_left, ta.value,
                                   tw, ta.catbits, IS_CAT)],
        depth=depth, has_cat=True))
    assert np.array_equal(no_hold, want)


def test_several_row_tiles_and_a_tail(monkeypatch):
    """Small tiles so that the numeric tile bound and the set tile bound
    both cut the frame, with an overlapping last tile."""
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 128 * 200)
    _both(5, 9, 1037, 300, 77)
    monkeypatch.setattr(E, "_SET_TILE_CELLS", 3 * 128 * 64)   # 64-row tiles
    _both(5, 9, 1037, 300, 78)


def test_a_row_sharded_frame_with_sets_stays_sharded(cloud8, monkeypatch):
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 128 * 24)
    ta, Xh, tw, want, _ = _both(5, 4, 8 * 8 * 40, 300, 5)
    X = jax.device_put(Xh, cloud8.rows_sharding(2))
    got = E.predict_ensemble(X, ta, weights=tw)
    assert got.sharding.is_equivalent_to(cloud8.rows_sharding(1), 1)
    assert np.array_equal(np.asarray(got), want)


def _set_traces(depth):
    return WP.KERNEL_TRACES.value(kernel="walk_dense_tile_sets",
                                  L=str(1 << max(depth, 3)))


# the kernel's set variant in every block regime (16 / 4 trees a block, one
# tree a block, two blocks a tree's top, a position level under them, its
# block index dynamic) x level rows that fill no int8 tile of 32 nor an MXU
# tile of 128 (43, 78, 338, 738); 1,037 rows in tiles of 512: two whole
# tiles and a tail; NaN, ids past a column's levels, negative and
# fractional ids among the rows (`_rows`)
@pytest.mark.parametrize("widest", [5, 40, 300, 700])
@pytest.mark.parametrize("depth", [1, 3, 5, 7, 8, 9])
def test_the_set_kernel_is_the_gather_walk_bit_for_bit(depth, widest,
                                                       monkeypatch):
    _take_the_kernel(monkeypatch, 512)
    before = _set_traces(depth)
    numeric = WP.KERNEL_TRACES.value(kernel="walk_dense_tile",
                                     L=str(1 << max(depth, 3)))
    _, _, _, want, cats = _both(depth, 7, 1037, widest,
                                2000 * depth + widest, body="kernel")
    assert sum(k for _, k in cats) % 32 and np.unique(want).size > 1
    assert _set_traces(depth) == before + 1
    assert WP.KERNEL_TRACES.value(
        kernel="walk_dense_tile", L=str(1 << max(depth, 3))) == numeric


# one categorical column (a segment alone, off a tile's edge); six of them,
# short ones among them (a tile of 32 level rows shared by three and four
# segments; a segment that ends where its tile does); the levels not known
# (a MOJO: all 32 W bits a column); one row; a frame shorter than a tile
@pytest.mark.parametrize("depth,n,levels,known", [
    (5, 1037, [0, 0, 300, 0, 0, 0], True),
    (3, 700, [0, 0, 0, 0, 0, 5], True),
    (5, 1037, [3, 2, 7, 20, 300, 31], True),
    (8, 517, [12, 31, 7, 29, 340, 340], True),
    (9, 300, [3, 2, 7, 20, 40, 31], True),
    (5, 517, [40, 0, 7, 0, 0, 31], False),
    (8, 300, [300, 0, 7, 0, 0, 31], False),
    (5, 1, [300, 0, 7, 0, 0, 31], True),
    (7, 100, [3, 29, 0, 32, 0, 64], True)])
def test_the_set_kernel_over_segment_layouts(depth, n, levels, known,
                                             monkeypatch):
    _take_the_kernel(monkeypatch, 256)
    before = _set_traces(depth)
    ta, _, _, _, cats = _both(depth, 5, n, max(levels), depth + n,
                              known=known, body="kernel", levels=levels)
    bits = 32 * ta.catbits.shape[-1]
    assert [k for _, k in cats] == [k if known else bits for k in levels if k]
    assert _set_traces(depth) == before + 1


def test_the_level_one_hots_budget_picks_the_kernel_or_the_twin(monkeypatch):
    """`_dense_body`: on the TPU a dense ensemble takes the kernel, with or
    without sets, while its level rows leave the one-hot scratch a whole
    chunk of rows; past that the XLA twin — from the shape alone."""
    # this backend is not a TPU: the twin, whatever the shape
    assert E._dense_body() == E._dense_body(((0, 759),)) == "xla"
    monkeypatch.setattr(WP, "use_pallas", lambda: True)
    airline = tuple((c, k) for c, k in enumerate(
        (12, 31, 7, 0, 29, 340, 340, 0)) if k)
    assert WP.level_rows(airline) == 768 and WP.level_rows(()) == 0
    assert WP.hot_rows(768) == 5 * WP.CHUNK     # the tile is TILE_ROWS
    assert E._dense_body() == E._dense_body(airline) == "kernel"
    # a MOJO of the same table: six columns x 384 bits
    assert E._dense_body(tuple((c, 384) for c in range(6))) == "kernel"
    assert WP.hot_rows(4096) == WP.CHUNK and WP.hot_rows(4224) == 0
    assert E._dense_body(((0, 4096),)) == "kernel"
    assert E._dense_body(((0, 4097),)) == "xla"
    assert E._dense_body(((0, 4000), (3, 97))) == "xla"
    # depth 3 admits it densely (7 x (6 + 5000) <= 2^19), the twin walks it
    assert E._walk_path(3, C, 5000) == "dense"
    monkeypatch.setattr(WP, "TILE_ROWS", 256)
    before = _set_traces(3)
    _both(3, 4, 300, 5000, 11, body="kernel", levels=[5000, 0, 7, 0, 0, 0])
    assert _set_traces(3) == before              # no kernel was traced
    _both(3, 4, 300, 4000, 12, body="kernel", levels=[4000, 0, 7, 0, 0, 0])
    assert _set_traces(3) == before + 1


def test_the_level_rows_count_as_columns_in_the_rule():
    cells = E._DENSE_MAX_CELLS
    # depth 5, 8 columns: 31 x (8 + K) <= 2^19 up to K = 16,904
    assert E._walk_path(5, 8, 759) == "dense"           # the airline model
    assert E._walk_path(5, 8, cells // 31 - 8) == "dense"
    assert E._walk_path(5, 8, cells // 31 - 7) == "gather"
    # a numeric ensemble: the rule it always had
    assert E._walk_path(14, 28) == "dense" and E._walk_path(15, 28) == "gather"
    # depth 10 at the airline's width: 1023 x (8 + K) <= 2^19 up to K = 504
    assert E._walk_path(10, 8, 504) == "dense"
    assert E._walk_path(10, 8, 505) == E._walk_path(10, 8, 759) == "gather"
    assert E._walk_path(0, 8, 12) == "gather"            # a root alone


def test_an_ensemble_over_the_bound_takes_the_gather_body():
    """The same model answers from either body: depth 9 with two columns
    past a code byte is over the bound, and `predict_ensemble` says so."""
    rng = np.random.default_rng(9)
    levels = np.array([700, 0, 7, 0, 0, 700])
    col, thr, nal, val, tw, sets = _ensemble(rng, 3, 9, levels)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=9,
                      catbits=sets, col_is_cat=IS_CAT, cat_levels=levels)
    cats = E._cat_layout(ta, C)
    assert E._walk_path(9, C, sum(k for _, k in cats)) == "gather"
    X = _rows(rng, 300, levels)
    before = E.WALKS.value(path="gather", block="")
    got = np.asarray(E.predict_ensemble(jnp.asarray(X), ta, weights=tw))
    assert E.WALKS.value(path="gather", block="") == before + 1
    dense = np.asarray(E._walk_dense(
        *[jnp.asarray(a) for a in (X, col, thr, nal, val, tw, sets)],
        depth=9, cats=cats))
    assert np.array_equal(got, dense)


def test_the_trees_pytree_keeps_the_levels():
    """The serving params pytree (`_trees_flatten`) carries `cat_levels` as
    static host metadata, beside `col_is_cat`."""
    rng = np.random.default_rng(1)
    levels = np.array([300, 0, 7, 0, 0, 31])
    col, thr, nal, val, _, sets = _ensemble(rng, 2, 3, levels)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=3,
                      catbits=sets, col_is_cat=IS_CAT, cat_levels=levels)
    leaves, tree = jax.tree_util.tree_flatten(ta)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert np.array_equal(back.cat_levels, levels)
    assert E._cat_layout(back, C) == E._cat_layout(ta, C) \
        == ((0, 300), (2, 7), (5, 31))
    # traced through a jit as an argument, the layout is still static
    X = jnp.asarray(_rows(rng, 64, levels))
    out = jax.jit(lambda t, x: E.predict_ensemble(x, t))(ta, X)
    assert np.array_equal(np.asarray(out),
                          np.asarray(E.predict_ensemble(X, ta)))


@pytest.mark.parametrize("with_sets", [True, False])
def test_the_walks_tables_are_placed_once_an_ensemble(with_sets):
    """`_walk_tables`: an ensemble's tables go to the device in its first
    call and are looked up after it — a host array handed to the jitted
    walk is a transfer of its own ahead of every frame's walk; the entry
    is made anew when the ensemble's arrays are others, is not pickled, and
    is not kept when it was made under a trace."""
    import dataclasses
    import pickle
    rng = np.random.default_rng(3)
    levels = np.array([300, 0, 7, 0, 0, 31])
    col, thr, nal, val, tw, sets = _ensemble(rng, 3, 3, levels)
    if not with_sets:
        col = np.where(np.isin(col, np.flatnonzero(IS_CAT)), 1, col)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=3,
                      **(dict(catbits=sets, col_is_cat=IS_CAT,
                              cat_levels=levels) if with_sets else {}))
    X = jnp.asarray(_rows(rng, 64, levels))
    first = np.asarray(E.predict_ensemble(X, ta))
    tables, cats = E._walk_tables(ta, C)
    assert all(isinstance(a, jax.Array) for a in tables)
    assert cats == E._cat_layout(ta, C) and bool(cats) == with_sets
    again, _ = E._walk_tables(ta, C)
    assert all(a is b for a, b in zip(tables, again))
    assert np.array_equal(np.asarray(E.predict_ensemble(X, ta)), first)
    # given weights go beside the placed tables, not into them
    weighted = np.asarray(E.predict_ensemble(X, ta, weights=tw))
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    hold = np.zeros(C, np.int32)
    hold[[c for c, _ in cats]] = [k for _, k in cats]
    want = np.asarray(E._walk_gather(
        *args, jnp.asarray(sets), jnp.asarray(IS_CAT), jnp.asarray(hold),
        depth=3, has_cat=with_sets))
    assert np.array_equal(weighted, want)
    assert E._walk_tables(ta, C)[0][4] is tables[4]
    # other arrays, another entry: never a stale table
    ta.value = val * 2
    fresh = dataclasses.replace(ta)
    assert "_tables" not in fresh.__dict__
    assert np.array_equal(np.asarray(E.predict_ensemble(X, ta)),
                          np.asarray(E.predict_ensemble(X, fresh)))
    assert E._walk_tables(ta, C)[0][3] is not tables[3]
    back = pickle.loads(pickle.dumps(ta))
    assert "_tables" not in back.__dict__ and "_tables" in ta.__dict__
    assert np.array_equal(np.asarray(E.predict_ensemble(X, back)),
                          np.asarray(E.predict_ensemble(X, ta)))
    # as a traced argument the ensemble keeps no tracer
    seen = []

    def through(t, x):
        seen.append(t)
        return E.predict_ensemble(x, t)
    jax.jit(through)(ta, X)
    assert "_tables" not in seen[0].__dict__
    # nor when it is closed over: under a trace a host constant is staged
    # as a tracer, and a kept one would escape (the serving scorer cache
    # traces `_score_matrix` over a model's concrete trees)
    closed = dataclasses.replace(ta)
    traced = jax.jit(lambda x: E.predict_ensemble(x, closed))(X)
    assert "_tables" not in closed.__dict__
    assert np.array_equal(np.asarray(E.predict_ensemble(X, closed)),
                          np.asarray(traced))
