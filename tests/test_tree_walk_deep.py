"""The scoring walk's deep body (models/tree/engine.py `_walk_deep`): the
top 11 levels of every tree in one dense walk (`_top_nodes`: the dense
body's class variant with a class a tree, the node ids as values — on the
TPU the kernel `walk_dense_tile_top`, interpreted here), the levels under
them by gathers (`_walk_tail`) — against `_walk_gather`, bit for bit
(`==`, never allclose), at depths 12, 16 and 20, on perfect trees and on
ragged ones (leaves at every depth), with NaN, ±inf and values on the
thresholds, and bit pattern for bit pattern where the tail's features and
thresholds are signed NaNs with payloads, -0.0 and subnormals; the rule
that sends a shape to it; and that the walks of the benchmark's four GBM
cells are the programs they were, letter for letter.

That the chip's kernel picks the same nodes is the chip run's (PERF.md).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.ops import walk_pallas as WP
from test_tree_walk_dense import (C, NO_BITS, _ensemble, _rows,
                                  _take_the_kernel, walked)


def _perfect(rng, ntrees, depth):
    """Every node above the bottom level splits: each row walks the whole
    depth."""
    col, thr, nal, val, tw = _ensemble(rng, ntrees, depth)
    inner = 2 ** depth - 1
    col[:, :inner] = rng.integers(0, C, size=(ntrees, inner))
    return col, thr, nal, val, tw


def _ragged(rng, ntrees, depth):
    """Leaves at every depth: `_ensemble`'s, and in tree 1 a leaf on each
    level of the path that always turns left."""
    col, thr, nal, val, tw = _ensemble(rng, ntrees, depth)
    if ntrees > 1:
        col[1] = rng.integers(0, C, size=col.shape[1])
        col[1, 2 ** depth - 1:] = -1
        for d in range(1, depth):
            col[1, 2 ** d] = -1           # the right child of the left spine
    return col, thr, nal, val, tw


def _both(depth, ntrees, n, seed, shape, body="xla"):
    rng = np.random.default_rng(seed)
    col, thr, nal, val, tw = shape(rng, ntrees, depth)
    X = _rows(rng, n, col, thr)
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    want = np.asarray(E._walk_gather(*args, *NO_BITS, depth=depth,
                                     has_cat=False))
    if body == "xla":
        got = np.asarray(E._walk_deep(*args, depth=depth))
    else:       # the TPU kernel, interpreted: the program has no such option
        with pltpu.force_tpu_interpret_mode():
            got = np.asarray(E._walk_deep.__wrapped__(*args, depth=depth))
    assert got.dtype == want.dtype == np.float32 and got.shape == (n,)
    assert np.array_equal(got, want), \
        f"{(got != want).sum()} of {n} rows differ"
    return want


@pytest.mark.parametrize("shape", [_perfect, _ragged],
                         ids=["perfect", "ragged"])
@pytest.mark.parametrize("depth,ntrees,n", [(12, 3, 1037), (16, 2, 517),
                                            (20, 3, 300), (20, 1, 1)])
def test_the_deep_walk_is_the_gather_walk_bit_for_bit(depth, ntrees, n,
                                                      shape):
    want = _both(depth, ntrees, n, 31 * depth + ntrees + n, shape)
    assert n == 1 or np.unique(want).size > 1      # rows went several ways


# f32 bit patterns the tail's one-pass pick must carry whole: a quiet NaN
# with the sign bit and a payload, a signalling NaN, -0.0, the smallest
# and the largest subnormals of either sign
EDGE_BITS = np.array([0xFFC01234, 0x7F800001, 0x80000000, 0x00000001,
                      0x80000001, 0x007FFFFF, 0x807FFFFF], np.uint32)


def _tail_reads(X, col, thr, nal, depth):
    """The bit patterns of the features rows read at the tail's steps
    (levels _DEEP_TOP and under), by a NumPy walk."""
    n, rows = X.shape[0], np.arange(X.shape[0])
    reads = []
    for t in range(col.shape[0]):
        node = np.zeros(n, np.int64)
        for d in range(depth):
            c = col[t][node]
            x = X[rows, np.maximum(c, 0)]
            if d >= E._DEEP_TOP:
                reads.append(x[c >= 0].view(np.uint32))
            with np.errstate(invalid="ignore"):
                right = np.where(np.isnan(x), ~nal[t][node], x > thr[t][node])
            node = np.where(c < 0, node, 2 * node + 1 + right)
    return np.concatenate(reads)


@pytest.mark.parametrize("ntrees,n", [(3, 517), (4, 1031)])
def test_the_deep_walk_keeps_every_bit_of_a_tail_feature(ntrees, n):
    """Ragged trees at depth 20 whose tail thresholds are often ±0 or
    subnormal, over rows whose cells are often EDGE_BITS: the sums are
    `_walk_gather`'s bit pattern for bit pattern, and every such pattern
    is read where the tail picks a feature."""
    depth = 20
    rng = np.random.default_rng(53 * n + ntrees)
    col, thr, nal, val, tw = _ragged(rng, ntrees, depth)
    odd = rng.random(thr.shape) < 0.3
    odd[:, :2 ** E._DEEP_TOP - 1] = False
    thr.view(np.uint32)[odd] = rng.choice(EDGE_BITS[2:], size=odd.sum())
    X = _rows(rng, n, col, thr)
    cells = rng.random(X.shape) < 0.15
    X.view(np.uint32)[cells] = rng.choice(EDGE_BITS, size=cells.sum())
    assert set(EDGE_BITS) <= set(_tail_reads(X, col, thr, nal, depth))
    args = [jnp.asarray(a) for a in (X, col, thr, nal, val, tw)]
    want = np.asarray(E._walk_gather(*args, *NO_BITS, depth=depth,
                                     has_cat=False))
    got = np.asarray(E._walk_deep(*args, depth=depth))
    assert got.dtype == want.dtype == np.float32 and got.shape == (n,)
    assert np.array_equal(got.view(np.int32), want.view(np.int32)), \
        f"{(got.view(np.int32) != want.view(np.int32)).sum()} of {n} differ"
    assert np.unique(want).size > 1                # rows went several ways


@pytest.mark.parametrize("depth,ntrees,n", [(12, 2, 300), (20, 3, 517)])
def test_the_top_kernel_is_the_gather_walk_bit_for_bit(depth, ntrees, n,
                                                       monkeypatch):
    """The kernel's class variant under its own name, interpreted, in
    tiles of 256 rows (whole tiles and a tail); counted as
    `walk_dense_tile_top` and under no other name."""
    _take_the_kernel(monkeypatch, 256)

    def traces(k):
        return WP.KERNEL_TRACES.value(kernel=k, L=str(1 << E._DEEP_TOP))
    before = {k: traces(k) for k in ("walk_dense_tile_top",
                                     "walk_dense_tile_classes")}
    _both(depth, ntrees, n, 7 * depth + n, _ragged, body="kernel")
    assert traces("walk_dense_tile_top") == \
        before["walk_dense_tile_top"] + 1
    assert traces("walk_dense_tile_classes") == \
        before["walk_dense_tile_classes"]


def test_a_node_after_the_top_is_where_the_gather_walk_stands():
    """`_top_nodes` is the node of each tree after 11 gather steps: an
    early leaf's id, or the row's node of level 11."""
    rng = np.random.default_rng(4)
    col, thr, nal, _, _ = _ragged(rng, 3, 12)
    X = _rows(rng, 700, col, thr)
    got = np.asarray(jax.jit(E._top_nodes)(
        *[jnp.asarray(a) for a in (X, col, thr, nal)]))
    want = np.zeros((700, 3), np.int64)
    for t in range(3):
        node = np.zeros(700, np.int64)
        for _ in range(E._DEEP_TOP):
            c = col[t][node]
            x = X[np.arange(700), np.maximum(c, 0)]
            right = np.where(np.isnan(x), ~nal[t][node], x > thr[t][node])
            node = np.where(c < 0, node, 2 * node + 1 + right)
        want[:, t] = node
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert (got < 2 ** E._DEEP_TOP - 1).any()          # early leaves
    assert (got >= 2 ** E._DEEP_TOP - 1).any()         # level 11


def test_predict_ensemble_takes_the_deep_body_and_says_so():
    rng = np.random.default_rng(6)
    col, thr, nal, val, tw = _ragged(rng, 4, 18)
    X = _rows(rng, 300, col, thr)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=18)
    assert E._walk_path(18, C) == "deep"         # 6 columns: past 2^19
    got, walks = walked(jnp.asarray(X), ta, weights=tw)
    assert walks == [("deep", "0.5x256")]
    want = E._walk_gather(*[jnp.asarray(a) for a in
                            (X, col, thr, nal, val, tw)], *NO_BITS,
                          depth=18, has_cat=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_a_row_sharded_frame_walks_deep_a_shard_at_a_time(cloud8):
    rng = np.random.default_rng(8)
    col, thr, nal, val, tw = _ragged(rng, 2, 18)
    Xh = _rows(rng, 8 * 64, col, thr)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=18)
    X = jax.device_put(Xh, cloud8.rows_sharding(2))
    got = E.predict_ensemble(X, ta, weights=tw)
    assert got.sharding.is_equivalent_to(cloud8.rows_sharding(1), 1)
    want = E._walk_gather(*[jnp.asarray(a) for a in
                            (Xh, col, thr, nal, val, tw)], *NO_BITS,
                          depth=18, has_cat=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# sha256[:16] of str(jax.make_jaxpr(_ensemble_walk)) at the four GBM cells'
# shapes — gbm_higgs (10 x depth 8), gbm_higgs_defaults (20 x depth 5) on a
# 2,750,000 x 28 frame, gbm_airline (20 x depth 5, 8 columns, 6 of them
# categorical) on 7,720,935 rows, gbm_kddcup99 (230 trees of 23 classes,
# depth 5, 41 columns, 3 categorical) on 2,449,215 — on the kernel and on
# the XLA twin, at commit 2ae6ad3, the parent of the deep body: the deep
# regime is a trace-time branch, and these walks hold nothing of it
AIRLINE = (12, 31, 7, 0, 29, 340, 340, 0)
KDD = (0, 3, 70, 11) + (0,) * 37
CELLS = {
    "gbm_higgs": (2_750_000, 28, 10, 8, (), 0),
    "gbm_higgs_defaults": (2_750_000, 28, 20, 5, (), 0),
    "gbm_airline": (7_720_935, 8, 20, 5, AIRLINE, 0),
    "gbm_kddcup99": (2_449_215, 41, 230, 5, KDD, 23),
}
PARENT = {
    ("kernel", "gbm_higgs"): "9ab16306534bb2a1",
    ("kernel", "gbm_higgs_defaults"): "f30537ce3cc8f2db",
    ("kernel", "gbm_airline"): "38c692ffb72070fa",
    ("kernel", "gbm_kddcup99"): "d74c6564b6c1c8f2",
    ("xla", "gbm_higgs"): "30b888f744cdf5e6",
    ("xla", "gbm_higgs_defaults"): "1a64f1604c45edba",
    ("xla", "gbm_airline"): "01bd82a6cdcd1dc1",
    ("xla", "gbm_kddcup99"): "3b648ffa4aaecf87",
}


def cell_jaxpr(body, cell):
    """The jaxpr text of `_ensemble_walk` at a cell's shapes."""
    rows, cols, ntrees, depth, levels, K = CELLS[cell]
    nodes, S = 2 ** (depth + 1) - 1, jax.ShapeDtypeStruct
    cats = tuple((c, k) for c, k in enumerate(levels) if k)
    tbl = [S((ntrees, nodes), d) for d in
           (jnp.int32, jnp.float32, jnp.bool_, jnp.float32)]
    of_sets = [S((ntrees, nodes, 12), jnp.uint32), S((cols,), jnp.bool_)] \
        if cats else [S((1, 1, 1), jnp.uint32), S((1,), jnp.bool_)]
    more = [S((ntrees,), jnp.int32)] if K else []
    return str(jax.make_jaxpr(
        lambda *a: E._ensemble_walk.__wrapped__(
            *a, depth=depth, has_cat=bool(cats), cats=cats,
            **(dict(classes=K) if K else {})))(
        S((rows, cols), jnp.float32), *tbl, S((ntrees,), jnp.float32),
        *of_sets, *more))


@pytest.mark.parametrize("body,cell", sorted(PARENT))
def test_the_four_cells_walk_as_they_did(body, cell, monkeypatch):
    monkeypatch.setattr(WP, "use_pallas", lambda: body == "kernel")
    jax.clear_caches()          # the body is chosen at trace time
    text = cell_jaxpr(body, cell)
    assert ("pallas_call" in text) == (body == "kernel")
    assert "walk_dense_tile_top" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT[body, cell]
