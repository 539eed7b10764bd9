"""A K-class ensemble through ONE walk (models/tree/engine.py,
`TreeArrays.tree_class`): every tree summed into its class's row, against K
separate `_walk_gather` walks of the classes' own trees, bit for bit (`==`,
never allclose) — on the gather body, on the XLA twin of the dense body and
on the TPU kernel's class variant (ops/walk_pallas.py, interpreted here), in
every block regime, numeric and with categorical SET columns, with trees of
weight 0 and a class that has only stumps; and that an ensemble of ONE
output walks by the program it always had (its jaxpr, letter for letter).

The CPU's matmul is exact whatever its operands; that the chip's kernel
sums the classes as the K walks do is chip_smoke.py's `walk_classes` phase.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.ops import walk_pallas as WP
from test_tree_walk_dense import NO_BITS, _take_the_kernel, walked
from test_tree_walk_dense import _ensemble as _numeric, _rows as _numeric_rows
from test_tree_walk_sets import _ensemble as _with_sets, _rows as _set_rows

C = 6
LEVELS = np.array([40, 0, 7, 0, 0, 300])


def _model(depth, iters, K, n, seed, sets):
    """An iteration-major ensemble of iters x K trees and its rows: some
    trees of weight 0, class 1 with stumps only."""
    rng = np.random.default_rng(seed)
    T = iters * K
    if sets:
        col, thr, nal, val, tw, bits = _with_sets(rng, T, depth, LEVELS)
        X = _set_rows(rng, n, LEVELS)
    else:
        col, thr, nal, val, tw = _numeric(rng, T, depth)
        X, bits = _numeric_rows(rng, n, col, thr), None
    cls = np.tile(np.arange(K, dtype=np.int32), iters)
    tw[1::4] = 0.0
    if K > 1:
        col[cls == 1] = -1                      # a class of stumps
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=depth,
                      tree_class=cls,
                      **(dict(catbits=bits, col_is_cat=LEVELS > 0,
                              cat_levels=LEVELS) if sets else {}))
    return ta, X, tw


def _gather_args(ta, X, tw, pick=slice(None)):
    """`_walk_gather`'s arguments for the trees `pick` of the ensemble."""
    cats = E._cat_layout(ta, C)
    hold = np.zeros(C, np.int32)
    hold[[c for c, _ in cats]] = [k for _, k in cats]
    of_sets = (jnp.asarray(ta.catbits[pick]), jnp.asarray(LEVELS > 0),
               jnp.asarray(hold)) if cats else NO_BITS
    return [jnp.asarray(a) for a in (
        X, ta.col[pick], ta.thr[pick], ta.na_left[pick], ta.value[pick],
        tw[pick])] + list(of_sets), cats


def _k_walks(ta, X, tw):
    """(n, K): class c's column from a walk of class c's trees alone."""
    out = []
    for c in range(ta.n_classes):
        args, cats = _gather_args(ta, X, tw, ta.tree_class == c)
        out.append(np.asarray(E._walk_gather(*args, depth=ta.depth,
                                             has_cat=bool(cats))))
    return np.stack(out, axis=1)


def _one_walk(ta, X, tw, body):
    K = ta.n_classes
    args, cats = _gather_args(ta, X, tw)
    cls = jnp.asarray(ta.tree_class)
    if body == "gather":
        rows = () if cats else (None,)
        return np.asarray(E._walk_gather(*args, *rows, cls, depth=ta.depth,
                                         has_cat=bool(cats), classes=K))
    dense = args[:6] + ([args[6]] if cats else [None])
    if body == "xla":
        return np.asarray(E._walk_dense(*dense, cls, depth=ta.depth,
                                        cats=cats, classes=K))
    with pltpu.force_tpu_interpret_mode():
        return np.asarray(E._walk_dense.__wrapped__(
            *dense, cls, depth=ta.depth, cats=cats, classes=K))


def _same(depth, iters, K, n, seed, sets, body):
    ta, X, tw = _model(depth, iters, K, n, seed, sets)
    want = _k_walks(ta, X, tw)
    got = _one_walk(ta, X, tw, body)
    assert got.dtype == np.float32 and got.shape == (n, K)
    assert np.array_equal(got, want), \
        f"{(got != want).any(1).sum()} of {n} rows differ"
    # the class of stumps reads one value a row, the others several
    assert np.unique(want[:, 1]).size == 1
    assert n == 1 or np.unique(want).size > K
    return ta, X, tw, want


# depths 1 and 5 (16 and 4 trees a block: a block holds several classes'
# trees), 8 (two blocks a tree's top) and 9 (a level under the path
# matrix); 5 and 23 classes (23: a row tile's accumulator three sublane
# tiles deep); 1,037 rows: off every tile's edge
@pytest.mark.parametrize("sets", [False, True], ids=["numeric", "sets"])
@pytest.mark.parametrize("body", ["gather", "xla"])
@pytest.mark.parametrize("depth,iters,K", [(1, 3, 5), (5, 3, 5), (5, 2, 23),
                                           (8, 2, 5), (9, 1, 3)])
def test_the_class_walk_is_the_k_walks_bit_for_bit(depth, iters, K, body,
                                                   sets):
    _same(depth, iters, K, 1037, 100 * depth + K, sets, body)


def _class_traces(depth, sets):
    return WP.KERNEL_TRACES.value(
        kernel="walk_dense_tile" + ("_sets" if sets else "") + "_classes",
        L=str(1 << max(depth, 3)))


@pytest.mark.parametrize("sets", [False, True], ids=["numeric", "sets"])
@pytest.mark.parametrize("depth,iters,K,n", [
    (1, 3, 5, 300), (5, 3, 5, 1037), (5, 2, 23, 1037), (8, 2, 5, 517),
    (9, 1, 3, 300), (5, 1, 9, 1)])
def test_the_class_kernel_is_the_k_walks_bit_for_bit(depth, iters, K, n,
                                                     sets, monkeypatch):
    """The kernel's class variant, interpreted, in tiles of 512 rows: whole
    tiles and a tail, the accumulator 8, 16 and 24 rows deep; it is counted
    under its own name and the one-output series do not move."""
    _take_the_kernel(monkeypatch, 512)
    before = _class_traces(depth, sets)
    others = [WP.KERNEL_TRACES.value(kernel=k, L=str(1 << max(depth, 3)))
              for k in ("walk_dense_tile", "walk_dense_tile_sets")]
    _same(depth, iters, K, n, 7 * depth + K + n, sets, "kernel")
    assert _class_traces(depth, sets) == before + 1
    assert others == [
        WP.KERNEL_TRACES.value(kernel=k, L=str(1 << max(depth, 3)))
        for k in ("walk_dense_tile", "walk_dense_tile_sets")]


@pytest.mark.parametrize("sets", [False, True], ids=["numeric", "sets"])
def test_predict_ensemble_scores_the_classes_in_one_dispatch(sets):
    """The entry point: (n, K) from ONE walk, counted once; given weights
    go beside the placed tables; the classes' table is placed with them."""
    ta, X, tw, want = _same(5, 3, 5, 517, 11, sets, "xla")
    got, walks = walked(jnp.asarray(X), ta, weights=tw)
    assert walks == [("dense", E._block_label(5))]
    assert np.array_equal(np.asarray(got), want)
    tables, _ = E._walk_tables(ta, C)
    assert len(tables) == 8 and isinstance(tables[7], jax.Array)
    assert np.array_equal(np.asarray(tables[7]), ta.tree_class)
    assert E._walk_tables(ta, C)[0][7] is tables[7]     # placed once
    unit = np.asarray(E.predict_ensemble(jnp.asarray(X), ta))
    assert np.array_equal(unit, _k_walks(ta, X, np.ones_like(tw)))


def test_a_deep_class_ensemble_takes_the_gather_body():
    ta, X, tw = _model(17, 1, 3, 64, 5, False)
    assert E._walk_path(17, C) == "gather"
    got, walks = walked(jnp.asarray(X), ta, weights=tw)
    got = np.asarray(got)
    assert walks == [("gather", "")]
    assert np.array_equal(got, _k_walks(ta, X, tw))


def test_a_row_sharded_frame_keeps_its_rows(cloud8, monkeypatch):
    monkeypatch.setattr(E, "_WALK_TILE_CELLS", 128 * 24)
    ta, Xh, tw = _model(5, 2, 5, 8 * 8 * 40, 3, True)
    X = jax.device_put(Xh, cloud8.rows_sharding(2))
    got = E.predict_ensemble(X, ta, weights=tw)
    assert got.shape == (Xh.shape[0], 5)
    assert got.sharding.is_equivalent_to(cloud8.rows_sharding(2), 2)
    assert np.array_equal(np.asarray(got), _k_walks(ta, Xh, tw))


def test_the_pytree_and_the_host_view_keep_the_classes():
    """`tree_class` is static host metadata of the serving params pytree;
    `class_ensembles` is the K one-output ensembles, on the host."""
    ta, X, tw = _model(3, 2, 4, 64, 9, True)
    leaves, tree = jax.tree_util.tree_flatten(ta)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    assert np.array_equal(back.tree_class, ta.tree_class)
    assert back.n_classes == 4 and E.TreeArrays(
        col=ta.col, thr=ta.thr, na_left=ta.na_left, value=ta.value,
        depth=3).n_classes == 0
    out = jax.jit(lambda t, x: E.predict_ensemble(x, t))(ta, jnp.asarray(X))
    unit = _k_walks(ta, X, np.ones_like(tw))
    assert np.array_equal(np.asarray(out), unit)
    views = E.class_ensembles(ta)
    assert len(views) == 4 and all(v.tree_class is None for v in views)
    assert all(isinstance(v.col, np.ndarray) and v.ntrees == 2
               for v in views)
    for c, v in enumerate(views):
        assert np.array_equal(
            np.asarray(E.predict_ensemble(jnp.asarray(X), v)), unit[:, c])


# sha256[:16] of str(jax.make_jaxpr(_ensemble_walk)) of an ensemble of ONE
# output at commit 497a121 (the parent of the class variant), 4,104 rows on
# the kernel and 4,112 on the XLA twin: (ntrees, depth, sets) -> digest.
# A change that is MEANT to alter the one-output walk renews them: the six
# with sets were renewed by PR 37 (the set product eight levels a byte row);
# the six numeric ones are 497a121's still
ONE_OUTPUT = {
    ("kernel", 10, 8, False): "fc7dabe0b3812e1d",
    ("kernel", 20, 5, False): "f6a98ffd55883608",
    ("kernel", 3, 9, False): "f55c295cdfbf802c",
    ("kernel", 20, 5, True): "a7b23608e8e6b214",
    ("kernel", 10, 8, True): "aa6d3509e2426a09",
    ("kernel", 3, 9, True): "c328a4398b3d6933",
    ("xla", 10, 8, False): "ddf57942b1a42406",
    ("xla", 20, 5, False): "056f21054255410f",
    ("xla", 3, 9, False): "ba99d6d49753e0bd",
    ("xla", 20, 5, True): "03e2089e4bf3d5e2",
    ("xla", 10, 8, True): "e2d78c3f08d9ab4a",
    ("xla", 3, 9, True): "950ee777d8d50197",
}
AIRLINE = (12, 31, 7, 0, 29, 340, 340, 0)


@pytest.mark.parametrize("body,ntrees,depth,sets", sorted(ONE_OUTPUT))
def test_a_one_output_walk_is_the_program_it_was(body, ntrees, depth, sets,
                                                 monkeypatch):
    """The class variant is a trace-time `if`: an ensemble without
    `tree_class` gets no operand, scratch or instruction of it."""
    monkeypatch.setattr(WP, "use_pallas", lambda: body == "kernel")
    nodes, S = 2 ** (depth + 1) - 1, jax.ShapeDtypeStruct
    cols = 8 if sets else 28
    cats = tuple((c, k) for c, k in enumerate(AIRLINE) if k) if sets else ()
    tbl = [S((ntrees, nodes), d) for d in
           (jnp.int32, jnp.float32, jnp.bool_, jnp.float32)]
    of_sets = [S((ntrees, nodes, 12), jnp.uint32), S((cols,), jnp.bool_)] \
        if sets else [S((1, 1, 1), jnp.uint32), S((1,), jnp.bool_)]
    rows = 4104 if body == "kernel" else 4112
    text = str(jax.make_jaxpr(
        lambda *a: E._ensemble_walk.__wrapped__(
            *a, depth=depth, has_cat=sets, cats=cats))(
        S((rows, cols), jnp.float32), *tbl, S((ntrees,), jnp.float32),
        *of_sets))
    assert ("pallas_call" in text) == (body == "kernel")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == ONE_OUTPUT[body, ntrees, depth, sets]


# the same at the KDD Cup 1999 cell's layout, of the class walk and of one
# output, at commit a5f5392 (the parent of the byte rows): its 84 levels and
# 12 byte rows are one MXU tile either way, so a level stays a row
# (`walk_pallas.fan_in`) and the program is the parent's, letter for letter
KDD = (0, 3, 70, 11) + (0,) * 37
ONE_LEVEL_A_ROW = {
    ("kernel", 230, 5, 23): "e3e6a95697414d3d",
    ("kernel", 46, 8, 23): "773bdad9ce7da4f6",
    ("kernel", 20, 5, 0): "5e920ae5d9f8c55b",
    ("xla", 230, 5, 23): "d6544561a3f609b5",
    ("xla", 46, 8, 23): "4caac00ccdfa1fbc",
    ("xla", 20, 5, 0): "f5e3bec2829fb57c",
}


@pytest.mark.parametrize("body,ntrees,depth,classes", sorted(ONE_LEVEL_A_ROW))
def test_a_layout_bytes_would_not_shorten_is_the_program_it_was(
        body, ntrees, depth, classes, monkeypatch):
    monkeypatch.setattr(WP, "use_pallas", lambda: body == "kernel")
    cats = tuple((c, k) for c, k in enumerate(KDD) if k)
    assert WP.fan_in(cats) == 1 and WP.set_rows(cats) == 128
    nodes, S = 2 ** (depth + 1) - 1, jax.ShapeDtypeStruct
    tbl = [S((ntrees, nodes), d) for d in
           (jnp.int32, jnp.float32, jnp.bool_, jnp.float32)]
    cls = (S((ntrees,), jnp.int32),) if classes else ()
    rows = 4104 if body == "kernel" else 4112
    text = str(jax.make_jaxpr(
        lambda *a: E._ensemble_walk.__wrapped__(
            *a, depth=depth, has_cat=True, cats=cats,
            **(dict(classes=classes) if classes else {})))(
        S((rows, len(KDD)), jnp.float32), *tbl, S((ntrees,), jnp.float32),
        S((ntrees, nodes, 3), jnp.uint32), S((len(KDD),), jnp.bool_), *cls))
    assert ("pallas_call" in text) == (body == "kernel")
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == ONE_LEVEL_A_ROW[body, ntrees, depth, classes]
