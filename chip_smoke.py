#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path ONCE, through the entry points a user calls, on one
TPU chip in one process, at the full width of the model bench.py and
BASELINE.json name (HIGGS-shaped GBM: 28 numeric columns, 255 bins + NA,
depth 8, bernoulli; only the tree count is cut):

    device  -> jax.devices() must be a TPU, else exit non-zero
    kernels -> Pallas kernels == their XLA twins at HIGGS width, on chip
    walk    -> the dense scoring walk == the gather walk, leaf for leaf,
               at both cells' shapes (10 x depth 8, 20 x depth 5)
    walk_sets -> the same with categorical SET splits matched INSIDE the
               fused kernel, at the airline cell's shape (8 columns, 6
               categorical, 2 past a code byte; 20 x depth 5) and with
               two blocks a tree's top and a level under them (10 x
               depth 8, 3 x depth 9)
    walk_classes -> a 23-class ensemble through ONE walk (the kernel's
               accumulator a row a class) == 23 gather walks of the
               classes' own trees, bit for bit, at the KDD Cup 1999
               cell's shape (41 columns, 3 categorical; 10 x 23 trees of
               depth 5) and at depths 8 and 9
    ingest  -> seeded CSV through h2o3_tpu.import_file (native tokenizer)
    train   -> 11M x 28 Frame -> H2OGradientBoostingEstimator.train
    predict -> large-frame sharded path AND compiled-scorer fast path,
               both against the exported artifact scored on the host;
               the large path's prediction frame (planes made on the
               device) == the frame built on the host from the same scores
    serve   -> REST POST /3/Predictions/models/{m}, 1 / 64 / 4096 rows

    python chip_smoke.py            # one chip: every phase above
    python chip_smoke.py --chips 4  # four chips: ONLY the row-sharded
                                    # train vs the same data on one chip

Each phase is a function that fails the run on its own assertion (non-zero
exit, traceback on stderr) — nothing is caught and carried on. One JSON
object per phase goes to stdout; the LAST line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
Every time printed here is a smoke reading off a host clock (compile
included where it says so), not a benchmark.

There is no option that lets this pass off the chip: on a CPU backend it
exits non-zero at the device check. tests/test_chip_smoke.py runs the
phase functions at a tiny size on the CPU mesh instead.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

COLS = 28                    # HIGGS width — never cut
DEPTH, NBINS, LEARN_RATE = 8, 255, 0.1
ROWS = 11_000_000            # HIGGS rows
NTREES = 10                  # cut (bench.py trains 50): keeps a cold run to minutes
INGEST_ROWS = 1_000_000
SLICE_ROWS = 1_000_000       # <= scorer_cache._max_rows(): the fast path
CHECK_ROWS = 10_000          # rows compared with the host scorer, per path
WALK_ROWS = 1_000_000        # rows walked by both bodies of the scoring walk
WALK_SHAPES = ((10, 8), (20, 5))   # (ntrees, depth) of the benchmark's two cells
# the airline cell's shape: levels a column (0: numeric), 20 trees x depth 5
WALK_SET_ROWS = 400_000
WALK_SET_LEVELS = (12, 31, 7, 0, 29, 340, 340, 0)
WALK_SET_SHAPES = (WALK_SHAPES[1], WALK_SHAPES[0], (3, 9))
# the KDD Cup 1999 cell's shape: 41 columns, three categorical, 23 classes,
# (iterations, depth): the cell's own 10 x 23 trees of depth 5, then 8 and 9
WALK_CLASS_LEVELS = (0, 3, 70, 11) + (0,) * 37
WALK_CLASSES = 23
WALK_CLASS_SHAPES = ((10, 5), (2, 8), (1, 9))
PARITY_ROWS = 1_100_003      # device-built frame == host-built: over the fast
                             # path's 2^20 rows, not a multiple of the padding
ONE_ROW_MS_PR28 = (7.41, 7.66)   # PERF.md's 1-row medians, to read "1" against
ONE_ROW_REQUESTS = 300       # 1-row REST requests behind the median
FOUR_CHIP_ROWS = 4_000_000   # cut so the 4-chip + 1-chip pair fits one call
FOUR_CHIP_NTREES = 5
# Training AUC floor, fixed from the generator below: the true logit
# ranks its own labels at AUC 0.824 (2M draws), 10 depth-8 trees reach
# 0.80 and the two linear terms alone 0.789; a misrouting kernel falls
# to ~0.5.
AUC_MIN = 0.78
FEATURES = [f"f{j}" for j in range(COLS)]
LABEL, LABEL_DOMAIN = "label", ["b", "s"]


def higgs_like(rows: int, seed: int):
    """Seeded HIGGS-shaped data on the host: (rows, 28) f32 standard
    normals and a 0/1 label from bench.py's target function."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, COLS), dtype=np.float32)
    logit = (1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
             + 0.4 * np.sin(X[:, 4]) + 0.3 * X[:, 5] * X[:, 6])
    y = rng.random(rows, dtype=np.float32) < 1.0 / (1.0 + np.exp(-logit))
    return X, y


def _counters():
    """Process-wide compile accounting (obs/metrics jax.monitoring taps)."""
    from h2o3_tpu.obs import metrics as om

    def val(name):
        m = om.REGISTRY.get(name)
        return m.value() if m is not None else 0.0
    return {"compiles": val("h2o3_xla_compiles_total"),
            "compile_s": val("h2o3_xla_compile_seconds_total"),
            "cache_hits": val("h2o3_xla_compile_cache_hits_total"),
            "cache_misses": val("h2o3_xla_compile_cache_misses_total")}


def _since(before: dict) -> dict:
    return {k: round(v - before[k], 3) for k, v in _counters().items()}


def _peak_bytes(on_chip: bool) -> list:
    """peak_bytes_in_use per device since process start ([] where the
    backend reports none — the CPU)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    if on_chip:
        assert all(s and "peak_bytes_in_use" in s for s in stats), stats
    return [int(s["peak_bytes_in_use"]) for s in stats if s]


def _frame(X, y=None):
    """The public constructors: one Vec per host column, row-sharded over
    the cloud as it is put (never whole on one device first)."""
    from h2o3_tpu.core.frame import Frame, T_CAT, Vec
    names = list(FEATURES)
    vecs = [Vec.from_numpy(X[:, j]) for j in range(COLS)]
    if y is not None:
        names.append(LABEL)
        vecs.append(Vec.from_numpy(y.astype(np.float64), type=T_CAT,
                                   domain=LABEL_DOMAIN))
    return Frame(names, vecs)


def _gbm(ntrees: int, seed: int):
    from h2o3_tpu.models import H2OGradientBoostingEstimator
    return H2OGradientBoostingEstimator(
        ntrees=ntrees, max_depth=DEPTH, nbins=NBINS, learn_rate=LEARN_RATE,
        seed=seed)


# ===========================================================================
def phase_device(want_chips: int) -> dict:
    """The device check, before anything else touches the package."""
    import jax
    import jaxlib
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no accelerator (platform "
            f"{devices[0].platform!r}, {len(devices)} device(s)); this "
            "script only runs on a TPU")
    if len(devices) != want_chips:
        raise SystemExit(f"chip_smoke: {len(devices)} chip(s) visible, "
                         f"this run needs {want_chips}")
    import h2o3_tpu
    from h2o3_tpu.utils import compile_cache
    from importlib import metadata
    cloud = h2o3_tpu.init()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu"),
            "compile_cache_dir": compile_cache.cache_dir(),
            "cloud": cloud.describe()["mesh_shape"]}


def phase_kernels(seed: int) -> dict:
    """Every kernel the selection rules pick at HIGGS width, Pallas
    against its `_xla` twin, on the chip (ops/parity.py)."""
    from h2o3_tpu.ops import hist_pallas as HP
    from h2o3_tpu.ops.parity import kernel_parity_check
    assert HP.use_pallas()
    devs = kernel_parity_check(seed=seed)
    return {"checks": len(devs), "max_dev": max(devs.values()),
            "kernels": sorted({k for k, _ in HP.kernel_traces()})}


def phase_walk_exact(rows: int, shapes, seed: int,
                     on_chip: bool = True) -> dict:
    """engine._walk_dense against engine._walk_gather on this device, at
    HIGGS width, for each (ntrees, depth) of `shapes` — the two cells'
    ensembles: 10 x depth 8 (two node blocks a tree) and 20 x depth 5 (four
    trees a block): thresholds drawn from the data's own values (so
    `x == thr` happens), early leaves, NaN and ±inf among the rows. Tree by
    tree the value walked to is the NODE's own number, so `==` is leaf
    for leaf; then once more with random values and weights, the
    ensemble's sum bit for bit. The CPU's matmul is exact whatever its
    operands; only the chip can show that its bfloat16 products select a
    feature's bytes exactly, and that the fused kernel (`walk_dense_tile`)
    is what ran."""
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import engine as E
    from h2o3_tpu.ops import hist_pallas as HP
    rng = np.random.default_rng(seed + 2)
    X, _ = higgs_like(rows, seed)
    for v, p in ((np.nan, 0.01), (np.inf, 0.001), (-np.inf, 0.001),
                 (0.0, 0.001), (-0.0, 0.001)):
        X[rng.random(X.shape) < p] = v
    no_bits = (jnp.zeros((1, 1, 1), jnp.uint32), jnp.zeros(1, bool))
    Xd = jnp.asarray(X)
    traces0 = HP.kernel_traces()
    recs = []
    for ntrees, depth in shapes:
        assert E._walk_path(depth, COLS, False) == "dense"
        nodes, inner = 2 ** (depth + 1) - 1, 2 ** depth - 1
        col = rng.integers(0, COLS, size=(ntrees, nodes)).astype(np.int32)
        col[:, inner:] = -1
        col[:, 1:inner][rng.random((ntrees, inner - 1)) < 0.1] = -1
        thr = X[rng.integers(0, rows, size=col.shape), np.maximum(col, 0)]
        nal = rng.random(col.shape) < 0.5
        ids = np.broadcast_to(np.arange(nodes, dtype=np.float32), col.shape)
        tbl = [jnp.asarray(a) for a in (col, thr, nal)]

        def both(val, tw):
            args = (Xd, *tbl, jnp.asarray(val), jnp.asarray(tw))
            return (np.asarray(E._walk_dense(*args, depth=depth)),
                    np.asarray(E._walk_gather(*args, *no_bits, depth=depth,
                                              has_cat=False)))

        reached = set()
        for t in range(ntrees):
            dense, gather = both(ids, np.eye(ntrees, dtype=np.float32)[t])
            assert np.array_equal(dense, gather), \
                (depth, t, int((dense != gather).sum()))
            reached.update(np.unique(gather).astype(int).tolist())
        on_thr = int((X[:, col[0, 0]] == thr[0, 0]).sum())
        assert on_thr >= 1 and len(reached) > inner // 2, \
            (on_thr, len(reached))
        t0 = time.perf_counter()
        dense, gather = both(
            rng.standard_normal(col.shape).astype(np.float32),
            (rng.random(ntrees) + 0.5).astype(np.float32))
        assert np.array_equal(dense, gather), int((dense != gather).sum())
        recs.append({"ntrees": ntrees, "depth": depth,
                     "block": E._block_label(depth),
                     "nodes_reached": len(reached),
                     "rows_on_root_thr": on_thr,
                     "both_bodies_s": round(time.perf_counter() - t0, 2)})
    picked = sorted(k for k, v in HP.kernel_traces().items()
                    if v > traces0.get(k, 0))
    # on the chip the dense body IS the fused kernel, at both regimes
    want = [("walk_dense_tile", 1 << d) for d in sorted({d for _, d in shapes})]
    assert picked == (want if on_chip else []), picked
    return {"rows": rows, "cols": COLS,
            "nonfinite_cells": int((~np.isfinite(X)).sum()),
            "pallas_kernels_traced": picked, "shapes": recs}


def _set_model(rng, rows: int, levels, ntrees: int, depth: int):
    """(X, col, thr, na_left, sets): rows over `levels` a column (0:
    numeric) — level ids over every level, with NaN, ±inf, ids past the
    column's levels, negative and fractional values among them — and
    random trees that mix numeric and SET splits, early leaves among
    them, thresholds drawn from the rows."""
    C = levels.size
    X = rng.standard_normal((rows, C)).astype(np.float32) * 700 + 1200
    for c in np.flatnonzero(levels):
        X[:, c] = rng.integers(0, levels[c], size=rows)
        odd = rng.random(rows) < 0.02
        X[odd, c] = rng.choice([-3.0, -0.5, 0.5, levels[c] - 0.25,
                                levels[c], levels[c] + 77.0, 1e9],
                               size=int(odd.sum()))
    for v, p in ((np.nan, 0.01), (np.inf, 0.001), (-np.inf, 0.001)):
        X[rng.random(X.shape) < p] = v
    nodes, inner = 2 ** (depth + 1) - 1, 2 ** depth - 1
    W = -(-int(levels.max()) // 128) * 4
    col = rng.integers(0, C, size=(ntrees, nodes)).astype(np.int32)
    col[:, inner:] = -1
    col[:, 1:inner][rng.random((ntrees, inner - 1)) < 0.1] = -1
    thr = X[rng.integers(0, rows, size=col.shape), np.maximum(col, 0)]
    nal = rng.random(col.shape) < 0.5
    bits = rng.integers(0, 2 ** 32, size=col.shape + (W,),
                        dtype=np.uint64).astype(np.uint32)
    return X, col, thr, nal, bits


def phase_walk_sets(rows: int, levels, shape, seed: int,
                    on_chip: bool = True) -> dict:
    """The dense body with categorical SET splits against the gather body
    on this device, at the airline cell's columns: `levels` a column (0:
    numeric; two columns past a code byte), `shape` = (ntrees, depth).
    Random trees that mix numeric and SET splits, level ids drawn over
    every level, with NaN, ids past the column's levels, negative and
    fractional values among them. Tree by tree the value walked to is the
    NODE's own number, so `==` is leaf for leaf; then random values and
    weights, the ensemble's sum bit for bit. The set match is a bfloat16
    product of {0, 1} summed in f32: only the chip can show that its MXU
    keeps it exact, that Mosaic's float -> int conversion reads a level id
    as `_cat_code` does, and that the kernel's set variant
    (`walk_dense_tile_sets`) is what ran."""
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import engine as E
    from h2o3_tpu.ops import hist_pallas as HP
    traces0 = HP.kernel_traces()
    rng = np.random.default_rng(seed + 5)
    levels = np.asarray(levels)
    C, (ntrees, depth) = levels.size, shape
    X, col, thr, nal, bits = _set_model(rng, rows, levels, ntrees, depth)
    nodes, inner, W = col.shape[1], 2 ** depth - 1, bits.shape[-1]
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=thr, depth=depth,
                      catbits=bits, col_is_cat=levels > 0, cat_levels=levels)
    cats = E._cat_layout(ta, C)
    K = sum(k for _, k in cats)
    assert E._walk_path(depth, C, K) == "dense", (depth, C, K)
    hold = np.zeros(C, np.int32)
    hold[[c for c, _ in cats]] = [k for _, k in cats]
    Xd, tbl = jnp.asarray(X), [jnp.asarray(a) for a in (col, thr, nal)]
    sets = (jnp.asarray(bits), jnp.asarray(levels > 0), jnp.asarray(hold))
    ids = np.broadcast_to(np.arange(nodes, dtype=np.float32), col.shape)

    def both(val, tw):
        args = (Xd, *tbl, jnp.asarray(val), jnp.asarray(tw))
        return (np.asarray(E._walk_dense(*args, sets[0], depth=depth,
                                         cats=cats)),
                np.asarray(E._walk_gather(*args, *sets, depth=depth,
                                          has_cat=True)))

    reached = set()
    for t in range(ntrees):
        dense, gather = both(ids, np.eye(ntrees, dtype=np.float32)[t])
        assert np.array_equal(dense, gather), \
            (depth, t, int((dense != gather).sum()))
        reached.update(np.unique(gather).astype(int).tolist())
    assert len(reached) > inner // 2, len(reached)
    dense, gather = both(rng.standard_normal(col.shape).astype(np.float32),
                         (rng.random(ntrees) + 0.5).astype(np.float32))
    assert np.array_equal(dense, gather), int((dense != gather).sum())
    is_set = (col >= 0) & (levels > 0)[np.maximum(col, 0)]
    picked = sorted(k for k, v in HP.kernel_traces().items()
                    if v > traces0.get(k, 0))
    # on the chip the dense body IS the fused kernel, sets and all
    assert picked == ([("walk_dense_tile_sets", 1 << depth)] if on_chip
                      else []), picked
    return {"rows": rows, "cols": C, "ntrees": ntrees, "depth": depth,
            "block": E._block_label(depth), "pallas_kernels_traced": picked,
            "level_rows": K, "set_words": W, "set_nodes": int(is_set.sum()),
            "split_nodes": int((col >= 0).sum()),
            "rows_past_a_byte": int((X[:, levels > 255] >= 256).sum()),
            "nodes_reached": len(reached),
            "nonfinite_cells": int((~np.isfinite(X)).sum())}


def phase_walk_classes(rows: int, levels, shape, classes: int, seed: int,
                       on_chip: bool = True) -> dict:
    """A K-class ensemble through ONE walk (`TreeArrays.tree_class`: the
    kernel's accumulator a row a class, each tree's w * v added to its
    class's row) against K separate gather walks of the classes' own trees
    on this device, bit for bit — at the KDD Cup 1999 cell's columns
    (`levels`) and `shape` = (iterations, depth), iteration-major trees of
    random values and weights (a few of weight 0), rows as `_set_model`
    makes them. Only the chip can show that the kernel's dynamic-sublane
    update sums a class's trees in that class's own order, and that the
    class variant (`walk_dense_tile_sets_classes`) is what ran."""
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import engine as E
    from h2o3_tpu.ops import hist_pallas as HP
    traces0 = HP.kernel_traces()
    rng = np.random.default_rng(seed + 6)
    levels = np.asarray(levels)
    C, (iters, depth) = levels.size, shape
    T = iters * classes
    X, col, thr, nal, bits = _set_model(rng, rows, levels, T, depth)
    val = rng.standard_normal(col.shape).astype(np.float32)
    tw = (rng.random(T) + 0.5).astype(np.float32)
    tw[3::7] = 0.0
    cls = np.tile(np.arange(classes, dtype=np.int32), iters)
    ta = E.TreeArrays(col=col, thr=thr, na_left=nal, value=val, depth=depth,
                      catbits=bits, col_is_cat=levels > 0, cat_levels=levels,
                      tree_class=cls)
    cats = E._cat_layout(ta, C)
    assert E._walk_path(depth, C, sum(k for _, k in cats)) == "dense"
    hold = np.zeros(C, np.int32)
    hold[[c for c, _ in cats]] = [k for _, k in cats]
    Xd = jnp.asarray(X)
    t0 = time.perf_counter()
    one = np.asarray(E.predict_ensemble(Xd, ta, weights=tw))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = np.asarray(E.predict_ensemble(Xd, ta, weights=tw))
    warm_s = time.perf_counter() - t0
    assert one.shape == (rows, classes) and np.array_equal(one, again)
    for c in range(classes):
        own = [jnp.asarray(a[cls == c]) for a in (col, thr, nal, val, tw,
                                                  bits)]
        want = np.asarray(E._walk_gather(
            Xd, *own, jnp.asarray(levels > 0), jnp.asarray(hold),
            depth=depth, has_cat=True))
        assert np.array_equal(one[:, c], want), \
            (depth, c, int((one[:, c] != want).sum()))
    picked = sorted(k for k, v in HP.kernel_traces().items()
                    if v > traces0.get(k, 0))
    assert picked == ([("walk_dense_tile_sets_classes", 1 << depth)]
                      if on_chip else []), picked
    return {"rows": rows, "cols": C, "classes": classes, "ntrees": T,
            "depth": depth, "block": E._block_label(depth),
            "pallas_kernels_traced": picked,
            "level_rows": sum(k for _, k in cats),
            "distinct_sums": int(np.unique(one).size),
            "nonfinite_cells": int((~np.isfinite(X)).sum()),
            "first_call_s": round(first_s, 3), "warm_call_s": round(warm_s, 3)}


def write_csv(path: str, X, y):
    """28 numeric columns + a b/s label, formatted in bulk: every value
    is a fixed 7-byte field ([-0]d.dddd), so the whole file is one uint8
    array — a per-value Python format of 29M cells would cost minutes."""
    n = X.shape[0]
    q = np.rint(np.clip(X, -9.9999, 9.9999).astype(np.float64) * 1e4) \
        .astype(np.int64)
    mag = np.abs(q)
    cell = np.empty((n, COLS, 8), np.uint8)
    cell[..., 0] = np.where(q < 0, ord("-"), ord("0"))
    cell[..., 1] = ord("0") + mag // 10_000
    cell[..., 2] = ord(".")
    for k, p in enumerate((1000, 100, 10, 1)):
        cell[..., 3 + k] = ord("0") + (mag // p) % 10
    cell[..., 7] = ord(",")
    row = np.empty((n, COLS * 8 + 2), np.uint8)
    row[:, :-2] = cell.reshape(n, COLS * 8)
    row[:, -2] = np.where(y, ord("s"), ord("b"))
    row[:, -1] = ord("\n")
    with open(path, "wb") as fh:
        fh.write((",".join(FEATURES + [LABEL]) + "\n").encode())
        fh.write(row.tobytes())
    return q / 1e4          # the values the file holds


def phase_ingest(rows: int, seed: int, out_dir: str) -> dict:
    """Seeded CSV -> h2o3_tpu.import_file; the native tokenizer must have
    done the work and the columns must be device arrays on the backend."""
    import jax
    import h2o3_tpu
    from h2o3_tpu.io import fastcsv
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", os.path.join(HERE, "native")], check=True,
                   capture_output=True)
    t_make = time.perf_counter() - t0
    assert fastcsv.available(), "native tokenizer did not load"
    X, y = higgs_like(rows, seed)
    path = os.path.join(out_dir, f"higgs_like_{rows}.csv")
    t0 = time.perf_counter()
    held = write_csv(path, X, y)
    t_write = time.perf_counter() - t0
    size = os.path.getsize(path)
    b0 = fastcsv.FASTCSV_BYTES.value()
    t0 = time.perf_counter()
    fr = h2o3_tpu.import_file(path)
    jax.block_until_ready([v.data for v in fr.vecs])
    t_parse = time.perf_counter() - t0
    tokenized = fastcsv.FASTCSV_BYTES.value() - b0
    os.unlink(path)
    # the Python csv fallback never touches this counter
    assert tokenized >= 0.99 * size, (tokenized, size)
    assert (fr.nrows, fr.names) == (rows, FEATURES + [LABEL]), \
        (fr.nrows, fr.names)
    platform = jax.devices()[0].platform
    for v in fr.vecs:
        assert isinstance(v.data, jax.Array), type(v.data)
        assert {d.platform for d in v.data.devices()} == {platform}
    assert list(fr.vec(LABEL).domain) == LABEL_DOMAIN
    head = slice(0, min(rows, 4096))
    for j in (0, COLS - 1):
        got = fr.vec(FEATURES[j]).to_numpy()[head]
        np.testing.assert_allclose(got, held[head, j], atol=1e-6)
    got_y = fr.vec(LABEL).to_numpy()[head]
    np.testing.assert_array_equal(got_y, y[head].astype(np.float64))
    h2o3_tpu.remove(fr.key)
    return {"rows": rows, "csv_bytes": size, "tokenized_bytes": tokenized,
            "make_s": round(t_make, 2), "write_csv_s": round(t_write, 2),
            "import_file_s": round(t_parse, 2),
            "import_mb_per_s": round(size / 1e6 / t_parse, 1)}


def phase_train(rows: int, ntrees: int, seed: int, on_chip: bool = True):
    """Frame from seeded host arrays -> GBM.train. Returns (record,
    model, frame, X) — the later phases score what was trained."""
    import jax
    from h2o3_tpu.ops import hist_pallas as HP
    t0 = time.perf_counter()
    X, y = higgs_like(rows, seed)
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    fr = _frame(X, y)
    jax.block_until_ready([v.data for v in fr.vecs])
    t_frame = time.perf_counter() - t0
    traces0, c0 = HP.kernel_traces(), _counters()
    model = _gbm(ntrees, seed)
    t0 = time.perf_counter()
    model.train(y=LABEL, training_frame=fr)
    jax.block_until_ready(jax.tree_util.tree_leaves(model._trees))
    t_train = time.perf_counter() - t0
    picked = sorted(k for k, v in HP.kernel_traces().items()
                    if v > traces0.get(k, 0))
    if on_chip:
        # the Pallas kernels are IN the compiled trainer: each entry was
        # traced into it (model_summary's "engine" string proves nothing)
        # (the training metrics' scoring walk is the fifth)
        assert {k for k, _ in picked} == {"hist", "fused", "route",
                                          "route_f",
                                          "walk_dense_tile"}, picked
    else:
        assert not picked, picked
    summ = model._output.model_summary
    assert summ["number_of_trees"] == ntrees and summ["max_depth"] == DEPTH
    assert summ["nbins_effective"] == NBINS, summ
    auc = float(model.auc())
    assert auc > AUC_MIN, f"training AUC {auc:.4f} <= {AUC_MIN}"
    rec = {"rows": rows, "cols": COLS, "ntrees": ntrees, "depth": DEPTH,
           "nbins": NBINS, "generate_s": round(t_gen, 2),
           "frame_s": round(t_frame, 2),
           "train_s_incl_compile": round(t_train, 2),
           "train_auc": round(auc, 5), "pallas_kernels_traced": picked,
           "peak_bytes": _peak_bytes(on_chip), **_since(c0)}
    return rec, model, fr, X


def _host_scores(model, X, idx, out_dir: str) -> np.ndarray:
    """p(s) for rows `idx` from the EXPORTED artifact, read back and
    walked in numpy (genmodel/mojo.py MojoModel) — no device code."""
    from h2o3_tpu.genmodel.mojo import MojoModel
    path = model.download_mojo(os.path.join(out_dir, "gbm_smoke.mojo"))
    mojo = MojoModel.load(path)
    rows = [dict(zip(FEATURES, map(float, X[i]))) for i in idx]
    out = mojo.predict(rows)
    assert out["domain"] == LABEL_DOMAIN
    return out["probs"][:, 1]


def _frame_parity(model, X, rows: int, seed: int) -> dict:
    """The large-frame predict()'s frame — planes one device program made
    from the walk's own output, nothing fetched — against the frame
    _prediction_frame builds on the HOST from the fetched copy of the
    same scores: every column through Vec.to_numpy(), type and domain,
    over rows with NaN and ±inf cells and a row count the padding does
    not divide. Then the rows no model produces (an all-NaN score row, a
    tie): the device's argmax must follow NumPy's rules on this chip."""
    import jax
    import jax.numpy as jnp
    import h2o3_tpu
    from h2o3_tpu.obs import metrics as om
    from h2o3_tpu.serving import scorer_cache as sc
    assert rows > sc._max_rows(), (rows, sc._max_rows())
    rng = np.random.default_rng(seed + 3)
    Xp = X[:rows].copy()
    for v, p in ((np.nan, 0.01), (np.inf, 0.001), (-np.inf, 0.001)):
        Xp[rng.random(Xp.shape) < p] = v
    fr = _frame(Xp)
    assert fr.padded_len > rows, (fr.padded_len, rows)

    def made():
        m = om.REGISTRY.get("h2o3_predict_frame_columns_total")
        return [m.value(algo="gbm", columns=w) for w in ("device", "host")]

    def same(dev, host):
        assert dev.names == host.names and dev.nrows == host.nrows == rows
        for name in dev.names:
            a, b = dev.vec(name), host.vec(name)
            assert isinstance(a.data, jax.Array) and a.type == b.type
            assert (a.domain is None) == (b.domain is None)
            x, y = a.to_numpy(), b.to_numpy()
            assert x.dtype == y.dtype and x.shape == y.shape == (rows,)
            assert np.array_equal(x, y, equal_nan=True), \
                (name, int((x != y).sum()))

    made0 = made()
    h2o3_tpu.remove(model.predict(fr).key)              # compile / load
    t0 = time.perf_counter()
    dev = model.predict(fr)
    t_warm = time.perf_counter() - t0
    assert made() == [made0[0] + 2, made0[1]], (made0, made())
    scores = model._score_host(fr)
    host = model._prediction_frame(scores, rows)
    assert made() == [made0[0] + 2, made0[1] + 1], (made0, made())
    same(dev, host)
    p1 = dev.vec("ps").to_numpy()
    assert np.isfinite(p1).all()        # a tree routes NaN/inf, never emits it
    planted = jnp.asarray(scores).at[1].set(jnp.nan).at[2].set(0.5) \
        .at[3, 1].set(jnp.nan)
    dev_p = model._prediction_frame(planted, rows)
    host_p = model._prediction_frame(np.asarray(planted), rows)
    same(dev_p, host_p)
    lab = dev_p.vec("predict").to_numpy()
    assert (lab[1], lab[2], lab[3]) == (0.0, 0.0, 1.0), lab[:4]
    assert np.isnan(dev_p.vec("ps").to_numpy()[[1, 3]]).all()
    for k in (dev.key, host.key, dev_p.key, host_p.key, fr.key):
        h2o3_tpu.remove(k)
    return {"rows": rows, "padded": int(fr.padded_len),
            "nonfinite_cells": int((~np.isfinite(Xp)).sum()),
            "columns_compared": 3, "planted_rows": 3,
            "predict_warm_s": round(t_warm, 4)}


def phase_predict(model, fr, X, seed: int, out_dir: str,
                  slice_rows: int, check_rows: int,
                  parity_rows: int = PARITY_ROWS) -> dict:
    """Both branches of ModelBase._score_device against the host scorer:
    the whole frame (over the fast path's row ceiling -> the sharded
    large-frame walk, its prediction frame made on the device) and a
    slice under it (the compiled-scorer cache); then the large path's
    frame against the host-built frame of the same scores."""
    import h2o3_tpu
    from h2o3_tpu.serving import scorer_cache as sc
    rows = fr.nrows
    assert rows > sc._max_rows() >= slice_rows, (rows, sc._max_rows())
    rng = np.random.default_rng(seed + 1)
    idx = np.sort(rng.choice(rows, size=min(check_rows, rows),
                             replace=False))
    idx_s = idx[idx < slice_rows]
    if idx_s.size < min(check_rows, slice_rows):
        idx_s = np.sort(rng.choice(slice_rows, replace=False,
                                   size=min(check_rows, slice_rows)))

    def fallbacks():
        return {e["labels"]["reason"]: e["value"]
                for e in sc.FALLBACKS._json()}

    fb0, c0 = fallbacks(), _counters()
    t0 = time.perf_counter()
    pred = model.predict(fr)
    p_full = pred.vec("ps").to_numpy()
    t_full = time.perf_counter() - t0
    fb1 = fallbacks()
    assert fb1.get("too-large", 0) == fb0.get("too-large", 0) + 1, (fb0, fb1)
    assert pred.nrows == rows and np.isfinite(p_full).all()
    want = _host_scores(model, X, idx, out_dir)
    np.testing.assert_allclose(p_full[idx], want, atol=2e-5, rtol=0)
    h2o3_tpu.remove(pred.key)
    full = {"rows": rows, "seconds_incl_compile": round(t_full, 2),
            "compared": int(idx.size),
            "max_abs_dev": float(np.abs(p_full[idx] - want).max()),
            **_since(c0)}

    fr_s = _frame(X[:slice_rows])
    hits0, c0 = sc.HITS.value() + sc.MISSES.value(), _counters()
    t0 = time.perf_counter()
    pred_s = model.predict(fr_s)
    p_slice = pred_s.vec("ps").to_numpy()
    t_slice = time.perf_counter() - t0
    t0 = time.perf_counter()
    pred_w = model.predict(fr_s)
    p_warm = pred_w.vec("ps").to_numpy()
    t_warm = time.perf_counter() - t0
    assert fallbacks() == fb1, (fb1, fallbacks())     # no fallback at all
    assert sc.HITS.value() + sc.MISSES.value() == hits0 + 2
    np.testing.assert_array_equal(p_slice, p_warm)
    want_s = _host_scores(model, X, idx_s, out_dir)
    np.testing.assert_allclose(p_slice[idx_s], want_s, atol=2e-5, rtol=0)
    # the two device paths agree with each other on the shared rows
    np.testing.assert_allclose(p_slice, p_full[:slice_rows], atol=2e-5,
                               rtol=0)
    for k in (pred_s.key, pred_w.key, fr_s.key):
        h2o3_tpu.remove(k)
    fast = {"rows": slice_rows, "seconds_incl_compile": round(t_slice, 2),
            "seconds_warm": round(t_warm, 3), "compared": int(idx_s.size),
            "max_abs_dev": float(np.abs(p_slice[idx_s] - want_s).max()),
            **_since(c0)}
    return {"large_frame_path": full, "fast_path": fast,
            "frame_parity": _frame_parity(model, X, parity_rows, seed),
            "p_full": p_full}


def phase_serve(model, X, p_full, sizes=(1, 64, 4096), repeats: int = 5,
                one_row_repeats: int = ONE_ROW_REQUESTS,
                timeout_s: float = 600.0) -> dict:
    """REST scoring in this process: the server on a free port, the
    client on a worker thread. The serving layer answers from the legacy
    scorer when its fast path raises (degrade, don't 500) — so a 200
    alone proves nothing: the trace-error fallback counter must stay at
    zero and the model must not be strike-parked. A 1-row request is
    repeated `one_row_repeats` times: its warm median is the serving
    path's latency reading (PERF.md §6)."""
    from h2o3_tpu.api.server import start_server
    from h2o3_tpu.serving import scorer_cache as sc

    def trace_errors():
        return sc.FALLBACKS.value(reason="trace-error")

    err0, hits0, c0 = trace_errors(), sc.HITS.value(), _counters()
    srv = start_server(port=0)
    url = f"http://127.0.0.1:{srv.port}/3/Predictions/models/{model.key}"

    def client():
        lat = {}
        for n in sizes:
            body = json.dumps({"columns": FEATURES,
                               "rows": X[:n].tolist()}).encode()
            for _ in range(one_row_repeats if n == 1 else repeats):
                req = urllib.request.Request(
                    url, data=body, method="POST",
                    headers={"Content-Type": "application/json"})
                t0 = time.perf_counter()
                with urllib.request.urlopen(req, timeout=timeout_s) as r:
                    assert r.status == 200, r.status
                    doc = json.loads(r.read())
                lat.setdefault(n, []).append(time.perf_counter() - t0)
                assert doc["row_count"] == n, doc["row_count"]
                got = np.array([p["ps"] for p in doc["predictions"]])
                np.testing.assert_allclose(got, p_full[:n], atol=2e-5,
                                           rtol=0)
                labels = [p["predict"] for p in doc["predictions"]]
                assert set(labels) <= set(LABEL_DOMAIN), set(labels)
        return lat

    try:
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            lat = pool.submit(client).result(timeout=timeout_s)
    finally:
        srv.stop()
    assert trace_errors() == err0, "the REST fast path fell back"
    assert not sc._is_broken((model.key, sc.model_token(model)))
    assert sc.HITS.value() > hits0
    return {"requests": {
        str(n): {"count": len(v), "first_ms_incl_compile":
                 round(1e3 * v[0], 2),
                 "median_warm_ms": round(1e3 * float(np.median(v[1:])), 3)}
        for n, v in lat.items()},
        "one_row_median_warm_ms_pr28": list(ONE_ROW_MS_PR28),
        "trace_error_fallbacks": trace_errors() - err0, **_since(c0)}


# ===========================================================================
def _all_reduces(model, fr) -> int:
    """all-reduce ops in the COMPILED K-tree trainer, rebuilt through the
    estimator's own setup (same program as train() ran, so the compile
    is a persistent-cache hit). Also asserts each device holds exactly
    its share of the code plane."""
    import re
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import binned as BN
    ctx = model._binned_setup(fr)
    codes, shards = ctx["codes"], ctx["cl"].n_rows_shards
    parts = codes.addressable_shards
    assert len(parts) == shards and \
        len({p.device for p in parts}) == shards, parts
    for p in parts:
        assert p.data.shape == (codes.shape[0], codes.shape[1] // shards), \
            (p.data.shape, codes.shape)
    trainer = BN.gbm_chunk_trainer(
        ctx["grower"], ctx["n"], dist="bernoulli", eta=LEARN_RATE,
        sample_rate=1.0, mtries=0,
        k_trees=min(int(model.params["score_tree_interval"]),
                    int(model.params["ntrees"])),
        mesh=ctx["mesh"])
    F = jax.device_put(jnp.zeros(ctx["n_pad"], jnp.float32),
                       ctx["cl"].rows_sharding(1))
    text = trainer.lower(codes, ctx["y1"], ctx["w1"], F,
                         jax.random.PRNGKey(0)).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text
    return len(re.findall(r" all-reduce(?:-start)?\(", text))


def phase_four_chips(rows: int, ntrees: int, seed: int, shards: int = 4,
                     on_chip: bool = True) -> dict:
    """The row-sharded cloud H2O's users depend on: train on a `shards`-
    device mesh, then the same seeded data on a 1-device mesh of this
    process, and compare tree by tree."""
    import h2o3_tpu

    def train_on(n_shards):
        cloud = h2o3_tpu.init(n_rows_shards=n_shards)
        assert cloud.n_rows_shards == n_shards
        return phase_train(rows, ntrees, seed, on_chip=on_chip)[:3]

    rec_m, model_m, fr_m = train_on(shards)
    peaks = rec_m["peak_bytes"]     # before device 0 trains alone below
    frame_bytes = rows * COLS * 4
    if on_chip:
        # nothing of frame size was materialised whole on device 0 and
        # then resharded: its high-water mark sits with the others'
        skew = peaks[0] - float(np.median(peaks[1:]))
        assert skew < 0.25 * frame_bytes, (peaks, frame_bytes)
    n_ar = _all_reduces(model_m, fr_m)
    assert n_ar == DEPTH, f"{n_ar} all-reduces, the design says {DEPTH}"
    trees_m = model_m._trees
    col_m, thr_m = np.asarray(trees_m.col), np.asarray(trees_m.thr)
    auc_m = rec_m["train_auc"]
    h2o3_tpu.remove(fr_m.key)
    del model_m, fr_m, trees_m

    rec_1, model_1, fr_1 = train_on(1)
    if on_chip:
        # ... nor whole on EVERY device: alone, device 0 needs a multiple
        # of what each of the `shards` needed for the same rows
        assert rec_1["peak_bytes"][0] > 2 * max(peaks), (rec_1, peaks)
    col_1 = np.asarray(model_1._trees.col)
    thr_1 = np.asarray(model_1._trees.thr)
    same = (col_m == col_1) & ((thr_m == thr_1) | (col_m < 0))
    per_tree = same.mean(axis=1)
    top = 2 ** 4 - 1        # levels 0-3 of the heap
    # f32 reduction order differs between 1 and N partial sums, so a
    # near-tie deep in a late tree may flip; the first tree's top levels
    # and the bulk of all split decisions may not
    assert same[0, :top].all(), (col_m[0, :top], col_1[0, :top])
    assert per_tree.mean() > 0.8, per_tree
    assert abs(auc_m - rec_1["train_auc"]) < 2e-3, (auc_m, rec_1)
    return {"rows": rows, "ntrees": ntrees, "shards": shards,
            "all_reduces_per_tree": n_ar,
            "split_agreement_per_tree": [round(float(a), 4)
                                         for a in per_tree],
            "auc": {str(shards): auc_m, "1": rec_1["train_auc"]},
            "peak_bytes_per_device": peaks, "frame_bytes": frame_bytes,
            f"train_{shards}": rec_m, "train_1": rec_1}


# ===========================================================================
def _emit(phase: str, t0: float, rec: dict):
    print(json.dumps({"phase": phase,
                      "seconds": round(time.perf_counter() - t0, 2), **rec}),
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the row-sharded train vs one chip")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    t_all = time.perf_counter()

    t0 = time.perf_counter()
    dev = phase_device(args.chips)
    _emit("device", t0, dev)
    os.makedirs(OUT_DIR, exist_ok=True)

    if args.chips == 4:
        t0 = time.perf_counter()
        _emit("four_chips", t0, phase_four_chips(
            FOUR_CHIP_ROWS, FOUR_CHIP_NTREES, args.seed))
    else:
        t0 = time.perf_counter()
        _emit("kernels", t0, phase_kernels(args.seed))
        t0 = time.perf_counter()
        _emit("walk", t0, phase_walk_exact(WALK_ROWS, WALK_SHAPES, args.seed))
        for shape in WALK_SET_SHAPES:
            t0 = time.perf_counter()
            _emit("walk_sets", t0, phase_walk_sets(
                WALK_SET_ROWS, WALK_SET_LEVELS, shape, args.seed))
        for shape in WALK_CLASS_SHAPES:
            t0 = time.perf_counter()
            _emit("walk_classes", t0, phase_walk_classes(
                WALK_SET_ROWS, WALK_CLASS_LEVELS, shape, WALK_CLASSES,
                args.seed))
        t0 = time.perf_counter()
        _emit("ingest", t0, phase_ingest(INGEST_ROWS, args.seed, OUT_DIR))
        t0 = time.perf_counter()
        rec, model, fr, X = phase_train(ROWS, NTREES, args.seed)
        _emit("train", t0, rec)
        t0 = time.perf_counter()
        rec = phase_predict(model, fr, X, args.seed, OUT_DIR, SLICE_ROWS,
                            CHECK_ROWS)
        p_full = rec.pop("p_full")
        _emit("predict", t0, rec)
        t0 = time.perf_counter()
        _emit("serve", t0, phase_serve(model, X, p_full))
    _emit("total", t_all, _counters())
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
