"""gbm_sets_plain.py — the plain reference SCORER for a bernoulli GBM whose
trees hold categorical SET splits beside numeric ones: straightforward
NumPy, f64 sums, written from H2O-3's documented semantics. It imports
nothing of `h2o3_tpu` and takes nothing the program made except what the
program ANSWERED (its trees), and walks them from the RAW host table: the
numeric values and the categorical columns' level ids as the data
generator made them.

The semantics, as H2O-3's tree scoring states them (hex/genmodel
`SharedTreeMojoModel.scoreTree`, `GenmodelBitSet.contains`):

  * trees are dense heaps: node 0 the root, children of i at 2i+1, 2i+2,
    `col` < 0 marks a leaf; a row walks `depth` levels or stops at a leaf.
  * a numeric split sends x <= thr left, x > thr right.
  * a categorical split holds a bitset over the column's level ids; a row
    whose level's bit is set goes RIGHT, else left. Bit l of a node is bit
    l % 32 of its 32-bit word l // 32 (`sets` (T, nodes, W) uint32).
  * a missing value (NaN) goes the way `na_left` says.
  * bernoulli: p = sigmoid(f0 + learn_rate * sum of the leaves' values).

Departures from `GenmodelBitSet`, and why they cannot show here: H2O-3
sends a level OUTSIDE a node's bitset range (an unseen level) the way of
a missing value; the program holds a level past the column's last to the
last (`levels[c]`, so does this scorer). The benchmark's tables hold
level ids 0..levels-1 only, as a frame adapted to the model's domains
does, so no compared row has such a level.

`precision="bf16"` is the CONTROL, the reference at the next precision
below the one the configuration states: features, thresholds and leaf
values rounded to bfloat16, the margin after every tree, the probability
at the end (a level id past 256 does not survive bfloat16 either).
`clip_codes=255` is the second control, a PLANTED FAULT: level ids capped
at a code byte's 254, which is how the program binned and scored such a
column before every level had its own bin.
"""

from __future__ import annotations

import numpy as np


def _bf16(a):
    """Round to bfloat16 and back (round-to-nearest-even on the top 16
    bits of the f32 pattern) — no ml_dtypes needed."""
    u = np.asarray(a, np.float32).view(np.uint32)
    r = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def sigmoid(F):
    return 1.0 / (1.0 + np.exp(-np.asarray(F, np.float64)))


def in_set(words, level):
    """Bit `level` of each row's node set: words (n, W) uint32."""
    w = words[np.arange(words.shape[0]), level // 32]
    return ((w >> (level % 32).astype(np.uint32)) & 1) == 1


def route(X, node, t, model):
    """One level of the walk in tree t: rows at a split node move to a
    child. X (n, C) f32: numeric values, level ids in categorical
    columns."""
    c = model["col"][t][node]
    split = c >= 0
    cc = np.maximum(c, 0)
    x = X[np.arange(X.shape[0]), cc]
    isna = np.isnan(x)
    right = x > model["thr"][t][node]
    cat = model["is_cat"][cc]
    if cat.any():
        # the level id, held to the column's levels (see the header)
        level = np.clip(np.nan_to_num(x).astype(np.int64), 0,
                        np.maximum(model["levels"][cc], 1) - 1)
        right = np.where(cat, in_set(model["sets"][t][node], level), right)
    right = np.where(isna, ~model["na_left"][t][node], right)
    return np.where(split, 2 * node + 1 + right, node)


def walk(X, t, model) -> np.ndarray:
    """Leaf (heap id) of every row in tree t."""
    node = np.zeros(X.shape[0], np.int64)
    for _ in range(model["depth"]):
        node = route(X, node, t, model)
    return node


def margins(X, model, precision="f32", clip_codes=None) -> np.ndarray:
    """f0 + learn_rate * sum of leaf values, f64."""
    X = np.array(X, np.float32)
    low = precision == "bf16"
    if clip_codes is not None:
        cat = np.flatnonzero(model["is_cat"][: X.shape[1]])
        X[:, cat] = np.minimum(X[:, cat], clip_codes - 1)
    val = model["value"]
    if low:
        X, val = _bf16(X), _bf16(val)
        model = dict(model, thr=_bf16(model["thr"]))
    F = np.full(X.shape[0], model["f0"], np.float64)
    for t in range(model["col"].shape[0]):
        leaf = walk(X, t, model)
        F += model["learn_rate"] * val[t][leaf].astype(np.float64)
        if low:
            F = _bf16(F).astype(np.float64)
    return F


def predict_proba(X, model, precision="f32", clip_codes=None) -> np.ndarray:
    """p(class 1) per row — what a scored row is compared with."""
    p = sigmoid(margins(X, model, precision, clip_codes))
    return _bf16(p).astype(np.float64) if precision == "bf16" else p
