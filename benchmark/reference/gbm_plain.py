"""gbm_plain.py — the plain reference: a histogram GBM (bernoulli) and its
scorer in straightforward NumPy, written from H2O-3's documented
semantics. It imports nothing of `h2o3_tpu` and takes nothing the program
made except what the program ANSWERED (its trees, the way a served
model's reference takes the served tokens).

The semantics, as H2O-3's GBM with `histogram_type="QuantilesGlobal"`
states them (and the repo's docs repeat):

  * bins: per column `nbins` quantile bins from a strided row sample of at
    most 2**18 rows (stride = max(1, n >> 18)); cut points are the
    1/nbins .. (nbins-1)/nbins quantiles (linear interpolation), stored
    in f32. A value's code is the number of cut points below it, so a
    split "at cut b" sends x <= cut[b] left and x > cut[b] right.
  * a tree grows level by level to `max_depth`. Per node the split is the
    (column, cut) with the largest squared-error reduction of the
    pseudo-residuals, gl^2/wl + gr^2/wr - gp^2/wp (w = row count), among
    cuts leaving at least `min_rows` rows on both sides; a node splits
    only if that gain exceeds `min_split_improvement`.
  * bernoulli: residual g = y - sigmoid(F), hessian h = p(1-p); a node's
    value is the Newton step sum(g)/sum(h), clipped to +-19; margins
    move by learn_rate * value of the row's leaf; F0 = logit(mean(y)).
  * trees are dense heaps: node 0 the root, children of i at 2i+1, 2i+2,
    `col` < 0 marks a leaf.

Accumulations are in float64, inputs and stored results float32: this is
the float32-or-wider side of every comparison, which is what the
configurations state. `precision=` turns the same grower and scorer into
the CONTROL, the reference in the program's place at the next precision
down: "bf16" keeps residual statistics, leaf values and margins in
bfloat16 with wide sums (what the MXU does with bfloat16 operands; the
scorer also rounds features and thresholds). `fault=` plants the faults a
training cell can have.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CLIP = 19.0
SAMPLE = 1 << 18


def _per_column(fn, C):
    """fn(c) for every column, a few threads wide (NumPy's search and
    bincount loops release the interpreter lock)."""
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return list(pool.map(fn, range(C)))


def _bf16(a):
    """Round to bfloat16 and back (round-to-nearest-even on the top 16
    bits of the f32 pattern) — no ml_dtypes needed."""
    u = np.asarray(a, np.float32).view(np.uint32)
    r = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def quantile_edges(X: np.ndarray, nbins: int) -> np.ndarray:
    """(C, nbins-1) f32 cut points from the strided sample."""
    n = X.shape[0]
    stride = max(1, n >> 18)
    Xs = np.asarray(X[::stride][:SAMPLE], np.float32)
    qs = np.linspace(0.0, 1.0, nbins + 1)[1:-1]
    return np.stack([np.quantile(Xs[:, c], qs) for c in range(X.shape[1])]
                    ).astype(np.float32)


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """(n, C) uint8: number of cut points strictly below the value."""
    out = np.empty(X.shape, np.uint8)

    def one(c):
        out[:, c] = np.searchsorted(edges[c], X[:, c], side="left")
    _per_column(one, X.shape[1])
    return out


def sigmoid(F):
    return 1.0 / (1.0 + np.exp(-np.asarray(F, np.float64)))


def grad_hess(F, y):
    p = sigmoid(F)
    return (y - p), p * (1.0 - p)


def logloss(F, y) -> float:
    p = np.clip(sigmoid(F), 1e-15, 1 - 1e-15)
    return float(-np.mean(np.where(y > 0.5, np.log(p), np.log1p(-p))))


def init_margin(y) -> float:
    p0 = min(max(float(np.mean(y, dtype=np.float64)), 1e-10), 1 - 1e-10)
    return float(np.log(p0 / (1 - p0)))


def score(w, g):
    return np.where(w > 0, g * g / np.maximum(w, 1e-30), 0.0)


def level_best_splits(codes, local, g, L, B, min_rows):
    """Best (gain, column, cut) of each of the L nodes of one level.
    codes (m, C) uint8 and local (m,) node index within the level, for the
    m rows that are AT this level; g (m,) f64. Returns gain (L,) with
    -inf where no cut leaves min_rows on both sides, col (L,), cut (L,)."""
    base = local.astype(np.int64) * B

    def one(c):
        idx = base + codes[:, c]
        w = np.bincount(idx, minlength=L * B).reshape(L, B).astype(np.float64)
        s = np.bincount(idx, weights=g, minlength=L * B).reshape(L, B)
        wl = np.cumsum(w, 1)[:, :-1]
        gl = np.cumsum(s, 1)[:, :-1]
        wp, gp = w.sum(1, keepdims=True), s.sum(1, keepdims=True)
        wr, gr = wp - wl, gp - gl
        gain = score(wl, gl) + score(wr, gr) - score(wp, gp)
        gain = np.where((wl >= min_rows) & (wr >= min_rows), gain, -np.inf)
        k = gain.argmax(1)
        return gain[np.arange(L), k], k

    best = np.full(L, -np.inf)
    bcol = np.full(L, -1, np.int64)
    bcut = np.full(L, -1, np.int64)
    for c, (gk, k) in enumerate(_per_column(one, codes.shape[1])):
        better = gk > best           # ties keep the first column
        best = np.where(better, gk, best)
        bcol = np.where(better, c, bcol)
        bcut = np.where(better, k, bcut)
    return best, bcol, bcut


def route(X, node, col_t, thr_t, nal_t):
    """One level of the walk: rows at a split node move to a child."""
    c = col_t[node]
    split = c >= 0
    x = X[np.arange(X.shape[0]), np.maximum(c, 0)]
    right = x > thr_t[node]
    isna = np.isnan(x)
    if isna.any():
        right = np.where(isna, ~nal_t[node], right)
    return np.where(split, 2 * node + 1 + right, node)


def walk(X, col_t, thr_t, nal_t, depth) -> np.ndarray:
    """Leaf (heap id) of every row in one tree."""
    node = np.zeros(X.shape[0], np.int64)
    for _ in range(depth):
        node = route(X, node, col_t, thr_t, nal_t)
    return node


def margins(X, model, precision="f32") -> np.ndarray:
    """f0 + learn_rate * sum of leaf values, f64. `precision="bf16"` is
    the scoring CONTROL: features, thresholds and leaf values rounded to
    bfloat16 and the margin rounded after every tree."""
    low = precision == "bf16"
    thr, val = model["thr"], model["value"]
    if low:
        X, thr, val = _bf16(X), _bf16(thr), _bf16(val)
    F = np.full(X.shape[0], model["f0"], np.float64)
    for t in range(model["col"].shape[0]):
        leaf = walk(X, model["col"][t], thr[t], model["na_left"][t],
                    model["depth"])
        F += model["learn_rate"] * val[t][leaf].astype(np.float64)
        if low:
            F = _bf16(F).astype(np.float64)
    return F


def predict_proba(X, model, precision="f32") -> np.ndarray:
    """p(class 1) per row — what a served answer or a scored row is
    compared with."""
    p = sigmoid(margins(np.asarray(X, np.float32), model, precision))
    return _bf16(p).astype(np.float64) if precision == "bf16" else p


def node_totals(leaf, g, h, nodes):
    """(w, g, h) of every node: leaf sums pushed up the heap."""
    tot = np.stack([np.bincount(leaf, minlength=nodes).astype(np.float64),
                    np.bincount(leaf, weights=g, minlength=nodes),
                    np.bincount(leaf, weights=h, minlength=nodes)])
    for i in range(nodes - 1, 0, -1):
        tot[:, (i - 1) // 2] += tot[:, i]
    return tot


def grow(X, y, *, ntrees, max_depth, nbins, learn_rate, min_rows=10.0,
         min_split_improvement=1e-5, precision="f32", fault=None,
         history_every=5):
    """The reference grower, used where the reference stands in the
    program's place (the control and the planted faults) and for the small
    CPU agreement test. Returns the same dict `check.model_of` builds from
    the program's model."""
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float64)
    n, C = X.shape
    low = precision == "bf16"
    keep = np.ones(n, bool)
    if fault == "half_batch":
        keep[n // 2:] = False         # half the rows never reach a histogram
    edges = quantile_edges(X, nbins)
    codes = bin_codes(X, edges)
    nodes = 2 ** (max_depth + 1) - 1
    f0 = init_margin(y)
    F = np.full(n, f0, np.float32)
    col = np.full((ntrees, nodes), -1, np.int32)
    thr = np.zeros((ntrees, nodes), np.float32)
    val = np.zeros((ntrees, nodes), np.float32)
    hist = []
    for t in range(ntrees):
        g, h = grad_hess(F, y)
        if low:
            g, h = _bf16(g).astype(np.float64), _bf16(h).astype(np.float64)
        node = np.zeros(n, np.int64)
        for d in range(max_depth):
            L, base = 1 << d, (1 << d) - 1
            at = keep & (node >= base)
            gain, bc, bb = level_best_splits(codes[at], node[at] - base,
                                             g[at], L, nbins, min_rows)
            did = gain > max(min_split_improvement, 0.0)
            ids = base + np.flatnonzero(did)
            col[t, ids] = bc[did]
            thr[t, ids] = edges[bc[did], bb[did]]
            node = route(X, node, col[t], thr[t], None)
        tot = node_totals(node[keep], g[keep], h[keep], nodes)
        v = np.clip(tot[1] / np.maximum(tot[2], 1e-30), -CLIP, CLIP)
        val[t] = _bf16(v) if low else v
        if fault == "altered_leaf" and t == 1:
            val[t, np.abs(val[t]).argmax()] *= 1.25   # one answer altered
        if fault != "state_unchanged":
            F = F + np.float32(learn_rate) * val[t][node]
            if low:
                F = _bf16(F)
        if (t + 1) % history_every == 0 or t == ntrees - 1:
            hist.append((t + 1, logloss(F[keep], y[keep])))
    model = {"col": col, "thr": thr, "na_left": np.zeros_like(col, bool),
             "value": val, "depth": max_depth, "f0": f0,
             "learn_rate": learn_rate, "history": hist}
    F = margins(X, model)
    model["final_logloss"] = logloss((_bf16(F) if low else F)[keep], y[keep])
    return model
