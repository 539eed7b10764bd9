"""gbm_classes_plain.py — the plain reference SCORER for a multinomial GBM:
K classes, every tree belonging to ONE of them, numeric and categorical SET
splits. Straightforward NumPy, f64 sums, written from H2O-3's documented
semantics. It imports nothing of `h2o3_tpu` and takes nothing the program
made except what the program ANSWERED (its trees, each tree's class, the K
initial margins), and walks the trees from the RAW host table: the numeric
values and the categorical columns' level ids as the data generator made
them.

The semantics, as H2O-3's scoring states them (hex/genmodel
`SharedTreeMojoModel.scoreTree`, `GbmMojoModel.unifyPreds`,
`GenModel.GBM_rescale`):

  * a tree is walked as reference/gbm_sets_plain.py walks it (its `walk`
    is used as it stands: dense heaps, numeric x > thr goes right, a set
    bit of the row's level goes right, NaN goes by `na_left`), and its
    leaf's value is added to the margin of ITS class, `tree_class[t]`:
    F[:, c] = f0[c] + learn_rate * sum of class c's leaves.
  * multinomial: p = softmax(F) over the K classes (exp of the margins
    less their largest, over their sum).

`precision="bf16"` is the CONTROL, the reference at the next precision
below the one the configuration states: features, thresholds and leaf
values rounded to bfloat16, a class's margin after every tree, the
probabilities at the end. Two PLANTED FAULTS beside it, each what a walk
that sums the K classes in one pass can get wrong: `fault="shift_class"`
adds every tree to the class after its own (mod K), `fault="drop_f0"`
leaves out the initial margins.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.gbm_sets_plain import _bf16, walk

# rows of one pass: the walk's per-row look-ups stay in cache
ROWS = 16_384


def softmax(F):
    e = np.exp(F - F.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def margins(X, model, precision="f32", fault=None) -> np.ndarray:
    """(n, K) f64: f0[c] + learn_rate * sum of class c's leaf values."""
    X = np.array(X, np.float32)
    low = precision == "bf16"
    val, cls = model["value"], np.asarray(model["tree_class"])
    K = len(model["f0"])
    if fault == "shift_class":
        cls = (cls + 1) % K
    if low:
        X, val = _bf16(X), _bf16(val)
        model = dict(model, thr=_bf16(model["thr"]))
    F = np.tile(np.asarray(model["f0"], np.float64), (X.shape[0], 1))
    if fault == "drop_f0":
        F[:] = 0.0
    for lo in range(0, X.shape[0], ROWS):
        x, f = X[lo:lo + ROWS], F[lo:lo + ROWS]
        for t in range(model["col"].shape[0]):
            c = cls[t]
            f[:, c] += model["learn_rate"] \
                * val[t][walk(x, t, model)].astype(np.float64)
            if low:
                f[:, c] = _bf16(f[:, c])
    return F


def predict_proba(X, model, precision="f32", fault=None) -> np.ndarray:
    """(n, K): p(class) per row, in the domain's order — what a scored row
    is compared with."""
    p = softmax(margins(X, model, precision, fault))
    return _bf16(p).astype(np.float64) if precision == "bf16" else p
