"""higgs_like — seeded data at UCI HIGGS's shape (no network on the chip
machine): f32 standard-normal features and a 0/1 label from bench.py's
target function (copied, not imported: the yardstick may not move when the
program's own scripts do). A configuration names this module under
`data`; another table is another file here with the same three names.

Rows are made in fixed blocks, each from (seed, block), so the first n
rows are the same whatever the total, and the blocks fill a few threads
wide (NumPy's generators release the interpreter lock).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

LABEL, DOMAIN = "label", ["b", "s"]
BLOCK = 250_000


def feature_names(cols: int):
    return [f"f{j}" for j in range(cols)]


def host_arrays(rows: int, cols: int, seed: int):
    """(X (rows, cols) f32, y (rows,) bool) from the seed."""
    X = np.empty((rows, cols), np.float32)
    y = np.empty(rows, bool)

    def fill(b):
        lo, hi = b * BLOCK, min((b + 1) * BLOCK, rows)
        rng = np.random.default_rng([seed, b])
        x = X[lo:hi]
        rng.standard_normal(out=x, dtype=np.float32)
        logit = (1.2 * x[:, 0] - 0.8 * x[:, 1] + 0.6 * x[:, 2] * x[:, 3]
                 + 0.4 * np.sin(x[:, 4]) + 0.3 * x[:, 5] * x[:, 6])
        u = np.random.default_rng([seed, b, 1]).random(hi - lo,
                                                       dtype=np.float32)
        y[lo:hi] = u < 1.0 / (1.0 + np.exp(-logit))

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK))))
    return X, y


def frame(X, y):
    """A Frame in the DKV through the public constructors: one Vec per
    host column, the label a two-level categorical."""
    import jax
    from h2o3_tpu.core.frame import Frame, T_CAT, Vec
    vecs = [Vec.from_numpy(X[:, j]) for j in range(X.shape[1])]
    vecs.append(Vec.from_numpy(y.astype(np.float64), type=T_CAT,
                               domain=DOMAIN))
    fr = Frame(feature_names(X.shape[1]) + [LABEL], vecs)
    jax.block_until_ready([v.data for v in fr.vecs])
    return fr
