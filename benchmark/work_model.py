"""work_model.py — the operations and bytes the ALGORITHM needs, from
shapes only. Nothing here knows which kernel runs: padded columns, packed
words, sibling subtraction, fused or split passes are implementation, and
a PR that fuses or deletes a kernel must not move its own yardstick.

Histogram GBM, per tree and level, per row: one byte of bin code for each
REAL column, the residual and hessian (2 x f32), the row's node id read
and written (2 x i32); and 3 adds per column (count, residual, hessian
into the row's bin). Per tree, per row: the margin read and written, the
label read, residual and hessian written (5 x f32). Split search works on
histograms, not rows: left out (it is < 1 % of either count at these
sizes). The algorithm is bytes-bound on every chip in peaks.json:
(cols + 16) bytes against 3 * cols adds per row and level.
"""

from __future__ import annotations

import math


def tree_bytes(rows: int, real_cols: int, depth: int, trees: int) -> float:
    return float(trees) * rows * (depth * (real_cols + 16) + 20)


def tree_ops(rows: int, real_cols: int, depth: int, trees: int) -> float:
    return float(trees) * rows * depth * real_cols * 3


def train_call(rows: int, real_cols: int, depth: int, trees: int,
               nbins: int) -> tuple[float, float]:
    """(ops, bytes) of everything one train() must do: one pass over the
    f32 frame to bin it (binary search per value, codes written), the
    trees, one scoring pass over the features for the training metrics
    (a compare per tree and level, a probability written)."""
    cells = float(rows) * real_cols
    ops = cells * math.ceil(math.log2(max(nbins, 2))) \
        + tree_ops(rows, real_cols, depth, trees) \
        + 2.0 * rows * trees * depth
    byts = cells * 5 + tree_bytes(rows, real_cols, depth, trees) \
        + cells * 4 + rows * 4.0
    return ops, byts


def score_call(rows: int, real_cols: int, depth: int,
               trees: int) -> tuple[float, float]:
    """(ops, bytes) of scoring `rows` rows with the ensemble."""
    return (2.0 * rows * trees * depth + 4.0 * rows,
            float(rows) * (4 * real_cols + 12))


def least_seconds(ops: float, byts: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(ops / peak["flops_per_s"], byts / peak["hbm_bytes_per_s"])


def share_pct(least_s: float, measured_s: float, what: str) -> float:
    """A share of a roofline or of a peak. Above 100 % the count is too
    high or the time leaves out work: that fails the run, it is not
    clipped."""
    pct = 100.0 * least_s / measured_s
    if not pct <= 100.0:
        raise ValueError(f"{what}: {pct:.2f} % of the chip's peak — the "
                         "work model or the measured time is wrong")
    return pct
