"""work_model_dense.py — the MXU passes a DENSE walk of an ensemble needs,
from the configuration alone: the yardstick of `score_walk_mxu_pct`.

work_model.score_call counts what ANY scorer must do (a compare a tree and
level; the features read once): against it a dense walk reads a few per
cent whatever it does. This counts what the dense formulation itself
cannot do without (models/tree/engine.py: every node of a tree meets a
tile of rows, a block of 128 node slots at a time), so that the share says
how close the kernel runs to the MXU, not how far the formulation is from
a gather walk.

A pass is one product of a (128 x 128) operand with (128 x rows): 2 x 128
x 128 flop a row at the chip's bf16 peak. A block of 128 slots holds 128 >>
levels trees (levels = the depth, at least 3) and takes, a row tile:

  2 passes   the one-hot select of every slot's feature, the low and the
             high 16-bit half of the f32 (two bytes each, so the product
             is 2 x columns deep: one pass up to 64 columns)
  1 pass     the slots' +-1 decisions with the block's path matrix
  1/2 pass   a 128 level rows of categorical columns: the slots' go-right
             bits with the rows' level one-hot, int8 at twice the bf16 rate

Depths past 7 (a tree over several blocks, levels walked by position) are
not counted here: no cell of the benchmark reads this metric there.
"""

from __future__ import annotations

import math

BLOCK = 128
PASS_FLOP = 2.0 * BLOCK * BLOCK


def walk_passes(trees: int, depth: int, columns: int,
                level_rows: int) -> float:
    """Passes a row tile: the blocks the trees fill x the passes a block."""
    if depth > 7:
        raise ValueError("work_model_dense counts block walks up to depth 7")
    blocks = math.ceil(trees / (BLOCK >> max(depth, 3)))
    select = 2 * math.ceil(2 * columns / BLOCK)
    sets = 0.5 * math.ceil(level_rows / BLOCK)
    return blocks * (select + 1 + sets)


def walk_flops(rows: int, trees: int, depth: int, columns: int,
               level_rows: int) -> float:
    return rows * PASS_FLOP * walk_passes(trees, depth, columns, level_rows)
