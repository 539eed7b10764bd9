"""Pallas TPU kernel of the scoring walk's dense body (models/tree/engine.py
`_walk_dense`): one fused kernel a row tile, the trees looped INSIDE it, so
that a tile's feature bytes are decomposed once and nothing of (rows x
nodes) size — node features, decisions, ±1 turns, path scores, the leaf
one-hot — ever leaves VMEM.

Layout: ROWS RIDE THE LANES. On the TPU a (rows, columns) f32 matrix of few
columns lives column-major in HBM (`{0,1:T(8,128)}`), so its transpose is
the same bytes and a (columns, rows) block is a plain tile of it; a node
block's 128 slots lie on the sublanes, every per-slot constant is a column,
a tree's leaf value is a sum over sublanes (vreg adds) and the tile's sum a
lane-dense row.

Grid (row tile, step), steps innermost: a step is `engine._perfect_tree`'s —
128 / 2^levels shallow trees in one 128-slot block, or one tree of 8 levels
and more: its top 8 levels in two blocks ({root, left subtree}, {root,
right subtree}), every level under them in 2^level / 128 more. At a tile's
first step the tile's four byte planes go to a VMEM scratch; every step
then runs, a chunk of lanes at a time: two bf16 products of a block's
one-hot select with the byte planes -> the 16-bit halves of every slot's
feature -> the f32 itself -> the decision (NaN: ~na_left, else x > thr) as
±1 -> the product with the block's path matrix == 8 (or the tree's levels)
-> [under level 8: the one slot of the level at the row's position says
which child] -> the tree's leaf value -> acc + w * v, tree by tree in tree
order.

`engine._walk_dense_xla` is the twin (the CPU's body and the test oracle);
the two agree with `engine._walk_gather` bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.ops.hist_pallas import KERNEL_TRACES, use_pallas  # noqa: F401

# slots of a node block: one MXU tile wide, one vreg row of lanes
BLOCK = 128
# the levels matched by path products: 2^8 slots, two blocks; the levels
# under them are walked by position
PATH_LEVELS = 8
# rows of a grid step (lanes of the tile), and of one pass of the chain
# inside it. A pass is a chain of three dependent products, and passes do
# not overlap: 2,750,000 x 28 at 20 x depth 5 / 10 x depth 8 walks in 41.7 /
# 95.8 ms at 128 rows a pass, 15.3 / 51.2 at 512, 10.2 / 35.5 at 2048 and
# 9.7 / 33.6 at 8192 (PERF.md §6, PR 33); the tile's size moves nothing
TILE_ROWS = 16384
CHUNK = 8192


def _walk_kernel(tw_ref, xt_ref, sel_ref, tbl_ref, paths_ref, out_ref,
                 planes, acc, *, levels, trees, chunk):
    step = pl.program_id(1)
    Cp = xt_ref.shape[0]
    top = min(levels, PATH_LEVELS)
    W = BLOCK // trees

    @pl.when(step == 0)
    def _():
        # rows of the block past the matrix's columns hold whatever the
        # copy left: any byte is a finite number, and its select is zero
        bits = pltpu.bitcast(xt_ref[...], jnp.int32)
        for k in range(4):
            byte = ((bits >> (8 * k)) & 0xFF).astype(jnp.float32)
            planes[k * Cp:(k + 1) * Cp, :] = byte.astype(jnp.bfloat16)
        acc[...] = jnp.zeros_like(acc)

    def one_chunk(j, carry):
        at_rows = pl.ds(pl.multiple_of(j * chunk, chunk), chunk)

        def turns(b, tbl):
            """±1 (128, chunk): which way each slot of block b sends a row;
            `tbl` its constants as columns (thr, a NaN's turn, leaf)."""
            lo, hi = (jnp.dot(sel_ref[0, b],
                              planes[2 * Cp * h:2 * Cp * (h + 1), at_rows],
                              preferred_element_type=jnp.float32)
                      .astype(jnp.int32) for h in (0, 1))
            x = pltpu.bitcast((hi << 16) | lo, jnp.float32)
            return jnp.where(x != x, tbl[:, 1:2],
                             jnp.where(x > tbl[:, 0:1], 1.0, -1.0))

        def reached(b, tbl):
            """(128, chunk): the position of level `top` each row reaches,
            among block b's: its ±1 decisions match that path in all."""
            return jnp.dot(paths_ref[b], turns(b, tbl).astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) == top

        def column(a):
            return jnp.sum(a, axis=0, keepdims=True)

        if levels <= PATH_LEVELS:
            v = [None] * trees
            for b in range(paths_ref.shape[0]):
                tbl = tbl_ref[0, b].T
                hit = jnp.where(reached(b, tbl), tbl[:, 2:3], 0.0)
                for g in range(trees):
                    vg = column(hit[g * W:(g + 1) * W])
                    # a tree's second block: one of the two reads +0.0
                    v[g] = vg if v[g] is None else v[g] + vg
        else:
            slot = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, chunk), 0)
            pos = column(jnp.where(reached(0, tbl_ref[0, 0].T), slot, 0)) \
                + column(jnp.where(reached(1, tbl_ref[0, 1].T),
                                   slot + BLOCK, 0))
            for d in range(PATH_LEVELS, levels):
                first = (1 << d) // BLOCK       # the level's first block

                def level(k, turn, first=first, pos=pos):
                    here = slot + k * BLOCK == pos
                    b = first + k
                    return turn + column(
                        jnp.where(here, turns(b, tbl_ref[0, b].T), 0.0))

                turn = jax.lax.fori_loop(0, first, level,
                                         jnp.zeros((1, chunk), jnp.float32))
                pos = 2 * pos + jnp.where(turn > 0, 1, 0)

            def leaf(k, v):
                here = slot + k * BLOCK == pos
                return v + column(jnp.where(here, tbl_ref[0, k].T[:, 2:3],
                                            0.0))

            v = [jax.lax.fori_loop(0, (1 << levels) // BLOCK, leaf,
                                   jnp.zeros((1, chunk), jnp.float32))]
        a = acc[:, at_rows]
        for g in range(trees):      # tree by tree, in tree order
            a = a + tw_ref[step, g] * v[g]
        acc[:, at_rows] = a
        return carry

    jax.lax.fori_loop(0, acc.shape[1] // chunk, one_chunk, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc[...]


def walk_dense_tile(X, sel, thr, nal, leafv, tws, paths, *, levels):
    """The steps of `engine._perfect_tree` over X (n, C): (n,) f32 =
    Σ_t w[t] · value[t, leaf_t(row)], tree by tree."""
    n, C = X.shape
    U, G = tws.shape
    blocks = thr.shape[1] // BLOCK
    KERNEL_TRACES.inc(kernel="walk_dense_tile", L=str(1 << levels))
    Cp = -(-C // 16) * 16       # a bf16 tile's 16 sublanes
    # (U, C, S) one-hot -> (U, blocks, 128, 2 Cp): [low byte | high byte]
    # of a 16-bit half -> low + 256 * high
    sel = jnp.pad(sel, ((0, 0), (0, Cp - C), (0, 0)))
    sel = jnp.concatenate([sel, 256 * sel], axis=1) \
        .reshape(U, 2 * Cp, blocks, BLOCK).transpose(0, 2, 3, 1)
    # a block's per-slot constants as columns: thr, a NaN's turn (±1), the
    # leaf value (of the bottom level's positions, laid as the slots are)
    tbl = jnp.stack([a.reshape(U, blocks, BLOCK) for a in
                     [thr, jnp.where(nal, -1.0, 1.0), leafv]
                     + [jnp.zeros_like(thr)] * 5], axis=2)
    # the path matrix transposed: positions on the sublanes too
    pathsT = jnp.asarray(paths.transpose(0, 2, 1), jnp.bfloat16)
    chunk = min(CHUNK, TILE_ROWS, -(-n // 256) * 256)
    rows = min(TILE_ROWS, -(-n // chunk) * chunk)
    XT = X.T
    if n < rows:                # a frame shorter than a tile: one padded tile
        XT = jnp.pad(XT, ((0, 0), (0, rows - n)))
    out = pl.pallas_call(
        functools.partial(_walk_kernel, levels=levels, trees=G, chunk=chunk),
        name="walk_dense_tile",
        grid=(-(-n // rows), U),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((Cp, rows), lambda i, u: (0, i)),
            pl.BlockSpec((1, blocks, BLOCK, 2 * Cp),
                         lambda i, u: (u, 0, 0, 0)),
            pl.BlockSpec((1, blocks, 8, BLOCK), lambda i, u: (u, 0, 0, 0)),
            pl.BlockSpec(pathsT.shape, lambda i, u: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows), lambda i, u: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, XT.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((4 * Cp, rows), jnp.bfloat16),
                        pltpu.VMEM((1, rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 << 20),
    )(tws, XT, sel, tbl, pathsT)
    return out[0, :n]
