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

Categorical SET splits (`cats`: the set variant, counted and named
`walk_dense_tile_sets`; a numeric ensemble's program holds none of this):
at a tile's first step the rows' level one-hots go to a VMEM scratch too
(`_level_one_hot`: a segment a categorical column, K' level rows in all,
int8), and a block's decision takes ONE more product — the block's bit
matrix (128 x K', `engine._perfect_sets`) times the one-hot, int8 with an
int32 sum of one term = the slot's bit of the row's level, exact — OR-ed
into x > thr (a slot that splits a set holds thr = +inf). The row tile
keeps the one-hot to HOT_BYTES (`hot_rows`); nothing of (rows x level
rows) size is ever an array in HBM.

A K-class ensemble (`classes`: the class variant, counted and named
`walk_dense_tile_classes`, with sets `walk_dense_tile_sets_classes`; an
ensemble of one output gets no operand, scratch or instruction of it): the
accumulator is K rows deep (filled up to whole sublane tiles), each step's
trees' classes ride in SMEM beside their weights, and a tree's w * v is
added to ITS class's row, tree by tree in tree order — a class's sum has
the terms and the order of that class's own walk, while the tile's byte
planes and level one-hot are made once for all K classes and the classes'
trees fill the node blocks together.

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
# the level one-hot of a row tile (level rows x rows, int8) keeps to this
# much VMEM: 768 level rows (the airline table's) x 16384 rows is 12.6 MB
HOT_BYTES = 32 << 20
# lanes of one pass of the one-hot's compares: a column's 340 levels x 256
# lanes are 88 vregs (128 / 256 / 512 / 1024 lanes moved a frame of the
# airline cell by +2.3 / 0 / +0.4 / -0.3 ms: PERF.md §6, PR 35)
HOT_LANES = 256
# level rows are filled up to whole MXU tiles (the product's depth); a
# segment starts where the one before ends: ONE layout,
# `engine._perfect_sets`' too
LEVEL_TILE = 128
# sublanes of an int8 tile: what a store into the one-hot is aligned to
_I8_ROWS = 32


def level_rows(cats) -> int:
    """K' of `cats` (`engine._cat_layout`): the segments one after the
    other, filled up to a multiple of LEVEL_TILE. 0 for no segment."""
    return -(-sum(k for _, k in cats) // LEVEL_TILE) * LEVEL_TILE


def hot_rows(Kp: int, chunk: int = CHUNK) -> int:
    """The rows of a row tile whose level one-hot (Kp, rows) keeps to
    HOT_BYTES, in whole chunks: 0 where not one fits (the XLA twin's)."""
    return HOT_BYTES // Kp // chunk * chunk


def _level_one_hot(xt_ref, hot, cats):
    """hot (K', rows) int8 <- the tile's level one-hots: row off_c + l is
    (column c's code == l), the code by `engine._cat_code`'s rule (NaN -> 0,
    truncated toward zero, held to [0, k - 1]: held first, as a float, and
    floored, so that the conversion neither overflows nor rounds — the
    same whole number). Made FOUR LEVEL ROWS A 32-BIT WORD, as
    `pltpu.bitcast` lays int8 rows (row 4 i + j is byte j of word row i):
    one compare and one select a word, not a level. A segment starts
    wherever the one before ends, and stores go in whole int8 tiles of 32
    sublanes: a segment is compared over the tiles it touches (a code
    inside [0, k) matches no row outside the segment) and ADDED to the
    tile it shares with the segments before."""
    Kp, rows = hot.shape

    def lanes(j, carry):
        at = pl.ds(pl.multiple_of(j * HOT_LANES, HOT_LANES), HOT_LANES)
        off = done = 0
        for c, k in cats:
            lo = off // _I8_ROWS * _I8_ROWS
            hi = -(-(off + k) // _I8_ROWS) * _I8_ROWS
            x = xt_ref[c:c + 1, at]
            x = jnp.clip(jnp.where(x != x, 0.0, x), 0.0, k - 1.0)
            code = jnp.floor(x).astype(jnp.int32) + (off - lo)
            word = jax.lax.broadcasted_iota(
                jnp.int32, ((hi - lo) // 4, HOT_LANES), 0)
            one = jnp.where(word == code >> 2, 1 << 8 * (code & 3), 0)
            if lo < done:
                shared = one[:(done - lo) // 4] \
                    + pltpu.bitcast(hot[lo:done, at], jnp.int32)
                one = shared if hi == done else \
                    jnp.concatenate([shared, one[(done - lo) // 4:]])
            hot[lo:hi, at] = pltpu.bitcast(one, jnp.int8)
            off, done = off + k, hi
        if done < Kp:
            hot[done:, at] = jnp.zeros((Kp - done, HOT_LANES), jnp.int8)
        return carry

    jax.lax.fori_loop(0, rows // HOT_LANES, lanes, 0)


def _walk_kernel(tw_ref, xt_ref, sel_ref, tbl_ref, paths_ref, *rest,
                 levels, trees, chunk, cats, classes):
    rest = list(rest)
    set_ref = rest.pop(0) if cats else None
    cls_ref = rest.pop(0) if classes else None
    out_ref, planes, acc, *hot = rest
    hot = hot[0] if cats else None
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
        if cats:
            _level_one_hot(xt_ref, hot, cats)
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
            nan, nan_turn = x != x, tbl[:, 1:2]
            right = x > tbl[:, 0:1]
            if cats:
                # the slot's bit of the row's level: {0, 1} x {0, 1} in
                # int8 (twice bf16's rate on this MXU), one non-zero term a
                # slot and row. A slot that splits a set holds thr = +inf,
                # every other slot an empty set
                right |= jnp.dot(set_ref[0, b], hot[:, at_rows],
                                 preferred_element_type=jnp.int32) > 0
            return jnp.where(nan, nan_turn, jnp.where(right, 1.0, -1.0))

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
        if classes:
            # tree by tree, each into its class's row: a dynamic-sublane
            # read-add-write (a select over the K rows instead read the same
            # 107.0 ms a frame of 2,449,215 x 41 at 230 trees: PERF.md §6, PR 36)
            for g in range(trees):
                row = pl.ds(cls_ref[step, g], 1)
                acc[row, at_rows] = acc[row, at_rows] \
                    + tw_ref[step, g] * v[g]
            return carry
        a = acc[:, at_rows]
        for g in range(trees):      # tree by tree, in tree order
            a = a + tw_ref[step, g] * v[g]
        acc[:, at_rows] = a
        return carry

    jax.lax.fori_loop(0, acc.shape[1] // chunk, one_chunk, 0)

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = acc[...]


def walk_dense_tile(X, sel, thr, nal, leafv, tws, paths, *, levels, cats=(),
                    sets=None, classes=None):
    """The steps of `engine._perfect_tree` over X (n, C): (n,) f32 =
    Σ_t w[t] · value[t, leaf_t(row)], tree by tree. `cats`, `sets`
    (`engine._cat_layout`, `_perfect_sets`): the categorical columns' level
    rows and the nodes' go-right sets (U, K', S) with the slots that split
    one (U, S) — int8 {0, 1}; the caller sees that `hot_rows(K')` is not 0.
    `classes`: (each step's trees' classes (U, G) int32, K) of a K-class
    ensemble — (n, K) out, column c the sum of class c's trees."""
    n, C = X.shape
    U, G = tws.shape
    blocks = thr.shape[1] // BLOCK
    name = "walk_dense_tile" + ("_sets" if cats else "") \
        + ("_classes" if classes else "")
    KERNEL_TRACES.inc(kernel=name, L=str(1 << levels))
    Cp = -(-C // 16) * 16       # a bf16 tile's 16 sublanes
    # (U, C, S) one-hot -> (U, blocks, 128, 2 Cp): [low byte | high byte]
    # of a 16-bit half -> low + 256 * high
    sel = jnp.pad(sel, ((0, 0), (0, Cp - C), (0, 0)))
    sel = jnp.concatenate([sel, 256 * sel], axis=1) \
        .reshape(U, 2 * Cp, blocks, BLOCK).transpose(0, 2, 3, 1)
    chunk = min(CHUNK, TILE_ROWS, -(-n // 256) * 256)
    rows = min(TILE_ROWS, -(-n // chunk) * chunk)
    operands, specs, scratch = (), [], []
    if cats:
        bits, is_set = sets
        Kp = bits.shape[1]
        # no finite or infinite x is over +inf: a set's slot turns by its bit
        thr = jnp.where(is_set, jnp.inf, thr)
        # (U, K', S) -> (U, blocks, 128, K'), as sel
        operands = (bits.reshape(U, Kp, blocks, BLOCK).transpose(0, 2, 3, 1),)
        specs = [pl.BlockSpec((1, blocks, BLOCK, Kp),
                              lambda i, u: (u, 0, 0, 0))]
        rows = min(rows, hot_rows(Kp, chunk))
        scratch = [pltpu.VMEM((Kp, rows), jnp.int8)]
    deep = 1
    if classes:
        operands += (classes[0],)
        specs = specs + [pl.BlockSpec(memory_space=pltpu.SMEM)]
        deep = -(-classes[1] // 8) * 8      # an f32 tile's 8 sublanes
    # a block's per-slot constants as columns: thr, a NaN's turn (±1), the
    # leaf value (of the bottom level's positions, laid as the slots are)
    tbl = jnp.stack([a.reshape(U, blocks, BLOCK) for a in
                     [thr, jnp.where(nal, -1.0, 1.0), leafv]
                     + [jnp.zeros_like(thr)] * 5], axis=2)
    # the path matrix transposed: positions on the sublanes too
    pathsT = jnp.asarray(paths.transpose(0, 2, 1), jnp.bfloat16)
    XT = X.T
    if n < rows:                # a frame shorter than a tile: one padded tile
        XT = jnp.pad(XT, ((0, 0), (0, rows - n)))
    out = pl.pallas_call(
        functools.partial(_walk_kernel, levels=levels, trees=G, chunk=chunk,
                          cats=cats, classes=bool(classes)),
        name=name,
        grid=(-(-n // rows), U),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((Cp, rows), lambda i, u: (0, i)),
            pl.BlockSpec((1, blocks, BLOCK, 2 * Cp),
                         lambda i, u: (u, 0, 0, 0)),
            pl.BlockSpec((1, blocks, 8, BLOCK), lambda i, u: (u, 0, 0, 0)),
            pl.BlockSpec(pathsT.shape, lambda i, u: (0, 0, 0)),
        ] + specs,
        out_specs=pl.BlockSpec((deep, rows), lambda i, u: (0, i)),
        out_shape=jax.ShapeDtypeStruct((deep, XT.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((4 * Cp, rows), jnp.bfloat16),
                        pltpu.VMEM((deep, rows), jnp.float32)] + scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=96 << 20),
    )(tws, XT, sel, tbl, pathsT, *operands)
    if classes:
        return out[:classes[1], :n].T
    return out[0, :n]
