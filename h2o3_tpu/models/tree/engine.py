"""Histogram tree-growing engine — the TPU rebuild of H2O's SharedTree core.

Reference hot path (SURVEY.md §3.3): hex/tree/ScoreBuildHistogram2.java
(2-phase: score rows→leaf, then per-(column,row-range) private histogram
accumulate), hex/tree/DHistogram.java:44 ({w,wY,wYY} bins packed in one
double[] :59-70, merged in reduce :338, uniform-adaptive binning :41),
hex/tree/DTree.java:514 (DecidedNode.bestCol — split scoring over bins),
hex/tree/SharedTree.java:507 (buildLayer).

TPU-native design — no CAS, no private copies, no reduce tree, and (critical
on real hardware) NO host↔device synchronization inside tree growth:
  * One tree level == ONE fused jitted program (`_level_step`): adaptive
    ranges → binning → histograms → split search → node-array writes → row
    routing. The controller dispatches D async programs per tree and never
    reads back until scoring time.
  * Uniform-adaptive bin ranges: per-(leaf,column) min/max are segment
    reductions over IN-SAMPLE rows; each row re-bins against ITS leaf's range
    each level — DHistogram's adaptive-range semantics, fully vectorized.
  * Histograms: hist[l,c,b,s] = Σ_r onehot_leaf[r,l]·stat_s[r]·onehot_bin[r,c,b].
    Shallow levels evaluate this as a dense matmul (leaf·stat panel)ᵀ @
    (bin one-hot) per column block — it rides the MXU, and the row
    contraction over the sharded dimension becomes one ICI all-reduce (the
    entire MRTask reduce tree collapses into a psum). Deep levels switch to
    segment-sum on a combined (leaf,bin) index.
  * Rows carry (leaf, heap-node) vectors; ALL rows are routed (so out-of-bag
    rows get tree predictions for the F update) while histogram contributions
    are weighted by the in-sample weights — H2O's sampling semantics.
  * Trees are dense heap-order DEVICE arrays (CompressedTree analog);
    training predictions are a gather val[heap] — no tree walk; ensemble
    scoring is a fixed-depth gather loop — static shapes, jit-friendly.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.obs import metrics as _om
from h2o3_tpu.parallel import compat as _compat
from h2o3_tpu.parallel import mesh as _mesh
from h2o3_tpu.obs.timeline import span as _span
from h2o3_tpu.ops import walk_pallas as _wp

# Σ rows·trees processed — the headline GBM throughput numerator; bench.py
# and /metrics read the same counter (per-ensemble rate = Δcounter/Δt)
ROW_TREES = _om.counter("h2o3_gbm_row_trees_total",
                        "rows x trees processed by the tree engines")
# which body of the scoring walk a predict_ensemble call took (a call made
# under an outer jit counts once, when that program is traced)
WALKS = _om.counter(
    "h2o3_tree_walk_total",
    "predict_ensemble calls by the scoring walk's body: path=dense (every "
    "node of a level, no per-row index; block = {trees a 128-slot node "
    "block}x{slots a tree}) or gather (deep trees, or too many level rows)")

# Dense-matmul histogram path is used while (leaves × 3 stats) stays MXU-sized.
# Measured on v5e: the one-hot matmul beats segment-sum scatter ~3× even at
# L=256 (scatter serializes on TPU); the threshold is a memory guard, not a
# FLOPs one.
_MATMUL_MAX_LEAVES = 256
_COL_BLOCK = 8


# ===========================================================================
# Building blocks (called inside the fused level step; individually jitted
# only for unit tests — nested jit inlines).
def leaf_ranges(X, lv, L):
    """Per-(leaf,col) min/max over in-sample rows (lv==L → excluded)."""
    big = jnp.float32(3.0e38)
    xmin = jnp.where(jnp.isnan(X), big, X)
    xmax = jnp.where(jnp.isnan(X), -big, X)
    mn = jax.ops.segment_min(xmin, lv, num_segments=L + 1)[:L]
    mx = jax.ops.segment_max(xmax, lv, num_segments=L + 1)[:L]
    return mn, mx


def bin_rows(X, lv, mn, mx, B):
    """Adaptive binning: row r, col c → bin in [0,B); NA → bin B."""
    safe = jnp.minimum(lv, mn.shape[0] - 1)
    lm = mn[safe]
    lM = mx[safe]
    span = jnp.maximum(lM - lm, 1e-30)
    b = jnp.floor((X - lm) / span * B).astype(jnp.int32)
    b = jnp.clip(b, 0, B - 1)
    return jnp.where(jnp.isnan(X), B, b)


def histogram_matmul(bins, lv, stats, L, B):
    """hist (L, C, B+1, 3) via MXU: (n,L·3)ᵀ @ (n,CB·(B+1)) per column block."""
    n, C = bins.shape
    oh_leaf = jax.nn.one_hot(lv, L, dtype=jnp.float32)            # (n, L)
    W3 = (oh_leaf[:, :, None] * stats[:, None, :]).reshape(n, L * 3)
    nb = B + 1
    pad_c = (-C) % _COL_BLOCK
    binsp = jnp.pad(bins, ((0, 0), (0, pad_c)), constant_values=B)
    nblk = binsp.shape[1] // _COL_BLOCK

    def block(carry, cb):
        blk = jax.lax.dynamic_slice(binsp, (0, cb * _COL_BLOCK),
                                    (n, _COL_BLOCK))
        oh = jax.nn.one_hot(blk, nb, dtype=jnp.float32)           # (n,CB,nb)
        h = jnp.einsum("nk,ncb->kcb", W3, oh,
                       preferred_element_type=jnp.float32)        # (L3,CB,nb)
        return carry, h

    _, hs = jax.lax.scan(block, 0, jnp.arange(nblk))   # (nblk, L3, CB, nb)
    h = hs.transpose(1, 0, 2, 3).reshape(L * 3, nblk * _COL_BLOCK, nb)[:, :C]
    return h.reshape(L, 3, C, nb).transpose(0, 2, 3, 1)


def histogram_scatter(bins, lv, stats, L, B):
    """Deep-tree path: segment-sum on combined (leaf·(B+1)+bin) per column."""
    n, C = bins.shape
    nb = B + 1
    base = lv * nb

    def one_col(c):
        idx = base + bins[:, c]
        return jax.ops.segment_sum(stats, idx,
                                   num_segments=(L + 1) * nb)[: L * nb]

    hs = jax.lax.map(one_col, jnp.arange(C))                      # (C, L·nb, 3)
    return hs.reshape(C, L, nb, 3).transpose(1, 0, 2, 3)


def build_histograms(bins, lv, stats, L, B):
    if L <= _MATMUL_MAX_LEAVES:
        return histogram_matmul(bins, lv, stats, L, B)
    return histogram_scatter(bins, lv, stats, L, B)


def find_best_splits(hist, mn, mx, min_rows, min_split_improvement,
                     col_mask, B, reg_lambda=0.0):
    """Vectorized DecidedNode.bestCol over every (leaf, col, threshold,
    NA-dir). col_mask: (L, C) bool — per-leaf column availability (mtries).

    hist: (L, C, B+1, 3); slot B is the NA bucket. Returns per-leaf arrays:
      did, gain, col, thr, na_left, leaf_w, leaf_wy.
    Split at t ∈ [0,B-1): left = bins ≤ t (+NA if na_left), right = rest.

    reg_lambda > 0 turns the SE reduction into the XGBoost regularized
    structure score: se = wyy - wy²/(w+λ). Since wyy is additive over a
    leaf's children it cancels in the gain difference, so the argmax is
    EXACTLY hist-mode XGBoost's Σ G²/(H+λ) split objective when the caller
    feeds hessian-weighted stats (w = Σh, wy = Σg).
    """
    w = hist[..., 0]
    wy = hist[..., 1]
    wyy = hist[..., 2]
    main_w, na_w = w[..., :B], w[..., B]
    main_wy, na_wy = wy[..., :B], wy[..., B]
    main_wyy, na_wyy = wyy[..., :B], wyy[..., B]

    def se(w_, wy_, wyy_):
        den = jnp.maximum(w_ + reg_lambda, 1e-30)
        return wyy_ - jnp.where(w_ > 0, wy_ * wy_ / den, 0.0)

    tot_w = main_w.sum(-1) + na_w                      # (L, C) — same ∀ c
    tot_wy = main_wy.sum(-1) + na_wy
    tot_wyy = main_wyy.sum(-1) + na_wyy
    se_parent = se(tot_w, tot_wy, tot_wyy)

    cl_w = jnp.cumsum(main_w, -1)[..., :-1]            # (L, C, B-1) left sums
    cl_wy = jnp.cumsum(main_wy, -1)[..., :-1]
    cl_wyy = jnp.cumsum(main_wyy, -1)[..., :-1]

    def gains(nal):
        lw = cl_w + (na_w[..., None] if nal else 0.0)
        lwy = cl_wy + (na_wy[..., None] if nal else 0.0)
        lwyy = cl_wyy + (na_wyy[..., None] if nal else 0.0)
        rw = tot_w[..., None] - lw
        rwy = tot_wy[..., None] - lwy
        rwyy = tot_wyy[..., None] - lwyy
        g = se_parent[..., None] - se(lw, lwy, lwyy) - se(rw, rwy, rwyy)
        ok = (lw >= min_rows) & (rw >= min_rows)
        return jnp.where(ok, g, -jnp.inf)

    g_right = gains(False)                             # (L, C, B-1)
    g_left = gains(True)
    g = jnp.maximum(g_right, g_left)
    na_left = g_left > g_right
    g = jnp.where(col_mask[:, :, None], g, -jnp.inf)

    L, C = tot_w.shape
    flat = g.reshape(L, C * (B - 1))
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    best_col = (best // (B - 1)).astype(jnp.int32)
    best_bin = (best % (B - 1)).astype(jnp.int32)
    best_nal = jnp.take_along_axis(
        na_left.reshape(L, C * (B - 1)), best[:, None], 1)[:, 0]
    # threshold value: upper edge of bin t in the leaf's adaptive range
    lmn = jnp.take_along_axis(mn, best_col[:, None], 1)[:, 0]
    lmx = jnp.take_along_axis(mx, best_col[:, None], 1)[:, 0]
    thr = lmn + (lmx - lmn) * (best_bin + 1).astype(jnp.float32) / B
    did = jnp.isfinite(best_gain) & \
        (best_gain > jnp.maximum(min_split_improvement, 0.0))
    leaf_w = tot_w[:, 0]
    leaf_wy = tot_wy[:, 0]
    return did, best_gain, best_col, thr, best_nal, leaf_w, leaf_wy


# ===========================================================================
# The fused per-level program — zero host syncs.
@_compat.guard_collective
@functools.partial(jax.jit, static_argnames=("d", "B", "mtries"))
def _level_step(X, stats, w_in, leaf, heap, active, colA, thrA, nalA, valA,
                gains, col_mask, key, *, d, B, mtries,
                min_rows, min_split_improvement, reg_lambda=0.0):
    # the named scopes are metadata: they name the level's stages in a
    # device trace and change neither the program nor its cache key
    L = 2 ** d
    C = X.shape[1]
    with jax.named_scope("tree.level.bin"):
        in_sample = active & (w_in > 0)
        lv = jnp.where(in_sample, leaf, L)
        mn, mx = leaf_ranges(X, lv, L)
        bins = bin_rows(X, lv, mn, mx, B)
    with jax.named_scope("tree.level.hist"):
        hist = build_histograms(bins, lv, stats, L, B)
    with jax.named_scope("tree.level.split"):
        if mtries > 0 and mtries < C:
            # per-leaf mtries column sampling (DRF per-node semantics)
            r = jax.random.uniform(jax.random.fold_in(key, d), (L, C))
            kth = jnp.sort(r, axis=1)[:, mtries - 1:mtries]
            cmask = (r <= kth) & col_mask[None, :]
        else:
            cmask = jnp.broadcast_to(col_mask[None, :], (L, C))
        did, gain, bcol, thr, nal, lw, lwy = find_best_splits(
            hist, mn, mx, min_rows, min_split_improvement, cmask, B,
            reg_lambda=reg_lambda)
    with jax.named_scope("tree.level.nodes"):
        base = 2 ** d - 1
        lvl_val = jnp.where(lw > 0, lwy / jnp.maximum(lw, 1e-30), 0.0)
        colA = jax.lax.dynamic_update_slice(
            colA, jnp.where(did, bcol, -1).astype(jnp.int32), (base,))
        thrA = jax.lax.dynamic_update_slice(thrA, thr, (base,))
        nalA = jax.lax.dynamic_update_slice(nalA, nal, (base,))
        valA = jax.lax.dynamic_update_slice(
            valA, lvl_val.astype(jnp.float32), (base,))
        gains = gains.at[bcol].add(
            jnp.where(did, jnp.maximum(gain, 0.0), 0.0))
    # route ALL rows in split nodes (OOB rows included — they need the tree's
    # prediction), freeze rows in terminal nodes
    with jax.named_scope("tree.level.route"):
        c = bcol[leaf]
        t = thr[leaf]
        x = jnp.take_along_axis(X, c[:, None], axis=1)[:, 0]
        isna = jnp.isnan(x)
        go_right = jnp.where(isna, ~nal[leaf], x > t)
        splits = did[leaf] & active
        leaf = jnp.where(splits, 2 * leaf + go_right.astype(jnp.int32), 0)
        heap = jnp.where(splits, 2 * heap + 1 + go_right.astype(jnp.int32),
                         heap)
        active = splits
    return leaf, heap, active, colA, thrA, nalA, valA, gains


@_compat.guard_collective
@functools.partial(jax.jit, static_argnames=("D",))
def _final_leaves(stats, leaf, active, w_in, valA, *, D):
    L = 2 ** D
    with jax.named_scope("tree.leaves"):
        lv = jnp.where(active & (w_in > 0), leaf, L)
        sums = jax.ops.segment_sum(stats[:, :2], lv, num_segments=L + 1)[:L]
        vals = jnp.where(sums[:, 0] > 0,
                         sums[:, 1] / jnp.maximum(sums[:, 0], 1e-30),
                         0.0).astype(jnp.float32)
        return jax.lax.dynamic_update_slice(valA, vals, (2 ** D - 1,))


def gamma_pass(heap, w, res, hess, val, *, nodes, scale=1.0,
               reg_lambda=0.0, reg_alpha=0.0):
    """GammaPass (GBM.java:1235) on device: Newton leaf Σw·res / Σw·hess.
    With reg_lambda/reg_alpha this is the XGBoost leaf weight
    sign(G)·max(|G|−α, 0)/(H+λ)."""
    with _span("tree.gamma", nodes=nodes):
        return _gamma_pass_jit(heap, w, res, hess, val, nodes=nodes,
                               scale=scale, reg_lambda=reg_lambda,
                               reg_alpha=reg_alpha)


@_compat.guard_collective
@functools.partial(jax.jit,
                   static_argnames=("nodes", "scale", "reg_lambda",
                                    "reg_alpha"))
def _gamma_pass_jit(heap, w, res, hess, val, *, nodes, scale=1.0,
                    reg_lambda=0.0, reg_alpha=0.0):
    num = jax.ops.segment_sum(w * res, heap, num_segments=nodes)
    den = jax.ops.segment_sum(w * hess, heap, num_segments=nodes)
    if reg_alpha:
        num = jnp.sign(num) * jnp.maximum(jnp.abs(num) - reg_alpha, 0.0)
    den = den + reg_lambda
    return jnp.where(den > 1e-10,
                     jnp.clip(scale * num / jnp.maximum(den, 1e-10), -19, 19),
                     val).astype(jnp.float32)


@_compat.guard_collective
@functools.partial(jax.jit, static_argnames=("nodes", "D"))
def _node_covers_jit(heap, w, *, nodes, D):
    cov = jax.ops.segment_sum(w, heap, num_segments=nodes)
    for d in range(D - 1, -1, -1):
        lo, hi = 2 ** d - 1, 2 ** (d + 1) - 1
        kids = cov[2 * lo + 1: 2 * hi + 1].reshape(hi - lo, 2).sum(axis=1)
        cov = cov.at[lo:hi].add(kids)
    return cov.astype(jnp.float32)


def node_covers(heap, w, *, nodes, D):
    """Per-node training weight R_j (MOJO node-weight analog, used by
    TreeSHAP): terminal weights from the row router, then children sums
    propagate up the heap level by level."""
    cov = _node_covers_jit(heap, w, nodes=nodes, D=D)
    if _cpu_backend():
        # same flaky-CPU-collective guard as TreeGrower.grow: this program
        # contains a psum over the sharded row axis — drain before piling on
        jax.block_until_ready(cov)
    return cov


# ===========================================================================
# Dense heap-order tree storage (hex/tree/CompressedTree analog)
@dataclass
class TreeArrays:
    """One ensemble's trees as stacked dense arrays, heap node order:
    node 0 = root; children of i are 2i+1 / 2i+2. Leaves carry values.
    Arrays may live on device (jnp) or host (np)."""
    col: object       # (T, nodes) int32, -1 = leaf
    thr: object       # (T, nodes) f32
    na_left: object   # (T, nodes) bool
    value: object     # (T, nodes) f32 — prediction if stopped here
    depth: int
    cover: object = None   # (T, nodes) f32 training weight per node (SHAP)
    # categorical SET splits (water/util/IcedBitSet.java analog): per-node
    # go-right bitset over level ids, plus which columns are categorical
    catbits: object = None      # (T, nodes, W) uint32 or None
    col_is_cat: object = None   # (C,) bool or None
    # levels of each column (0: numeric, or not known): host metadata like
    # col_is_cat. A categorical column's code is clipped to its levels, and
    # the dense walk matches that many bits of a node's set, not all 32 W
    cat_levels: object = None   # (C,) int or None
    # a K-class ensemble (multinomial): the class each tree adds to, HOST
    # metadata like col_is_cat — trees stored iteration-major (iteration i
    # holds classes 0..K-1 in order), every class with a tree. None: one
    # output. The walk then returns a margin a class, (n, K)
    tree_class: object = None   # (T,) int32 or None

    @property
    def ntrees(self):
        return self.col.shape[0]

    @property
    def n_classes(self) -> int:
        """K of a K-class ensemble; 0 for an ensemble of one output."""
        if self.tree_class is None:
            return 0
        return int(np.max(self.tree_class)) + 1

    def __getstate__(self):
        # the walk's placed tables (`_walk_tables`) are derived state, made
        # on demand; never pickled
        state = dict(self.__dict__)
        state.pop("_tables", None)
        return state


def stack_trees(tree_list, depth) -> TreeArrays:
    """Stack per-tree device arrays into one ensemble — stays on device.
    Accepts (col, thr, nal, val) or (col, thr, nal, val, cover) tuples."""
    cover = None
    if len(tree_list[0]) >= 5:
        cover = jnp.stack([t[4] for t in tree_list])
    return TreeArrays(
        col=jnp.stack([t[0] for t in tree_list]),
        thr=jnp.stack([t[1] for t in tree_list]),
        na_left=jnp.stack([t[2] for t in tree_list]),
        value=jnp.stack([t[3] for t in tree_list]),
        depth=depth, cover=cover)


# TreeArrays is a pytree: the mesh-sharded serving fast path passes
# whole ensembles as SHARED DEVICE ARGUMENTS into pjit'd scorer programs
# (one HBM copy per model, every row-bucket program reuses it) instead
# of baking them in as closure constants. Children are the per-node
# arrays; `depth` is static trace structure, and `col_is_cat` and
# `cat_levels` stay HOST data (aux) because predict_ensemble lays out the
# categorical columns' level rows (`_cat_layout`) at trace time; so does
# `tree_class`, whose largest entry is the width of the walk's output.
def _trees_flatten(t: TreeArrays):
    aux = (t.depth,
           None if t.col_is_cat is None
           else tuple(bool(b) for b in np.asarray(t.col_is_cat)),
           None if t.cat_levels is None
           else tuple(int(k) for k in np.asarray(t.cat_levels)),
           None if t.tree_class is None
           else tuple(int(k) for k in np.asarray(t.tree_class)))
    return (t.col, t.thr, t.na_left, t.value, t.cover, t.catbits), aux


def _trees_unflatten(aux, children):
    depth, cat, levels, cls = aux
    col, thr, nal, val, cover, catbits = children
    return TreeArrays(col=col, thr=thr, na_left=nal, value=val,
                      depth=depth, cover=cover, catbits=catbits,
                      col_is_cat=None if cat is None
                      else np.asarray(cat, bool),
                      cat_levels=None if levels is None
                      else np.asarray(levels, np.int64),
                      tree_class=None if cls is None
                      else np.asarray(cls, np.int32))


def class_ensembles(trees: TreeArrays) -> list:
    """A K-class ensemble as K ensembles of one output each, on the HOST
    (NumPy): class c's trees in their order. What the artifact writers and
    the tree route read of a multinomial model; nothing here goes to the
    device."""
    cls = np.asarray(trees.tree_class)
    # every table fetched once, then cut a class at a time
    host = {k: None if a is None else np.asarray(a) for k, a in (
        ("col", trees.col), ("thr", trees.thr), ("na_left", trees.na_left),
        ("value", trees.value), ("cover", trees.cover),
        ("catbits", trees.catbits))}
    return [TreeArrays(depth=trees.depth, col_is_cat=trees.col_is_cat,
                       cat_levels=trees.cat_levels,
                       **{k: None if a is None else a[cls == c]
                          for k, a in host.items()})
            for c in range(trees.n_classes)]


jax.tree_util.register_pytree_node(TreeArrays, _trees_flatten,
                                   _trees_unflatten)


# ---- the scoring walk -------------------------------------------------------
# Two bodies of one algorithm; `_walk_path` picks by shape, at trace time
# (and `_dense_body` the dense body's form: the TPU's kernel, or XLA).
#
# dense: no per-row index anywhere. A tile of rows meets EVERY node of a tree
# with dense arithmetic, a BLOCK of 128 node slots at a time (one MXU tile
# wide, one vreg row of lanes): the node's feature is selected on the MXU by
# a one-hot product over the BYTES of the f32 features (exact for every bit
# pattern, NaN and ±inf included); the top levels are matched together
# against the block's path matrix, on the MXU again; the levels under them
# by a position one-hot on the VPU. A block holds 128 / 2^depth shallow
# trees side by side, one tree of 7 levels, or half of a deeper tree's top
# 8 levels ({root, left subtree}, {root, right subtree}); `_block_regime`.
# It does 2^depth node evaluations a row and tree where the gather body
# does `depth` dependent gathers. One v5e retires 24-54 M gather steps a
# second and, in the fused kernel, 181-208 G dense evaluations (2,750,000 x
# 28: 9.7 ms at 20 x depth 5, 33.8 at 10 x depth 8, 485 at 2 x depth 14,
# where the gather body takes 3,055: PERF.md §6, PR 33; the XLA body 17.0 /
# 45.0 / 517): at depth 8 the dense body is ~270 x the faster, at depth 14
# 6.3 x, and the two would cross at depth 16-17. _DENSE_MAX_CELLS bounds
# (2^depth - 1) x columns, the size of a tree's selection matrix: depth 14
# at 28 columns, the deepest shape measured, is the last to take the dense
# body.
#
# A categorical SET split is decided densely too: the rows' level one-hots
# (one segment a categorical column, K rows in all) times the blocks' bit
# matrices, on the MXU, give every slot's bit (a row has one 1 in a
# column's segment and a slot is non-zero in one segment only: exact), and
# the decision is where(slot splits a set, bit, x > thr) under the same
# NaN rule. The K level rows count as columns in the bound. On the TPU the
# match is INSIDE the fused kernel (the one-hot a VMEM tile, one int8
# product more a node block: 7,720,935 x 8 with 759 level rows at 20 x
# depth 5 walks in 49.8 ms where the XLA body takes 105.7: PERF.md §6, PR
# 35); the XLA body matches once a row tile, beside the feature select.
_DENSE_MAX_CELLS = 1 << 19
# rows x (steps x slots) of the set match of one row tile, kept as booleans
_SET_TILE_CELLS = 1 << 26
# levels 0.._PATH_LEVELS-1 are matched by path products, (128, 128) each; a
# level walked by position instead costs a compare, a select and a
# reduction over (rows, 2^level)
_PATH_LEVELS = _wp.PATH_LEVELS
assert 1 << _PATH_LEVELS == 2 * _wp.BLOCK, "a tree's top is two blocks"
# a tree shallower than this is scored as one of this many levels, its
# leaves pushed down: a tree's slots then fill whole (8, 128) vregs
_MIN_LEVELS = 3
# the XLA body's tile: rows x slots of a scan step (128, or a deep tree's
# 2^depth): its (rows, slots) f32 intermediates are 32 MB each, whatever
# the depth (2^21..2^24 read within 6 % of each other: PR 28)
_WALK_TILE_CELLS = 1 << 23


def _walk_path(depth: int, n_cols: int, cat_rows: int = 0) -> str:
    """Which body scores this shape: "dense" or "gather". `cat_rows`: the
    level rows of the categorical columns (`_cat_layout`), 0 for a numeric
    ensemble; they count as columns."""
    dense = depth >= 1 and \
        ((1 << depth) - 1) * (n_cols + cat_rows) <= _DENSE_MAX_CELLS
    return "dense" if dense else "gather"


def _dense_body(cats=()) -> str:
    """Which of the dense body's two forms: "kernel" on the TPU, "xla"
    elsewhere — and on the TPU for an ensemble whose level rows
    (`_cat_layout`) leave the kernel's one-hot scratch not one whole chunk
    of rows (`walk_pallas.hot_rows`: K' over 4,096 — a MOJO without
    `cat_levels` matches all 32 W bits a column, and at depth 1-3
    `_DENSE_MAX_CELLS` admits K in the tens of thousands)."""
    fits = not cats or _wp.hot_rows(_wp.level_rows(cats)) > 0
    return "kernel" if _wp.use_pallas() and fits else "xla"


def _cat_layout(trees, n_cols: int) -> tuple:
    """((column, level rows), ...) of the ensemble's categorical columns:
    a column's known levels, at most the 32 W bits a node's set holds; all
    32 W where the levels are not known (a MOJO). () for a numeric
    ensemble. Host metadata: static in every program."""
    # h2o3-ok: R025 col_is_cat / cat_levels are host numpy model metadata (the pytree's aux, never a tracer); catbits is asked its static shape only
    if trees.catbits is None or trees.col_is_cat is None:
        return ()
    nb = 32 * trees.catbits.shape[-1]
    flags = np.asarray(trees.col_is_cat, bool)[:n_cols]
    known = np.zeros(n_cols, np.int64) if trees.cat_levels is None else \
        np.asarray(trees.cat_levels, np.int64)[:n_cols]
    rows = np.where((known > 0) & (known < nb), known, nb)
    # h2o3-ok: R025 host metadata, as above
    return tuple((int(c), int(rows[c])) for c in np.flatnonzero(flags))


def _block_regime(depth: int):
    """(levels, trees, blocks) of one step of the dense body: the perfect
    tree's levels, and how many trees and 128-slot blocks a step holds — 128
    / 2^levels trees in one block up to 7 levels, one tree from 8 on, its
    top 8 levels in two blocks."""
    levels = max(depth, _MIN_LEVELS)
    top = 1 << min(levels, _PATH_LEVELS)
    return levels, max(1, _wp.BLOCK // top), max(1, top // _wp.BLOCK)


def _block_label(depth: int) -> str:
    """`h2o3_tree_walk_total`'s `block`: "{trees a block}x{slots a tree}" of
    the path-matched levels — 4x32, 1x128, 0.5x256."""
    levels, trees, blocks = _block_regime(depth)
    per = trees if blocks == 1 else 1 / blocks
    return f"{per:g}x{1 << min(levels, _PATH_LEVELS)}"


def _path_matrix(levels: int) -> np.ndarray:
    """(2^levels, 2^levels) in {-1, 0, +1}: column p is the path from the
    root to position p of level `levels` — +1 where it turns right at node
    j, -1 where it turns left, 0 off the path. Nodes are numbered from 1
    (node j's children are 2j and 2j + 1; row 0 is unused), so a row of ±1
    decisions times this matrix reads `levels` exactly at the position the
    row reaches, and less everywhere else."""
    W = 1 << levels
    P = np.zeros((W, W), np.float32)
    pos = np.arange(W)
    for d in range(levels):
        node = (1 << d) + (pos >> (levels - d))
        P[node, pos] = 2.0 * ((pos >> (levels - d - 1)) & 1) - 1.0
    return P


def _block_paths(depth: int) -> np.ndarray:
    """(blocks, 128, 128): the path matrix of each block of a step. Shallow
    trees side by side: their path matrices down the diagonal. A tree's top
    8 levels: the 7-level matrix of a subtree with the ROOT in slot 0,
    which must have turned left for the first block's positions (0..127 of
    level 8) and right for the second's."""
    levels, trees, blocks = _block_regime(depth)
    if blocks == 1:
        return np.kron(np.eye(trees, dtype=np.float32),
                       _path_matrix(min(levels, _PATH_LEVELS)))[None]
    P = np.stack([_path_matrix(_PATH_LEVELS - 1)] * 2)
    P[0, 0, :], P[1, 0, :] = -1.0, 1.0
    return P


def _split_top(a):
    """The first 256 slots of the last axis (nodes numbered from 1, slot 0
    unused) as two blocks of 128: [root, left subtree], [root, right
    subtree], each numbered from 1 again below its slot 0."""
    halves = ([a[..., 1:2]], [a[..., 1:2]])
    for d in range(1, _PATH_LEVELS):
        lo, mid, hi = 1 << d, 3 << (d - 1), 2 << d
        halves[0].append(a[..., lo:mid])
        halves[1].append(a[..., mid:hi])
    return jnp.concatenate(
        halves[0] + halves[1] + [a[..., 1 << _PATH_LEVELS:]], axis=-1)


def _deepen(a, width, fill):
    """Axis 1 filled up to `width` slots."""
    return jnp.concatenate(
        [a, jnp.full((a.shape[0], width - a.shape[1]), fill, a.dtype)],
        axis=1)


def _from_one(a, inner, fill):
    """The first `inner` heap slots numbered from 1 (slot 0 unused)."""
    return jnp.concatenate(
        [jnp.full((a.shape[0], 1), fill, a.dtype), a[:, :inner]], axis=1)


def _steps(a, U, G, fill):
    """(T, slots, ...) as U steps of G trees side by side, the last step
    filled up with `fill`."""
    a = jnp.concatenate(
        [a, jnp.full((U * G - a.shape[0],) + a.shape[1:], fill, a.dtype)])
    return a.reshape((U, G * a.shape[1]) + a.shape[2:])


def _perfect_tree(col, thr, nal, val, tw, n_cols, depth):
    """The (T, nodes) heap arrays as the dense body's steps (`_block_regime`):
    perfect trees of `levels` levels with nodes numbered from 1 (level d is
    [2^d, 2^(d+1)): every level starts at a multiple of its own width).
    An early leaf keeps any route and its value is pushed down to every
    bottom slot under it, so a row always takes `levels` steps and ends on
    the value the gather walk would have stopped at. Shallow trees are
    laid side by side, G to a step, the last step filled up with trees of
    weight 0 and value 0 (acc + 0 * 0 is acc); a tree of 8 levels or more
    has its top 256 slots as `_split_top` leaves them. Returns the one-hot
    feature select (U, C, S) bf16, thr and na_left (U, S), the bottom
    level's values (U, S) and the weights (U, G): S = 128, or 2^levels."""
    levels, G, _ = _block_regime(depth)
    T = col.shape[0]
    real, inner, L = (1 << depth) - 1, (1 << levels) - 1, 1 << levels

    # the bottom level is all leaves, and so is every level added under it
    col = _deepen(col[:, :real], 2 * L - 1, -1)
    thr, nal, val = _deepen(thr, 2 * L - 1, 0), \
        _deepen(nal, 2 * L - 1, False), _deepen(val, 2 * L - 1, 0)
    stopped = col[:, :1] < 0
    leafv = val[:, :1]
    for d in range(1, levels + 1):
        lo, hi = (1 << d) - 1, (1 << (d + 1)) - 1
        above = jnp.repeat(stopped, 2, axis=1)
        leafv = jnp.where(above, jnp.repeat(leafv, 2, axis=1), val[:, lo:hi])
        stopped = above | (col[:, lo:hi] < 0)

    col1, thr1, nal1 = _from_one(col, inner, -1), _from_one(thr, inner, 0), \
        _from_one(nal, inner, False)
    if levels >= _PATH_LEVELS:
        col1, thr1, nal1 = _split_top(col1), _split_top(thr1), \
            _split_top(nal1)
    U = -(-T // G)
    col1, thr1, nal1, leafv = _steps(col1, U, G, -1), _steps(thr1, U, G, 0), \
        _steps(nal1, U, G, False), _steps(leafv, U, G, 0)
    cols = jnp.arange(n_cols, dtype=col.dtype)[None, :, None]
    sel = (col1[:, None, :] == cols).astype(jnp.bfloat16)
    return sel, thr1, nal1, leafv, _steps(tw[:, None], U, G, 0)


def _perfect_sets(col, catbits, cats, depth):
    """The nodes' go-right sets in `_perfect_tree`'s layout, by the same
    moves that place `col`: B (U, K', S) int8 in {0, 1} — row off_c + l of
    slot s of step u is bit l of the node in that slot when it splits
    categorical column c, else 0 (`cats` = `_cat_layout`: the segments in
    order, K' = `walk_pallas.level_rows`: their sum filled up to whole MXU
    tiles) — and which slots split a set, (U, S) bool. ONE layout for both
    bodies: the XLA twin multiplies by it a row tile, the kernel a block."""
    levels, G, _ = _block_regime(depth)
    T, nodes, W = catbits.shape
    real, inner, L = (1 << depth) - 1, (1 << levels) - 1, 1 << levels
    col1 = _from_one(_deepen(col[:, :real], 2 * L - 1, -1), inner, -1)
    # bit l of a node's set is bit l % 32 of its word l // 32; the level
    # axis goes in front of the slots, which then move as col's do
    bits = ((catbits[:, :real, :, None] >> jnp.arange(32, dtype=jnp.uint32))
            & 1).astype(jnp.int8).reshape(T, real, 32 * W)
    bits = bits.transpose(0, 2, 1).reshape(T * 32 * W, real)
    bits = _from_one(_deepen(bits, 2 * L - 1, 0), inner, 0)
    if levels >= _PATH_LEVELS:
        col1, bits = _split_top(col1), _split_top(bits)
    U = -(-T // G)
    col1 = _steps(col1, U, G, -1)                            # (U, S)
    bits = _steps(bits.reshape(T, 32 * W, -1).transpose(0, 2, 1), U, G, 0) \
        .transpose(0, 2, 1)                                  # (U, 32 W, S)
    segs = [jnp.where((col1 == c)[:, None, :], bits[:, :k, :], 0)
            for c, k in cats]
    B = jnp.concatenate(segs, axis=1)
    B = jnp.pad(B, ((0, 0), (0, _wp.level_rows(cats) - B.shape[1]), (0, 0)))
    is_set = functools.reduce(jnp.logical_or, [col1 == c for c, _ in cats])
    return B, is_set


# the two bodies are jitted for the tests that set one against the other;
# inside `_ensemble_walk` a nested jit inlines
@functools.partial(jax.jit, static_argnames=("depth", "cats", "classes"))
def _walk_dense(X, col, thr, nal, val, tw, catbits=None, cls=None, *, depth,
                cats=(), classes=0):
    """Σ_t tw[t] · value[t, leaf_t(row)] with no per-row index: bit for bit
    what `_walk_gather` returns. On the TPU ONE fused kernel a row tile
    (ops/walk_pallas.py), categorical SET splits (`cats`, `_cat_layout`)
    matched inside it; `_walk_dense_xla` is its twin everywhere else
    (`_dense_body`). `classes` = K > 0, `cls` (T,): each tree is summed
    into its class's row, (n, K) out of the one walk."""
    tables = _perfect_tree(col, thr, nal, val, tw, X.shape[1], depth)
    body = _wp.walk_dense_tile if _dense_body(cats) == "kernel" \
        else _walk_dense_xla
    more = dict(cats=cats, sets=_perfect_sets(col, catbits, cats, depth)) \
        if cats else {}
    if classes:
        U, G = tables[4].shape
        more["classes"] = (_steps(cls[:, None], U, G, 0), classes)
    return body(X, *tables, _block_paths(depth),
                levels=_block_regime(depth)[0], **more)


def _cat_code(x, rows):
    """A categorical feature's level id as the walk reads it: truncated,
    NaN as 0, held to the column's `rows` levels."""
    return jnp.clip(jnp.nan_to_num(x).astype(jnp.int32), 0, rows - 1)


def _class_add(acc, c, term):
    """acc (rows, K) with `term` (rows,) added into column c (traced): the
    column read, added to and put back, so that a class's sum has the terms
    and the order of that class's own walk."""
    at = jax.lax.dynamic_slice_in_dim(acc, c, 1, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(acc, at + term[:, None], c,
                                               axis=1)


def _walk_dense_xla(X, sel, thr1, nal1, leafv, tws, paths, *, levels,
                    cats=(), sets=None, classes=None):
    """The dense body in plain XLA: row tiles in a `fori_loop` (the last
    tile overlaps the one before), the steps of `_perfect_tree` in a
    `scan`. `cats`, `sets` (`_perfect_sets`): the ensemble's categorical
    columns and its nodes' go-right sets. `classes`: (each step's trees'
    classes (U, G), K) of a K-class ensemble — (n, K) out."""
    n = X.shape[0]
    L = 1 << levels
    top = min(levels, _PATH_LEVELS)
    S, G = thr1.shape[1], tws.shape[1]
    B = _wp.BLOCK
    # [low byte | high byte] of a 16-bit half -> low + 256 * high
    sel2 = jnp.concatenate([sel, 256 * sel], axis=1)
    paths = jnp.asarray(paths, jnp.bfloat16)
    t = max(1, min(n, _WALK_TILE_CELLS // S))
    tables = (sel2, thr1, nal1, leafv, tws)
    if cats:
        setB, is_set = sets
        # (U, K', S) -> (K', U * S): every step's slots in one product
        setB = setB.transpose(1, 0, 2).reshape(setB.shape[1], -1) \
            .astype(jnp.bfloat16)
        t = max(1, min(t, _SET_TILE_CELLS // setB.shape[1]))
        tables += (is_set, jnp.arange(thr1.shape[0]))
        # the level rows' own tables: where each categorical column's
        # segment ends, and a row's level within its segment
        ends = np.cumsum([k for _, k in cats])
        level = np.full(setB.shape[0], -1, np.int32)
        level[:ends[-1]] = np.concatenate([np.arange(k) for _, k in cats])
    if classes:
        tables += (classes[0],)

    def tile(i, out):
        s = jnp.minimum(i * t, n - t)   # the last tile overlaps the one before
        Xt = jax.lax.dynamic_slice_in_dim(X, s, t, axis=0)
        bits = jax.lax.bitcast_convert_type(Xt, jnp.int32)
        # the four bytes of every feature: whole numbers under 256 are
        # exact in bfloat16, and a one-hot column picks ONE of them, so the
        # f32 accumulator holds low + 256 * high of a 16-bit half exactly
        b = [((bits >> k) & 0xFF).astype(jnp.bfloat16)
             for k in (0, 8, 16, 24)]
        halves = (jnp.concatenate(b[:2], axis=1),
                  jnp.concatenate(b[2:], axis=1))
        if cats:
            # every slot's bit for the tile's rows, all steps at once: the
            # rows' level one-hots (a segment a categorical column) times
            # the sets. {0, 1} in bfloat16 with an f32 sum of one term: exact
            # A level row's one-hot is (its column's code == its level):
            # the code is chosen a segment at a time, elementwise, so no
            # segment is built apart and joined and the one-hot is the
            # product's own operand, never an array in HBM.
            with jax.named_scope("walk.set"):
                rows = jnp.arange(setB.shape[0])[None, :]
                code = _cat_code(Xt[:, cats[-1][0]], cats[-1][1])[:, None]
                for (c, k), end in zip(cats[-2::-1], ends[-2::-1]):
                    code = jnp.where(rows < end,
                                     _cat_code(Xt[:, c], k)[:, None], code)
                hot = (code == level[None, :]).astype(jnp.bfloat16)
                in_set = jnp.dot(hot, setB,
                                 preferred_element_type=jnp.float32) > 0.5

        def step(acc, tables):
            s2, th, na, lv, ws, *of_sets = tables
            if classes:
                *of_sets, cs = of_sets
            with jax.named_scope("walk.level"):
                lo, hi = (jnp.dot(h, s2, preferred_element_type=jnp.float32)
                          .astype(jnp.int32) for h in halves)
                x = jax.lax.bitcast_convert_type((hi << 16) | lo,
                                                 jnp.float32)
                over = x > th[None, :]
                if cats:
                    splits_set, u = of_sets
                    over = jnp.where(
                        splits_set[None, :], jax.lax.dynamic_slice_in_dim(
                            in_set, u * S, S, axis=1), over)
                right = jnp.where(jnp.isnan(x), ~na[None, :], over)
                # the path-matched levels at once, a block at a time: the
                # row's ±1 decisions match the path to exactly one
                # position of level `top` of each tree in all of them
                turn = jnp.where(right[:, :B * len(paths)], 1.0, -1.0) \
                    .astype(jnp.bfloat16)
                at = jnp.concatenate(
                    [jnp.dot(turn[:, B * k:B * (k + 1)], P,
                             preferred_element_type=jnp.float32) == top
                     for k, P in enumerate(paths)], axis=1)
                if top < levels:
                    Wt = 1 << top
                    pos = jnp.sum(jnp.where(at, jnp.arange(Wt)[None, :], 0),
                                  axis=1)
                    for d in range(top, levels):
                        W = 1 << d
                        here = jnp.arange(W)[None, :] == pos[:, None]
                        pos = 2 * pos + jnp.any(right[:, W:2 * W] & here,
                                                axis=1)
                    at = jnp.arange(L)[None, :] == pos[:, None]
            with jax.named_scope("walk.leaf"):
                v = jnp.sum(jnp.where(at, lv[None, :], 0.0)
                            .reshape(t, G, -1), axis=2)
                for g in range(G):      # tree by tree, in tree order
                    if classes:
                        acc = _class_add(acc, cs[g], ws[g] * v[:, g])
                    else:
                        acc = acc + ws[g] * v[:, g]
                return acc, None

        # the sum starts from a zero the compiler cannot fold away: with one
        # step unrolled, 0 + w0 v0 + w1 v1 would leave it two products to
        # choose from when it contracts the add into a multiply-add (CPU)
        zero = jax.lax.optimization_barrier(
            jnp.zeros((t, classes[1]) if classes else t, jnp.float32))
        with jax.named_scope("walk.tree"):
            acc, _ = jax.lax.scan(step, zero, tables)
        return jax.lax.dynamic_update_slice_in_dim(out, acc, s, axis=0)

    return jax.lax.fori_loop(
        0, -(-n // t), tile,
        jnp.zeros((n, classes[1]) if classes else n, jnp.float32))


@functools.partial(jax.jit, static_argnames=("depth", "has_cat", "classes"))
def _walk_gather(X, col, thr, nal, val, tw, catbits, iscat, cat_rows=None,
                 cls=None, *, depth, has_cat, classes=0):
    """Σ_t tw[t] · value[t, leaf_t(row)] by a fixed-depth chain of gathers
    per tree: deep trees, and the dense body's oracle. `cat_rows` (C,): the
    levels a categorical column's code is held to (`_cat_layout`); None:
    the 32 W bits of a set. `classes` = K > 0, `cls` (T,): tree t is summed
    into column cls[t] of (n, K)."""
    n = X.shape[0]
    if has_cat:
        nb = catbits.shape[-1] * 32

    # walk.tree > walk.level / walk.leaf: stable names for the walk's ops in
    # a device trace (XLA's own — fusion.25 — change with any edit)
    def per_tree(acc, t):
        node = jnp.zeros(n, jnp.int32)

        def step(d, node):
            with jax.named_scope("walk.level"):
                c = col[t][node]
                leafish = c < 0
                cc = jnp.maximum(c, 0)
                x = jnp.take_along_axis(X, cc[:, None], axis=1)[:, 0]
                isna = jnp.isnan(x)
                right = x > thr[t][node]
                if has_cat:
                    code = _cat_code(x, nb if cat_rows is None
                                     else jnp.maximum(cat_rows[cc], 1))
                    word = catbits[t][node, code // 32]
                    bit = (word >> (code % 32).astype(jnp.uint32)) & 1
                    right = jnp.where(iscat[cc], bit == 1, right)
                right = jnp.where(isna, ~nal[t][node], right)
                child = 2 * node + 1 + right.astype(jnp.int32)
                return jnp.where(leafish, node, child)

        node = jax.lax.fori_loop(0, depth, step, node)
        with jax.named_scope("walk.leaf"):
            if classes:
                return _class_add(acc, cls[t], tw[t] * val[t][node]), None
            return acc + tw[t] * val[t][node], None

    with jax.named_scope("walk.tree"):
        out, _ = jax.lax.scan(
            per_tree,
            jnp.zeros((n, classes) if classes else n, jnp.float32),
            jnp.arange(col.shape[0]))
    return out


def _rows_mesh(X):
    """The mesh whose rows axis X's rows are sharded over, or None (one
    shard; a tracer, which carries no placement). The dense body's tile
    loop slices rows, so over a sharded X it runs once per shard
    (shard_map) and no shard asks for another's rows."""
    sh = getattr(X, "sharding", None)
    # h2o3-ok: R025 a placement, not a value: a concrete array's sharding is host metadata and a tracer has none (-> None, the unsharded program)
    if isinstance(sh, jax.sharding.NamedSharding) and len(sh.spec) \
            and sh.spec[0] == _mesh.ROWS and sh.mesh.shape[_mesh.ROWS] > 1:
        return sh.mesh
    return None


@_compat.guard_collective
@functools.partial(jax.jit, static_argnames=("depth", "has_cat", "mesh",
                                             "cats", "classes"))
def _ensemble_walk(X, col, thr, nal, val, tw, catbits, iscat, cls=None, *,
                   depth, has_cat, mesh=None, cats=(), classes=0):
    """Module-level jitted scoring walk: cached per (shapes, depth, has_cat)
    signature. Defining this as a closure inside predict_ensemble gave the
    jit a fresh function identity per call — every single ensemble predict
    retraced AND recompiled, which dominated serving latency. The shape
    picks the body (`_walk_path`); the XLA module is `jit__ensemble_walk`
    either way. `mesh`: where X's rows are sharded (`_rows_mesh`); `cats`:
    the categorical columns' level rows (`_cat_layout`); `classes` = K > 0
    and `cls` (T,): a K-class ensemble, (n, K) margins out of the ONE walk
    (`TreeArrays.tree_class`)."""
    if _walk_path(depth, X.shape[1], sum(k for _, k in cats)) == "gather":
        rows = None
        if cats:
            rows = np.zeros(iscat.shape[0], np.int32)
            rows[[c for c, _ in cats]] = [k for _, k in cats]
            rows = jnp.asarray(rows)
        return _walk_gather(X, col, thr, nal, val, tw, catbits, iscat,
                            rows, cls, depth=depth, has_cat=has_cat,
                            classes=classes)
    dense = functools.partial(_walk_dense, depth=depth, cats=cats)
    tables = (col, thr, nal, val, tw) + ((catbits,) if cats else ())
    out_spec = (_mesh.ROWS,)
    if classes:
        # the classes go last; the sets, where there are any, stay sixth
        def dense(X, *tables):
            return _walk_dense(X, *tables[:-1], cls=tables[-1], depth=depth,
                               cats=cats, classes=classes)
        tables += (cls,)
        out_spec += (None,)
    if mesh is not None:
        P = jax.sharding.PartitionSpec
        dense = jax.shard_map(dense, mesh=mesh, out_specs=P(*out_spec),
                              in_specs=(P(_mesh.ROWS),) + (P(),) * len(tables),
                              check_vma=False)
    return dense(X, *tables)


_NO_SETS = (np.zeros((1, 1, 1), np.uint32), np.zeros(1, bool))


def _walk_tables(trees: TreeArrays, n_cols: int):
    """(the walk's arguments after X with unit weights, a K-class ensemble's
    followed by its trees' classes; the level layout):
    the ensemble's tables on the device and `_cat_layout`, made ONCE an
    ensemble and kept beside it. A host array handed to the jitted walk —
    `col_is_cat`, the unit weights, the numeric program's two unused
    arguments, a MOJO's tables — is a transfer of its own in EVERY call,
    and the walk is not enqueued before the last of them is done: two
    thread hops a transfer on the host, ahead of every frame's walk. The
    entry holds the arrays it was made from and is made anew when the
    ensemble's are others; tables made under a trace keep none."""
    src = (trees.col, trees.thr, trees.na_left, trees.value, trees.catbits,
           trees.col_is_cat, trees.cat_levels, trees.tree_class)
    hit = trees.__dict__.get("_tables")
    same = hit is not None and hit[1] == n_cols \
        and all(a is b for a, b in zip(hit[0], src))
    if same:  # h2o3-ok: R025 object identity of the ensemble's arrays, never a value: a tracer is only ever itself
        return hit[2]
    cats = _cat_layout(trees, n_cols)  # h2o3-ok: R025 col_is_cat / cat_levels are host numpy model metadata excluded from the serving params pytree — static per artifact (covers the if below)
    if cats:
        sets = (trees.catbits, np.asarray(trees.col_is_cat))
    else:
        # fixed dummy shapes so the no-cat program signature is stable
        sets = _NO_SETS
    got = tuple(jnp.asarray(a) for a in (
        trees.col, trees.thr, trees.na_left, trees.value,
        np.ones(trees.ntrees, np.float32), *sets)
        + (() if trees.tree_class is None
           else (np.asarray(trees.tree_class, np.int32),))), cats
    if not any(isinstance(a, jax.core.Tracer) for a in got[0]):  # h2o3-ok: R025 asks what the arrays ARE, not what they hold: made of traced arguments, or inside a trace (there a host constant is staged as a tracer too), the tables belong to that trace and no entry is kept
        trees.__dict__["_tables"] = (src, n_cols, got)
    return got


def predict_ensemble(X, trees: TreeArrays, weights=None):
    """Σ_t value[t, leaf_t(row)], (n,) — of a K-class ensemble
    (`tree_class`) the sum of each class's trees, (n, K), from the same ONE
    dispatch. Ensembles of moderate depth are scored
    densely, every node of a tree for a tile of rows (`_walk_dense`) —
    categorical SET splits too: a node routes by bitset membership of the
    level id (hex/genmodel GenModel.bitSetContains analog), matched on the
    MXU — and deep trees take a fixed-depth gather walk per tree
    (`_walk_gather`). The two agree bit for bit; `_walk_path` picks from
    (depth, columns, level rows) and h2o3_tree_walk_total counts it."""
    # the host's share before the walk is enqueued: the ensemble's tables
    # onto the device (inside `predict.dispatch` on the large-frame path);
    # after an ensemble's first call a look-up (`_walk_tables`)
    with _span("predict.tables"):
        (col, thr, nal, val, tw, catbits, iscat, *cls), cats = \
            _walk_tables(trees, X.shape[1])
        if weights is not None:
            tw = jnp.asarray(weights, jnp.float32)
    path = _walk_path(trees.depth, X.shape[1], sum(k for _, k in cats))
    WALKS.inc(path=path,
              block=_block_label(trees.depth) if path == "dense" else "")
    # h2o3-ok: R025 tree_class is host numpy model metadata (the pytree's aux, never a tracer)
    of_classes = dict(classes=trees.n_classes) if cls else {}
    return _ensemble_walk(X, col, thr, nal, val, tw, catbits, iscat, *cls,
                          depth=trees.depth, has_cat=cats != (), cats=cats,
                          mesh=_rows_mesh(X) if path == "dense" else None,
                          **of_classes)


@_compat.guard_collective
@functools.partial(jax.jit, static_argnames=("depth",))
def _leaf_id_walk(X, col, thr, nal, *, depth):
    """Module-level (cached) version of the leaf-id walk — same per-call
    recompile hazard as _ensemble_walk."""
    n = X.shape[0]

    def per_tree(_, t):
        node = jnp.zeros(n, jnp.int32)
        dep = jnp.zeros(n, jnp.int32)

        def step(d, carry):
            node, dep = carry
            c = col[t][node]
            leafish = c < 0
            cc = jnp.maximum(c, 0)
            x = jnp.take_along_axis(X, cc[:, None], axis=1)[:, 0]
            isna = jnp.isnan(x)
            right = jnp.where(isna, ~nal[t][node], x > thr[t][node])
            child = 2 * node + 1 + right.astype(jnp.int32)
            return (jnp.where(leafish, node, child),
                    jnp.where(leafish, dep, dep + 1))

        node, dep = jax.lax.fori_loop(0, depth, step, (node, dep))
        return None, (node, dep)

    _, (nodes, deps) = jax.lax.scan(per_tree, None,
                                    jnp.arange(col.shape[0]))
    return nodes, deps


def predict_leaf_ids(X, trees: TreeArrays):
    """Per-(row, tree) terminal node ids and depths (IF path length, SHAP)."""
    return _leaf_id_walk(X, jnp.asarray(trees.col), jnp.asarray(trees.thr),
                         jnp.asarray(trees.na_left), depth=trees.depth)


# ===========================================================================
class TreeGrower:
    """Grows ONE tree level-by-level with D async device programs and no host
    round-trips. Returns device arrays; used by the GBM/DRF/IF drivers."""

    def __init__(self, nbins: int, max_depth: int, min_rows: float,
                 min_split_improvement: float, reg_lambda: float = 0.0):
        self.B = int(nbins)
        self.D = int(max_depth)
        self.min_rows = float(min_rows)
        self.msi = float(min_split_improvement)
        self.reg_lambda = float(reg_lambda)
        self.nodes = 2 ** (self.D + 1) - 1

    def grow(self, X, w, grad, col_mask=None, key=None, mtries: int = 0):
        """X: (n,C) f32 NaN-NA; w: (n,) in-sample weights (0 = out-of-bag);
        grad: (n,) regression target (residual/gradient).

        Returns device arrays (col, thr, na_left, value, heap, gains):
        heap = per-row terminal node id (val[heap] = this tree's prediction).
        """
        n, C = X.shape
        stats = jnp.stack([w, w * grad, w * grad * grad], axis=1)
        leaf = jnp.zeros(n, jnp.int32)
        heap = jnp.zeros(n, jnp.int32)
        active = jnp.ones(n, bool)
        colA = jnp.full(self.nodes, -1, jnp.int32)
        thrA = jnp.zeros(self.nodes, jnp.float32)
        nalA = jnp.zeros(self.nodes, bool)
        valA = jnp.zeros(self.nodes, jnp.float32)
        gains = jnp.zeros(C, jnp.float32)
        if col_mask is None:
            col_mask = jnp.ones(C, bool)
        if key is None:
            key = jax.random.PRNGKey(0)
        ROW_TREES.inc(n, engine="adaptive")
        with _span("tree.grow", rows=n, cols=C, depth=self.D):
            for d in range(self.D):
                # span covers the level DISPATCH (histogram + split search
                # + routing are one fused async program; on TPU the enqueue
                # returns before the device finishes)
                with _span("tree.level", depth=d):
                    leaf, heap, active, colA, thrA, nalA, valA, gains = \
                        _level_step(
                            X, stats, w, leaf, heap, active, colA, thrA,
                            nalA, valA, gains, col_mask, key, d=d, B=self.B,
                            mtries=int(mtries), min_rows=self.min_rows,
                            min_split_improvement=self.msi,
                            reg_lambda=self.reg_lambda)
                if _cpu_backend():
                    # XLA CPU collectives abort flakily when programs
                    # containing all-reduces pile up in the async queue
                    # (virtual-device test mesh only): drain per level. And
                    # since the controller is synchronous here anyway, stop
                    # growing once every row is frozen — deep levels of
                    # unbalanced limits (max_depth 15+ on small data) would
                    # otherwise compile and run for nothing. TPU stays
                    # fully async at fixed depth.
                    # h2o3-ok: R002 intentional per-level drain barrier (CPU collective flakiness), gated to the CPU backend
                    jax.block_until_ready(valA)
                    # the early-exit probe is an EAGER cross-shard reduce:
                    # it must take the same collective guard as the level
                    # programs or a concurrent build can rendezvous-starve
                    # against it on the host mesh
                    if not _compat.run_host_serialized(
                            lambda: bool(jnp.any(active))):
                        return colA, thrA, nalA, valA, heap, gains
            valA = _final_leaves(stats, leaf, active, w, valA, D=self.D)
            if _cpu_backend():
                # h2o3-ok: R002 same intentional CPU-only drain barrier as above
                jax.block_until_ready(valA)
        return colA, thrA, nalA, valA, heap, gains


_CPU_BACKEND_CACHE: bool | None = None


def _cpu_backend() -> bool:
    """Lazy, memoized backend probe: importing a module must not
    initialize the backend (and take the chip), so the question waits
    until the first tree actually trains."""
    global _CPU_BACKEND_CACHE
    if _CPU_BACKEND_CACHE is None:
        _CPU_BACKEND_CACHE = jax.default_backend() == "cpu"
    return _CPU_BACKEND_CACHE
