"""Binned (pre-quantized) tree engine — the TPU rebuild of the reference's
global-quantile histogram path, designed for MXU/VPU throughput.

Reference mapping:
  * hex/tree/GlobalQuantilesCalc.java — quantize features ONCE per training
    run into small-integer bin codes against global quantile edges (the
    `histogram_type="QuantilesGlobal"` mode; also xgboost `tree_method=hist`
    semantics, the BASELINE.json comparison target).
  * hex/tree/ScoreBuildHistogram2.java:20-60 — the fused score+build pass.
    Here rows are kept PARTITIONED by leaf (stable partition maintained per
    level entirely on device), so histogram accumulation is leaf-local and
    rides the Pallas kernel in ops/hist_pallas.py.
  * hex/tree/DTree.java:514 (DecidedNode.bestCol) — vectorized split search
    over (leaf, col, threshold, NA-direction), plus categorical SET splits:
    bins sorted by mean gradient and split on the best prefix (the optimal
    subset search for 1-D loss, replacing IcedBitSet group splits
    water/util/IcedBitSet.java) with the decision stored as a 256-bit mask.
  * hex/tree/Constraints.java — monotone constraints: sign-violating splits
    are rejected and child values are clamped to propagated bounds.
  * hex/tree/SharedTree.java:548-561 — task parallelism over trees becomes
    a lax.scan over trees inside ONE jitted program (a dispatch through the
    controller costs ~10ms; per-level dispatch would dominate runtime).

Everything per level is static-shaped: leaf arrays are sized L_MAX = 2^D,
the slot count n_pad = (ceil(n/R) + L_MAX) * R never changes, and empty
leaves own one all-dummy block. No host synchronization inside training.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from h2o3_tpu.ops import hist_pallas as HP

R = HP.BLOCK_ROWS


# ===========================================================================
# Quantization (GlobalQuantilesCalc analog)
@dataclass
class Planes:
    """A frame whose columns do not all fit a code byte, laid out as BYTE
    PLANES for the kernels: a column with up to 255 codes (its NA code
    among them) is one plane; a wider one — a categorical column past a
    code byte, every level its own bin — takes several, plane k holding
    the codes [255 k, 255 k + 255) as bytes 0..254 and byte 255 for a row
    whose code lies in another plane. The kernels see `cp_pad` byte columns
    of 256 bins; the split search sees the columns themselves, `n_search`
    bins wide with the NA bin at `BinSpec.b_val`, through the index maps
    below (all host constants)."""
    nb: np.ndarray         # (C,) value bins of each column; its NA code
    levels: np.ndarray     # (C,) levels of a categorical column, 0: numeric
    src: np.ndarray        # (cp_pad,) the column a plane belongs to, -1: none
    off: np.ndarray        # (cp_pad,) first code of the plane
    multi: np.ndarray      # (cp_pad,) plane of a column that takes several
    first: np.ndarray      # (c_pad,) first plane of each column
    per: int               # most planes a column takes
    cp_pad: int            # planes, padded to the kernels' column tile
    n_search: int          # bins of the split search, padded (mult 128)
    hist_src: np.ndarray   # (c_pad, n_search) -> slot of the planes' hist
    route_dst: np.ndarray  # (c_pad, per * 256) -> bin of a node's route row
    level_bin: np.ndarray  # (c_pad, set bits) -> bin of a level's route

    @property
    def grouped(self) -> dict:
        """{column: (levels, bins)} of the categorical columns whose levels
        share bins (more levels than nbins_cats)."""
        return {int(c): (int(self.levels[c]), int(self.nb[c]))
                for c in np.flatnonzero(self.levels > self.nb)}


@dataclass
class BinSpec:
    """Per-column binning of a training frame."""
    edges: np.ndarray        # (C, B_val-1) f32 — ascending cut points
    is_cat: np.ndarray       # (C,) bool — categorical column (codes = level)
    b_val: int               # number of value bins; NA code == b_val
    n_bins: int              # padded bin count used by the kernel (mult 128)
    c_pad: int               # padded column count (mult COL_TILE)
    planes: Planes | None = None   # None: every column fits a code byte

    @property
    def na_code(self):
        return self.b_val


PLANE = 255     # codes of one byte plane of a column that takes several


def _plane_layout(nb, levels, c_pad) -> Planes:
    """The byte planes and index maps of columns with `nb` value bins."""
    C = nb.size
    b_val = int(nb.max())
    n_search = -(-(b_val + 1) // 128) * 128
    # planes a column takes: one while its codes (NA among them) fit a byte
    ks = [1 if k + 1 <= 256 else -(-(int(k) + 1) // PLANE) for k in nb]
    src, off, multi, first = [], [], [], np.zeros(c_pad, np.int32)
    for c, k in enumerate(ks):
        first[c] = len(src)
        src += [c] * k
        off += [PLANE * j for j in range(k)]
        multi += [k > 1] * k
    per = max(ks)
    cp_pad = -(-len(src) // HP.COL_TILE) * HP.COL_TILE
    pad = cp_pad - len(src)
    src = np.asarray(src + [-1] * pad, np.int32)
    off = np.asarray(off + [0] * pad, np.int32)
    multi = np.asarray(multi + [False] * pad, bool)
    # a column's code as (plane, byte), vectorised over every code 0..nb
    zero_h, zero_r = cp_pad * 256, n_search
    hist_src = np.full((c_pad, n_search), zero_h, np.int32)
    route_dst = np.full((c_pad, per * 256), zero_r, np.int32)
    bits = -(-int(max(levels.max(), 1)) // 32) * 32
    level_bin = np.full((c_pad, bits), zero_r, np.int32)
    for c in range(C):
        codes = np.arange(int(nb[c]) + 1)            # the last is NA
        plane = codes // PLANE if ks[c] > 1 else 0 * codes
        byte = codes - PLANE * plane
        where = np.where(codes < nb[c], codes, b_val)   # NA bin of the search
        hist_src[c, where] = (first[c] + plane) * 256 + byte
        route_dst[c, plane * 256 + byte] = where
        if levels[c] > 0:
            lv = np.minimum(np.arange(bits), levels[c] - 1)
            level_bin[c] = lv * int(nb[c]) // int(levels[c])
    return Planes(nb=nb, levels=levels, src=src, off=off, multi=multi,
                  first=first, per=per, cp_pad=cp_pad, n_search=n_search,
                  hist_src=hist_src, route_dst=route_dst, level_bin=level_bin)


def make_bins(X, is_cat, nbins: int, sample: int = 1 << 18,
              cat_levels=None, nbins_cats: int = 1024) -> BinSpec:
    """Global quantile edges from a row sample. X: (n, C) f32 with NaN NAs.
    Categorical columns are identity-binned (code == level id): every
    level its own bin. With `cat_levels` (levels of each column, 0 for a
    numeric one) a categorical column past a code byte keeps every level
    apart too, up to `nbins_cats` bins (`Planes`); past `nbins_cats` its
    levels share bins in runs of consecutive ids, as H2O-3's DHistogram
    steps them — `BinSpec.planes.grouped` says which. Without it, or
    when every column fits, a column's codes are capped at `nbins`."""
    n, C = X.shape
    b_val = int(min(nbins, 255))
    stride = max(1, n // sample)
    Xs = np.asarray(X[::stride][:sample], np.float32)
    is_cat = np.asarray(is_cat, bool)
    levels = np.zeros(C, np.int64) if cat_levels is None else \
        np.where(is_cat, np.asarray(cat_levels, np.int64), 0)
    wide = bool((levels > min(b_val, int(nbins_cats))).any())
    nb = np.where(levels > 0, np.minimum(levels, int(nbins_cats)), b_val) \
        if wide else np.full(C, b_val, np.int64)
    edges = np.full((C, int(nb.max()) - 1), np.inf, np.float32)
    for c in range(C):
        k = int(nb[c])
        if is_cat[c]:
            # identity binning: edge j at j+0.5 so code(level j)=j; levels
            # that share a bin: the edge under the first level of each bin
            lv = levels[c] if wide else k
            edges[c, :k - 1] = -(-np.arange(1, k) * lv // k) - 0.5
            continue
        qs = np.linspace(0.0, 1.0, k + 1)[1:-1]
        col = Xs[:, c]
        col = col[~np.isnan(col)]
        if col.size == 0:
            edges[c, :k - 1] = np.arange(1, k, dtype=np.float32)
            continue
        e = np.quantile(col, qs).astype(np.float32)
        # strictly non-decreasing is fine: duplicate edges => empty bins
        edges[c, :k - 1] = e
    cp = -(-C // HP.COL_TILE) * HP.COL_TILE
    if wide:
        return BinSpec(edges=edges, is_cat=is_cat, b_val=int(nb.max()),
                       n_bins=256, c_pad=cp,
                       planes=_plane_layout(nb, levels, cp))
    return BinSpec(edges=edges, is_cat=is_cat, b_val=b_val, c_pad=cp,
                   n_bins=max(128, -(-(b_val + 1) // 128) * 128))


def row_granule() -> int:
    """Per-shard row-count granularity: the Pallas kernels sweep rows in
    BLOCK_ROWS tiles; the XLA fallbacks (CPU tests) have no tiling constraint
    so a smaller granule keeps tiny sharded test frames cheap."""
    return R if HP.use_pallas() else 512


def padded_rows(n: int, shards: int = 1) -> int:
    """Slots for n data rows + 1 dummy, padded so every shard's local block
    is a granule multiple (the rows axis splits evenly over the mesh)."""
    blk = row_granule() * max(1, shards)
    return -(-(n + 1) // blk) * blk


@functools.partial(jax.jit, static_argnames=("b_val", "c_pad", "n_pad",
                                             "sharding"))
def _quantize(X, edges, *, b_val, c_pad, n_pad, sharding=None):
    """codes[r,c] = #edges < x (0..b_val-1), NA -> b_val. Rows are padded to
    the kernel block multiple with dummy rows (code 0, zero stats) and dummy
    columns for the kernel's column tiling. Codes are uint8 END-TO-END
    (b_val <= 255 so the NA code fits): the code plane is the per-level
    HBM bandwidth floor (ops/PERF_NOTES.md) and one byte per code is 4x
    less stream than the old i32 planes. `sharding` pins the plane's
    layout: left to itself the partitioner is free to REPLICATE the
    output of a row-sharded X (the CPU's does — every device gathers the
    whole matrix and quantizes all of it; the TPU's happens to shard)."""
    n, C = X.shape

    def one_col(x, e):
        code = jnp.searchsorted(e, x, side="left").astype(jnp.int32)
        return jnp.where(jnp.isnan(x), b_val, code)

    with jax.named_scope("bin.search"):
        codes = jax.vmap(one_col, in_axes=(1, 0), out_axes=0)(X, edges)
    codes = jnp.clip(codes, 0, b_val).astype(jnp.uint8)  # (C, n)
    out = jnp.zeros((c_pad, n_pad), jnp.uint8)
    out = lax.dynamic_update_slice(out, codes, (0, 0))
    if sharding is not None:
        out = lax.with_sharding_constraint(out, sharding)
    return out


@functools.partial(jax.jit, static_argnames=("n_pad", "sharding"))
def _quantize_planes(X, edges, nb, src, off, multi, *, n_pad, sharding=None):
    """`_quantize` for columns that do not all fit a code byte (`Planes`):
    a column's code is 0..nb-1, NA -> nb, and the plane of column src[j]
    that starts at code off[j] holds it as a byte, 255 in a plane it does
    not fall in."""
    def one_col(x, e, k):
        code = jnp.searchsorted(e, x, side="left").astype(jnp.int32)
        return jnp.where(jnp.isnan(x), k, jnp.minimum(code, k - 1))

    with jax.named_scope("bin.search"):
        codes = jax.vmap(one_col, in_axes=(1, 0, 0), out_axes=0)(X, edges, nb)
    with jax.named_scope("bin.planes"):
        rel = codes[jnp.maximum(src, 0)] - off[:, None]        # (cp_pad, n)
        byte = jnp.where(multi[:, None] & ((rel < 0) | (rel >= PLANE)),
                         255, rel)
        byte = jnp.where(src[:, None] < 0, 0, byte).astype(jnp.uint8)
    out = jnp.zeros((src.shape[0], n_pad), jnp.uint8)
    out = lax.dynamic_update_slice(out, byte, (0, 0))
    if sharding is not None:
        out = lax.with_sharding_constraint(out, sharding)
    return out


def quantize(X, spec: BinSpec, n_pad: int | None = None, sharding=None):
    """(n, C) f32 -> (C_pad, n_pad) uint8 code plane (the XLA-fallback /
    canonical layout; `prepare_codes` derives the TPU kernel layout); with
    `spec.planes`, its byte planes. `sharding`: the plane's row sharding
    on a multi-device cloud."""
    n = X.shape[0]
    if n_pad is None:
        n_pad = padded_rows(n)
    pl = spec.planes
    if pl is not None:
        return _quantize_planes(
            X, jnp.asarray(spec.edges), jnp.asarray(pl.nb, jnp.int32),
            jnp.asarray(pl.src), jnp.asarray(pl.off), jnp.asarray(pl.multi),
            n_pad=n_pad, sharding=sharding)
    return _quantize(X, jnp.asarray(spec.edges), b_val=spec.b_val,
                     c_pad=spec.c_pad, n_pad=n_pad, sharding=sharding)


def prepare_codes(codes_u8):
    """Backend-appropriate kernel layout for a quantized plane: the packed
    i32 word plane (4 codes/word, HP.pack_codes) on the Pallas backend,
    the uint8 plane unchanged everywhere else. Row axis untouched — row
    sharding specs carry over."""
    return HP.prepare_codes(codes_u8)


def pad_rows(x, n_pad: int):
    """Zero-pad a per-row vector to the quantize() row layout."""
    return jnp.pad(x, (0, n_pad - x.shape[0]))


# ===========================================================================
# Split search over binned histograms
def _se_gain(wl, gl, wr, gr_, wp, gp, lam):
    """Un-halved SE / structure-score reduction (same objective family as
    engine.find_best_splits; lam>0 = XGBoost G^2/(H+lambda))."""
    def score(w_, g_):
        return jnp.where(w_ > 0, g_ * g_ / jnp.maximum(w_ + lam, 1e-30), 0.0)
    return score(wl, gl) + score(wr, gr_) - score(wp, gp)


@functools.partial(
    jax.jit,
    static_argnames=("b_val", "use_hess", "any_cat"))
def find_splits_binned(hist, is_cat, mono, cmask, lo, hi, *, b_val,
                       min_rows, msi, lam, use_hess, any_cat=True, nb=None):
    """Vectorized bestCol over every (leaf, col, threshold/subset, NA-dir).

    hist: (L, C_pad, 4, BP) — stats rows 0=w 1=wg 2=wh (3 spare)
    is_cat: (C_pad,) bool; mono: (C_pad,) int32 in {-1,0,1}
    cmask: (L, C_pad) bool column availability (mtries / padding)
    lo, hi: (L,) f32 monotone value bounds for each leaf
    nb: (C_pad,) value bins of each column where they differ (`Planes`):
        a column's bins nb..b_val-1 are empty, and no cut lies among them

    Returns dict of per-leaf arrays: did, col, bin, nal, route (L, BP) bool,
    val_l, val_r (clamped), gain, plus per-leaf totals (w_t, val_t).
    """
    L, C, _, BP = hist.shape
    w = hist[:, :, 0, :]
    wg = hist[:, :, 1, :]
    wh = hist[:, :, 2, :]
    den = wh if use_hess else w

    B = b_val
    v_w, na_w = w[..., :B], w[..., B]
    v_wg, na_wg = wg[..., :B], wg[..., B]
    v_wh, na_wh = wh[..., :B], wh[..., B]
    v_den, na_den = den[..., :B], den[..., B]

    # ---- parent totals (identical for every real column; col 0 is real) --
    w_t = v_w[:, 0].sum(-1) + na_w[:, 0]
    wg_t = v_wg[:, 0].sum(-1) + na_wg[:, 0]
    wh_t = v_wh[:, 0].sum(-1) + na_wh[:, 0]
    den_t = v_den[:, 0].sum(-1) + na_den[:, 0]
    # leaf VALUES are always the Newton step wg/wh (GammaPass,
    # GBM.java:1235); `den`/use_hess only selects the split-gain objective
    val_t = wg_t / jnp.maximum(wh_t, 1e-30)

    # ---- categorical: sort bins by mean gradient (optimal-subset order) --
    # (statically skipped when the frame has no categorical columns)
    # (`tree.level.split.set` names the SET search's ops in a device trace)
    if any_cat:
        with jax.named_scope("tree.level.split.set"):
            ratio = jnp.where(v_den > 1e-30,
                              v_wg / jnp.maximum(v_den, 1e-30),
                              jnp.inf)                      # empty bins last
            order = jnp.argsort(ratio, axis=-1)             # (L, C, B)
            sc_w = jnp.take_along_axis(v_w, order, -1)
            sc_wg = jnp.take_along_axis(v_wg, order, -1)
            sc_den = jnp.take_along_axis(v_den, order, -1)

    def eval_axis(aw, awg, aden):
        """Prefix-split gains along the (possibly re-ordered) bin axis.
        Returns (gain, nal) each (L, C, B-1)."""
        cl_w = jnp.cumsum(aw, -1)[..., :-1]
        cl_wg = jnp.cumsum(awg, -1)[..., :-1]
        cl_den = jnp.cumsum(aden, -1)[..., :-1]

        def gains(nal):
            lw = cl_w + (na_w[..., None] if nal else 0.0)
            lg = cl_wg + (na_wg[..., None] if nal else 0.0)
            ld = cl_den + (na_den[..., None] if nal else 0.0)
            rw = w_t[:, None, None] - lw
            rg = wg_t[:, None, None] - lg
            rd = den_t[:, None, None] - ld
            g = _se_gain(ld, lg, rd, rg, den_t[:, None, None],
                         wg_t[:, None, None], lam)
            ok = (lw >= min_rows) & (rw >= min_rows)
            # monotone: reject sign-violating splits on constrained columns
            vl = lg / jnp.maximum(ld, 1e-30)
            vr = rg / jnp.maximum(rd, 1e-30)
            mok = (mono[None, :, None] == 0) | \
                  ((vr - vl) * mono[None, :, None] >= 0)
            return jnp.where(ok & mok, g, -jnp.inf)

        g0, g1 = gains(False), gains(True)
        return jnp.maximum(g0, g1), g1 > g0

    gn_num, nal_num = eval_axis(v_w, v_wg, v_den)           # natural order
    if any_cat:
        with jax.named_scope("tree.level.split.set"):
            gn_cat, nal_cat = eval_axis(sc_w, sc_wg, sc_den)  # sorted order
        catC = is_cat[None, :, None]
        gain_all = jnp.where(catC, gn_cat, gn_num)          # (L, C, B-1)
        nal_all = jnp.where(catC, nal_cat, nal_num)
    else:
        gain_all, nal_all = gn_num, nal_num
    gain_all = jnp.where(cmask[:, :, None], gain_all, -jnp.inf)
    if nb is not None:
        cuts = jnp.arange(B - 1)[None, :] < nb[:, None] - 1
        gain_all = jnp.where(cuts[None], gain_all, -jnp.inf)

    flat = gain_all.reshape(L, C * (B - 1))
    best = jnp.argmax(flat, axis=1)
    bgain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    bcol = (best // (B - 1)).astype(jnp.int32)
    bbin = (best % (B - 1)).astype(jnp.int32)               # threshold index
    bnal = jnp.take_along_axis(nal_all.reshape(L, C * (B - 1)),
                               best[:, None], 1)[:, 0]
    did = jnp.isfinite(bgain) & (bgain > jnp.maximum(msi, 0.0))

    # ---- routing table: route[l, code] = goes-right ----------------------
    takeL = lambda a: jnp.take_along_axis(    # noqa: E731  (L,C,X)->(L,X)
        a, bcol[:, None, None], 1)[:, 0]
    bin_ids = jnp.arange(BP)[None, :]                       # (1, BP)
    num_right = bin_ids > bbin[:, None]                     # natural order
    if any_cat:
        with jax.named_scope("tree.level.split.set"):
            rank_of_bin = jnp.argsort(takeL(order), axis=-1)    # (L, B)
            rank_pad = jnp.pad(rank_of_bin, ((0, 0), (0, BP - B)),
                               constant_values=BP)
            cat_right = rank_pad > bbin[:, None]
        leaf_cat = is_cat[bcol]
        route = jnp.where(leaf_cat[:, None], cat_right, num_right)
    else:
        route = num_right
    # NA code: by chosen NA direction
    route = route.at[:, B].set(~bnal)
    route = jnp.where(did[:, None], route, False)           # frozen: stay

    # ---- child values (Newton wg/wh) with monotone clamping --------------
    bw = takeL(v_w)
    bg = takeL(v_wg)
    bh = takeL(v_wh)
    goes_left = ~route[:, :B]
    # NA-bin mass of the CHOSEN column (each column sees different NA rows)
    takeL1 = lambda a: jnp.take_along_axis(   # noqa: E731  (L,C)->(L,)
        a, bcol[:, None], 1)[:, 0]
    w_l = (bw * goes_left).sum(-1) + jnp.where(bnal, takeL1(na_w), 0.0)
    g_l = (bg * goes_left).sum(-1) + jnp.where(bnal, takeL1(na_wg), 0.0)
    h_l = (bh * goes_left).sum(-1) + jnp.where(bnal, takeL1(na_wh), 0.0)
    val_l = g_l / jnp.maximum(h_l, 1e-30)
    g_r = wg_t - g_l
    h_r = wh_t - h_l
    val_r = g_r / jnp.maximum(h_r, 1e-30)
    val_l = jnp.clip(val_l, lo, hi)
    val_r = jnp.clip(val_r, lo, hi)
    val_tc = jnp.clip(val_t, lo, hi)

    return dict(did=did, col=bcol, bin=bbin, nal=bnal, route=route,
                gain=jnp.where(did, jnp.maximum(bgain, 0.0), 0.0),
                val_l=val_l, val_r=val_r, val_t=val_tc,
                w_t=w_t, w_l=w_l, wg_l=g_l, wh_l=h_l)


# ===========================================================================
# The grower: one jitted program per chunk of trees
class BinnedGrower:
    """Grows trees level-by-level on pre-binned codes with device-resident
    leaf partitioning. One lax.scan over K trees per dispatch."""

    def __init__(self, spec: BinSpec, *, max_depth: int, min_rows: float,
                 min_split_improvement: float, reg_lambda: float = 0.0,
                 reg_alpha: float = 0.0, use_hess_denom: bool = False,
                 monotone: np.ndarray | None = None,
                 axis_name: str | None = None):
        # axis_name: mesh axis the row dimension is sharded over. grow() then
        # runs shard-local and merges per-level histograms with ONE psum —
        # the reduce-tree of ScoreBuildHistogram.java:98 / MRTask.java:907
        # riding ICI. Split search stays replicated (identical on all shards).
        self.axis_name = axis_name
        self.spec = spec
        self.D = int(max_depth)
        self.L = 2 ** self.D
        self.nodes = 2 ** (self.D + 1) - 1
        self.min_rows = float(min_rows)
        self.msi = float(min_split_improvement)
        self.lam = float(reg_lambda)
        self.alpha = float(reg_alpha)
        self.use_hess = bool(use_hess_denom)
        mono = np.zeros(spec.c_pad, np.int32) if monotone is None else \
            np.asarray(monotone, np.int32)
        self.mono = jnp.asarray(mono)
        self.is_cat_dev = jnp.asarray(
            np.pad(spec.is_cat, (0, spec.c_pad - spec.is_cat.size)))
        # columns that do not all fit a code byte: the planes' index maps
        pl = spec.planes
        if pl is not None:
            self.nb_dev = jnp.asarray(np.pad(
                pl.nb, (0, spec.c_pad - pl.nb.size), constant_values=1))
            self.first_dev = jnp.asarray(pl.first, jnp.float32)
            self.hist_src, self.route_dst, self.level_bin = (
                jnp.asarray(a) for a in (pl.hist_src, pl.route_dst,
                                         pl.level_bin))

    # ---- static layout ---------------------------------------------------
    def layout(self, n: int, shards: int = 1):
        """Slots for n data rows + 1 dummy, padded to the kernel block
        (per-shard when the rows axis is sharded over `shards` devices)."""
        return padded_rows(n, shards)

    def _columns(self, hist):
        """The planes' histogram (L, cp_pad, 4, 256) as the columns' own,
        (L, c_pad, 4, n_search) with the NA bin at b_val: a gather by a
        host constant."""
        L, CP, S, nb = hist.shape
        flat = hist.transpose(0, 2, 1, 3).reshape(L, S, CP * nb)
        flat = jnp.concatenate([flat, jnp.zeros((L, S, 1), flat.dtype)], -1)
        return flat[:, :, self.hist_src].transpose(0, 2, 1, 3)

    def sets(self, out):
        """A grown tree's go-right sets as bitsets (`pack_route`): over the
        bins of a code byte, or, with planes, over each node's column's
        LEVELS (a level past the column's last goes its way)."""
        spec = self.spec
        if spec.planes is None:
            return pack_route(out["route"], spec.n_bins, spec.b_val)
        route = jnp.pad(out["route"], ((0, 0), (0, 1)))
        lv = jnp.take_along_axis(
            route, self.level_bin[jnp.maximum(out["col"], 0)], axis=1)
        return pack_route(lv, lv.shape[1])

    def grow(self, codes, stats, F, *, eta, clip_val, key, mtries: int = 0,
             tree_mask=None):
        """Grow ONE tree and apply its margin update — all device-resident.

        codes: uint8 (C_pad, n_pad) code plane from `quantize`, or the
               packed i32 (W_pad, n_pad) plane from `prepare_codes` on the
               Pallas backend — COLUMN-major either way (dummy rows carry
               zero stats)
        stats: (S_STATS, n_pad) f32 — rows 0=w 1=w*grad 2=w*hess 3=0
        F:     (n_pad,) f32 margins (updated in the terminal route pass)

        Returns dict(col, bin, nal, route, val, cover, gains, F).
        Per-row state is ONE heap-id int32 array; no row reordering ever
        happens (measured: TPU gathers are 10x slower than the histogram
        kernel — see ops/hist_pallas.py header).
        """
        spec, D = self.spec, self.D
        C = spec.c_pad
        n_pad = codes.shape[1]
        BP = spec.n_bins
        # with planes the kernels see CH byte columns of BP bins, P of them
        # a column at most, and the search the C columns, BS bins wide
        pl = spec.planes
        CH, BS, P = (C, BP, 1) if pl is None else \
            (pl.cp_pad, pl.n_search, pl.per)
        big = jnp.float32(3e38)
        nodes_p = -(-(self.nodes + 1) // 128) * 128
        heap = jnp.zeros(n_pad, jnp.int32)
        colA = jnp.full(self.nodes, -1, jnp.int32)
        binA = jnp.full(self.nodes, -1, jnp.int32)
        nalA = jnp.zeros(self.nodes, bool)
        routeA = jnp.zeros((self.nodes, BS), bool)
        valA = jnp.zeros(self.nodes, jnp.float32)
        coverA = jnp.zeros(self.nodes, jnp.float32)
        gains = jnp.zeros(C + 1, jnp.float32)
        c_real = int(spec.is_cat.size)

        lo = jnp.full(1, -big)
        hi = jnp.full(1, big)
        any_cat = bool(spec.is_cat.any())
        prev = None                    # routing tables of level d-1
        hist_prev = None               # full histogram of level d-1
        did_prev = None                # split mask of level d-1
        # jax.named_scope below is metadata only: it names the stages of
        # the K-tree program in a device trace (tree.level.hist /
        # .route_hist / .split / .nodes, tree.margin) and changes neither
        # the program nor its persistent-cache key
        for d in range(D):
            L = 1 << d
            base = L - 1
            if d == 0:
                with jax.named_scope("tree.level.hist"):
                    hist = HP.sbh_hist(codes, heap, stats, base=base, L=L,
                                       n_bins=BP)[:L, :CH]
                    if self.axis_name:
                        # the ScoreBuildHistogram reduce: merge shard-local
                        # histograms in one collective per level
                        hist = lax.psum(hist, self.axis_name)
            else:
                # ONE fused-or-sequential pass: route the previous level's
                # splits, then (sibling subtraction) histogram LEFT
                # children only over the UPDATED heap — half the leaf
                # window -> half the MXU dot, and on the fused Pallas path
                # the code tile is read ONCE for both phases. Right =
                # parent - left: routing moves every row of a split leaf,
                # so parent = left + right exactly; unsplit parents are
                # masked to zero (their child slots are dead).
                with jax.named_scope("tree.level.route_hist"):
                    heap, left = HP.sbh_route_hist(
                        codes, heap, prev["tbl"], prev["route_f"], stats,
                        base_r=(L >> 1) - 1, L_r=L >> 1, base_h=base, L_h=L,
                        n_bins=BP, any_cat=any_cat, na_code=spec.b_val,
                        planes=P)
                with jax.named_scope("tree.level.hist"):
                    left = left[: L >> 1, :CH]
                    if self.axis_name:
                        # psum BEFORE subtraction: hist_prev is already
                        # global
                        left = lax.psum(left, self.axis_name)
                    par = jnp.where(did_prev[:, None, None, None],
                                    hist_prev, jnp.zeros_like(hist_prev))
                    right = par - left
                    hist = jnp.stack([left, right], axis=1) \
                        .reshape(L, *left.shape[1:])
            hist_prev = hist

            with jax.named_scope("tree.level.split"):
                if mtries and mtries < c_real:
                    r = jax.random.uniform(jax.random.fold_in(key, d),
                                           (L, C))
                    r = jnp.where(jnp.arange(C) < c_real, r, 2.0)
                    kth = jnp.sort(r, axis=1)[:, mtries - 1:mtries]
                    cmask = r <= kth
                else:
                    cmask = jnp.broadcast_to(
                        (jnp.arange(C) < c_real)[None], (L, C))
                if tree_mask is not None:
                    # col_sample_rate_per_tree: a whole-tree column subset
                    # drawn by the caller (SharedTree _rand per-tree cols
                    # analog)
                    cmask = cmask & tree_mask[None, :]

                s = find_splits_binned(
                    hist if pl is None else self._columns(hist),
                    self.is_cat_dev, self.mono, cmask, lo, hi,
                    b_val=spec.b_val, min_rows=self.min_rows, msi=self.msi,
                    lam=self.lam, use_hess=self.use_hess, any_cat=any_cat,
                    nb=None if pl is None else self.nb_dev)

            with jax.named_scope("tree.level.nodes"):
                did = s["did"]
                did_prev = did
                ids = jnp.arange(L)
                tgt = base + ids
                colA = colA.at[tgt].set(jnp.where(did, s["col"], -1))
                binA = binA.at[tgt].set(jnp.where(did, s["bin"], -1))
                nalA = nalA.at[tgt].set(s["nal"])
                routeA = routeA.at[tgt].set(s["route"])
                valA = valA.at[tgt].set(s["val_t"])
                coverA = coverA.at[tgt].set(s["w_t"])
                kidL = jnp.where(did, 2 * tgt + 1, self.nodes)
                kidR = jnp.where(did, 2 * tgt + 2, self.nodes)
                valA = valA.at[kidL].set(s["val_l"], mode="drop")
                valA = valA.at[kidR].set(s["val_r"], mode="drop")
                coverA = coverA.at[kidL].set(s["w_l"], mode="drop")
                coverA = coverA.at[kidR].set(s["w_t"] - s["w_l"], mode="drop")
                gains = gains.at[jnp.where(did, s["col"], C)].add(s["gain"])

                # ---- routing tables for the next level -------------------
                Lp = max(8, L)
                tbl = jnp.zeros((8, Lp), jnp.float32)
                if pl is None:
                    kcol, kroute = s["col"].astype(jnp.float32), s["route"]
                else:
                    # the kernels route by the split column's first plane
                    # and a route row laid out plane by plane
                    kcol = self.first_dev[s["col"]]
                    kroute = jnp.take_along_axis(
                        jnp.pad(s["route"], ((0, 0), (0, 1))),
                        self.route_dst[s["col"]], axis=1)
                tbl = tbl.at[0, :L].set(kcol)
                tbl = tbl.at[1, :L].set(did.astype(jnp.float32))
                tbl = tbl.at[2, :L].set(s["bin"].astype(jnp.float32))
                tbl = tbl.at[3, :L].set(s["nal"].astype(jnp.float32))
                route_f = jnp.zeros((Lp, P * BP), jnp.float32)
                route_f = route_f.at[:L].set(kroute.astype(jnp.float32))
                prev = dict(tbl=tbl, route_f=route_f)

                # ---- monotone bounds for children ------------------------
                mc = self.mono[s["col"]]
                mid = 0.5 * (s["val_l"] + s["val_r"])
                lo_l = jnp.where(mc < 0, jnp.maximum(lo, mid), lo)
                hi_l = jnp.where(mc > 0, jnp.minimum(hi, mid), hi)
                lo_r = jnp.where(mc > 0, jnp.maximum(lo, mid), lo)
                hi_r = jnp.where(mc < 0, jnp.minimum(hi, mid), hi)
                lo = jnp.stack([jnp.where(did, lo_l, lo),
                                jnp.where(did, lo_r, lo)], 1).reshape(2 * L)
                hi = jnp.stack([jnp.where(did, hi_l, hi),
                                jnp.where(did, hi_r, hi)], 1).reshape(2 * L)

        # terminal pass: route the last level + fused F update
        L = 1 << D
        with jax.named_scope("tree.margin"):
            valt = jnp.clip(valA, -clip_val, clip_val) if clip_val else valA
            valtab = jnp.zeros((8, nodes_p), jnp.float32) \
                .at[0, : self.nodes].set(valt)
            heap, F = HP.sbh_route(codes, heap, prev["tbl"],
                                   prev["route_f"], valtab, F,
                                   base=(L >> 1) - 1, L=L >> 1, eta=eta,
                                   emit_f=True, any_cat=any_cat,
                                   na_code=spec.b_val, planes=P)
        return dict(col=colA, bin=binA, nal=nalA, route=routeA, val=valt,
                    cover=coverA, gains=gains[:C], F=F, heap=heap)


# ===========================================================================
# Chunked boosting driver: ONE dispatch trains K trees (lax.scan); the host
# only sees tree arrays + updated margins between chunks (scoring / early
# stopping cadence — SharedTree.doScoringAndSaveModel analog).
def _grad_hess_binned(dist, F, y):
    """ComputePredAndRes on the padded margin vector (GBM.java:981)."""
    if dist == "gaussian":
        return y - F, jnp.ones_like(F)
    if dist in ("bernoulli", "quasibinomial"):
        p = jax.nn.sigmoid(F)
        return y - p, p * (1 - p)
    if dist == "poisson":
        mu = jnp.exp(jnp.clip(F, -30, 30))
        return y - mu, mu
    if dist == "gamma":
        mu = jnp.exp(jnp.clip(F, -30, 30))
        return y / mu - 1.0, y / mu
    if dist == "tweedie":
        mu = jnp.exp(jnp.clip(F, -30, 30))
        rmu = jnp.sqrt(mu)
        return y / rmu - rmu, 0.5 * (y / rmu + rmu)
    if dist == "laplace":
        return jnp.sign(y - F), jnp.ones_like(F)
    raise NotImplementedError(f"binned engine distribution {dist}")


def pack_route(route, n_bins, b_val=None):
    """(nodes, BP) bool -> (nodes, BP//32) uint32 bitset (IcedBitSet analog,
    water/util/IcedBitSet.java). With b_val given, slots >= b_val-1 replicate
    slot b_val-1 so float-scoring code clipping of high-cardinality
    categorical levels routes like training's capped codes (the NA slot is
    never consulted by the scorer — NaN takes the nal path first)."""
    nodes = route.shape[0]
    r = route[:, :n_bins]
    if b_val is not None and b_val < n_bins:
        r = jnp.concatenate(
            [r[:, : b_val - 1],
             jnp.broadcast_to(r[:, b_val - 1: b_val],
                              (nodes, n_bins - b_val + 1))], axis=1)
    r = r.reshape(nodes, n_bins // 32, 32)
    return (r.astype(jnp.uint32) <<
            jnp.arange(32, dtype=jnp.uint32)[None, None, :]).sum(
        -1, dtype=jnp.uint32)


def _memo_trainer(grower: BinnedGrower, cache_key, build_run, mesh,
                  in_specs, out_specs):
    """Shared trainer finalization: memoize the jitted program on the
    grower INSTANCE (a global id()-keyed cache can hand a recycled id a
    stale closure over another grower's bin edges), shard_map over the
    rows axis when a mesh is given. One definition so an in/out-spec or
    check_vma change cannot silently diverge across the three trainers."""
    cache = getattr(grower, "_trainer_cache", None)
    if cache is None:
        cache = grower._trainer_cache = {}
    fn = cache.get(cache_key)
    if fn is not None:
        return fn
    run = build_run()
    if mesh is not None:
        if grower.axis_name is None:
            raise ValueError("mesh given but grower has no axis_name")
        fn = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
    else:
        fn = jax.jit(run)
    cache[cache_key] = fn
    return fn


def _tree_col_mask(grower: BinnedGrower, key, col_rate_tree: float):
    """Per-tree column subset (col_sample_rate_per_tree): common key across
    shards so every shard draws the SAME mask. Returns None when disabled."""
    if col_rate_tree >= 1.0:
        return None
    c_real = int(grower.spec.is_cat.size)
    C = grower.spec.c_pad
    k = max(1, int(round(col_rate_tree * c_real)))
    r = jax.random.uniform(key, (C,))
    r = jnp.where(jnp.arange(C) < c_real, r, 2.0)
    kth = jnp.sort(r)[k - 1]
    return r <= kth


def gbm_chunk_trainer(grower: BinnedGrower, n: int, *, dist: str, eta: float,
                      sample_rate: float, mtries: int, k_trees: int,
                      clip_val: float = 19.0, col_rate_tree: float = 1.0,
                      mesh=None):
    """Build (and cache) the jitted K-tree training program.

    Contract: codes from `quantize` (uint8 (C_pad, n_pad)) run through
    `prepare_codes` (the packed i32 plane on the Pallas backend; n real
    rows, the rest dummies); y1/w1/F are (n_pad,) f32 with zeros beyond
    row n. Returns (new F, stacked tree arrays) per call.

    With `mesh` given (and grower.axis_name set) the program is shard_mapped
    over the rows axis: codes/y1/w1/F are row-sharded, each shard grows the
    tree on its local rows, and grow()'s per-level psum merges histograms —
    the MRTask reduce tree (MRTask.java:907-921) as ONE ICI collective per
    level. Split search and the tree arrays are replicated by construction
    (identical on every shard given the global histograms).
    """
    from jax.sharding import PartitionSpec as P
    axis = grower.axis_name if mesh is not None else None
    key_ = (n, dist, eta, sample_rate, mtries, k_trees, clip_val,
            col_rate_tree, axis, id(mesh) if mesh is not None else 0)

    gaussian = dist == "gaussian"
    cv = 0.0 if gaussian else clip_val

    # NOTE: keep the inner function literally named `run` — the persistent
    # XLA compile cache keys include the jitted function name, and a
    # rename would cold-compile the big K-tree program in every deployment
    def build():
        def run(codes, y1, w1, F, key):
            def per_tree(carry, k):
                F, key = carry
                key, ks, kt = jax.random.split(key, 3)
                if axis:
                    # decorrelate row sampling across shards; the mtries key
                    # kt stays common so every shard draws the SAME col masks
                    ks = jax.random.fold_in(ks, lax.axis_index(axis))
                with jax.named_scope("tree.grad"):
                    g, h = _grad_hess_binned(dist, F, y1)
                with jax.named_scope("tree.sample"):
                    if sample_rate < 1.0:
                        u = jax.random.uniform(ks, w1.shape)
                        wt = w1 * (u < sample_rate)
                    else:
                        wt = w1
                    stats = jnp.stack(
                        [wt, wt * g, wt * h, jnp.zeros_like(wt)], axis=0)
                    tmask = _tree_col_mask(
                        grower, jax.random.fold_in(kt, 7), col_rate_tree)
                out = grower.grow(codes, stats, F, eta=eta, clip_val=cv,
                                  key=kt, mtries=mtries, tree_mask=tmask)
                F = out["F"]
                with jax.named_scope("tree.pack"):
                    tree = (out["col"], out["bin"], out["nal"],
                            grower.sets(out),
                            out["val"], out["gains"], out["cover"])
                return (F, key), tree

            (F, _), trees = lax.scan(per_tree, (F, key),
                                     jnp.arange(k_trees))
            return F, trees
        return run

    return _memo_trainer(
        grower, key_, build, mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P()))


# ===========================================================================
# Multinomial boosting: K class trees per iteration through the binned
# engine (SharedTree.java:548-561 builds the K trees of an iteration as one
# fused layer; here a lax.scan over classes inside ONE jitted program —
# codes stay device-resident, each class tree rides every binned
# optimization incl. the histogram psum and int8 stats).
def gbm_multi_chunk_trainer(grower: BinnedGrower, n: int, *, n_classes: int,
                            eta: float, sample_rate: float, mtries: int,
                            k_iters: int, clip_val: float = 19.0,
                            col_rate_tree: float = 1.0, mesh=None):
    """K-class K-tree-per-iteration program. F is (n_pad, K) margins;
    y1 is (n_pad,) class ids (f32); returns (F, stacked trees with leading
    dims (k_iters, K, ...))."""
    from jax.sharding import PartitionSpec as P
    axis = grower.axis_name if mesh is not None else None
    key_ = ("multi", n, n_classes, eta, sample_rate, mtries, k_iters,
            clip_val, col_rate_tree, axis, id(mesh) if mesh is not None else 0)

    K = int(n_classes)
    kscale = (K - 1) / K       # GammaPass multinomial leaf scale (GBM.java)

    def build():
        def run(codes, y1, w1, F, key):
            onehot = jax.nn.one_hot(y1.astype(jnp.int32), K)   # (n_pad, K)

            def per_iter(carry, it):
                F, key = carry
                key, ks, kt = jax.random.split(key, 3)
                if axis:
                    ks = jax.random.fold_in(ks, lax.axis_index(axis))
                probs = jax.nn.softmax(F, axis=1)
                RK = onehot - probs                            # residuals
                if sample_rate < 1.0:
                    u = jax.random.uniform(ks, w1.shape)
                    wt = w1 * (u < sample_rate)
                else:
                    wt = w1
                tmask = _tree_col_mask(grower, jax.random.fold_in(kt, 7),
                                       col_rate_tree)

                def per_class(_, k):
                    res = jnp.take_along_axis(RK, k[None, None], 1)[:, 0]
                    absr = jnp.abs(res)
                    hess = absr * (1.0 - absr)   # |res|(1-|res|) GammaPass
                    stats = jnp.stack([wt, wt * res * kscale, wt * hess,
                                       jnp.zeros_like(wt)], axis=0)
                    out = grower.grow(codes, stats, jnp.zeros_like(wt),
                                      eta=1.0, clip_val=clip_val,
                                      key=jax.random.fold_in(kt, k),
                                      mtries=mtries, tree_mask=tmask)
                    tree = (out["col"], out["bin"], out["nal"],
                            grower.sets(out),
                            out["val"], out["gains"], out["cover"])
                    return None, (tree, out["F"])  # F==val[heap]: row pred

                _, (trees, dF) = lax.scan(per_class, None, jnp.arange(K))
                F = F + eta * dF.T                              # (n_pad, K)
                return (F, key), trees

            (F, _), trees = lax.scan(per_iter, (F, key),
                                     jnp.arange(k_iters))
            return F, trees
        return run

    return _memo_trainer(
        grower, key_, build, mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P()))


# ===========================================================================
# DRF: independent trees, leaf = in-bag response mean, OOB accumulation
# (hex/tree/drf/DRF.java:78 doOOBScoring()=true — the reference default).
def drf_chunk_trainer(grower: BinnedGrower, n: int, *, sample_rate: float,
                      mtries: int, k_trees: int, col_rate_tree: float = 1.0,
                      mesh=None):
    """Per tree: Bernoulli(sample_rate) in-bag mask; stats (w, w*y, w) so
    the Newton leaf value wg/wh is exactly the in-bag mean response (class
    frequency for 0/1 targets — ScoreBuildHistogram response-mean leaves);
    grow() with F=0, eta=1 returns per-row leaf values, accumulated into
    (oob_sum, oob_cnt) on OOB rows only. Returns (oob_sum, oob_cnt, trees)."""
    from jax.sharding import PartitionSpec as P
    axis = grower.axis_name if mesh is not None else None
    key_ = ("drf", n, sample_rate, mtries, k_trees, col_rate_tree, axis,
            id(mesh) if mesh is not None else 0)

    def build():
        def run(codes, y1, w1, oob_sum, oob_cnt, key):
            def per_tree(carry, t):
                oob_sum, oob_cnt, key = carry
                key, ks, kt = jax.random.split(key, 3)
                if axis:
                    ks = jax.random.fold_in(ks, lax.axis_index(axis))
                u = jax.random.uniform(ks, w1.shape)
                inbag = u < sample_rate
                wt = w1 * inbag
                stats = jnp.stack([wt, wt * y1, wt, jnp.zeros_like(wt)],
                                  axis=0)
                tmask = _tree_col_mask(grower, jax.random.fold_in(kt, 7),
                                       col_rate_tree)
                out = grower.grow(codes, stats, jnp.zeros_like(wt),
                                  eta=1.0, clip_val=0.0,
                                  key=kt, mtries=mtries, tree_mask=tmask)
                pred = out["F"]                       # per-row leaf value
                oob = (~inbag) & (w1 > 0)
                oob_sum = oob_sum + jnp.where(oob, pred, 0.0)
                oob_cnt = oob_cnt + oob.astype(jnp.float32)
                tree = (out["col"], out["bin"], out["nal"],
                        grower.sets(out),
                        out["val"], out["gains"], out["cover"])
                return (oob_sum, oob_cnt, key), tree

            (oob_sum, oob_cnt, _), trees = lax.scan(
                per_tree, (oob_sum, oob_cnt, key), jnp.arange(k_trees))
            return oob_sum, oob_cnt, trees
        return run

    return _memo_trainer(
        grower, key_, build, mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis), P()))
