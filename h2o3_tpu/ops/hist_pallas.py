"""Pallas TPU kernels for the binned tree engine — the rebuild of H2O's
ScoreBuildHistogram2 hot loop (SURVEY §2.4 row 1).

Reference semantics: hex/tree/ScoreBuildHistogram2.java:20-60 — ONE fused
pass per level that (phase 1) routes each row to its current leaf by applying
the previous level's split decisions and (phase 2) accumulates per-
(leaf, column) histograms of {w, wY, wYY} over binned rows
(DHistogram.java:59-70, :338). The reference avoids CAS by giving each
(column, row-range) task a private histogram copy merged in reduce.

TPU-native design (measured on v5e): random gathers/scatters run at only
~50-100M elem/s on TPU, so the engine NEVER physically reorders rows
(an explicit leaf-partition + gather design measured ~10x slower than the
kernels it fed). Rows stay in original order; per-row state is ONE int32
`heap` (node id in the 2^(D+1)-1 heap; a row whose node did not split keeps
its heap id and freezes). Codes are stored COLUMN-major — the natural
layout for both kernels (rows ride the 128-wide lane dimension).

CODE PLANES (round 4): bins are <= 255+NA so a code needs ONE byte, and the
HBM code stream at 150-200 GB/s effective is the measured per-level
bandwidth floor (ops/PERF_NOTES.md). The binner therefore emits codes as
uint8 (C_pad, n_pad); for the TPU kernels `pack_codes` packs FOUR uint8
codes per int32 word along the COLUMN axis into a (W_pad, n_pad) i32
"packed plane" — 1 byte/code in HBM (4x less code traffic than the old i32
planes) while every Pallas block stays an i32 tile that satisfies Mosaic's
sublane granule (a raw uint8 (8, R) block would violate the (32, 128) int8
tile; the i32 word is the legal carrier and bytes are extracted INSIDE the
kernel tile, never widened in HBM). The XLA fallbacks (CPU tests, exotic
backends) consume the uint8 plane directly — dtype-agnostic segment sums,
bit-identical to the old i32 planes.

Kernels per level:

  * sbh_route — phase 1. Applies the previous level's splits: the per-leaf
    split metadata lives in small VMEM tables and every per-row lookup is a
    one-hot matmul / compare-select (there is no vector gather on TPU).
    The split column's code comes from a word compare-select over the
    packed plane's sublanes plus a per-lane variable shift (byte extract).
    The full (numeric threshold / categorical SET / NA direction) decision
    is precompiled by the split search into a per-leaf
    `route[leaf, code] -> goes-right` table, so the kernel is decision-
    agnostic. Non-terminal levels no longer stream F through the kernel
    (8 bytes/row/level saved); the terminal pass fuses the margin update
    F += eta*val[heap] (ComputePredAndRes's gather folded into the stream).

  * sbh_hist — phase 2. Grid (pass, word-block, row-tile); output block
    (32 cols, gwe*S lanes, nb bins) stays VMEM-resident across the whole
    row sweep (the grouped-matmul revisiting pattern) and accumulates
    onehot(codes) @ A where A packs (leaf-slot x {w,wg,wh}) MXU lanes.
    No CAS, no private copies, no reduce tree: cross-shard merging is one
    psum over the mesh row axis by the caller.

  * sbh_route_hist — the LEVEL-FUSED pass (PERF_NOTES item 4, the
    ScoreBuildHistogram2 shape itself): ONE kernel reads the code tile
    once, routes the rows, and accumulates the histogram over the UPDATED
    heap — halving code traffic again at the shallow levels where the
    histogram is bandwidth-floor (not dot) bound. On wherever the
    whole-level histogram fits VMEM (`_fused_applicable`, a shape rule);
    the unfused route+hist pair serves the deeper levels and is the XLA
    path.

One kernel per regime, selected by SHAPE RULE only (`is_packed`,
`_fused_applicable`) — no option picks a kernel. There is one
installation; what its Mosaic compiles at the widths users train at is
pinned by tests/test_chip_compile.py, and a kernel the compiler refuses
raises — it never degrades to a slower path.

Stats panel rows (S_STATS=4): 0=w, 1=w*grad, 2=w*hess, 3=spare(0) —
(w, wg, wh) feed split gain, min_rows and Newton leaf values
(hex/tree/DHistogram.java _vals packing analog).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o3_tpu.obs import metrics as _om

# Bumped while a Pallas entry point below is TRACED (its Python body runs
# once per distinct shape/static signature, inside whatever outer program
# is being built) — the evidence that a compiled tree program holds the
# TPU kernels. A backend that took the `_xla` twins leaves it at zero;
# model_summary's "engine" string says nothing either way.
KERNEL_TRACES = _om.counter(
    "h2o3_pallas_kernel_traces_total",
    "Pallas TPU kernels traced into device programs, by kernel and "
    "leaf-window L")


def kernel_traces() -> dict:
    """{(kernel, L): traces so far} — snapshot it around a build to see
    which kernels the selection rules put into the program."""
    return {(e["labels"]["kernel"], int(e["labels"]["L"])): int(e["value"])
            for e in KERNEL_TRACES._json()}

# Rows per kernel grid step. n_pad must be a multiple of this.
BLOCK_ROWS = 4096
# Stats panel sublane count.
S_STATS = 4
# Leaf-window width per histogram pass. 64 (not 128): the packed kernels
# sweep 32 columns per grid step, and a 128-leaf window's output block
# (32 x 512 x 256 f32) would blow the 16MB VMEM budget; 64 keeps the
# resident block at 8MB and only doubles npass at l_eff >= 128 — where
# the packed plane already cut the re-streamed code bytes 4x.
GW = 64
# Column tile of the LEGACY (unpacked) layout; kept for the XLA fallbacks'
# callers and the padded-column contract (c_pad is a COL_TILE multiple).
COL_TILE = 8
# uint8 codes per packed i32 word (column-axis packing).
PACK = 4
# Packed words per histogram grid step (PACK*WORD_TILE = 32 columns).
WORD_TILE = 8


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def is_packed(codes) -> bool:
    """True when `codes` is a packed i32 plane for the Pallas kernels (the
    TPU layout produced by pack_codes); uint8/int32-unpacked planes run
    the XLA fallbacks. The dtype IS the layout tag: prepare_codes only
    ever emits i32 on the Pallas backend."""
    return use_pallas() and codes.dtype == jnp.int32


# ===========================================================================
# Packed code planes
def packed_words(c_pad: int) -> int:
    """Words per packed plane for a c_pad-column code plane: ceil(C/4),
    padded to a WORD_TILE multiple once it exceeds one tile (sub-tile
    planes ride a single full-dim block, like the (S, R) stats panel)."""
    w = -(-c_pad // PACK)
    return w if w <= WORD_TILE else -(-w // WORD_TILE) * WORD_TILE


@jax.jit
def pack_codes(codes_u8):
    """(C_pad, n_pad) uint8 -> (W_pad, n_pad) int32 packed plane: little-
    endian bytes, 4 codes/word along the COLUMN axis (dummy columns pack
    as code 0 = zero-stat rows' bin). The row axis is untouched, so row
    sharding specs carry over unchanged.

    Built word by word from ROW slices of the uint8 plane: the obvious
    pad + reshape(W, 4, n) form re-tiles the whole plane as int32 and, at
    HIGGS size (32 x 11M), took the chip's compiler 64 s, 34 MB of code
    and 2.8 GB of temporaries; this form compiles in ~1.5 s with 0.26 GB
    (compile for a described v5e, tests/test_chip_compile.py)."""
    c_pad, n_pad = codes_u8.shape
    words = []
    for w in range(packed_words(c_pad)):
        acc = jnp.zeros(n_pad, jnp.int32)
        for k in range(PACK):
            c = w * PACK + k
            if c < c_pad:
                acc = acc | (codes_u8[c].astype(jnp.int32) << (8 * k))
        words.append(acc)
    return jnp.stack(words)


@functools.partial(jax.jit, static_argnames=("c_pad",))
def unpack_codes(packed, *, c_pad):
    """Inverse of pack_codes (tests + reference math)."""
    w_pad, n_pad = packed.shape
    parts = [(packed >> (8 * k)) & 255 for k in range(PACK)]
    u = jnp.stack(parts, axis=1).reshape(w_pad * PACK, n_pad)
    return u[:c_pad].astype(jnp.uint8)


def prepare_codes(codes_u8):
    """Backend-appropriate kernel layout for a quantized uint8 plane:
    packed i32 words on the Pallas backend, the uint8 plane itself (the
    XLA fallbacks' input) everywhere else."""
    if use_pallas():
        return pack_codes(codes_u8)
    return codes_u8


# ===========================================================================
# Shared kernel bodies (route math / stats panel / per-column accumulation)
# — one definition each so the standalone kernels and the fused kernel
# cannot drift semantically.
def _route_math(words, heap, tbl, route, *, base, L, n_bins, any_cat,
                na_code, planes=1):
    """New heap ids for one row tile. `words` is the loaded packed-plane
    tile (W_pad, R); `tbl`/`route` the loaded split tables. `planes` > 1:
    a split column's code lies in one of up to `planes` byte columns from
    tbl's column on (models/tree/binned.py `Planes`), `route` holds a
    (n_bins)-wide row for each of them, and the row's bit is the one that
    is set in any: a byte of 255 in a plane the code is not in, and every
    slot of a plane the leaf's column does not have, read 0."""
    R = heap.shape[0]
    leaf = heap - base
    active = (leaf >= 0) & (leaf < L)
    leaf_c = jnp.where(active, leaf, 0)
    # one-hot over the level's leaves — per-row table lookups are matmuls
    Lp = tbl.shape[1]
    iota_l = lax.broadcasted_iota(jnp.int32, (R, Lp), 1)
    active_f = active.astype(jnp.float32)
    ohl_f = ((iota_l == leaf_c[:, None]).astype(jnp.float32)
             * active_f[:, None])                             # (R, Lp) f32
    # props lookup stays f32 — and says so to Mosaic: its DEFAULT
    # contraction rounds f32 operands to bf16, which cannot represent col
    # ids > 256 or split bins > 256 exactly and would silently misroute
    # wide frames
    props = lax.dot_general(ohl_f, tbl,
                            (((1,), (1,)), ((), ())),
                            precision=lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (R, 8)
    did_r = props[:, 1] > 0.5
    # split column's code: word compare-select over the packed sublanes
    # (exact i32 sum — a one-hot f32 dot would round packed words > 2^24),
    # then a per-lane variable shift extracts the byte
    col_i = props[:, 0].astype(jnp.int32)
    w_pad = words.shape[0]
    iota_w = lax.broadcasted_iota(jnp.int32, (w_pad, R), 0)

    def code_of(col_i):
        wi = col_i >> 2
        shift = (col_i & 3) * 8
        wsel = jnp.sum(jnp.where(iota_w == wi[None, :], words, 0), axis=0)
        return ((wsel >> shift) & 255).astype(jnp.float32)    # (R,)

    code_sel = code_of(col_i)
    if any_cat:
        # goes-right bit via the full route table: route[leaf, code]
        def bit_of(code_sel, route):
            rowroute = lax.dot_general(
                ohl_f.astype(jnp.bfloat16), route.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (R, BP)
            iota_b = lax.broadcasted_iota(jnp.int32, (R, n_bins), 1) \
                .astype(jnp.float32)
            bsel = (iota_b == code_sel[:, None]).astype(jnp.float32)
            return jnp.sum(rowroute * bsel, axis=1)           # (R,)

        bit = bit_of(code_sel, route[:, :n_bins] if planes > 1 else route)
        for k in range(1, planes):
            bit = bit + bit_of(code_of(col_i + k),
                               route[:, k * n_bins:(k + 1) * n_bins])
        go = bit > 0.5
    else:
        # numeric-only fast path: threshold compare + NA direction from the
        # props table (rows 2 = split bin, 3 = na-goes-left). All-f32
        # arithmetic — Mosaic rejects mixed i1 selects here.
        bin_r = props[:, 2]
        nal_f = props[:, 3]
        isna_f = (code_sel == jnp.float32(na_code)).astype(jnp.float32)
        gt_f = (code_sel > bin_r).astype(jnp.float32)
        go = (isna_f * (1.0 - nal_f) + (1.0 - isna_f) * gt_f) > 0.5
    splits = active & did_r
    return jnp.where(splits, 2 * heap + 1 + go.astype(jnp.int32), heap)


def _stats_panel(heap, stats, *, base, L, gwe, p, half):
    """The (gwe*S_STATS, R) MXU lhs panel A: row (slot, s) holds stat s of
    rows whose leaf sits in window slot `slot` of pass `p`. With half=True
    only EVEN leaf indices (left children) are accumulated — window slot =
    leaf >> 1 — and the caller derives right children by sibling
    subtraction (parent minus left; the same trick xgboost/lightgbm use —
    valid because routing moves EVERY row of a split leaf to a child, so
    parent = left + right exactly)."""
    R = heap.shape[0]
    leaf = heap - base
    if half:
        slot = (leaf >> 1) - p * gwe
        inw = (leaf >= 0) & (leaf < L) & ((leaf & 1) == 0)
    else:
        slot = leaf - p * gwe
        inw = (leaf >= 0) & (leaf < L)
    inw = inw & (slot >= 0) & (slot < gwe)
    slot_c = jnp.where(inw, slot, 0)
    iota_s = lax.broadcasted_iota(jnp.int32, (gwe, R), 0)
    inw_f = inw.astype(jnp.float32)
    ohs = ((iota_s == slot_c[None, :]).astype(jnp.float32)
           * inw_f[None, :])                                  # (gwe, R)
    return (ohs[:, None, :] * stats[None, :, :]) \
        .reshape(gwe * S_STATS, R).astype(jnp.bfloat16)


def _dense_parts(words, A, *, n_bins):
    """Per-column histogram dots for one packed-word tile: byte-extract
    each code INSIDE the tile (never widened in HBM), one-hot it, dot
    against the stats panel. Returns 4*W parts of (M, nb)."""
    R = words.shape[1]
    # one-hot built TRANSPOSED (nb, R): bins on sublanes, rows on
    # lanes. Measured 1.9x faster than the (R, nb) orientation — the
    # compare broadcast is a major-dim insert (free) instead of a
    # minor-dim layout change, and the dot contracts the rhs on dim 1.
    iota_b = lax.broadcasted_iota(jnp.int32, (n_bins, R), 0)
    parts = []
    for w in range(words.shape[0]):
        word = words[w, :]                                    # (R,) static w
        for k in range(PACK):
            code = (word >> (8 * k)) & 255
            ohT = (iota_b == code[None, :]).astype(jnp.bfloat16)
            h = lax.dot_general(A, ohT, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            parts.append(h)                                   # (M, nb)
    return parts


# ===========================================================================
# Phase 1: route rows by the previous level's splits
def _route_kernel(codesP_ref, heap_ref, tbl_ref, route_ref,
                  heap_out_ref, *, base, L, n_bins, any_cat, na_code,
                  planes=1):
    """Non-terminal route: heap update only — F is NOT streamed through
    the kernel (it is untouched between terminal passes)."""
    heap_out_ref[0, :] = _route_math(
        codesP_ref[...], heap_ref[0, :], tbl_ref[...], route_ref[...],
        base=base, L=L, n_bins=n_bins, any_cat=any_cat, na_code=na_code,
        planes=planes)


def _route_kernel_f(codesP_ref, heap_ref, tbl_ref, route_ref, valtab_ref,
                    f_ref, heap_out_ref, f_out_ref, *, base, L, n_bins,
                    eta, any_cat, na_code, planes=1):
    """Terminal route: heap update + fused margin update F += eta*val[heap]
    (ComputePredAndRes's gather folded into the same stream)."""
    R = f_ref.shape[1]
    newheap = _route_math(
        codesP_ref[...], heap_ref[0, :], tbl_ref[...], route_ref[...],
        base=base, L=L, n_bins=n_bins, any_cat=any_cat, na_code=na_code,
        planes=planes)
    heap_out_ref[0, :] = newheap
    nodes_p = valtab_ref.shape[1]
    iota_n = lax.broadcasted_iota(jnp.int32, (R, nodes_p), 1)
    # f32 one-hot x f32 table at fp32 contraction precision: leaf values
    # must reach F at full precision (scoring reads the same values as
    # f32). Mosaic's DEFAULT contraction rounds the table to bf16 — on the
    # chip every tree's margin update was off by up to 2^-9 of its leaf
    # value (ISSUE 22, first on-chip parity run: 7.7e-4 against 1e-5).
    ohn = (iota_n == newheap[:, None]).astype(jnp.float32)
    val_r = lax.dot_general(
        ohn, valtab_ref[...],
        (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:, 0]
    f_out_ref[0, :] = f_ref[0, :] + eta * val_r


@functools.partial(jax.jit,
                   static_argnames=("base", "L", "eta", "emit_f",
                                    "any_cat", "na_code", "planes"))
def sbh_route_pallas(codesP, heap, tbl, route_f, valtab=None, F=None, *,
                     base, L, eta=0.0, emit_f=False, any_cat=True,
                     na_code=255, planes=1):
    """codesP (W_pad, n_pad) i32 packed plane; heap (n_pad,) i32;
    tbl (8, Lp) f32 (row 0 = split col, 1 = did, 2 = split bin,
    3 = na-goes-left); route_f (Lp, planes * n_bins) f32 (1.0 = code goes
    right); valtab (8, NODES_P) f32 / F (n_pad,) f32 only with emit_f.
    Returns (newheap, newF) — newF is None when emit_f=False."""
    w_pad, n_pad = codesP.shape
    nblk = n_pad // BLOCK_ROWS
    n_bins = route_f.shape[1] // planes
    KERNEL_TRACES.inc(kernel="route_f" if emit_f else "route", L=str(L))

    def row():
        return pl.BlockSpec((1, BLOCK_ROWS), lambda j: (0, j))

    in_specs = [
        pl.BlockSpec((w_pad, BLOCK_ROWS), lambda j: (0, j)),
        row(),
        pl.BlockSpec(tbl.shape, lambda j: (0, 0)),
        pl.BlockSpec(route_f.shape, lambda j: (0, 0)),
    ]
    args = (codesP, heap.reshape(1, n_pad), tbl, route_f)
    heap_shape = jax.ShapeDtypeStruct((1, n_pad), jnp.int32)
    if emit_f:
        kernel = functools.partial(_route_kernel_f, base=base, L=L,
                                   n_bins=n_bins, eta=eta, any_cat=any_cat,
                                   na_code=na_code, planes=planes)
        in_specs += [pl.BlockSpec(valtab.shape, lambda j: (0, 0)), row()]
        args += (valtab, F.reshape(1, n_pad))
        out_specs = [row(), row()]
        out_shape = [heap_shape,
                     jax.ShapeDtypeStruct((1, n_pad), jnp.float32)]
    else:
        kernel = functools.partial(_route_kernel, base=base, L=L,
                                   n_bins=n_bins, any_cat=any_cat,
                                   na_code=na_code, planes=planes)
        out_specs, out_shape = row(), heap_shape
    out = pl.pallas_call(
        kernel,
        name="sbh_route_f" if emit_f else "sbh_route",
        grid=(nblk,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(*args)
    if emit_f:
        return out[0][0], out[1][0]
    return out[0], None


def sbh_route_xla(codesT, heap, tbl, route_f, valtab=None, F=None, *,
                  base, L, eta=0.0, emit_f=False, any_cat=True,
                  na_code=255, planes=1):
    """Pure-XLA fallback: same contract (CPU scatter/gather is fast).
    codesT is the UNPACKED (C_pad, n_pad) plane — uint8 or legacy i32;
    the integer arithmetic below is dtype-agnostic and bit-identical."""
    leaf = heap - base
    active = (leaf >= 0) & (leaf < L)
    leaf_c = jnp.where(active, leaf, 0)
    col_r = tbl[0, leaf_c].astype(jnp.int32)
    did_r = (tbl[1, leaf_c] > 0.5) & active
    code_sel = jnp.take_along_axis(
        codesT, jnp.clip(col_r, 0, codesT.shape[0] - 1)[None, :],
        axis=0)[0].astype(jnp.int32)
    n_bins = route_f.shape[1]
    go = route_f.reshape(-1)[leaf_c * n_bins + code_sel] > 0.5
    for k in range(1, planes):      # the column's further byte planes
        code_k = jnp.take_along_axis(
            codesT, jnp.clip(col_r + k, 0, codesT.shape[0] - 1)[None, :],
            axis=0)[0].astype(jnp.int32)
        go = go | (route_f.reshape(-1)[
            leaf_c * n_bins + k * (n_bins // planes) + code_k] > 0.5)
    splits = active & did_r
    newheap = jnp.where(splits, 2 * heap + 1 + go.astype(jnp.int32), heap)
    newF = F + eta * valtab[0, newheap] if emit_f else F
    return newheap, newF


def sbh_route(codes, heap, tbl, route_f, valtab=None, F=None, *, base, L,
              eta=0.0, emit_f=False, any_cat=True, na_code=255, planes=1):
    if is_packed(codes):
        return sbh_route_pallas(codes, heap, tbl, route_f, valtab, F,
                                base=base, L=L, eta=eta, emit_f=emit_f,
                                any_cat=any_cat, na_code=na_code,
                                planes=planes)
    return sbh_route_xla(codes, heap, tbl, route_f, valtab, F,
                         base=base, L=L, eta=eta, emit_f=emit_f,
                         any_cat=any_cat, na_code=na_code, planes=planes)


# ===========================================================================
# Phase 2: leaf-window histogram accumulation
def _hist_kernel(codesP_ref, heap_ref, stats_ref, out_ref, *, base, L,
                 n_bins, gwe, half):
    """Grid (pass, word-block, row-tile): accumulate the (4*W, gwe*S, nb)
    window block over the row sweep; gwe = min(l_eff, GW) leaves/pass."""
    p = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    A = _stats_panel(heap_ref[0, :], stats_ref[...], base=base, L=L,
                     gwe=gwe, p=p, half=half)
    parts = _dense_parts(codesP_ref[...], A, n_bins=n_bins)
    out_ref[...] = out_ref[...] + jnp.stack(parts)[None]


@functools.partial(jax.jit, static_argnames=("base", "L", "n_bins", "half"))
def sbh_hist_pallas(codesP, heap, stats, *, base, L, n_bins, half=False):
    """codesP (W_pad, n_pad) i32 packed plane; heap (n_pad,) i32;
    stats (S, n_pad) f32. Returns (L_pad, c_pack, S_STATS, n_bins) f32
    with L_pad = npass*gwe and c_pack = 4*W_pad:
    hist[l] = per-(col, stat, bin) sums over rows with heap == base + l
    (half=True: over rows with heap == base + 2l — left children only)."""
    KERNEL_TRACES.inc(kernel="hist", L=str(L))
    w_pad, n_pad = codesP.shape
    cw = min(w_pad, WORD_TILE)
    ncw = w_pad // cw
    cc = cw * PACK
    l_eff = (L + 1) // 2 if half else L
    gwe = min(l_eff, GW)
    npass = max(1, -(-l_eff // gwe))
    # VMEM budget: out (cc, gwe*S, nb) f32 + A (gwe*S, R) + ohT (nb, R);
    # at gwe*S = 256 the 8MB out block forces a narrower row tile
    r_blk = BLOCK_ROWS if gwe * S_STATS <= 128 else BLOCK_ROWS // 2
    nblk = n_pad // r_blk
    kernel = functools.partial(_hist_kernel, base=base, L=L, n_bins=n_bins,
                               gwe=gwe, half=half)
    out = pl.pallas_call(
        kernel,
        name="sbh_hist",
        grid=(npass, ncw, nblk),
        in_specs=[
            pl.BlockSpec((cw, r_blk), lambda p, g, j: (g, j)),
            pl.BlockSpec((1, r_blk), lambda p, g, j: (0, j)),
            pl.BlockSpec((S_STATS, r_blk), lambda p, g, j: (0, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, cc, gwe * S_STATS, n_bins),
            lambda p, g, j: (p * ncw + g, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (npass * ncw, cc, gwe * S_STATS, n_bins), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
    )(codesP, heap.reshape(1, n_pad), stats)
    # (npass*ncw, cc, gwe*S, nb) -> (L_pad, c_pack, S, nb)
    out = out.reshape(npass, ncw, cc, gwe, S_STATS, n_bins)
    return out.transpose(0, 3, 1, 2, 4, 5).reshape(
        npass * gwe, ncw * cc, S_STATS, n_bins)


@functools.partial(jax.jit, static_argnames=("base", "L", "n_bins", "half"))
def sbh_hist_xla(codesT, heap, stats, *, base, L, n_bins, half=False):
    """Pure-XLA fallback via segment-sum (CPU tests / non-TPU backends).
    codesT is the UNPACKED (C_pad, n_pad) plane — uint8 or legacy i32
    (bit-identical: the segment indices agree element-for-element).
    Jitted with static config: the lax.map below is a fresh-closure scan
    that would otherwise recompile on EVERY eager call (the per-level
    dispatch-count guard in tests/test_compile_guard.py watches this)."""
    c_pad, n_pad = codesT.shape
    l_eff = (L + 1) // 2 if half else L
    gwe = min(l_eff, GW)
    npass = max(1, -(-l_eff // gwe))
    L_pad = npass * gwe
    leaf = heap - base
    ok = (leaf >= 0) & (leaf < L)
    if half:
        ok = ok & ((leaf & 1) == 0)
        leaf = leaf >> 1
    lf = jnp.where(ok, leaf, L_pad)

    def one_col(c):
        idx = lf * n_bins + codesT[c].astype(jnp.int32)
        return jax.ops.segment_sum(stats.T, idx,
                                   num_segments=(L_pad + 1) * n_bins)

    hs = lax.map(one_col, jnp.arange(c_pad))       # (C, (L+1)*B, S)
    return hs.reshape(c_pad, L_pad + 1, n_bins, S_STATS)[:, :L_pad] \
             .transpose(1, 0, 3, 2)


def sbh_hist(codes, heap, stats, *, base, L, n_bins, half=False):
    if is_packed(codes):
        return sbh_hist_pallas(codes, heap, stats, base=base, L=L,
                               n_bins=n_bins, half=half)
    return sbh_hist_xla(codes, heap, stats, base=base, L=L, n_bins=n_bins,
                        half=half)


# ===========================================================================
# Level-fused route+hist (PERF_NOTES item 4 — the last big code-stream
# saving: route and hist were TWO full streams of the code plane per
# level; one kernel reads the tile once, updates the heap, and
# accumulates the histogram over the UPDATED heap).
#
# Applicability is VMEM-bound: the WHOLE level's histogram block
# (c_pack, l_eff*S, nb) must stay resident across the single row sweep
# (there is no col-block grid dimension — the route phase needs every
# column's words in the tile anyway). That caps fusion at shallow levels
# (l_eff <= FUSE_MAX_WINDOW), exactly where the histogram is bandwidth-
# floor bound and the saving is real; deep (dot-bound) levels keep the
# tiled unfused kernels.
FUSE_MAX_WINDOW = 16
_FUSE_VMEM_OUT = 6 * 2 ** 20


def _fused_applicable(L_h: int, n_bins: int, c_pack: int) -> bool:
    l_eff = (L_h + 1) // 2
    return (l_eff <= FUSE_MAX_WINDOW
            and c_pack * l_eff * S_STATS * n_bins * 4 <= _FUSE_VMEM_OUT)


def _fused_kernel(codesP_ref, heap_ref, tbl_ref, route_ref, stats_ref,
                  heap_out_ref, hist_ref, *, base_r, L_r, base_h, L_h,
                  n_bins, any_cat, na_code, gwe, planes=1):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    words = codesP_ref[...]                                   # (W_pad, R)
    newheap = _route_math(words, heap_ref[0, :], tbl_ref[...],
                          route_ref[...], base=base_r, L=L_r,
                          n_bins=n_bins, any_cat=any_cat, na_code=na_code,
                          planes=planes)
    heap_out_ref[0, :] = newheap
    # histogram over the UPDATED heap: left children of [base_h, base_h+L_h)
    A = _stats_panel(newheap, stats_ref[...], base=base_h, L=L_h, gwe=gwe,
                     p=0, half=True)
    parts = _dense_parts(words, A, n_bins=n_bins)
    hist_ref[...] = hist_ref[...] + jnp.stack(parts)


@functools.partial(jax.jit,
                   static_argnames=("base_r", "L_r", "base_h", "L_h",
                                    "n_bins", "any_cat", "na_code",
                                    "planes"))
def sbh_route_hist_fused_pallas(codesP, heap, tbl, route_f, stats, *,
                                base_r, L_r, base_h, L_h, n_bins,
                                any_cat=True, na_code=255, planes=1):
    """ONE kernel: route splits of [base_r, base_r+L_r), then accumulate
    the half (left-children) histogram of [base_h, base_h+L_h) over the
    updated heap. Returns (newheap, hist (l_eff, c_pack, S, n_bins))."""
    KERNEL_TRACES.inc(kernel="fused", L=str(L_h))
    w_pad, n_pad = codesP.shape
    c_pack = w_pad * PACK
    l_eff = (L_h + 1) // 2
    gwe = max(1, l_eff)
    nblk = n_pad // BLOCK_ROWS
    n_bins_rf = route_f.shape[1]
    assert n_bins_rf == planes * n_bins
    hist_shape = (c_pack, gwe * S_STATS, n_bins)
    kernel = functools.partial(_fused_kernel, base_r=base_r, L_r=L_r,
                               base_h=base_h, L_h=L_h, n_bins=n_bins,
                               any_cat=any_cat, na_code=na_code, gwe=gwe,
                               planes=planes)
    newheap, hist = pl.pallas_call(
        kernel,
        name="sbh_route_hist_fused",
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((w_pad, BLOCK_ROWS), lambda j: (0, j)),
            pl.BlockSpec((1, BLOCK_ROWS), lambda j: (0, j)),
            pl.BlockSpec(tbl.shape, lambda j: (0, 0)),
            pl.BlockSpec(route_f.shape, lambda j: (0, 0)),
            pl.BlockSpec((S_STATS, BLOCK_ROWS), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, BLOCK_ROWS), lambda j: (0, j)),
            pl.BlockSpec(hist_shape, lambda j: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct(hist_shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(codesP, heap.reshape(1, n_pad), tbl, route_f, stats)
    hist = hist.reshape(c_pack, gwe, S_STATS, n_bins).transpose(1, 0, 2, 3)
    return newheap[0], hist


def sbh_route_hist(codes, heap, tbl, route_f, stats, *, base_r, L_r,
                   base_h, L_h, n_bins, any_cat=True, na_code=255, planes=1):
    """Fused-or-sequential level pass: route the previous level's splits,
    then accumulate the new level's half (left-children) histogram over
    the updated heap. The fused Pallas program wherever the level
    qualifies (`_fused_applicable`); the sequential pair elsewhere — it
    is also the XLA/CPU path and is semantically identical (tier-1
    gated). Returns (newheap, hist)."""
    if (is_packed(codes)
            and _fused_applicable(L_h, n_bins, codes.shape[0] * PACK)):
        return sbh_route_hist_fused_pallas(
            codes, heap, tbl, route_f, stats, base_r=base_r, L_r=L_r,
            base_h=base_h, L_h=L_h, n_bins=n_bins, any_cat=any_cat,
            na_code=na_code, planes=planes)
    newheap, _ = sbh_route(codes, heap, tbl, route_f, base=base_r, L=L_r,
                           any_cat=any_cat, na_code=na_code, planes=planes)
    hist = sbh_hist(codes, newheap, stats, base=base_h, L=L_h,
                    n_bins=n_bins, half=True)
    return newheap, hist
