"""Kernel parity gate — the Pallas TPU kernels vs their XLA twins.

The CPU test suite only exercises the `_xla` fallbacks (`use_pallas()` is
False off-TPU), so a misrouting Pallas kernel could ship behind a good
throughput number. `kernel_parity_check` runs the real kernels against the
fallbacks on random numeric + categorical + NA inputs and asserts
bit-tolerance — the analog of the reference's POJO/MOJO parity discipline
(h2o-py/tests/testdir_javapredict). It runs on the chip as a phase of
chip_smoke.py and as a bench.py pre-step.

Shapes are the ones users train at (HIGGS: 28 -> 32 padded columns -> 8
packed words, 255 value bins + NA in a 256-bin plane), at the first, a
middle and the last level of a depth-8 tree (L = 1, 8, 128). The Pallas
kernels consume PACKED code planes (4 uint8 codes per i32 word,
HP.pack_codes) while the XLA twins consume the uint8 plane — every check
therefore also proves the pack/extract round trip on-chip.

Every kernel the selection rules can pick is checked, with no gate in
front: a kernel this installation's compiler refuses FAILS the check.
`kernel_parity_check` covers what the default rules select at that width.
`optin_parity_check` covers the two opt-in families (int8 stats, radix)
at 16 columns: at HIGGS's 32 Mosaic refuses the radix kernel and every
int8 window below 64 leaves (VMEM; the first chip run of ISSUE 22 and the
same compile for a described chip) — which is why neither is on a default
path.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from h2o3_tpu.ops import hist_pallas as HP

C_PAD = 32          # HIGGS: 28 columns padded to the 8-word packed plane
B_VAL = 255         # value bins; NA code == B_VAL
N_BINS = 256
N_PAD = 4 * HP.BLOCK_ROWS
LEVELS = (1, 8, 128)
# bf16-exact stats make the f32-accumulating MXU dot and the f32
# segment-sum agree to summation order, so the bound is tight at any
# rows-per-bin (a 1e-2 bound only held at ~16 rows per bin)
HIST_TOL = 1e-3


def _rand_inputs(seed, L, c_pad=C_PAD, n_pad=N_PAD):
    """Random uint8 codes incl. NA codes + their packed plane + heap
    spread over [base, base+L) + bf16-representable f32 stats."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B_VAL, (c_pad, n_pad)).astype(np.uint8)
    codes[rng.random((c_pad, n_pad)) < 0.05] = B_VAL          # NA code
    base = L - 1
    heap = rng.integers(base, base + L, n_pad).astype(np.int32)
    stats = rng.normal(0, 1, (HP.S_STATS, n_pad)).astype(np.float32)
    stats[3] = 0.0
    stats = jnp.asarray(stats).astype(jnp.bfloat16).astype(jnp.float32)
    si = jnp.asarray(
        rng.integers(-127, 128, stats.shape).astype(np.int32))
    u8 = jnp.asarray(codes)
    return u8, HP.pack_codes(u8), jnp.asarray(heap), stats, si, base


def _route_tables(rng, L, c_pad=C_PAD):
    """Random split tables incl. categorical SET routing + NA dir. The
    pallas numeric fast path reads tbl rows 2/3 while the xla fallback
    always reads route_f — route_num is built consistent with both."""
    Lp = max(8, L)
    tbl = np.zeros((8, Lp), np.float32)
    tbl[0, :L] = rng.integers(0, c_pad, L)
    tbl[1, :L] = rng.random(L) < 0.8
    tbl[2, :L] = rng.integers(0, B_VAL - 1, L)       # numeric split bin
    tbl[3, :L] = rng.random(L) < 0.5                 # NA goes left
    route_cat = (rng.random((Lp, N_BINS)) < 0.5).astype(np.float32)
    route_num = np.zeros((Lp, N_BINS), np.float32)
    code_ids = np.arange(N_BINS)[None, :]
    route_num[:L] = (code_ids > tbl[2, :L, None]).astype(np.float32)
    route_num[:L, B_VAL] = 1.0 - tbl[3, :L]
    return jnp.asarray(tbl), jnp.asarray(route_cat), jnp.asarray(route_num)


@functools.partial(jax.jit, static_argnames=("fns",))
def _run_all(fns, argsets):
    """All of one side's instantiations as ONE program: the compiler
    builds a program's Mosaic kernels in parallel, which is most of what
    a cold check costs (five fused kernels: ~28 s together, ~17 s each
    alone)."""
    return [f(*a) for f, a in zip(fns, argsets)]


def _run_cases(cases) -> dict:
    """`cases` rows are (tag, pallas thunk, xla thunk, pallas args, xla
    args, f32 tolerance); both sides run as one program each, leaf by
    leaf compared. Returns {tag#leaf: max deviation}."""
    tags, pfns, xfns, pargs, xargs, tols = zip(*cases)
    got = _run_all(tuple(pfns), list(pargs))
    want = _run_all(tuple(xfns), list(xargs))
    devs = {}
    for tag, g, w, tol in zip(tags, got, want, tols):
        for k, (a, b) in enumerate(zip(jax.tree_util.tree_leaves(g),
                                       jax.tree_util.tree_leaves(w))):
            assert a.shape == b.shape, (tag, a.shape, b.shape)
            exact = jnp.issubdtype(a.dtype, jnp.integer)
            d = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
            devs[f"{tag}#{k}"] = d
            # heaps and i32 histograms are exact; f32 within `tol`
            assert d == 0 if exact else d < tol, (tag, k, d)
    return devs


def _add_hist(cases, seed, int8=False, c_pad=C_PAD, n_pad=N_PAD):
    """Dense histogram at every (L, window). int8 stats: only the (L,
    window) pairs grow() reaches (the full window only at the root)."""
    kernel = HP.sbh_hist_pallas_i8 if int8 else HP.sbh_hist_pallas
    for L in LEVELS:
        u8, packed, heap, stats, si, base = _rand_inputs(
            seed + L, L, c_pad=c_pad, n_pad=n_pad)
        st = si if int8 else stats
        for half in (False, True):
            if int8 and half != (L > 1):
                continue
            l_eff = (L + 1) // 2 if half else L
            kw = dict(base=base, L=L, n_bins=N_BINS, half=half)

            def cut(h, l_eff=l_eff):
                return h[:l_eff, :c_pad]
            cases.append((
                f"hist_L={L}_half={half}_i8={int8}",
                lambda c, h, s, kw=kw, cut=cut: cut(kernel(c, h, s, **kw)),
                lambda c, h, s, kw=kw, cut=cut: cut(
                    HP.sbh_hist_xla(c, h, s, **kw)),
                (packed, heap, st), (u8, heap, st), HIST_TOL))


def _add_route(cases, seed):
    """Non-terminal (heap only) at every L, numeric threshold fast path
    and categorical SET tables; terminal (heap + fused F update — the
    same code for both table kinds) at the last level."""
    for L in LEVELS:
        u8, packed, heap, _, _, base = _rand_inputs(seed + 10 + L, L)
        rng = np.random.default_rng(seed + 20 + L)
        tbl, route_cat, route_num = _route_tables(rng, L)
        for any_cat, route_f in ((False, route_num), (True, route_cat)):
            kw = dict(base=base, L=L, any_cat=any_cat, na_code=B_VAL)
            cases.append((
                f"route_L={L}_cat={any_cat}",
                lambda c, h, t, r, kw=kw: HP.sbh_route_pallas(
                    c, h, t, r, **kw)[0],
                lambda c, h, t, r, kw=kw: HP.sbh_route_xla(
                    c, h, t, r, **kw)[0],
                (packed, heap, tbl, route_f), (u8, heap, tbl, route_f), 0))
        if L != LEVELS[-1]:
            continue
        nodes_p = -(-(2 * (base + L) + 1) // 128) * 128
        valtab = jnp.asarray(np.concatenate(
            [rng.normal(0, 1, (1, nodes_p)),
             np.zeros((7, nodes_p))]).astype(np.float32))
        F = jnp.asarray(rng.normal(0, 1, N_PAD).astype(np.float32))
        kw = dict(base=base, L=L, any_cat=False, na_code=B_VAL, eta=0.1,
                  emit_f=True)
        cases.append((
            f"route_L={L}_terminal",
            lambda c, h, t, r, v, f, kw=kw: HP.sbh_route_pallas(
                c, h, t, r, v, f, **kw),
            lambda c, h, t, r, v, f, kw=kw: HP.sbh_route_xla(
                c, h, t, r, v, f, **kw),
            (packed, heap, tbl, route_num, valtab, F),
            (u8, heap, tbl, route_num, valtab, F), 1e-5))


def _add_fused(cases, seed, specs, c_pad=C_PAD, n_pad=N_PAD):
    """Level-fused route+hist vs the sequential XLA pair (the exact
    grow() level-d contract: route [base_r, base_r+L_r) then half-hist
    [base_h, base_h+L_h)). `specs` = (L_h, any_cat, int8, radix)."""
    for L_h, any_cat, int8, radix in specs:
        L_r = L_h >> 1
        base_r, base_h = L_r - 1, L_h - 1
        l_eff = (L_h + 1) // 2
        u8, packed, heap, stats, si, _ = _rand_inputs(
            seed + 30 + L_h, L_r, c_pad=c_pad, n_pad=n_pad)
        rng = np.random.default_rng(seed + 40 + L_h)
        tbl, route_cat, route_num = _route_tables(rng, L_r, c_pad=c_pad)
        route_f = route_cat if any_cat else route_num
        st = si if int8 else stats

        def fused(c, h, t, r, s, L_r=L_r, base_r=base_r, base_h=base_h,
                  L_h=L_h, any_cat=any_cat, int8=int8, radix=radix,
                  l_eff=l_eff):
            nh, hist = HP.sbh_route_hist_fused_pallas(
                c, h, t, r, s, base_r=base_r, L_r=L_r, base_h=base_h,
                L_h=L_h, n_bins=N_BINS, any_cat=any_cat, na_code=B_VAL,
                int8=int8, radix=radix)
            return nh, hist[:l_eff, :c_pad]

        def pair(c, h, t, r, s, L_r=L_r, base_r=base_r, base_h=base_h,
                 L_h=L_h, any_cat=any_cat, l_eff=l_eff):
            nh, _ = HP.sbh_route_xla(c, h, t, r, base=base_r, L=L_r,
                                     any_cat=any_cat, na_code=B_VAL)
            return nh, HP.sbh_hist_xla(c, nh, s, base=base_h, L=L_h,
                                       n_bins=N_BINS, half=True)[:l_eff]
        cases.append((f"fused_L={L_h}_cat={any_cat}_i8={int8}_radix={radix}",
                  fused, pair, (packed, heap, tbl, route_f, st),
                  (u8, heap, tbl, route_f, st), HIST_TOL))


def fused_levels(c_pack=C_PAD, n_bins=N_BINS):
    """Every L_h of a depth<=10 tree the fused shape rule admits."""
    return [1 << d for d in range(1, 11)
            if HP._fused_applicable(1 << d, n_bins, c_pack)]


def kernel_parity_check(seed=0):
    """Assert pallas == xla at HIGGS width for everything the DEFAULT
    rules select: hist (full + half), route (with and without the F
    stream, numeric and categorical) and the level-fused route+hist at
    every level its rule admits. Returns a dict of max deviations."""
    devs = {}
    # one program pair per family: a refusal names its family, and each
    # family's Mosaic kernels still compile in parallel
    for add in (_add_hist, _add_route, functools.partial(
            _add_fused, specs=[(L_h, False, False, False)
                               for L_h in fused_levels()]
            + [(LEVELS[1], True, False, False)])):
        cases = []
        add(cases, seed)
        devs.update(_run_cases(cases))
    return devs


def optin_parity_check(seed=0, c_pad=16):
    """The opt-in families, for the chip run that decides whether they
    live (ROADMAP D2): int8 stats (hist at the windows grow() reaches,
    fused) and radix at effective window 1 (full at the root, half at
    L=2; f32 + i8; standalone and fused). Radix at effective window 2 —
    (L=2, full), (L=4, half) — is left out: Mosaic refuses it at 16
    columns in f32 and even at 8 with int8 stats."""
    n_pad = 2 * HP.BLOCK_ROWS
    cases = []
    _add_hist(cases, seed, int8=True, c_pad=c_pad, n_pad=n_pad)
    for Lw, half in ((1, False), (2, True)):
        u8, packed, heap, stats, si, base = _rand_inputs(
            seed + 50 + Lw, Lw, c_pad=c_pad, n_pad=n_pad)
        kw = dict(base=base, L=Lw, n_bins=N_BINS, half=half)
        for int8, st in ((False, stats), (True, si)):
            cases.append((
                f"radix_L={Lw}_half={half}_i8={int8}",
                lambda c, h, s, kw=kw, int8=int8: HP.sbh_hist_radix(
                    c, h, s, int8=int8, **kw)[:1, :c_pad],
                lambda c, h, s, kw=kw: HP.sbh_hist_xla(c, h, s, **kw)[:1],
                (packed, heap, st), (u8, heap, st), HIST_TOL))
    _add_fused(cases, seed,
               [(LEVELS[1], True, True, False), (2, True, False, True),
                (2, True, True, True)], c_pad=c_pad, n_pad=n_pad)
    return _run_cases(cases)
