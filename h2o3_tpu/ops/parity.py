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


def _rand_inputs(seed, L):
    """Random uint8 codes incl. NA codes + their packed plane + heap
    spread over [base, base+L) + bf16-representable f32 stats."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B_VAL, (C_PAD, N_PAD)).astype(np.uint8)
    codes[rng.random((C_PAD, N_PAD)) < 0.05] = B_VAL          # NA code
    base = L - 1
    heap = rng.integers(base, base + L, N_PAD).astype(np.int32)
    stats = rng.normal(0, 1, (HP.S_STATS, N_PAD)).astype(np.float32)
    stats[3] = 0.0
    stats = jnp.asarray(stats).astype(jnp.bfloat16).astype(jnp.float32)
    u8 = jnp.asarray(codes)
    return u8, HP.pack_codes(u8), jnp.asarray(heap), stats, base


def _route_tables(rng, L):
    """Random split tables incl. categorical SET routing + NA dir. The
    pallas numeric fast path reads tbl rows 2/3 while the xla fallback
    always reads route_f — route_num is built consistent with both."""
    Lp = max(8, L)
    tbl = np.zeros((8, Lp), np.float32)
    tbl[0, :L] = rng.integers(0, C_PAD, L)
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
            # heaps are exact; f32 within `tol`
            assert d == 0 if exact else d < tol, (tag, k, d)
    return devs


def _add_hist(cases, seed):
    """Dense histogram at every (L, window)."""
    for L in LEVELS:
        u8, packed, heap, stats, base = _rand_inputs(seed + L, L)
        for half in (False, True):
            l_eff = (L + 1) // 2 if half else L
            kw = dict(base=base, L=L, n_bins=N_BINS, half=half)

            def cut(h, l_eff=l_eff):
                return h[:l_eff, :C_PAD]
            cases.append((
                f"hist_L={L}_half={half}",
                lambda c, h, s, kw=kw, cut=cut: cut(
                    HP.sbh_hist_pallas(c, h, s, **kw)),
                lambda c, h, s, kw=kw, cut=cut: cut(
                    HP.sbh_hist_xla(c, h, s, **kw)),
                (packed, heap, stats), (u8, heap, stats), HIST_TOL))


def _add_route(cases, seed):
    """Non-terminal (heap only) at every L, numeric threshold fast path
    and categorical SET tables; terminal (heap + fused F update — the
    same code for both table kinds) at the last level."""
    for L in LEVELS:
        u8, packed, heap, _, base = _rand_inputs(seed + 10 + L, L)
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


def _add_fused(cases, seed, specs):
    """Level-fused route+hist vs the sequential XLA pair (the exact
    grow() level-d contract: route [base_r, base_r+L_r) then half-hist
    [base_h, base_h+L_h)). `specs` = (L_h, any_cat)."""
    for L_h, any_cat in specs:
        L_r = L_h >> 1
        base_r, base_h = L_r - 1, L_h - 1
        l_eff = (L_h + 1) // 2
        u8, packed, heap, stats, _ = _rand_inputs(seed + 30 + L_h, L_r)
        rng = np.random.default_rng(seed + 40 + L_h)
        tbl, route_cat, route_num = _route_tables(rng, L_r)
        route_f = route_cat if any_cat else route_num

        def fused(c, h, t, r, s, L_r=L_r, base_r=base_r, base_h=base_h,
                  L_h=L_h, any_cat=any_cat, l_eff=l_eff):
            nh, hist = HP.sbh_route_hist_fused_pallas(
                c, h, t, r, s, base_r=base_r, L_r=L_r, base_h=base_h,
                L_h=L_h, n_bins=N_BINS, any_cat=any_cat, na_code=B_VAL)
            return nh, hist[:l_eff, :C_PAD]

        def pair(c, h, t, r, s, L_r=L_r, base_r=base_r, base_h=base_h,
                 L_h=L_h, any_cat=any_cat, l_eff=l_eff):
            nh, _ = HP.sbh_route_xla(c, h, t, r, base=base_r, L=L_r,
                                     any_cat=any_cat, na_code=B_VAL)
            return nh, HP.sbh_hist_xla(c, nh, s, base=base_h, L=L_h,
                                       n_bins=N_BINS, half=True)[:l_eff]
        cases.append((f"fused_L={L_h}_cat={any_cat}",
                      fused, pair, (packed, heap, tbl, route_f, stats),
                      (u8, heap, tbl, route_f, stats), HIST_TOL))


def _add_planes(cases, seed, planes=2):
    """A split column whose code lies in one of `planes` byte columns
    (models/tree/binned.py `Planes`: a categorical column past a code
    byte): the terminal route and the fused route+hist with a route row a
    plane, random bits in every plane's row — the kernels sum the planes'
    bits, the twin ors them."""
    L_h = 16
    L_r = L_h >> 1
    base_r, base_h, l_eff = L_r - 1, L_h - 1, L_h >> 1
    u8, packed, heap, stats, _ = _rand_inputs(seed + 50, L_r)
    rng = np.random.default_rng(seed + 51)
    tbl, _, _ = _route_tables(rng, L_r)
    tbl = tbl.at[0].set(jnp.minimum(tbl[0], C_PAD - planes))
    route = jnp.asarray((rng.random((max(8, L_r), planes * N_BINS)) < 0.4)
                        .astype(np.float32))
    nodes_p = -(-(2 * (base_r + L_r) + 1) // 128) * 128
    valtab = jnp.asarray(np.concatenate(
        [rng.normal(0, 1, (1, nodes_p)),
         np.zeros((7, nodes_p))]).astype(np.float32))
    F = jnp.asarray(rng.normal(0, 1, N_PAD).astype(np.float32))
    kw = dict(base=base_r, L=L_r, any_cat=True, na_code=B_VAL, eta=0.1,
              emit_f=True, planes=planes)
    cases.append((
        f"route_planes={planes}_terminal",
        lambda c, h, t, r, v, f: HP.sbh_route_pallas(c, h, t, r, v, f, **kw),
        lambda c, h, t, r, v, f: HP.sbh_route_xla(c, h, t, r, v, f, **kw),
        (packed, heap, tbl, route, valtab, F),
        (u8, heap, tbl, route, valtab, F), 1e-5))

    def fused(c, h, t, r, s):
        nh, hist = HP.sbh_route_hist_fused_pallas(
            c, h, t, r, s, base_r=base_r, L_r=L_r, base_h=base_h, L_h=L_h,
            n_bins=N_BINS, any_cat=True, na_code=B_VAL, planes=planes)
        return nh, hist[:l_eff, :C_PAD]

    def pair(c, h, t, r, s):
        nh, _ = HP.sbh_route_xla(c, h, t, r, base=base_r, L=L_r,
                                 any_cat=True, na_code=B_VAL, planes=planes)
        return nh, HP.sbh_hist_xla(c, nh, s, base=base_h, L=L_h,
                                   n_bins=N_BINS, half=True)[:l_eff]
    cases.append((f"fused_planes={planes}_L={L_h}", fused, pair,
                  (packed, heap, tbl, route, stats),
                  (u8, heap, tbl, route, stats), HIST_TOL))


def fusable_levels(c_pack=C_PAD, n_bins=N_BINS):
    """Every L_h of a depth<=10 tree the fused shape rule admits."""
    return [1 << d for d in range(1, 11)
            if HP._fused_applicable(1 << d, n_bins, c_pack)]


def kernel_parity_check(seed=0):
    """Assert pallas == xla at HIGGS width for everything the DEFAULT
    rules select: hist (full + half), route (with and without the F
    stream, numeric and categorical), the level-fused route+hist at
    every level its rule admits, and both routes over a column of two
    byte planes. Returns a dict of max deviations."""
    devs = {}
    # one program pair per family: a refusal names its family, and each
    # family's Mosaic kernels still compile in parallel
    for add in (_add_hist, _add_route, functools.partial(
            _add_fused, specs=[(L_h, False) for L_h in fusable_levels()]
            + [(LEVELS[1], True)]), _add_planes):
        cases = []
        add(cases, seed)
        devs.update(_run_cases(cases))
    return devs
