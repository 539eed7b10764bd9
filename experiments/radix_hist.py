"""Radix-factored shallow-level histogram kernel — PERF_NOTES item 1,
scoped to the regime the analysis says it can win (UNSORTED rows, small
leaf windows). The production kernel now lives in
h2o3_tpu/ops/hist_pallas.py (`sbh_hist_radix`, packed code planes); this
drive is the on-chip parity + timing harness for it.

Idea: at level windows L<=2 the dense kernel's cost floor is the 256-wide
one-hot generation (~210ms/level at 11M x 32). Factor code = hi*16+lo and
fuse leaf+hi into ONE joint key compare:

    key[r,c]  = leaf[r]*16 + hi[r,c]                  (i32, VPU)
    J[(l,hi),r] = (iota == key)                       (L*16-wide compare)
    A[(l,hi,s),r] = J ? stats[s,r] : 0                (select, L*16*S lanes)
    H[(l,hi,s),lo] = A @ onehot_lo.T                  ((L*16*S, R)@(R, 16))

VPU element-ops per (row, col): L*16 (compare) + L*16*S (select) + 16
(lo compare)  vs  dense 256 (compare) + L*S (select):
    L=1:  96 vs 260  (2.7x)     L=2: 176 vs 264  (1.5x)
    L=4: 336 vs 272  (worse)    -> use radix ONLY for L<=2, dense beyond.

Run on TPU:   python experiments/radix_hist.py          (parity + timings;
              prints ONE JSON line — blocked-structured off-chip)
Correctness:  python experiments/radix_hist.py --interpret
              (the factorization math vs the XLA reference, any backend —
              promoted into tier-1 as tests/test_binned_engine.py
              test_radix_factorization_math)
"""

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from h2o3_tpu.ops import hist_pallas as HP  # noqa: E402

NH = HP.RADIX_NH
S = HP.S_STATS
R = HP.BLOCK_ROWS


def radix_math(codes, heap, stats, *, base, L, nb):
    """Pure-jnp replica of the kernel's factorization (minus the pallas
    tiling) — pallas interpret mode is impractically slow at kernel
    shapes, so correctness splits into (a) this math check (tier-1) and
    (b) the on-TPU parity check in measure()/ops/parity.py."""
    c_pad, n_pad = codes.shape
    nl = nb // NH
    leaf = heap - base
    inw = (leaf >= 0) & (leaf < L)
    leaf_c = jnp.where(inw, leaf, L)
    outs = []
    for c in range(c_pad):
        code = codes[c].astype(jnp.int32)
        key = leaf_c * NH + code // nl
        lo = code % nl
        J = jax.nn.one_hot(key, L * NH, dtype=jnp.float32)      # (n, L*NH)
        A = (J[:, :, None] * stats.T[:, None, :]) \
            .reshape(n_pad, L * NH * S)
        ohlo = jax.nn.one_hot(lo, nl, dtype=jnp.float32)
        h = A.T @ ohlo                                          # (LNHS, nl)
        outs.append(h.reshape(L, NH, S, nl).transpose(0, 2, 1, 3)
                    .reshape(L, S, nb))
    return jnp.stack(outs, axis=1)                              # (L,C,S,nb)


def check_math(L=2, nb=256):
    rng = np.random.default_rng(0)
    n, c_pad = 4096, 8
    codes = jnp.asarray(rng.integers(0, nb, (c_pad, n)), jnp.uint8)
    base = L - 1
    heap = jnp.asarray(rng.integers(base, base + L + 1, n), jnp.int32)
    stats = jnp.asarray(rng.normal(0, 1, (S, n)), jnp.float32)
    got = radix_math(codes, heap, stats, base=base, L=L, nb=nb)
    want = HP.sbh_hist_xla(codes, heap, stats, base=base, L=L, n_bins=nb)
    d = float(jnp.max(jnp.abs(got - want[:L])))
    print(f"radix math L={L}: max dev {d:.5f}")
    assert d < 1e-2, d
    return d


def check_chip(n_pad=2 * R, L=2, nb=256):
    """On-chip parity: the packed radix kernel vs the XLA reference."""
    rng = np.random.default_rng(0)
    c_pad = 16
    u8 = jnp.asarray(rng.integers(0, nb, (c_pad, n_pad)), jnp.uint8)
    packed = HP.pack_codes(u8)
    base = L - 1
    heap = jnp.asarray(rng.integers(base, base + L, n_pad), jnp.int32)
    stats = jnp.asarray(rng.normal(0, 1, (S, n_pad)), jnp.float32)
    got = HP.sbh_hist_radix(packed, heap, stats, base=base, L=L, n_bins=nb)
    want = HP.sbh_hist_xla(u8, heap, stats, base=base, L=L, n_bins=nb)
    d = float(jnp.max(jnp.abs(got[:L, :c_pad] - want[:L])))
    print(f"radix L={L} max dev vs xla: {d:.4f}", file=sys.stderr)
    assert d < 0.5, d          # bf16 accumulation tolerance
    return d


def measure():
    """Per-window radix vs dense timings at the honest bench shape;
    returns the rows for the JSON record."""
    N = 11_000_000
    n_pad = -(-N // R) * R
    # 16 columns: the widest plane the radix kernel compiles at — at
    # HIGGS's 32 Mosaic refuses it (VMEM; tests/test_chip_compile.py)
    c_pad = 16
    rng = np.random.default_rng(0)
    u8 = jnp.asarray(rng.integers(0, 255, (c_pad, n_pad)), jnp.uint8)
    packed = HP.pack_codes(u8)
    stats = jnp.asarray(rng.normal(0, 1, (S, n_pad)), jnp.float32)
    rows = []
    for L in (1, 2, 4):
        base = L - 1
        heap = jnp.asarray(rng.integers(base, base + L, n_pad), jnp.int32)

        def timed(fn):
            jax.block_until_ready(fn())
            t0 = time.time()
            for _ in range(3):
                r = fn()
            jax.block_until_ready(r)
            return (time.time() - t0) / 3 * 1e3

        tr = timed(lambda: HP.sbh_hist_radix(
            packed, heap, stats, base=base, L=L, n_bins=256))
        td = timed(lambda: HP.sbh_hist_pallas(
            packed, heap, stats, base=base, L=L, n_bins=256))
        print(f"L={L}: radix {tr:.0f} ms  dense {td:.0f} ms  "
              f"({td / tr:.2f}x)", file=sys.stderr)
        rows.append({"window": L, "radix_ms": round(tr, 1),
                     "dense_ms": round(td, 1),
                     "speedup": round(td / tr, 2)})
    return rows


if __name__ == "__main__":
    if "--interpret" in sys.argv:        # CPU-safe factorization check
        for L in (1, 2, 4):
            check_math(L=L)
    else:                                # on-TPU parity + timings
        if not HP.use_pallas():
            raise SystemExit("radix_hist: the timing drive needs a TPU "
                             f"(backend {jax.default_backend()!r})")
        dev = check_chip()
        print(json.dumps({
            "drive": "radix_hist",
            "device_kind": jax.devices()[0].device_kind,
            "parity_max_dev": dev,
            "windows": measure()}))
