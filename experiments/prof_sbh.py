"""Profile sbh_route / sbh_hist / find_splits at 11M rows on TPU."""
import time
import numpy as np
import jax, jax.numpy as jnp
import os, sys; sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from h2o3_tpu.utils import compile_cache; compile_cache.enable()
from h2o3_tpu.ops import hist_pallas as HP
from h2o3_tpu.models.tree import binned as BN

N = 11_000_000
R = HP.BLOCK_ROWS
n_pad = -(-(N + 1) // R) * R
C_pad, BP = 32, 256
rng = np.random.default_rng(0)
codesU8 = jnp.asarray(rng.integers(0, 255, (C_pad, n_pad)), jnp.uint8)
codesT = HP.pack_codes(codesU8)      # packed i32 code plane (round 4)
stats = jnp.asarray(rng.normal(0, 1, (4, n_pad)), jnp.float32)
F = jnp.zeros(n_pad, jnp.float32)


def bench(name, fn, *args, n=3):
    r = fn(*args)
    float(jnp.asarray(r[0] if isinstance(r, tuple) else r)
          .ravel()[0].astype(jnp.float32))
    t0 = time.time()
    for _ in range(n):
        r = fn(*args)
    float(jnp.asarray(r[0] if isinstance(r, tuple) else r)
          .ravel()[0].astype(jnp.float32))
    print(f"  {name}: {(time.time()-t0)/n*1e3:.1f} ms")


for d in (3, 7):
    L = 2 ** d
    base = L - 1
    heap = jnp.asarray(rng.integers(base, base + L, n_pad), jnp.int32)
    Lp = max(8, L)
    tbl = jnp.zeros((8, Lp), jnp.float32)
    route_f = jnp.zeros((Lp, BP), jnp.float32)
    valtab = jnp.zeros((8, 640), jnp.float32)
    bench(f"sbh_route L={L}",
          lambda c, h, t, r, v, f: HP.sbh_route(
              c, h, t, r, v, f, base=base, L=L),
          codesT, heap, tbl, route_f, valtab, F)
    bench(f"sbh_route L={L} emit_f",
          lambda c, h, t, r, v, f: HP.sbh_route(
              c, h, t, r, v, f, base=base, L=L, eta=0.1, emit_f=True),
          codesT, heap, tbl, route_f, valtab, F)
    bench(f"sbh_hist L={L}",
          lambda c, h, s: HP.sbh_hist(c, h, s, base=base, L=L, n_bins=BP),
          codesT, heap, stats)

# find_splits at L=128
hist = jnp.asarray(rng.random((128, C_pad, 4, BP)), jnp.float32)
is_cat = jnp.zeros(C_pad, bool)
mono = jnp.zeros(C_pad, jnp.int32)
cmask = jnp.ones((128, C_pad), bool)
lo = jnp.full(128, -3e38); hi = jnp.full(128, 3e38)
bench("find_splits L=128 (no cat)",
      lambda h: BN.find_splits_binned(
          h, is_cat, mono, cmask, lo, hi, b_val=255, min_rows=1.0,
          msi=0.0, lam=0.0, use_hess=False, l_max=128, any_cat=False)["gain"],
      hist)
bench("find_splits L=128 (cat path)",
      lambda h: BN.find_splits_binned(
          h, is_cat, mono, cmask, lo, hi, b_val=255, min_rows=1.0,
          msi=0.0, lam=0.0, use_hess=False, l_max=128, any_cat=True)["gain"],
      hist)

# fast-path route (no cat)
for d in (3, 7):
    L = 2 ** d; base = L - 1
    heap = jnp.asarray(rng.integers(base, base + L, n_pad), jnp.int32)
    Lp = max(8, L)
    tbl = jnp.zeros((8, Lp), jnp.float32)
    route_f = jnp.zeros((Lp, BP), jnp.float32)
    valtab = jnp.zeros((8, 640), jnp.float32)
    bench(f"sbh_route L={L} FAST",
          lambda c, h, t, r, v, f, base=base, L=L: HP.sbh_route(
              c, h, t, r, v, f, base=base, L=L, any_cat=False),
          codesT, heap, tbl, route_f, valtab, F)
    bench(f"sbh_route L={L} FAST emit_f",
          lambda c, h, t, r, v, f, base=base, L=L: HP.sbh_route(
              c, h, t, r, v, f, base=base, L=L, eta=0.1, emit_f=True,
              any_cat=False),
          codesT, heap, tbl, route_f, valtab, F)
    bench(f"sbh_hist L={L} v4",
          lambda c, h, s, base=base, L=L: HP.sbh_hist(
              c, h, s, base=base, L=L, n_bins=BP),
          codesT, heap, stats)
