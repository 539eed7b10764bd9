"""Does the int8 Pallas dot compile + run, and how fast vs bf16?"""
import time, numpy as np, jax, jax.numpy as jnp, sys
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from h2o3_tpu.utils import compile_cache; compile_cache.enable()
from h2o3_tpu.ops import hist_pallas as HP

N = 11_000_000
R = HP.BLOCK_ROWS
n_pad = -(-(N + 1) // R) * R
C_pad, BP = 32, 256
rng = np.random.default_rng(0)
codesU8 = jnp.asarray(rng.integers(0, 255, (C_pad, n_pad)), jnp.uint8)
codesT = HP.pack_codes(codesU8)      # packed i32 code plane (round 4)
stats = jnp.asarray(rng.normal(0, 1, (4, n_pad)), jnp.float32)
stats_i8 = jnp.asarray(rng.integers(-127, 128, (4, n_pad)), jnp.int32)

def bench(name, fn, *args, n=3):
    r = fn(*args)
    print(name, "first:", float(jnp.asarray(r).ravel()[0].astype(jnp.float32)))
    t0 = time.time()
    for _ in range(n):
        r = fn(*args)
    float(jnp.asarray(r).ravel()[0].astype(jnp.float32))
    print(f"  {name}: {(time.time()-t0)/n*1e3:.1f} ms")

for d, L in ((3, 8), (7, 128)):
    base = L - 1
    heap = jnp.asarray(rng.integers(base, base + L, n_pad), jnp.int32)
    bench(f"i8 hist L={L}",
          lambda c, h, st, base=base, L=L: HP.sbh_hist_pallas_i8(
              c, h, st, base=base, L=L, n_bins=BP).sum(),
          codesT, heap, stats_i8)
    bench(f"bf16 hist L={L}",
          lambda c, h, st, base=base, L=L: HP.sbh_hist_pallas(
              c, h, st, base=base, L=L, n_bins=BP).sum(),
          codesT, heap, stats)

# correctness: i8 vs exact numpy on small
n0 = 4 * R
c0u = jnp.asarray(rng.integers(0, BP, (C_pad, n0)), jnp.uint8)
c0 = HP.pack_codes(c0u)
h0 = jnp.asarray(rng.integers(7, 15, n0), jnp.int32)
s0 = jnp.asarray(rng.integers(-127, 128, (4, n0)), jnp.int32)
out = np.asarray(HP.sbh_hist_pallas_i8(c0, h0, s0, base=7, L=8, n_bins=BP))
ref = np.zeros((8, C_pad, 4, BP), np.int64)
cn, hn, sn = np.asarray(c0u).astype(np.int64), np.asarray(h0), np.asarray(s0)
for c in range(C_pad):
    for st in range(4):
        np.add.at(ref[:, c, st, :], (hn - 7, cn[c]), sn[st])
err = np.abs(out[:8] - ref).max()
print("i8 exactness:", err)
