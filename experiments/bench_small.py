"""Small-scale TPU check of the binned trainer before full bench."""
import json, time
import numpy as np
import jax, jax.numpy as jnp
import os, sys; sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from h2o3_tpu.utils import compile_cache; compile_cache.enable()
from h2o3_tpu.models.tree import binned as BN

N, C, DEPTH, NBINS = 1_000_000, 28, 8, 255
key = jax.random.PRNGKey(7)
kx, ky = jax.random.split(key)
X = jax.random.normal(kx, (N, C), jnp.float32)
logit = 1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
y = (jax.random.uniform(ky, (N,)) < jax.nn.sigmoid(logit)).astype(jnp.float32)
Xs = np.asarray(X[:1 << 18])
spec = BN.make_bins(Xs, np.zeros(C, bool), NBINS)
codes = BN.quantize(X, spec)
grower = BN.BinnedGrower(spec, max_depth=DEPTH, min_rows=1.0,
                         min_split_improvement=0.0)
trainer = BN.gbm_chunk_trainer(grower, N, dist="bernoulli", eta=0.1,
                               sample_rate=1.0, mtries=0, k_trees=10)
n_pad = grower.layout(N)
y1 = BN.pad_rows(y, n_pad); w1 = BN.pad_rows(jnp.ones(N, jnp.float32), n_pad)
p0 = float(jnp.mean(y))
F = jnp.where(jnp.arange(n_pad) < N,
              float(np.log(p0 / (1 - p0))), 0.0).astype(jnp.float32)
k = jax.random.PRNGKey(0)
k, kc = jax.random.split(k)
t0 = time.time(); F, _ = trainer(codes, y1, w1, F, kc); print("warm/compile:", round(time.time()-t0,1), "s, F0:", float(F[0]))
t0 = time.time()
for _ in range(2):
    k, kc = jax.random.split(k)
    F, _ = trainer(codes, y1, w1, F, kc)
float(F[0]); dt = (time.time() - t0)
print(f"20 trees: {dt:.2f}s -> {N*20/dt/1e6:.1f}M row*trees/s")
# quality: AUC on device
p = jax.nn.sigmoid(F[:N])
order = jnp.argsort(p)
r = jnp.zeros(N).at[order].set(jnp.arange(1, N + 1, dtype=jnp.float32))
npos = float(jnp.sum(y)); nneg = N - npos
auc = (float(jnp.sum(r * y)) - npos * (npos + 1) / 2) / (npos * nneg)
print("AUC after 30 trees:", round(auc, 4))
