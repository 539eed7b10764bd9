"""Smoke: BinnedGrower + gbm_chunk_trainer e2e on CPU, AUC sanity."""
import sys, time
import numpy as np
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from h2o3_tpu.models.tree import binned as BN

rng = np.random.default_rng(0)
n, C = 20000, 8
X = rng.normal(0, 1, (n, C)).astype(np.float32)
logit = 1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
X[rng.random((n, C)) < 0.02] = np.nan  # NAs

is_cat = np.zeros(C, bool)
spec = BN.make_bins(X, is_cat, nbins=64)
codes = BN.quantize(jnp.asarray(X), spec)
print("codes", codes.shape, codes.dtype, "nb", spec.n_bins, "bval", spec.b_val)

grower = BN.BinnedGrower(spec, max_depth=5, min_rows=10,
                         min_split_improvement=1e-5)
trainer = BN.gbm_chunk_trainer(grower, n, dist="bernoulli", eta=0.1,
                               sample_rate=1.0, mtries=0, k_trees=10)

n_pad = grower.layout(n)
y1 = BN.pad_rows(jnp.asarray(y), n_pad)
w1 = BN.pad_rows(jnp.ones(n, jnp.float32), n_pad)
p0 = float(y.mean())
F = jnp.where(jnp.arange(n_pad) < n,
              np.log(p0 / (1 - p0)), 0.0).astype(jnp.float32)
key = jax.random.PRNGKey(0)
t0 = time.time()
for it in range(5):
    F, trees = trainer(codes, y1, w1, F, key)
    key, _ = jax.random.split(key)
F = np.asarray(F)[:n]
print("50 trees in", round(time.time() - t0, 1), "s")
p = 1 / (1 + np.exp(-F))

# AUC
order = np.argsort(p)
r = np.empty(n); r[order] = np.arange(1, n + 1)
npos = y.sum(); nneg = n - npos
auc = (r[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)
print("train AUC after 50 trees:", round(float(auc), 4))
print("auc check:", auc)
print("OK")

# --- compare with the adaptive engine on identical data ---
from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.models.tree.shared_tree import _grad_hess
Xj = jnp.asarray(X)
g2 = E.TreeGrower(nbins=64, max_depth=5, min_rows=10, min_split_improvement=1e-5)
F2 = jnp.full(n, np.log(p0 / (1 - p0)), jnp.float32)
w = jnp.ones(n, jnp.float32)
k = jax.random.PRNGKey(0)
t0 = time.time()
for t in range(50):
    res, hess = _grad_hess("bernoulli", F2, jnp.asarray(y))
    col, thr, nal, val, heap, _ = g2.grow(Xj, w, res, key=k)
    val = E.gamma_pass(heap, w, res, hess, val, nodes=g2.nodes)
    F2 = F2 + 0.1 * val[heap]
F2 = np.asarray(F2)
print("adaptive 50 trees in", round(time.time() - t0, 1), "s")
p2 = 1 / (1 + np.exp(-F2))
order = np.argsort(p2); r = np.empty(n); r[order] = np.arange(1, n + 1)
auc2 = (r[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)
print("adaptive train AUC:", round(float(auc2), 4))
