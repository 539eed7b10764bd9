"""Headline benchmark: GBM (bernoulli) training throughput on HIGGS-shaped
data — 11M rows x 28 features, depth 8, 255 value bins, sustained trees/s.

BASELINE.json metric: "HIGGS + airlines-1B GBM wall-clock vs H100 gpu_hist".
The reference publishes no absolute number ("published": {}); the comparison
point is XGBoost `gpu_hist` on HIGGS on one H100: ~11M rows x 28 features x
500 trees (depth 8, 256 bins) in ~35 s ~= 157M row*trees/s. We report
sustained row*trees/s of the binned tree engine (global quantile codes +
Pallas histogram kernel — the same `hist` algorithm family) at the SAME
shape: full 11M rows, depth 8, 255+NA bins, no extrapolation.

Prints ONE JSON line.
"""

import json
import sys
import time

import numpy as np

N, C = 11_000_000, 28
DEPTH, NBINS = 8, 255
CHUNK, NCHUNK = 10, 4                    # 10 warm-up + 40 timed trees


def main():
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip; this backend is "
            f"{jax.default_backend()!r}")

    from h2o3_tpu.utils import compile_cache
    compile_cache.enable()

    from h2o3_tpu.models.tree import binned as BN
    from h2o3_tpu.models.tree.engine import ROW_TREES
    from h2o3_tpu.ops import hist_pallas as HP
    from h2o3_tpu.ops.parity import kernel_parity_check

    # generate HIGGS-like data ON DEVICE (the benchmark measures training,
    # not a 1.2GB host->device copy)
    kx, _, ky = jax.random.split(jax.random.PRNGKey(7), 3)

    @jax.jit
    def gen(kx, ky):
        X = jax.random.normal(kx, (N, C), jnp.float32)
        logit = (1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
                 + 0.4 * jnp.sin(X[:, 4]) + 0.3 * X[:, 5] * X[:, 6])
        y = (jax.random.uniform(ky, (N,)) <
             jax.nn.sigmoid(logit)).astype(jnp.float32)
        return X, y

    X, y = gen(kx, ky)

    # ---- kernel parity gate (pre-step): a misrouting Pallas kernel must
    # not ship behind a good throughput number
    kernel_parity_check(seed=0)
    print("kernel parity: OK", file=sys.stderr)

    # bin spec from a host-side sample (29MB readback), codes on device:
    # uint8 planes end-to-end, packed to the i32 word layout for the
    # Pallas kernels (1 B/code in HBM)
    spec = BN.make_bins(np.asarray(X[: 1 << 18]), np.zeros(C, bool), NBINS)
    codes = BN.prepare_codes(BN.quantize(X, spec))
    del X

    # ---- AUC: rank-sum (Mann-Whitney) on device; a broken histogram or
    # route kernel collapses this to ~0.5 regardless of throughput.
    @jax.jit
    def auc_dev(F, y):
        order = jnp.argsort(F[:N])
        ranks = jnp.zeros(N, jnp.float64).at[order].set(
            jnp.arange(1, N + 1, dtype=jnp.float64))
        pos = y.astype(jnp.float64)
        npos = pos.sum()
        return (ranks @ pos - npos * (npos + 1) / 2) / (npos * (N - npos))

    n_pad = BN.padded_rows(N)
    y1 = BN.pad_rows(y, n_pad)
    w1 = BN.pad_rows(jnp.ones(N, jnp.float32), n_pad)
    p0 = float(jnp.mean(y))
    f0 = float(np.log(p0 / (1 - p0)))

    before = HP.kernel_traces()
    grower = BN.BinnedGrower(spec, max_depth=DEPTH, min_rows=1.0,
                             min_split_improvement=0.0)
    trainer = BN.gbm_chunk_trainer(grower, N, dist="bernoulli", eta=0.1,
                                   sample_rate=1.0, mtries=0,
                                   k_trees=CHUNK)
    F = jnp.where(jnp.arange(n_pad) < N, f0, 0.0).astype(jnp.float32)
    k = jax.random.PRNGKey(0)
    # warm-up: compile + first chunk
    k, kc = jax.random.split(k)
    F, _ = trainer(codes, y1, w1, F, kc)
    jax.block_until_ready(F)
    t0 = time.time()
    for _ in range(NCHUNK):
        k, kc = jax.random.split(k)
        F, _ = trainer(codes, y1, w1, F, kc)
    jax.block_until_ready(F)
    dt = time.time() - t0
    ntrees = CHUNK * NCHUNK
    ROW_TREES.inc(N * ntrees, engine="binned")   # /metrics sees the bench
    auc = float(auc_dev(F, y))
    assert auc > 0.72, f"AUC gate failed: {auc:.4f} — kernels mis-trained"

    # what the selection rules traced into the trainer (HP.KERNEL_TRACES)
    traced = sorted({kern for (kern, L), v in HP.kernel_traces().items()
                     if v > before.get((kern, L), 0)})
    print(json.dumps({
        "row_trees_per_sec": round(N * ntrees / dt),
        "train_auc": round(auc, 4),
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "rows": N,
        "trees": ntrees,
        "kernels": traced,
    }))


if __name__ == "__main__":
    # any failure is a failure: the traceback goes to stderr and the exit
    # code is non-zero
    main()
