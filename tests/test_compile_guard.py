"""Tier-1 compile-count regression guard.

The serving fast path's whole value is that repeated scoring NEVER
recompiles: scoring one model at several row counts inside one row bucket
must cost at most ONE XLA backend compile (the first trace of that
bucket's program). A future change that sneaks a per-shape jit back into
the predict path (a closure jit, an unbucketed matrix build, a per-call
lambda) makes this test fail immediately.

Compile observations come from jax.monitoring's
/jax/core/compile/backend_compile_duration events, surfaced as the
h2o3_xla_compiles_total counter by h2o3_tpu/obs/metrics.py.
"""

import numpy as np

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.models import ESTIMATORS
from h2o3_tpu.obs import metrics as om
from h2o3_tpu.serving import scorer_cache as sc

RNG = np.random.default_rng(11)


def _frame(n, with_resp=False):
    cols = {"a": RNG.normal(size=n), "b": RNG.normal(size=n),
            "c": RNG.choice(["u", "v"], size=n)}
    if with_resp:
        cols["resp"] = RNG.choice(["no", "yes"], size=n)
    return Frame.from_dict(cols)


def test_one_bucket_three_row_counts_at_most_one_compile():
    fr = _frame(250, with_resp=True)
    m = ESTIMATORS["glm"](family="binomial")
    m.train(x=["a", "b", "c"], y="resp", training_frame=fr)

    bucket = sc.row_bucket(1)
    counts = [max(2, bucket - 40), max(3, bucket - 20), bucket]
    assert len({sc.row_bucket(n) for n in counts}) == 1, \
        "test row counts must share one bucket"

    keys = [fr.key, m.key]
    c0 = om.xla_compile_count()
    for n in counts:
        f = _frame(n)
        p = m.predict(f)
        assert p.nrows == n
        keys += [f.key, p.key]
    compiled = om.xla_compile_count() - c0
    assert compiled <= 1, (
        f"scoring 3 row counts in one bucket took {compiled} XLA compiles "
        "(expected ≤1) — a per-shape recompile crept back into the "
        "serving path")
    for k in keys:
        DKV.remove(k)


def test_binned_level_loop_dispatch_bounded():
    """ISSUE 14 dispatch-count guard: the eager per-level grow loop must
    dispatch a BOUNDED number of compiled programs per level — a change
    that sneaks a per-leaf or per-column jit into the loop (a closure
    jit, an unhashable static arg, a fresh lambda) shows up here as a
    compile-count explosion; and a second identical run must add ZERO
    compiles (every program is cached)."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import binned as BN

    rng = np.random.default_rng(3)
    n, C, D = 1500, 4, 4
    X = rng.normal(0, 1, (n, C)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    spec = BN.make_bins(X, np.zeros(C, bool), 32)
    n_pad = BN.padded_rows(n)
    codes = BN.prepare_codes(BN.quantize(jnp.asarray(X), spec,
                                         n_pad=n_pad))
    w1 = BN.pad_rows(jnp.ones(n, jnp.float32), n_pad)
    y1 = BN.pad_rows(jnp.asarray(y), n_pad)
    stats = jnp.stack([w1, w1 * (y1 - 0.5), w1 * 0.25,
                       jnp.zeros_like(w1)], axis=0)
    F = jnp.zeros(n_pad, jnp.float32)
    grower = BN.BinnedGrower(spec, max_depth=D, min_rows=2.0,
                             min_split_improvement=0.0)

    def run(g):
        out = g.grow(codes, stats, F, eta=0.1, clip_val=0.0,
                     key=jax.random.PRNGKey(0))
        jax.block_until_ready(out["F"])

    c0 = om.xla_compile_count()
    run(grower)
    first = om.xla_compile_count() - c0
    run(grower)
    second = om.xla_compile_count() - c0 - first
    assert second == 0, (
        f"second identical eager grow re-compiled {second} programs — a "
        "per-call recompile crept into the level loop")
    # scaling guard: deepening the tree adds a BOUNDED number of programs
    # per NEW level (each level's static L recompiles the per-level
    # programs once — that is the contract). A per-leaf or per-column jit
    # would scale the per-level cost with 2^d and explode this ratio.
    D2 = 6
    grower2 = BN.BinnedGrower(spec, max_depth=D2, min_rows=2.0,
                              min_split_improvement=0.0)
    c1 = om.xla_compile_count()
    run(grower2)
    deep = om.xla_compile_count() - c1
    per_level, per_level_deep = first / D, deep / D2
    assert per_level_deep <= 2.0 * per_level + 8, (
        f"per-level compile cost grew from {per_level:.1f} (depth {D}) to "
        f"{per_level_deep:.1f} (depth {D2}) — dispatch count is scaling "
        "with the leaf count, not the level count")
