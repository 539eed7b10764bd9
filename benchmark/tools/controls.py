#!/usr/bin/env python3
"""controls.py — read the limits' two sides on the chip, at the cell's own
size, several seeds in one process (never run by the benchmark itself):

    python benchmark/tools/controls.py --config gbm_higgs --seeds 1,2,3 \
        --what scores|model [--variants ...]

`--what scores` (the frame-scoring cells): per seed it builds the training
frame and one more frame of the table as run.py does, trains through
`run.train_once`, scores both frames with `model.predict`, and prints what
checks/gbm.py `check_scores` reads for

  sound        the program as the configuration states it (a LOWER reading)
  bf16         the control, an UPPER reading: the reference scorer in the
               program's place with bfloat16 features, thresholds, leaf
               values and margins, on the same trees and sampled rows
  altered      one scored probability in 997 moved by 1e-3 where predict()
               forms its columns (planted in the program)
  half_unscored  the second half of a frame's rows given the first row's
               answer (planted in the program)

`--what model` (the training cells, held out of BENCHMARK.json): per
variant it trains through `run.train_once` and prints what `check_model`
reads: sound; half_batch; altered_leaf; state_unchanged. On the chip the
program's own histogram kernels round to bfloat16, so "sound" there IS
the bfloat16 control; the float32 side is the same command with
`--rehearse` sizes on the CPU.

The faults are planted in the program by patching it in this process
only. `--serve 1` (with `--what model`) also scores 4,096 + 256 + 16 + 1
rows through `serving.score_payload` (the REST route's scorer) with f32 and
with the served probabilities rounded to bfloat16.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np                                    # noqa: E402

from benchmark import run                             # noqa: E402
from benchmark.checks import gbm as check             # noqa: E402
from benchmark.datasets import higgs_like as data     # noqa: E402
from benchmark.reference import gbm_plain as ref      # noqa: E402


def plant(variant):
    """Patch the program for one variant; returns the undo."""
    import jax.numpy as jnp
    from h2o3_tpu.models.tree import binned as BN
    from h2o3_tpu.models.tree.shared_tree import SharedTreeEstimator as ST
    if variant == "half_batch":
        orig = ST._prep

        def prep(self, frame):
            X, y, w = orig(self, frame)
            return X, y, jnp.where(
                jnp.arange(w.shape[0]) < frame.nrows // 2, w, 0.0)
        ST._prep = prep
        return lambda: setattr(ST, "_prep", orig)
    if variant == "altered_leaf":
        orig = ST._binned_tree_arrays

        def arrays(self, ctx, chunks, **kw):
            ta, gains = orig(self, ctx, chunks, **kw)
            ta.value = ta.value.at[1, abs(ta.value[1]).argmax()].multiply(1.25)
            return ta, gains
        ST._binned_tree_arrays = arrays
        return lambda: setattr(ST, "_binned_tree_arrays", orig)
    if variant == "state_unchanged":
        orig = BN.BinnedGrower.grow

        def grow(self, codes, stats, F, **kw):
            return dict(orig(self, codes, stats, F, **kw), F=F)
        BN.BinnedGrower.grow = grow
        return lambda: setattr(BN.BinnedGrower, "grow", orig)
    if variant == "altered":
        from h2o3_tpu.models.model import ModelBase
        orig = ModelBase._prediction_columns

        def cols(self, out, n):
            out = np.array(out, np.float64)
            out[:n:997, 1] += 1e-3           # one answer in 997 altered
            out[:n:997, 0] -= 1e-3
            return orig(self, out, n)
        ModelBase._prediction_columns = cols
        return lambda: setattr(ModelBase, "_prediction_columns", orig)
    if variant == "half_unscored":
        from h2o3_tpu.models.model import ModelBase
        orig = ModelBase._prediction_columns

        def cols(self, out, n):
            out = np.array(out, np.float64)
            out[n // 2:n] = out[0]          # half the rows never scored
            return orig(self, out, n)
        ModelBase._prediction_columns = cols
        return lambda: setattr(ModelBase, "_prediction_columns", orig)
    return lambda: None


def scores(m, model, X, n, variant, seed, sample=100_000):
    """check_scores' readings for the first two frames of n rows."""
    import h2o3_tpu
    rng = np.random.default_rng([seed, 0x5C0BE])
    got = []
    for k in range(X.shape[0] // n):
        ids = k * n + np.sort(rng.choice(n, min(sample, n), replace=False))
        if variant == "bf16":
            p1 = ref.predict_proba(X[ids], model, precision="bf16")
            got.append((ids, 1.0 - p1, p1, p1 >= 0.5))
            continue
        fr = data.frame(X[k * n:(k + 1) * n],
                        np.zeros(n, bool))
        undo = plant(variant)
        try:
            pred = m.predict(fr)
        finally:
            undo()
        got.append((ids, *[pred.vec(c).to_numpy()[ids - k * n]
                           for c in ("pb", "ps", "predict")]))
        for key in (pred.key, fr.key):
            h2o3_tpu.remove(key)
    return check.check_scores(got, X, model, model["domain"])


def served(ctx, m, model, X):
    """served_gap of the scorer fast path, f32 and bf16-rounded."""
    from h2o3_tpu import serving
    names = data.feature_names(X.shape[1])
    out = {}
    ans, low, at = [], [], 0
    for k in (4096, 256, 16, 1):
        ids = np.arange(at, at + k)
        at += k
        preds = serving.score_payload(m, X[ids].tolist(), names)
        ans.append((len(ans), ids, preds))
        lo = []
        for p in preds:
            q = float(ref._bf16(np.float32(p["ps"])))
            lo.append(dict(p, ps=q, pb=1.0 - q))
        low.append((len(low), ids, lo))
    dom = list(m._dinfo.response_domain)
    out["served_sound"] = check.check_answers(ans, lambda i: X[i], model, dom)
    out["served_bf16"] = check.check_answers(low, lambda i: X[i], model, dom)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", choices=("scores", "model"), default="scores")
    ap.add_argument("--variants", default=None)
    ap.add_argument("--serve", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    variants = (args.variants or {"scores": "sound,bf16,altered",
                                  "model": "sound,half_batch,altered_leaf"}
                [args.what]).split(",")
    config = run.load_json("configs", args.config + ".json")
    sizes = dict(config["sizes"])
    if args.rehearse:
        sizes.update(config["rehearse"])
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("controls.py: needs a TPU", file=sys.stderr)
        return 2
    import h2o3_tpu
    h2o3_tpu.init()
    cols, n = int(config["table"]["columns"]), int(sizes["train_rows"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        X, y = data.host_arrays(n * (2 if args.what == "scores" else 1),
                                cols, seed)
        frame = data.frame(X[:n], y[:n])
        ctx = {"config": config, "params": dict(config["params"]),
               "seed": seed, "frame": frame, "data": data}
        m = None
        for variant in variants:
            t0 = time.perf_counter()
            if args.what == "model" or m is None:
                undo = plant(variant) if args.what == "model" else \
                    (lambda: None)
                try:
                    m = run.train_once(ctx)
                finally:
                    undo()
                model = check.read_model(m)
            t1 = time.perf_counter()
            rec = {"config": args.config, "seed": seed, "variant": variant,
                   "train_s": t1 - t0}
            if args.what == "scores":
                rec.update(scores(m, model, X, n, variant, seed))
            else:
                rec.update(check.check_model(
                    X, y, ctx["params"], model,
                    check_trees=int(config["check"]["trees"])))
                if args.serve and variant == "sound":
                    rec.update(served(ctx, m, model, X))
            rec["check_s"] = time.perf_counter() - t1
            if args.what == "model":
                run.drop_model(m)
            print(json.dumps(rec), flush=True)
        if args.what == "scores":
            run.drop_model(m)
        h2o3_tpu.remove(frame.key)
    return 0


if __name__ == "__main__":
    sys.exit(main())
