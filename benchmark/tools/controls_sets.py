#!/usr/bin/env python3
"""controls_sets.py — read the limits' two sides for a configuration whose
check is checks/gbm_sets.py, on the chip, at the cell's own size, several
seeds in one process (never run by the benchmark itself):

    python benchmark/tools/controls_sets.py --config gbm_airline --seeds 1,2

Per seed it builds the training frame and one more frame of the table as
run.py does, trains through `run.train_once`, scores both frames with
`model.predict`, and prints what `check_scores` reads for

  sound     the program as the configuration states it (a LOWER reading)
  bf16      control, an UPPER reading: the reference scorer in the
            program's place with bfloat16 features (level ids too),
            thresholds, leaf values and margins, on the same trees and rows
  clip255   control, a planted fault: the reference scorer in the
            program's place with level ids capped at a code byte (254),
            what the program did before every level had its own bin — it
            fails only if the sampled rows REACH levels past a byte and
            the trees send them another way than level 254

Both controls must read over the configuration's limits (`score_gap` or
`score_bad`). `--rehearse`: tiny sizes on the CPU.
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

from benchmark import checks, run                     # noqa: E402
from benchmark.checks import gbm_sets as check        # noqa: E402
from benchmark.reference import gbm_sets_plain as ref   # noqa: E402

CONTROLS = {"bf16": {"precision": "bf16"}, "clip255": {"clip_codes": 255}}


def readings(m, model, data, X, n, variant, seed, sample=100_000) -> dict:
    """check_scores' readings for the frames of n rows X holds."""
    import h2o3_tpu
    rng = np.random.default_rng([seed, 0x5C0BE])
    dom, got = data.DOMAIN, []
    for k in range(X.shape[0] // n):
        ids = k * n + np.sort(rng.choice(n, min(sample, n), replace=False))
        if variant in CONTROLS:
            p1 = ref.predict_proba(X[ids], model, **CONTROLS[variant])
            got.append((ids, 1.0 - p1, p1, p1 >= 0.5))
            continue
        fr = data.frame(X[k * n:(k + 1) * n], np.zeros(n, bool))
        pred = m.predict(fr)
        got.append((ids, *[pred.vec(c).to_numpy()[ids - k * n]
                           for c in ("p" + dom[0], "p" + dom[1], "predict")]))
        for key in (pred.key, fr.key):
            h2o3_tpu.remove(key)
    return check.check_scores(got, X, model)


def main(argv=None):
    import importlib
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="sound,bf16,clip255")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    config = run.load_json("configs", args.config + ".json")
    data = importlib.import_module("benchmark.datasets." + config["data"])
    sizes = dict(config["sizes"])
    if args.rehearse:
        sizes.update(config["rehearse"])
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("controls_sets.py: needs a TPU", file=sys.stderr)
        return 2
    import h2o3_tpu
    h2o3_tpu.init()
    cols, n = int(config["table"]["columns"]), int(sizes["train_rows"])
    limits = config["check"]["limits"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        X, y = data.host_arrays(2 * n, cols, seed)
        frame = data.frame(X[:n], y[:n])
        t0 = time.perf_counter()
        m = run.train_once({"config": config, "seed": seed, "frame": frame,
                            "params": dict(config["params"]), "data": data})
        model = check.read_model(m)
        t1 = time.perf_counter()
        sets, splits = check.set_nodes(model)
        past = [int((X[:, c] >= 255).sum())
                for c in np.flatnonzero(model["is_cat"])]
        for variant in args.variants.split(","):
            t2 = time.perf_counter()
            rec = {"config": args.config, "seed": seed, "variant": variant,
                   "train_s": t1 - t0, "set_nodes": sets, "split_nodes": splits,
                   "rows_past_a_byte": past,
                   "cat_levels_lost": check.levels_lost(model)}
            rec.update(readings(m, model, data, X, n, variant, seed))
            rec["within_limits"] = all(
                ok for *_, ok in checks.verdict(
                    rec, {k: v for k, v in limits.items() if k in rec}))
            rec["check_s"] = time.perf_counter() - t2
            print(json.dumps(rec), flush=True)
        run.drop_model(m)
        h2o3_tpu.remove(frame.key)
    return 0


if __name__ == "__main__":
    sys.exit(main())
