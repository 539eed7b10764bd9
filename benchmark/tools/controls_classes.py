#!/usr/bin/env python3
"""controls_classes.py — read the limits' two sides for a configuration
whose check is checks/gbm_classes.py, on the chip, at the cell's own size,
several seeds in one process (never run by the benchmark itself):

    python benchmark/tools/controls_classes.py --config gbm_kddcup99 --seeds 1,2

Per seed it builds the table's two frames as run.py does, trains through
`run.train_once`, scores both frames with `model.predict`, and prints what
`check_scores` reads for

  sound        the program as the configuration states it (a LOWER reading)
  bf16         control, an UPPER reading: the reference scorer in the
               program's place with bfloat16 features, thresholds, leaf
               values, margins and probabilities, on the same trees and rows
  shift_class  a planted fault: the reference in the program's place with
               every tree added to the class after its own (mod K)
  drop_f0      a planted fault: the reference without the initial margins

The three controls must read over the configuration's limits (`score_gap`
or `score_bad`); `gap_all_rows` is a control's distance from the reference
over all its rows, bad ones too. `--rehearse`: tiny sizes on the CPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np                                      # noqa: E402

from benchmark import checks, run                       # noqa: E402
from benchmark.checks import gbm_classes as check       # noqa: E402
from benchmark.reference import gbm_classes_plain as ref  # noqa: E402

CONTROLS = {"bf16": {"precision": "bf16"},
            "shift_class": {"fault": "shift_class"},
            "drop_f0": {"fault": "drop_f0"}}


def readings(m, model, data, X, n, columns, variant, seed,
             sample=100_000) -> dict:
    """check_scores' readings for the frames of n rows X holds."""
    import h2o3_tpu
    rng = np.random.default_rng([seed, 0x5C0BE])
    got = []
    for k in range(X.shape[0] // n):
        ids = k * n + np.sort(rng.choice(n, min(sample, n), replace=False))
        if variant in CONTROLS:
            P = ref.predict_proba(X[ids][:, columns], model,
                                  **CONTROLS[variant])
            got.append((ids, P, P.argmax(axis=1).astype(np.float64)))
            continue
        fr = data.frame(X[k * n:(k + 1) * n], np.zeros(n, np.int8))
        pred = m.predict(fr)
        got.append((ids, np.stack(
            [pred.vec("p" + c).to_numpy()[ids - k * n] for c in data.DOMAIN],
            axis=1), pred.vec("predict").to_numpy()[ids - k * n]))
        for key in (pred.key, fr.key):
            h2o3_tpu.remove(key)
    out = check.check_scores(got, X, model, columns=columns)
    if variant in CONTROLS:
        # the control's distance over ALL its rows: `score_gap` is taken
        # over the rows that are not `score_bad`, and a control whose
        # probabilities do not sum to 1 leaves it none
        out["gap_all_rows"] = max(float(np.abs(
            P - ref.predict_proba(X[ids][:, columns], model)).max())
            for ids, P, _ in got)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="sound,bf16,shift_class,drop_f0")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    config = run.load_json("configs", args.config + ".json")
    data = importlib.import_module("benchmark.datasets." + config["data"])
    sizes = dict(config["sizes"])
    if args.rehearse:
        sizes.update(config["rehearse"])
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("controls_classes.py: needs a TPU", file=sys.stderr)
        return 2
    import h2o3_tpu
    h2o3_tpu.init()
    cols, n = int(config["table"]["columns"]), int(sizes["train_rows"])
    limits, names = config["check"]["limits"], config["check"]["names"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        X, y = data.host_arrays(2 * n, cols, seed)
        frame = data.frame(X[:n], y[:n])
        t0 = time.perf_counter()
        m = run.train_once({"config": config, "seed": seed, "frame": frame,
                            "params": dict(config["params"]), "data": data})
        model = check.read_model(m)
        t1 = time.perf_counter()
        columns = np.array([names.index(c) for c in model["predictors"]])
        split = model["col"] >= 0
        for variant in args.variants.split(","):
            t2 = time.perf_counter()
            rec = {"config": args.config, "seed": seed, "variant": variant,
                   "train_s": t1 - t0, "trees": int(split.shape[0]),
                   "stumps": int((~split.any(axis=1)).sum()),
                   "classes_in_train": int(np.unique(y[:n]).size),
                   "columns": int(columns.size),
                   "engine": (m._output.model_summary or {}).get("engine"),
                   "cat_levels_lost": check.levels_lost(model)}
            rec.update(readings(m, model, data, X, n, columns, variant, seed))
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
