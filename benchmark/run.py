#!/usr/bin/env python3
"""benchmark/run.py — one process, one cell, one run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

load -> set-up (host arrays of --seed, the training frame, one train())
-> warm the cell's own shapes -> measure for --seconds -> read the peak,
free the program's state -> compare with the plain reference -> print the
contract's last line. Everything that belongs to one configuration (its
data generator, its check), one traffic mix (its driver, what it
compares) or one per-layer metric is a file found by name, from
BENCHMARK.json down (see benchmark/README.md); this file knows no cell,
no table and no kind of model by name.

It runs on the machine it is started on and fails (exit 2, no result)
unless JAX shows a TPU that benchmark/peaks.json knows, with as many
chips as the cell asks for. `--rehearse` is the one way round that: tiny
sizes on the CPU, `"platform": "cpu"` in the line, no device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up counts from here

import argparse                          # noqa: E402
import importlib                         # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


# ===========================================================================
# What the harness takes from the program: counters, peak, jobs, train()
def counters() -> dict:
    """Process-wide compile accounting (obs/metrics jax.monitoring taps)."""
    from h2o3_tpu.obs import metrics as om

    def val(name):
        m = om.REGISTRY.get(name)
        return m.value() if m is not None else 0.0
    return {"compiles": val("h2o3_xla_compiles_total"),
            "compile_s": val("h2o3_xla_compile_seconds_total"),
            "cache_hits": val("h2o3_xla_compile_cache_hits_total"),
            "cache_misses": val("h2o3_xla_compile_cache_misses_total")}


def since(before: dict) -> dict:
    return {k: v - before[k] for k, v in counters().items()}


def memory_bytes():
    """(peak, in use now) on the fullest chip; (None, None) on a backend
    that reports none (the CPU)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = [s.get("peak_bytes_in_use") for s in stats]
    now = [s.get("bytes_in_use") for s in stats]
    if any(p is None for p in peak):
        return None, None
    return int(max(peak)), int(max(now))


def train_once(ctx):
    """One train() through the normal entry point, ended by
    block_until_ready on the model's trees. The window's driver and set-up
    both call this: one path, warmed once."""
    import jax
    from h2o3_tpu import models
    est = getattr(models, ctx["config"]["estimator"])
    m = est(**ctx["params"], seed=ctx["seed"] & 0x7FFFFFFF)
    m.train(y=ctx["data"].LABEL, training_frame=ctx["frame"])
    jax.block_until_ready(jax.tree_util.tree_leaves(m._trees))
    return m


def drop_model(m):
    """Remove a model and what train() keyed beside it from the DKV."""
    import h2o3_tpu
    from h2o3_tpu.core.kvstore import DKV
    for k in [k for k in DKV.keys() if k.startswith(m.key)]:
        h2o3_tpu.remove(k)


def job_phases(model_keys) -> list:
    """Job.phases (ms) of the jobs that built these models, from the
    /3/Jobs document."""
    from h2o3_tpu.core.jobs import jobs_list
    by = {j["dest"]: j for j in jobs_list()}
    return [by[k]["phases"] for k in model_keys if k in by]


# ===========================================================================
def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def reader_of(metric: str):
    return importlib.import_module(
        "benchmark.layer_metrics." + metric.replace(".", "__")).read


def e2e_in_cell(metric: dict, cell: dict) -> bool:
    return cell["name"] in metric.get("workloads", [cell["name"]])


def layer_in_cell(metric: dict, cell: dict, bench: dict) -> bool:
    """With a `workloads` key: those cells. Without: every cell that
    reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return any(m["name"] == metric["moves"] and e2e_in_cell(m, cell)
               for m in bench["end_to_end"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; no device metric")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: copy the .xplane.pb and a "
                         "description of it to DIR")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
                    metavar="FILE", help="cells and metrics to read instead "
                    "of BENCHMARK.json (tests: cells held out of it)")
    args = ap.parse_args(argv)

    with open(args.bench) as fh:
        bench = json.load(fh)
    cell = find_cell(bench, args.workload)
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    peaks = load_json("peaks.json")
    sizes = dict(config["sizes"])
    if args.rehearse:
        sizes.update(config["rehearse"])
        mix.update(mix.get("rehearse", {}))

    # ---- the device, before the package is touched ------------------------
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if not args.rehearse:
        if dev["platform"] != "tpu" or dev["kind"] not in peaks:
            log(f"run.py: needs a TPU listed in peaks.json, found {dev}")
            return 2
        if dev["count"] != cell["chips"]:
            log(f"run.py: {dev['count']} chip(s) visible, the cell needs "
                f"{cell['chips']}")
            return 2
    peak = peaks.get(dev["kind"])

    import h2o3_tpu
    from h2o3_tpu.utils import compile_cache
    h2o3_tpu.init()
    log(f"device {dev} compile_cache_dir={compile_cache.cache_dir()}")

    # ---- set-up shared by every cell of the configuration ----------------
    # host arrays of --seed (the reference needs them on the host) -> the
    # training frame -> one train() -> the cell's own warm-up. The mix
    # names the size of the table it works on; the default is the
    # training frame alone. Nothing is put on the device that the window
    # does not use.
    data = importlib.import_module("benchmark.datasets." + config["data"])
    chk = importlib.import_module(
        "benchmark.checks." + config["check"]["module"])
    driver = importlib.import_module("benchmark.drivers." + mix["driver"])
    cols = int(config["table"]["columns"])
    n_train = int(sizes["train_rows"])
    t0 = time.perf_counter()
    X, y = data.host_arrays(int(sizes[mix.get("table", "train_rows")]), cols,
                            args.seed)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    frame = data.frame(X[:n_train], y[:n_train])
    t_frame = time.perf_counter() - t0
    ctx = {"cell": cell, "config": config, "mix": mix, "data": data,
           "sizes": sizes, "params": dict(config["params"]),
           "seed": args.seed, "X": X, "y": y, "frame": frame,
           "peak": peak, "root": ROOT, "keep_trace": args.keep_trace,
           "train_once": train_once, "drop_model": drop_model,
           "job_phases": job_phases, "log": log}
    t0 = time.perf_counter()
    ctx["model"] = train_once(ctx)       # warms every program train() uses
    t_train = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = driver.prepare(ctx)          # the cell's own shapes, no others
    t_warm = time.perf_counter() - t0
    setup_counters = counters()
    setup_s = time.perf_counter() - T_PROCESS
    log(f"setup_s={setup_s:.2f} (data {t_data:.2f}, frame {t_frame:.2f}, "
        f"first train {t_train:.2f}, cell warm-up {t_warm:.2f}) compile "
        f"counters {setup_counters}")

    # ---- the window -------------------------------------------------------
    c0 = counters()
    try:
        window = driver.window(ctx, state, args.seconds, bool(args.trace))
    except BaseException:
        driver.finish(ctx, state, None)      # leave no process behind
        raise
    in_window = since(c0)
    log(f"in the window: {in_window['compiles']:.0f} executable builds "
        f"({in_window['compile_s']:.2f} s), persistent-cache hits "
        f"{in_window['cache_hits']:.0f}, MISSES (true compilations) "
        f"{in_window['cache_misses']:.0f}")
    mem_peak, mem_now = memory_bytes()
    produced = driver.finish(ctx, state, window)   # stops what it started

    # ---- free the program's state, then the reference --------------------
    model = chk.read_model(window.get("model") or ctx["model"])
    for m in (window.pop("model", None), ctx.pop("model")):
        if m is not None:
            drop_model(m)
    h2o3_tpu.remove(frame.key)
    del frame, state
    ctx["frame"] = None

    from benchmark import checks
    t0 = time.perf_counter()
    opts = dict(config["check"], train_rows=n_train)
    readings = {}
    for what in mix["compares"]:
        readings.update(chk.compare(what, X=X, y=y, params=ctx["params"],
                                    model=model, produced=produced,
                                    opts=opts))
    limits = {k: v for k, v in opts["limits"].items() if k in readings}
    rows = checks.verdict(readings, limits)
    correct = bool(rows) and all(ok for *_, ok in rows) \
        and window["failed"] == 0
    t_check = time.perf_counter() - t0

    # ---- metrics -----------------------------------------------------------
    rec = {"window": window, "setup_s": setup_s, "peak": peak,
           "config": config, "sizes": sizes, "params": ctx["params"],
           "cell": cell, "setup_counters": setup_counters,
           "in_window_counters": in_window, "rehearse": args.rehearse}
    metrics = {}
    device = dict(dev, memory_peak_bytes=mem_peak,
                  memory_resident_bytes=mem_now)
    out = {}
    if args.trace:
        tr = window.get("trace")
        if tr is not None:
            device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
            out["breakdown"] = {"device_ops": tr["top_ops"],
                                "idle_gaps": window.get("idle_gaps", [])}
        for m in bench["per_layer"]:
            if not layer_in_cell(m, cell, bench):
                continue
            v = reader_of(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if e2e_in_cell(m, cell) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

    compared = {n: {"value": v, "limit": lim, "ok": ok}
                for n, v, lim, ok in rows}
    compared["failed_operations"] = {"value": window["failed"], "limit": 0,
                                     "ok": window["failed"] == 0}
    log(f"reference {t_check:.2f} s; window {window['seconds']:.2f} s; "
        f"memory peak {mem_peak} resident {mem_now}")
    for n, c in compared.items():
        log(f"compared {n}: {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'NOT OK'}")
    line = {"correct": bool(correct), "attempted": int(window["attempted"]),
            "failed": int(window["failed"]), "metrics": metrics,
            "device": device, **out, "compared": compared}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
