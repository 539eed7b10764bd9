"""trace_checks.py — what run.py does not measure, by hand, on the chip
(PERF.md §5's phase table and §6's PR 27 readings come from here):

    python benchmark/tools/trace_checks.py --out DIR [--full] [--config C]
    python benchmark/tools/trace_checks.py --scopes FILE.xplane.pb

The cost of one empty span(); a warm train()'s Job.phases against its
job.run span; with --full a traced train() and two traced predict()
calls: device time by module, idle gaps by the program's spans, and the
difference between each span's start in the ring and its TraceAnnotation
in the trace mapped by benchmark/profile.py's anchor; 1-row REST latency.
`--root` runs another checkout's program (the parent's) under this file.
`--scopes` reads a kept trace's raw proto and prints device seconds by
named scope: the scope is the `tf_op` stat of an op's XEventMetadata,
which jax.profiler.ProfileData does not show. One process, one chip.
"""

import argparse
import json
import os
import statistics
import sys
import time
import timeit

ap = argparse.ArgumentParser()
ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
ap.add_argument("--out", default=None)
ap.add_argument("--scopes", default=None, metavar="XPLANE")
ap.add_argument("--full", action="store_true")
ap.add_argument("--seed", type=int, default=2345678917)
ap.add_argument("--config", default="gbm_higgs")
ap.add_argument("--rows", type=int, default=0)
args = ap.parse_args()
ROOT = os.path.abspath(args.root)
sys.path.insert(0, ROOT)


def say(*a):
    print(*a, flush=True)


def scopes(path, prefixes=("walk.", "tree.", "bin.", "sbh_")):
    """{(program, scope path): device seconds} over the XLA Ops line of
    every TPU plane — durations as traced, nested ops counted in both."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    by = {}
    for pl in space.planes:
        if not pl.name.startswith("/device:TPU"):
            continue
        names = {k: v.name for k, v in pl.stat_metadata.items()}
        tf_op = {}
        for mid, em in pl.event_metadata.items():
            for st in em.stats:
                if names.get(st.metadata_id) == "tf_op":
                    tf_op[mid] = st.str_value or names.get(st.ref_value, "")
        for ln in pl.lines:
            if ln.name != "XLA Ops":
                continue
            for e in ln.events:
                parts = tf_op.get(e.metadata_id, "").split("/")
                key = (parts[0], "/".join(p for p in parts[1:]
                                          if p.startswith(prefixes)))
                by[key] = by.get(key, 0.0) + e.duration_ps / 1e12
    return by


if args.scopes:
    for (prog, scope), sec in sorted(scopes(args.scopes).items(),
                                     key=lambda kv: -kv[1])[:30]:
        say(f"{sec:12.6f} s  {prog or '-'}  {scope or '-'}")
    sys.exit(0)
if not args.out:
    ap.error("--out is needed unless --scopes is given")
os.makedirs(args.out, exist_ok=True)
RES = {"root": ROOT}


# ---- 1. the cost of one empty span() --------------------------------------
from h2o3_tpu.obs.timeline import SPANS, span          # noqa: E402


def _empty():
    with span("t.cost"):
        pass


N = 100_000
per = [timeit.timeit(_empty, number=N) / N * 1e6 for _ in range(3)]
RES["span_us_no_backend"] = per
say("span() cost us/span (3 x 100000, no backend yet):", per)
SPANS.clear()

import jax                                              # noqa: E402
import h2o3_tpu                                         # noqa: E402
from h2o3_tpu import models                             # noqa: E402
from h2o3_tpu.core.jobs import jobs_list                # noqa: E402
from benchmark import profile, trace_reduce             # noqa: E402
from benchmark.datasets import higgs_like as data       # noqa: E402

h2o3_tpu.init()
dev = jax.devices()[0]
RES["device"] = [dev.platform, dev.device_kind, len(jax.devices())]
say("device", RES["device"])
per = [timeit.timeit(_empty, number=N) / N * 1e6 for _ in range(3)]
RES["span_us"] = per
say("span() cost us/span (backend up):", per)
SPANS.clear()

cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                  args.config + ".json")))
rows = args.rows or int(cfg["sizes"]["train_rows"])
cols = int(cfg["table"]["columns"])
X, y = data.host_arrays(rows, cols, args.seed)
frame = data.frame(X, y)
est = getattr(models, cfg["estimator"])


def train():
    SPANS.clear()
    m = est(**cfg["params"], seed=args.seed & 0x7FFFFFFF)
    t0 = time.perf_counter()
    m.train(y=data.LABEL, training_frame=frame)
    jax.block_until_ready(jax.tree_util.tree_leaves(m._trees))
    wall = time.perf_counter() - t0
    spans = SPANS.snapshot()
    job = next(j for j in jobs_list() if j["dest"] == m.key)
    run = next(s for s in spans if s["name"] == "job.run")
    keep = [(s["name"], round(s["duration_ms"], 2)) for s in spans
            if s["name"].split(".")[0] in ("gbm", "job", "model")]
    out = {"wall_s": wall, "job_run_ms": run["duration_ms"],
           "phases_ms": job["phases"],
           "phases_over_job_run": sum(job["phases"].values())
           / run["duration_ms"], "spans": keep}
    return m, out, spans


def drop(m):
    from h2o3_tpu.core.kvstore import DKV
    for k in [k for k in DKV.keys() if k.startswith(m.key)]:
        h2o3_tpu.remove(k)


m, first, _ = train()
say("train #1 (executables loaded/compiled):", json.dumps(first))
drop(m)
m, warm, _ = train()
say("train #2 (warm):", json.dumps(warm))
RES["train_first"], RES["train_warm"] = first, warm


def host_events(pd, prefix):
    ev = []
    for pl in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(pl.name):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    ev.append((e.name, float(e.start_ns),
                               float(e.duration_ns), pl.name, ln.name))
    return sorted(ev, key=lambda e: e[1])


def kept(tdir):
    return trace_reduce.load([os.path.join(tdir, f) for f in os.listdir(tdir)
                              if f.endswith(".xplane.pb")][0])


def to_wall_of(tr, pd):
    """benchmark/profile.py's anchor, as Trace.reduce builds it."""
    off = tr.anchor_wall_ns - trace_reduce.anchor_ns(pd, profile.ANCHOR)[0]
    return lambda ns: (ns + off) / 1e9


def clock_check(pd, to_wall, spans, prefixes):
    """Each annotation of the trace against the ring's span of the same
    name, matched in start order: worst |start difference| in ms."""
    rows_, worst, worst_d = [], 0.0, 0.0
    for prefix in prefixes:
        ev = host_events(pd, prefix)
        ring = sorted([s for s in spans if s["name"].startswith(prefix)],
                      key=lambda s: s["start"])
        by_name = {}
        for s in ring:
            by_name.setdefault(s["name"], []).append(s)
        seen = {}
        for name, s_ns, d_ns, plane, line in ev:
            i = seen.get(name, 0)
            seen[name] = i + 1
            if name not in by_name or i >= len(by_name[name]):
                rows_.append([name, "no ring span", plane, line])
                continue
            r = by_name[name][i]
            d_start = 1e3 * (to_wall(s_ns) - r["start"])
            d_dur = d_ns / 1e6 - r["duration_ms"]
            worst, worst_d = max(worst, abs(d_start)), max(worst_d,
                                                           abs(d_dur))
            rows_.append([name, round(d_start, 4), round(d_dur, 4), plane,
                          line])
    return {"worst_start_ms": worst, "worst_duration_ms": worst_d,
            "events": rows_}


def dump_stats(pd, path, modules=("jit__ensemble_walk", "jit_run",
                                  "jit__quantize")):
    """Every stat of the first ops of each module: which one carries the
    named scope on this device."""
    out = []
    for pl in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(pl.name):
            continue
        mods = trace_reduce._events(pl, trace_reduce.MODULES_LINE)
        for ln in pl.lines:
            out.append(f"PLANE {pl.name} LINE {ln.name!r}")
            if ln.name not in (trace_reduce.OPS_LINE,
                               trace_reduce.MODULES_LINE):
                ev = list(ln.events)
                for e in ev[:3]:
                    out.append(f"   {e.name[:100]!r} "
                               f"{[(k, str(v)[:200]) for k, v in e.stats]}")
                continue
            seen = {}
            for e in ln.events:
                mod = next((trace_reduce.module_base(n) for n, s, d in mods
                            if s <= e.start_ns < s + d), "")
                if ln.name == trace_reduce.OPS_LINE and mod not in modules:
                    continue
                lab = trace_reduce.op_label(e.name)
                if (mod, lab) in seen or sum(
                        1 for k in seen if k[0] == mod) >= 40:
                    continue
                seen[(mod, lab)] = 1
                out.append(f" {mod}/{lab} dur={e.duration_ns}")
                for k, v in e.stats:
                    out.append(f"     {k} = {str(v)[:400]}")
    with open(path, "w") as fh:
        fh.write("\n".join(out))


def scope_hits(pd, needles=("walk.", "tree.", "bin.search", "sbh_")):
    """{needle: {stat name: events whose stat (or name) holds it}}."""
    hits = {}
    for pl in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(pl.name):
            continue
        for ln in pl.lines:
            for e in ln.events:
                fields = [("<event name>", e.name)] + [
                    (k, str(v)) for k, v in e.stats]
                for k, v in fields:
                    for nd in needles:
                        if nd in v:
                            h = hits.setdefault(nd, {})
                            key = f"{ln.name}:{k}"
                            h[key] = h.get(key, 0) + 1
    return hits


if args.full:
    # ---- a traced warm train(): device time by module against the phases --
    drop(m)
    tdir = os.path.join(args.out, "trace_train")
    tr = profile.Trace(ROOT, copy_to=tdir)
    with tr:
        m, traced, spans = train()
    red = tr.reduce() or {"gaps": [], "module_s": {}, "busy_s": None,
                          "window_s": None, "top_ops": []}   # the CPU
    label = profile.label_by_spans(spans, "outside_spans")
    gaps = trace_reduce.label_gaps(
        red["gaps"], lambda s, e: label(red["to_wall"](s),
                                        red["to_wall"](e)))
    traced.update(module_s=red["module_s"], busy_s=red["busy_s"],
                  window_s=red["window_s"], idle_gaps=gaps,
                  top_ops=red["top_ops"])
    pd = kept(tdir)
    traced["clock"] = clock_check(pd, to_wall_of(tr, pd), spans,
                                  ("job.", "gbm.", "model."))
    traced["scope_hits"] = scope_hits(pd)
    dump_stats(pd, os.path.join(tdir, "stats.txt"))
    q = red["module_s"].get("jit__quantize", 0.0)
    traced["setup_minus_quantize_s"] = \
        traced["phases_ms"].get("setup", 0.0) / 1e3 - q
    say("train #3 (traced):", json.dumps(
        {k: v for k, v in traced.items() if k != "spans"}))
    RES["train_traced"] = traced

    # ---- two traced predict() calls: the anchor, checked ------------------
    pred = m.predict(frame)
    h2o3_tpu.remove(pred.key)
    pdir = os.path.join(args.out, "trace_predict")
    SPANS.clear()
    tr = profile.Trace(ROOT, copy_to=pdir)
    walls = []
    with tr:
        for _ in range(2):
            t0 = time.perf_counter()
            pred = m.predict(frame)
            walls.append(time.perf_counter() - t0)
            h2o3_tpu.remove(pred.key)
    spans = SPANS.snapshot()
    red = tr.reduce() or {"module_s": {}, "top_ops": []}
    pd = kept(pdir)
    chk = clock_check(pd, to_wall_of(tr, pd), spans, ("predict",))
    chk["walls"] = walls
    chk["module_s"] = red["module_s"]
    chk["scope_hits"] = scope_hits(pd)
    chk["top_ops"] = red["top_ops"]
    dump_stats(pd, os.path.join(pdir, "stats.txt"))
    say("predict x2 (traced):", json.dumps(chk))
    RES["predict_traced"] = chk

# ---- 1-row REST latency ---------------------------------------------------
import urllib.request                                   # noqa: E402
from h2o3_tpu.api.server import start_server            # noqa: E402

srv = start_server(port=0)
url = f"http://127.0.0.1:{srv.port}/3/Predictions/models/{m.key}"
body = json.dumps({"columns": data.feature_names(cols),
                   "rows": X[:1].tolist()}).encode()
lat = []
try:
    for i in range(320):
        req = urllib.request.Request(
            url, data=body, method="POST",
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=600) as r:
            assert r.status == 200
            json.loads(r.read())
        lat.append(1e3 * (time.perf_counter() - t0))
finally:
    srv.stop()
lat = lat[20:]
q = statistics.quantiles(lat, n=4)
RES["rest_1row_ms"] = {"n": len(lat), "median": statistics.median(lat),
                       "q1": q[0], "q3": q[2], "min": min(lat)}
say("REST 1-row ms:", json.dumps(RES["rest_1row_ms"]))
with open(os.path.join(args.out, "trace_checks.json"), "w") as fh:
    json.dump(RES, fh)
say("TRACE_CHECKS DONE")
