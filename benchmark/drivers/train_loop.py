"""train_loop — the window calls train() back to back on the resident
training frame through the normal entry point (`run.train_once`). A call
is started while elapsed < --seconds, the call in flight finishes, and the
window is all of that time. The model of the previous call is dropped
from the DKV as the next call starts. With --trace 1 the window is the
traced stretch alone: `trace_calls` calls under the profiler.

Parameters (traffic file): `trace_calls`.
"""

from __future__ import annotations

import contextlib
import time

from benchmark import profile, trace_reduce


def prepare(ctx):
    return {}                      # set-up's own train() warmed every shape


def _calls(ctx, more):
    walls, keys, prev = [], [], None
    while more(len(walls)):
        if prev is not None:
            ctx["drop_model"](prev)
        t0 = time.perf_counter()
        prev = ctx["train_once"](ctx)
        walls.append(time.perf_counter() - t0)
        keys.append(prev.key)
    return walls, keys, prev


def window(ctx, state, seconds, trace):
    from h2o3_tpu.obs.timeline import SPANS
    n = int(ctx["mix"].get("trace_calls", 1))
    tr = profile.Trace(ctx["root"], copy_to=ctx["keep_trace"]) if trace \
        else None
    if trace:
        SPANS.clear()
    t_start = time.perf_counter()
    with tr or contextlib.nullcontext():
        walls, keys, model = _calls(
            ctx, (lambda done: done < n) if trace else
            (lambda done: time.perf_counter() - t_start < seconds))
    total = time.perf_counter() - t_start
    spans = SPANS.snapshot()
    red = tr.reduce() if tr else None
    rows, trees = int(ctx["sizes"]["train_rows"]), int(ctx["params"]["ntrees"])
    out = {"seconds": total, "attempted": len(walls), "failed": 0,
           "model": model, "call_walls": walls,
           "job_phases": ctx["job_phases"](keys), "trace": red,
           "end_to_end": {
               "train_rowtrees_per_s": rows * trees * len(walls) / total}}
    if red is not None and "to_wall" in red:
        label = profile.label_by_spans(spans, "train.outside_job_phases")
        out["idle_gaps"] = trace_reduce.label_gaps(
            red["gaps"], lambda s, e: label(red["to_wall"](s),
                                            red["to_wall"](e)))
    ctx["log"](f"train_loop: {len(walls)} call(s), walls "
               f"{[round(w, 3) for w in walls]} s")
    return out


def finish(ctx, state, window):
    return None                    # nothing was started; no served answers
