"""predict_loop — the window calls `model.predict(frame)` back to back on
the frames of the table the deployment holds, in rotation, through the
normal entry point; predict() returns when the predictions are a Frame in
the DKV. A call is started while elapsed < --seconds, the call in flight
finishes, and the window is all of that time. Set-up cuts the table (the
configuration's size named by `table`) into frames of the size named by
`frame` — the first is the training frame itself — and scores one of them
once: every frame has the same shape, so that warms every program.

The prediction frames of the last `keep_calls` calls stay in the DKV (an
older one is dropped as the next call starts); after the window `finish`
reads `sample_rows` rows of each, drawn from the seed, for the comparison.
With --trace 1 the window is the traced stretch alone: `trace_calls` calls
under the profiler.

Parameters (traffic file): table, frame, keep_calls, sample_rows,
trace_calls.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import profile, trace_reduce


def prepare(ctx):
    import h2o3_tpu
    mix, sizes, X, y = ctx["mix"], ctx["sizes"], ctx["X"], ctx["y"]
    n, total = int(sizes[mix["frame"]]), int(sizes[mix["table"]])
    if n != ctx["frame"].nrows or total % n or total > X.shape[0]:
        raise ValueError(f"predict_loop: table {total} is not whole frames "
                         f"of the training frame's {ctx['frame'].nrows} rows")
    frames = [ctx["frame"]] + [ctx["data"].frame(X[lo:lo + n], y[lo:lo + n])
                               for lo in range(n, total, n)]
    h2o3_tpu.remove(ctx["model"].predict(frames[0]).key)
    return {"frames": frames, "rows": n}


def _calls(ctx, state, more):
    import h2o3_tpu
    keep = int(ctx["mix"]["keep_calls"])
    walls, kept = [], []                    # kept: (frame index, Frame)
    while more(len(walls)):
        if len(kept) == keep:
            h2o3_tpu.remove(kept.pop(0)[1].key)
        k = len(walls) % len(state["frames"])
        t0 = time.perf_counter()
        pred = ctx["model"].predict(state["frames"][k])
        walls.append(time.perf_counter() - t0)
        kept.append((k, pred))
    return walls, kept


def window(ctx, state, seconds, trace):
    from h2o3_tpu.obs.timeline import SPANS
    n = int(ctx["mix"].get("trace_calls", 1))
    tr = profile.Trace(ctx["root"], copy_to=ctx["keep_trace"]) if trace \
        else None
    if trace:
        SPANS.clear()
    t_start = time.perf_counter()
    with tr or contextlib.nullcontext():
        walls, kept = _calls(
            ctx, state, (lambda done: done < n) if trace else
            (lambda done: time.perf_counter() - t_start < seconds))
    total = time.perf_counter() - t_start
    spans = SPANS.snapshot()
    red = tr.reduce() if tr else None
    state["kept"] = kept
    out = {"seconds": total, "attempted": len(walls), "failed": 0,
           "call_walls": walls, "call_rows": state["rows"], "trace": red,
           "end_to_end": {
               "score_rows_per_s": state["rows"] * len(walls) / total}}
    if red is not None and "to_wall" in red:
        label = profile.label_by_spans(spans, "predict.outside_spans")
        out["idle_gaps"] = trace_reduce.label_gaps(
            red["gaps"], lambda s, e: label(red["to_wall"](s),
                                            red["to_wall"](e)))
    ctx["log"](f"predict_loop: {len(walls)} call(s) of {state['rows']} rows, "
               f"walls {[round(w, 3) for w in walls[:12]]} s"
               + (" ..." if len(walls) > 12 else ""))
    return out


def finish(ctx, state, window):
    """The sampled rows of every kept prediction frame: (row ids into the
    host table, p0, p1, label codes)."""
    import h2o3_tpu
    if window is None:
        return None
    rng = np.random.default_rng([ctx["seed"], 0x5C0BE])
    n, dom = state["rows"], ctx["data"].DOMAIN
    take = min(int(ctx["mix"]["sample_rows"]), n)
    scores = []
    for k, pred in state.pop("kept"):
        ids = np.sort(rng.choice(n, take, replace=False))
        try:
            cols = [pred.vec(c).to_numpy()[ids]
                    for c in ("p" + dom[0], "p" + dom[1], "predict")]
            if pred.nrows != n:
                cols = [None] * 3
        except Exception as e:               # a frame that cannot be read
            ctx["log"](f"predict_loop: prediction frame unreadable: {e!r}")
            cols = [None] * 3
        scores.append((k * n + ids, *cols))
        h2o3_tpu.remove(pred.key)
    for fr in state["frames"][1:]:           # [0] is run.py's to remove
        h2o3_tpu.remove(fr.key)
    return scores
