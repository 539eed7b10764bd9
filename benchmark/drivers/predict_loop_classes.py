"""predict_loop_classes — predict_loop for a model of K classes. The timed
code IS predict_loop's (`window` is imported, not copied:
`model.predict(frame)` back to back over the table's frames in rotation,
the last `keep_calls` prediction frames kept; `prepare` is predict_loop's
and then one warm call a frame). What `finish` reads of a kept frame
differs: ALL K probability columns, where predict_loop reads a two-class
model's pair.

Parameters (traffic file): predict_loop's.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import predict_loop
from benchmark.drivers.predict_loop import window  # noqa: F401


def prepare(ctx):
    """predict_loop's, and then EVERY frame of the table scored once, not
    the first alone: a frame's columns take the codecs their own values
    allow (a counter whose largest value in this frame passes a byte, a
    column that is constant here), so the second frame's matrix may be
    built by another program than the first's — found compiling inside
    the window (1.9 s of its second call; PERF.md §6, PR 36)."""
    import h2o3_tpu
    state = predict_loop.prepare(ctx)
    for fr in state["frames"][1:]:
        h2o3_tpu.remove(ctx["model"].predict(fr).key)
    return state


def finish(ctx, state, window):
    """The sampled rows of every kept prediction frame: (row ids into the
    host table, P (rows, K) in DOMAIN's order, label codes). A column that
    cannot be read is NaN; a frame that cannot, (ids, None, None)."""
    import h2o3_tpu
    if window is None:
        return None
    rng = np.random.default_rng([ctx["seed"], 0x5C0BE])
    n, dom = state["rows"], ctx["data"].DOMAIN
    take = min(int(ctx["mix"]["sample_rows"]), n)
    scores = []
    for k, pred in state.pop("kept"):
        ids = np.sort(rng.choice(n, take, replace=False))
        P = np.full((take, len(dom)), np.nan)
        try:
            lab = pred.vec("predict").to_numpy()[ids]
            for c, level in enumerate(dom):
                if "p" + level in pred.names:
                    P[:, c] = pred.vec("p" + level).to_numpy()[ids]
            if pred.nrows != n:
                P = lab = None
        except Exception as e:               # a frame that cannot be read
            ctx["log"](f"predict_loop_classes: prediction frame "
                       f"unreadable: {e!r}")
            P = lab = None
        scores.append((k * n + ids, P, lab))
        h2o3_tpu.remove(pred.key)
    for fr in state["frames"][1:]:           # [0] is run.py's to remove
        h2o3_tpu.remove(fr.key)
    return scores
