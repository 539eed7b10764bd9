"""score_link_pct — share of the window's predict() time the host spends
dispatching the K-class link after the walk is enqueued: Σ `predict.link` ÷
Σ `predict` (models/tree/shared_tree.py `_score_matrix`: the margins and
their softmax, ONE program; the span lies inside `predict.dispatch`, as
`predict.tables` does, and is read as score_tables_pct reads that one).
Tied to the driver's `call_walls` as `_spans.calls` ties the others. A
program without the span: nothing."""

from benchmark.layer_metrics import _spans

NAME = "predict.link"


def read(rec):
    cs = _spans.calls(rec)
    if cs is None:
        return None
    from h2o3_tpu.obs.timeline import SPANS
    spans = [s for s in SPANS.snapshot() if s["end"] is not None]
    by_id = {s["id"]: s for s in spans}
    roots = {c["root"]["id"] for c in cs}

    def in_a_call(s):
        while s is not None and s["id"] not in roots:
            s = by_id.get(s["parent"])
        return s is not None
    part = [s["end"] - s["start"] for s in spans
            if s["name"] == NAME and in_a_call(s)]
    if not part:
        return None
    return 100.0 * sum(part) / sum(c["seconds"] for c in cs)
