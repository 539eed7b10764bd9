"""score_dispatch_pct — share of the window's predict() time under the
program's `predict.dispatch` span: `_score_matrix(X)`, which returns at
enqueue: a retrace or an executable rebuilt from the cache shows here. Σ
`predict.dispatch` ÷ Σ `predict` (models/model.py)."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.stage_pct(rec, "predict.dispatch")
