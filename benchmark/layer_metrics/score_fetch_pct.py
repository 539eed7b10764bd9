"""score_fetch_pct — share of the window's predict() time under the program's
`predict.fetch` span: the device→host fetch of the scores. Σ
`predict.fetch` ÷ Σ `predict` (models/model.py)."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.stage_pct(rec, "predict.fetch")
