"""score_frame_pct — share of the window's predict() time under the program's
`predict.frame` span: the float64 columns, the Vecs and the Frame of the
prediction frame (its DKV put included). Σ `predict.frame` ÷ Σ `predict`
(models/model.py)."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.stage_pct(rec, "predict.frame")
