"""score_matrix_pct — share of the window's predict() time under the
program's `predict.matrix` span: `DataInfo.matrix(frame)`: adapt, stack
and the `jit_build` concat's dispatch. Σ `predict.matrix` ÷ Σ `predict`
(models/model.py)."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.stage_pct(rec, "predict.matrix")
