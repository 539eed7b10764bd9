"""score_host_pct — share of the window's predict() time in which the host
was NOT blocked on the device: 100 x (Σ `predict` − Σ `predict.wait`) ÷
Σ `predict`, from the program's own spans (models/model.py). Matrix build,
dispatch, fetch, prediction frame and the root's own self time; the four
stage shares (score_matrix_pct … score_frame_pct) add up to it within the
root's self time — over 1 point, a stage is missing a span."""

from benchmark.layer_metrics import _spans


def read(rec):
    got = _spans.stage_seconds(rec, "predict.wait")
    return None if got is None else 100.0 * (got[1] - got[0]) / got[1]
