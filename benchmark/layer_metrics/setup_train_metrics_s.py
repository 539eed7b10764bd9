"""setup_train_metrics_s — seconds of set-up's train() spent scoring what
it built: Job.phases["score"] (the scoring history, per chunk) +
Job.phases["metrics"] (the training-metrics walk over the whole frame,
Model.train). None from a program that does not book `metrics`."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.phase_seconds(rec, "score", "metrics")
