"""train_grow_pct — share of the window's train() wall time under
Job.phases["grow"] (the K-tree trainer's dispatches)."""

from benchmark.layer_metrics.train_setup_pct import phase_pct


def read(rec):
    return phase_pct(rec, "grow")
