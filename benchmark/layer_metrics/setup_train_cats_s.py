"""setup_train_cats_s — seconds of set-up's train() under
Job.phases["setup.cats"] = span `gbm.bin.cats` (shared_tree._binned_setup):
the byte planes of a frame with a categorical column past a code byte,
from the dispatch of their program until the device HAS made them. Inside
the `setup` phase that setup_train_bin_s reads. A program that books no
such phase: nothing."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.phase_seconds(rec, "setup.cats")
