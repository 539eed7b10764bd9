"""setup_train_bin_s — seconds of set-up's train() under Job.phases["setup"]:
the quantile spec, the code plane and its device_put, ended when the device
HAS binned (shared_tree._binned_setup_phase)."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.phase_seconds(rec, "setup")
