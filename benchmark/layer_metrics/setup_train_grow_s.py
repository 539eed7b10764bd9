"""setup_train_grow_s — seconds of set-up's train() under Job.phases["grow"]:
the K-tree trainer's build (`gbm.chunk.build`: the executable comes from
the cache in every call), its dispatch and the wait for its trees."""

from benchmark.layer_metrics import _spans


def read(rec):
    return _spans.phase_seconds(rec, "grow")
