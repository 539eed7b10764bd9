"""setup_train_publish_s — seconds of set-up's train() after its job: the
`model.publish` step (the drift baseline under `modelmon.baseline`, the
DKV put, the optional serving pre-warm), read from
`h2o3_model_publish_seconds_total` (models/model.py) summed over `algo`.
In a scoring cell train() runs only in set-up. A program without the
counter (an older commit), or a rehearsed run: None."""


def read(rec):
    if rec["rehearse"]:
        return None
    from h2o3_tpu.obs.metrics import REGISTRY
    m = REGISTRY.get("h2o3_model_publish_seconds_total")
    if m is None:
        return None
    return sum(v for _, v in m.series())
