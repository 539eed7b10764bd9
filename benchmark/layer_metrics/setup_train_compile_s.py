"""setup_train_compile_s — seconds set-up's train() spent on executables,
compiled or loaded from the persistent cache
(`h2o3_xla_build_seconds_total{stage=compile, within="job.run"}`,
obs/metrics): the share of warm_compile_s that train() paid; the rest is
publish's and the warm predict() calls'. In a scoring cell train() runs
only in set-up. A program without the counter (an older commit), or a
rehearsed run: None."""


def read(rec):
    if rec["rehearse"]:
        return None
    from h2o3_tpu.obs.metrics import REGISTRY
    m = REGISTRY.get("h2o3_xla_build_seconds_total")
    if m is None:
        return None
    return m.value(stage="compile", within="job.run")
