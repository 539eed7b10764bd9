"""setup_train_trace_s — seconds set-up's train() spent turning Python into
programs: the jaxpr traces and MLIR lowerings booked inside `job.run`
(`h2o3_xla_build_seconds_total{stage=trace|lower, within="job.run"}`,
obs/metrics; every train() traces and lowers its chunk program again under
`gbm.chunk.build`). Outermost builds only: the jnp ops traced inside a
jit's trace are part of it. The counter outlives the traced window's
`SPANS.clear()`, and in a scoring cell train() runs only in set-up. A
program without the counter (an older commit), or a rehearsed run: None."""


def read(rec):
    if rec["rehearse"]:
        return None
    from h2o3_tpu.obs.metrics import REGISTRY
    m = REGISTRY.get("h2o3_xla_build_seconds_total")
    if m is None:
        return None
    return sum(v for lab, v in m.series() if lab["within"] == "job.run"
               and lab["stage"] in ("trace", "lower"))
