"""score_build_pct — share of the window's predict() time spent building
programs: 100 × Σ seconds of the `xla.trace` / `xla.lower` / `xla.compile`
spans under one of the window's `predict` roots ÷ Σ `predict` (obs/metrics
books each outermost jax build as such a span, named by its `fun`). 0 in a
warm window; above 0, a program was built inside the timed window. Tied to
the driver's `call_walls` as `_spans.calls` ties the others. A program
without the build counter (an older commit): nothing."""

from benchmark.layer_metrics import _spans

NAMES = ("xla.trace", "xla.lower", "xla.compile")


def read(rec):
    cs = _spans.calls(rec)
    from h2o3_tpu.obs.metrics import REGISTRY
    if cs is None or REGISTRY.get("h2o3_xla_build_seconds_total") is None:
        return None
    from h2o3_tpu.obs.timeline import SPANS
    spans = [s for s in SPANS.snapshot() if s["end"] is not None]
    by_id = {s["id"]: s for s in spans}
    roots = {c["root"]["id"] for c in cs}

    def in_a_call(s):
        while s is not None and s["id"] not in roots:
            s = by_id.get(s["parent"])
        return s is not None
    part = [s["end"] - s["start"] for s in spans
            if s["name"] in NAMES and in_a_call(s)]
    return 100.0 * sum(part) / sum(c["seconds"] for c in cs)
