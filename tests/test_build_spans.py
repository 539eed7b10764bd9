"""Program builds as spans (obs/metrics `_install_jax_listeners`): every
OUTERMOST jaxpr trace, lowering and backend compile of a thread is one
`xla.*` span in the timeline ring, parented to the span open at its start,
and its seconds land in h2o3_xla_build_seconds_total{stage, within}; the
publish step of train() is timed by h2o3_model_publish_seconds_total; the
benchmark's four readers of them (setup_train_trace_s,
setup_train_compile_s, setup_train_publish_s, score_build_pct)."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.obs.metrics import REGISTRY
from h2o3_tpu.obs.timeline import SPANS, span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BUILDS = REGISTRY.get("h2o3_xla_build_seconds_total")
STAGES = ("trace", "lower", "compile")
XLA = ("xla.trace", "xla.lower", "xla.compile")
READERS = ("setup_train_trace_s", "setup_train_compile_s",
           "setup_train_publish_s", "score_build_pct")


def _fresh_jit(k):
    """A jitted function that calls a jitted inner one and a few jnp ops:
    new function objects, so no cache of jax's knows them."""
    @jax.jit
    def inner(x):
        return jnp.where(x > 0, x, -x) * k

    @jax.jit
    def outer(x):
        return jnp.sin(inner(x)) + jnp.cumsum(x) * k
    return outer


def _builds():
    return {(lab["stage"], lab["within"]): v for lab, v in BUILDS.series()}


def _xla_spans():
    return [s for s in SPANS.snapshot() if s["name"] in XLA]


def test_a_nested_jit_is_one_build_of_each_stage_under_the_open_span():
    outer = _fresh_jit(3.0)
    x = jnp.arange(16, dtype=jnp.float32)
    jax.block_until_ready(x)
    SPANS.clear()
    with span("t.call") as sp:
        t0 = time.time()
        # h2o3-ok: R002 the call's wall, which its builds are held to
        jax.block_until_ready(outer(x))
        wall = time.time() - t0
    got = _xla_spans()
    assert [s["name"] for s in got] == list(XLA)
    # the inner jit and the jnp ops are part of outer's trace
    assert got[0]["attrs"]["fun"] == "outer"
    assert all("outer" in s["attrs"]["fun"] for s in got)
    assert all(s["parent"] == sp.span_id for s in got)
    assert got[2]["attrs"]["cache"] in ("hit", "miss", "off")
    assert sum(s["end"] - s["start"] for s in got) <= wall
    for a, b in zip(got, got[1:]):      # one after the other, no overlap
        assert a["end"] <= b["start"] + 1e-6


def test_a_second_call_of_the_same_shape_builds_nothing():
    outer = _fresh_jit(5.0)
    x = jnp.ones(8, jnp.float32)
    jax.block_until_ready(outer(x))
    before = _builds()
    SPANS.clear()
    jax.block_until_ready(outer(x))
    assert _xla_spans() == []
    assert _builds() == before


@pytest.mark.parametrize("opened,within", [
    ((), "other"), (("job.run",), "job.run"),
    (("model.publish",), "model.publish"), (("predict",), "predict"),
    (("job.run", "predict"), "predict")])
def test_a_build_is_booked_to_the_nearest_owning_span(opened, within):
    outer = _fresh_jit(7.0 + len(opened))
    x = jnp.ones(12, jnp.float32)
    jax.block_until_ready(x)
    before = _builds()
    opens = [span(name) for name in opened + ("t.inner",)]
    for cm in opens:
        cm.__enter__()
    try:
        jax.block_until_ready(outer(x))
    finally:
        for cm in reversed(opens):
            cm.__exit__(None, None, None)
    after = _builds()
    grew = {k for k in after if after[k] > before.get(k, 0.0)}
    assert grew == {(st, within) for st in STAGES}


def test_compile_spans_are_the_compile_counter_events():
    secs = REGISTRY.get("h2o3_xla_compile_seconds_total")
    fns = [_fresh_jit(float(k)) for k in range(4)]
    xs = [jnp.ones(n, jnp.float32) for n in (3, 5, 7, 9)]
    jax.block_until_ready(xs)
    SPANS.clear()
    s0 = secs.value()
    for f, x in zip(fns, xs):
        jax.block_until_ready(f(x))
    grown = secs.value() - s0
    spans = [s for s in _xla_spans() if s["name"] == "xla.compile"]
    assert len(spans) == 4
    assert sum(s["end"] - s["start"] for s in spans) == pytest.approx(
        grown, rel=0.01)


def test_a_compile_names_what_the_persistent_cache_did(tmp_path):
    """`cache` on xla.compile: off with no cache; with one, miss for the
    first build of a program (looked up, compiled) and hit, with the
    retrieval seconds, for the same program built again."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    x = jnp.ones(6, jnp.float32)
    jax.block_until_ready(x)

    def compiled(k):
        SPANS.clear()
        jax.block_until_ready(_fresh_jit(k)(x))
        return [s["attrs"] for s in _xla_spans() if s["name"] == "xla.compile"]
    assert [a["cache"] for a in compiled(13.0)] == ["off"]
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        first, again = compiled(17.0), compiled(17.0)
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert [a["cache"] for a in first] == ["miss"]
    assert [a["cache"] for a in again] == ["hit"]
    assert again[0]["cache_s"] >= 0 and "cache_s" not in first[0]


@pytest.fixture(scope="module")
def small_gbm():
    """A small binned GBM trained twice (the second train() is read: the
    first also builds the process's one-off eager programs), with the
    spans and counters of that second train()."""
    from h2o3_tpu.models import H2OGradientBoostingEstimator
    rng = np.random.default_rng(13)
    n = 400
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    f = Frame.from_dict({
        "x1": x1, "x2": x2,
        "y": np.array(["n", "p"], object)[(x1 + x2 > 0).astype(int)]})
    pub = REGISTRY.get("h2o3_model_publish_seconds_total")
    for _ in range(2):
        SPANS.clear()
        b0, p0 = _builds(), pub.value(algo="gbm")
        m = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=5,
                                         model_id="builds_small_gbm")
        m.train(y="y", training_frame=f)
    spans = SPANS.snapshot()
    b1 = _builds()
    grown = {k: b1[k] - b0.get(k, 0.0) for k in b1}
    yield m, f, spans, grown, pub.value(algo="gbm") - p0
    DKV.remove(f.key)
    DKV.remove(m.key)


def test_train_books_its_builds_to_the_job_and_its_publish(small_gbm):
    _, _, spans, grown, published = small_gbm
    assert grown.get(("trace", "job.run"), 0) > 0
    assert grown.get(("lower", "job.run"), 0) > 0
    assert grown.get(("compile", "job.run"), 0) > 0
    assert published > 0
    by_id = {s["id"]: s for s in spans}
    pub = next(s for s in spans if s["name"] == "model.publish")
    base = next(s for s in spans if s["name"] == "modelmon.baseline")
    assert base["parent"] == pub["id"]
    assert published == pytest.approx(pub["end"] - pub["start"])
    # every train() rebuilds its chunk program under gbm.chunk.build:
    # each executable it builds or loads is an xla.compile span there
    builds = [s for s in spans if s["name"] == "gbm.chunk.build"]
    under = [s for s in spans if s["name"] == "xla.compile"
             and by_id.get(s["parent"], {}).get("name") == "gbm.chunk.build"]
    assert builds and len(under) >= len(builds)
    for s in under:
        assert s["attrs"]["fun"] and s["attrs"]["cache"] in ("hit", "miss",
                                                             "off")


def _read(name, rec):
    from benchmark import run
    return run.reader_of(name)(rec)


def _window(m, f):
    """The ring and a `rec` as a traced window leaves them: predict() roots
    and the driver's call_walls tied to them."""
    SPANS.clear()
    m.predict(f)
    roots = [s for s in SPANS.snapshot() if s["name"] == "predict"]
    return {"rehearse": False,
            "window": {"call_walls": [r["end"] - r["start"] for r in roots]}}


def test_the_four_readers_read_a_train_and_a_warm_window(small_gbm,
                                                         monkeypatch):
    m, f, _, _, _ = small_gbm
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "10")
    m.predict(f)                                    # warm
    rec = _window(m, f)
    got = {n: _read(n, rec) for n in READERS}
    assert all(got[n] is not None and got[n] > 0 for n in READERS[:3]), got
    assert got["score_build_pct"] == 0.0
    assert all(_read(n, dict(rec, rehearse=True)) is None for n in READERS)
    # a row count the window has not seen builds inside it
    g = Frame.from_dict({"x1": np.linspace(-1, 1, 3001),
                         "x2": np.linspace(1, -1, 3001)})
    rec = _window(m, g)
    assert 0 < _read("score_build_pct", rec) <= 100
    DKV.remove(g.key)
    # a program without the counters (an older commit): nothing
    get = REGISTRY.get
    monkeypatch.setattr(REGISTRY, "get", lambda name: None if name in (
        "h2o3_xla_build_seconds_total",
        "h2o3_model_publish_seconds_total") else get(name))
    assert all(_read(n, rec) is None for n in READERS)
