"""Tests of the span-reading layer metrics (layer_metrics/_spans.py and the
nine readers on it) — CPU only. Run:
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A rehearsed traced run of a `.score` cell, steered onto the large-frame
path (the rehearsal's frames are bucket-sized), leaves the program's spans
in the ring and set-up's job in the DKV; the readers are then read on the
run's own `rec` with `rehearse` off — a rehearsed run's line itself
carries no share of a CPU call (test_benchmark.py pins that).
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run                               # noqa: E402
from benchmark.layer_metrics import _spans              # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    B = json.load(_fh)
CELL = "gbm_higgs_defaults.score"
STAGE_SHARES = ["score_matrix_pct", "score_dispatch_pct", "score_fetch_pct",
                "score_frame_pct"]
NINE = ["score_host_pct"] + STAGE_SHARES + [
    "score_wait_spread_pct", "setup_train_bin_s", "setup_train_grow_s",
    "setup_train_metrics_s"]


def _rehearsed_rec(monkeypatch, capsys):
    """One rehearsed traced run on the large-frame path; the `rec` run.py
    handed its readers, and the line it printed."""
    monkeypatch.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", "1000")
    seen = {}
    reader_of = run.reader_of

    def capture(name):
        def read(rec):
            seen["rec"] = rec
            return reader_of(name)(rec)
        return read
    monkeypatch.setattr(run, "reader_of", capture)
    rc = run.main(["--workload", CELL, "--seed", "2987654321", "--seconds",
                   "0.5", "--trace", "1", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return seen["rec"], line


def test_the_nine_are_entries_with_readers():
    names = [m["name"] for m in B["per_layer"]]
    assert names[-9:] == NINE
    for m in B["per_layer"][-9:]:
        assert callable(run.reader_of(m["name"]))
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert CELL in m["workloads"]


def test_a_rehearsed_run_yields_the_nine_and_they_add_up(monkeypatch, capsys):
    rec, line = _rehearsed_rec(monkeypatch, capsys)
    assert line["correct"] is True and line["metrics"] == {}
    assert all(run.reader_of(n)(rec) is None for n in NINE)   # rehearsed

    rec = dict(rec, rehearse=False)
    got = {n: run.reader_of(n)(rec) for n in NINE}
    assert all(v is not None and v > 0 for v in got.values()), got
    calls = _spans.calls(rec)
    assert len(calls) == len(rec["window"]["call_walls"])
    for c in calls:
        assert c["root"]["attrs"]["path"] == "frame"
        assert [k["name"] for k in c["children"]] == list(_spans.STAGES)
        assert 0 <= c["self_s"] <= c["seconds"]
    # the four stage shares and the root's own self time are the host's
    # share: a stage without a span shows as a remainder over 1 point
    rest = got["score_host_pct"] - sum(got[n] for n in STAGE_SHARES)
    self_pct = 100.0 * sum(c["self_s"] for c in calls) / sum(
        c["seconds"] for c in calls)
    assert rest == pytest.approx(self_pct, abs=1e-6)
    # on the chip a call takes seconds and the remainder is held under 1
    # point (PERF.md); a rehearsed call takes ~10 ms, so here the same
    # bound is put on what the remainder is made of: under 1 ms a call
    assert all(c["self_s"] < 1e-3 for c in calls), (rest, got)
    assert 0 < got["score_host_pct"] < 100
    # set-up's train(): the phases of the run's first model-building job
    ph = _spans.setup_job_phases(rec)
    assert {"setup", "grow", "score", "finish", "metrics"} <= set(ph)
    assert got["setup_train_metrics_s"] == pytest.approx(
        (ph["score"] + ph["metrics"]) / 1e3)

    # ---- a planted missing child: predict.fetch patched out --------------
    from h2o3_tpu.obs.timeline import SPANS
    snap = SPANS.snapshot()
    monkeypatch.setattr(SPANS, "snapshot", lambda limit=0: [
        s for s in snap if s["name"] != "predict.fetch"])
    assert run.reader_of("score_fetch_pct")(rec) is None     # never 0
    host = run.reader_of("score_host_pct")(rec)
    rest = host - sum(run.reader_of(n)(rec) or 0.0 for n in STAGE_SHARES)
    assert rest == pytest.approx(got["score_fetch_pct"] + self_pct, abs=1e-6)
    assert rest > self_pct

    # ---- the inside measurement is tied to the outside one ---------------
    monkeypatch.setattr(SPANS, "snapshot", lambda limit=0: [
        s for s in snap if s["id"] != calls[0]["root"]["id"]])
    with pytest.raises(ValueError):                          # a root short
        _spans.calls(rec)
    monkeypatch.setattr(SPANS, "snapshot", lambda limit=0: snap)
    slow = dict(rec, window=dict(rec["window"], call_walls=[
        1.05 * w for w in rec["window"]["call_walls"]]))
    with pytest.raises(ValueError):                          # 5 % apart
        _spans.calls(slow)
    # a program without the spans (an older commit): nothing, no error
    monkeypatch.setattr(SPANS, "snapshot", lambda limit=0: [
        s for s in snap if not s["name"].startswith("predict")])
    assert all(run.reader_of(n)(rec) is None for n in NINE[:6])
