"""_spans — what the PROGRAM's own spans and job phases say about a run;
shared by the span-reading layer metrics (score_*_pct, setup_train_*_s).

`rec` holds no spans, so they are taken from the program as run.py's
`counters()` takes the registry: `h2o3_tpu.obs.timeline.SPANS.snapshot()`
(a traced window starts with `SPANS.clear()`, so the ring holds the
window's calls) and `h2o3_tpu.core.jobs.jobs_list()`.

The inside measurement is tied to the outside one: with `predict` roots in
the ring, their number has to be the driver's `len(call_walls)` and their
summed duration its Σ `call_walls` within 1 % — else `calls` RAISES. A
program without such spans (an older commit) has nothing to read: None.
A rehearsed run reads None too: no line of a CPU run carries a share of a
call (tests/test_span_metrics.py reads the same spans with `rehearse` off).
"""

from __future__ import annotations

ROOT = "predict"
STAGES = ("predict.matrix", "predict.dispatch", "predict.wait",
          "predict.fetch", "predict.frame")
TIE = 0.01          # Σ predict spans vs Σ call_walls


def _cover(root, kids) -> float:
    """Seconds of the root's interval that its children cover (union)."""
    got, end = 0.0, root["start"]
    for k in sorted(kids, key=lambda k: k["start"]):
        lo, hi = max(k["start"], end), min(k["end"], root["end"])
        if hi > lo:
            got, end = got + hi - lo, hi
    return got


def calls(rec):
    """The window's predict() calls, oldest first: [{"root": span,
    "children": [span, ...], "seconds": s, "self_s": s - children's
    cover}], spans as `Span.to_dict()` gives them. None when there is
    nothing to read."""
    walls = rec["window"].get("call_walls")
    if rec["rehearse"] or not walls:
        return None
    from h2o3_tpu.obs.timeline import SPANS
    spans = [s for s in SPANS.snapshot() if s["end"] is not None]
    by_id = {s["id"]: s for s in spans}

    def inside_a_call(s):
        p = by_id.get(s["parent"])
        while p is not None:
            if p["name"] == ROOT:
                return True
            p = by_id.get(p["parent"])
        return False
    roots = [s for s in spans if s["name"] == ROOT and not inside_a_call(s)]
    if not roots:
        return None
    out = []
    for r in roots:
        kids = [s for s in spans if s["parent"] == r["id"]]
        sec = r["end"] - r["start"]
        out.append({"root": r, "children": kids, "seconds": sec,
                    "self_s": sec - _cover(r, kids)})
    total, want = sum(c["seconds"] for c in out), sum(walls)
    if len(out) != len(walls) or abs(total - want) > TIE * want:
        raise ValueError(
            f"_spans: {len(out)} `{ROOT}` roots of {total:.4f} s in the "
            f"ring against {len(walls)} call_walls of {want:.4f} s: the "
            f"program's spans and the driver's clock disagree")
    return out


def stage_seconds(rec, name):
    """(Σ seconds of the calls' children called `name`, Σ seconds of the
    calls), or None when no call has such a child."""
    cs = calls(rec)
    if cs is None:
        return None
    part = [k["end"] - k["start"] for c in cs for k in c["children"]
            if k["name"] == name]
    if not part:
        return None
    return sum(part), sum(c["seconds"] for c in cs)


def stage_pct(rec, name):
    """Share of the window's predict() time under the child span `name`."""
    got = stage_seconds(rec, name)
    return None if got is None else 100.0 * got[0] / got[1]


def setup_job_phases(rec):
    """Job.phases (ms) of set-up's train(): the first model-building job
    in the /3/Jobs document (`job_*` keys outlive run.py's drop_model and
    sort by their counter; only a builder's job books phases)."""
    if rec["rehearse"]:
        return None
    from h2o3_tpu.core.jobs import jobs_list
    for j in jobs_list():
        if j["phases"]:
            return j["phases"]
    return None


def phase_seconds(rec, *names):
    """Σ seconds of those phases of set-up's train(); None unless the
    program booked every one of them."""
    ph = setup_job_phases(rec)
    if ph is None or any(n not in ph for n in names):
        return None
    return sum(ph[n] for n in names) / 1e3
