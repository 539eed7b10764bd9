"""serve_decode_pct — sum of the `decode` stage over the sum of all
stages, from the Server-Timing header of every answered request of the
window (stages: obs/usage.py STAGE_ORDER)."""

STAGE = "decode"


def stage_pct(rec, stage):
    t = rec["window"].get("server_timing")
    if not t or sum(t.values()) <= 0:
        return None
    return 100.0 * t.get(stage, 0.0) / sum(t.values())


def read(rec):
    return stage_pct(rec, STAGE)
