"""score_wait_spread_pct — how far a frame's place moves its walk: the mean
`predict.wait` (the host blocked on the device) of the window's calls by
the root span's `frame`, 100 x (max − min) ÷ min over the frames. Says THAT
a frame scores slower, not why. None with fewer than two frames."""

from benchmark.layer_metrics import _spans


def read(rec):
    cs = _spans.calls(rec)
    if cs is None:
        return None
    by = {}
    for c in cs:
        for k in c["children"]:
            if k["name"] == "predict.wait":
                by.setdefault(c["root"]["attrs"].get("frame"), []).append(
                    k["end"] - k["start"])
    means = [sum(v) / len(v) for v in by.values()]
    if len(means) < 2 or min(means) <= 0:
        return None
    return 100.0 * (max(means) - min(means)) / min(means)
