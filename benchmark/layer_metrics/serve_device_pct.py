"""serve_device_pct — sum of the `device` stage over the sum of all
stages, from the Server-Timing header of every answered request."""

from benchmark.layer_metrics.serve_decode_pct import stage_pct


def read(rec):
    return stage_pct(rec, "device")
