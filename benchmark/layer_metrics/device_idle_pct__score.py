"""device_idle_pct.score — as device_idle_pct.train, in the frame-scoring
cells (split because the two move different end-to-end metrics)."""

from benchmark.layer_metrics.device_idle_pct__train import read  # noqa: F401
