"""device_idle_pct.serve — as device_idle_pct.train, in the serving cells
(split because the two move different end-to-end metrics)."""

from benchmark.layer_metrics.device_idle_pct__train import read  # noqa: F401
