"""checks/ — the comparisons that decide `correct`, one module per kind of
model, named by the configuration (`check.module`). A module gives
`read_model(m)` (what the program ANSWERED, as plain arrays) and
`compare(what, ...)` for each thing a traffic mix says it `compares`."""

from __future__ import annotations

import math


def verdict(readings: dict, limits: dict):
    """[(name, reading, limit, ok)] for every limit; a reading that is
    missing or not finite is not ok."""
    rows = []
    for name, lim in limits.items():
        v = readings.get(name)
        ok = v is not None and math.isfinite(v) and v <= lim
        rows.append((name, v, lim, bool(ok)))
    return rows
