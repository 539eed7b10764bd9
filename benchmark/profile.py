"""profile.py — take one profiler trace of a steady stretch and reduce it.

The trace goes to a fixed directory inside the checkout (`bench_out/`,
git-ignored), is reduced by trace_reduce.py and deleted: a few MB a run.
A host-side `TraceAnnotation` at a known wall time maps the trace's clock
onto `time.time()`, so idle gaps can be labelled by what the host says it
was doing then.
"""

from __future__ import annotations

import os
import shutil
import time

from benchmark import trace_reduce

ANCHOR, WINDOW = "bench.anchor", "bench.window"


class Trace:
    def __init__(self, root: str, keep: bool = False, copy_to=None):
        self.dir = os.path.join(root, "bench_out", "trace")
        self.keep, self.copy_to = keep, copy_to
        self.offset_ns = None

    def __enter__(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # device ops + annotations only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.anchor_wall_ns = time.time_ns()
        self._win = jax.profiler.TraceAnnotation(WINDOW)
        self._win.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._win.__exit__(*exc)
        jax.profiler.stop_trace()
        return False

    def reduce(self) -> dict | None:
        """trace_reduce.reduce over the annotated window, plus
        `to_wall(ns)` -> time.time() seconds. None when the trace holds no
        device op (the CPU)."""
        path = trace_reduce.newest_xplane(self.dir)
        pd = trace_reduce.load(path)
        if self.copy_to:
            os.makedirs(self.copy_to, exist_ok=True)
            shutil.copy(path, self.copy_to)
            with open(os.path.join(self.copy_to, "describe.txt"), "w") as fh:
                fh.write(trace_reduce.describe(pd, n=8))
        win = trace_reduce.anchor_ns(pd, WINDOW)
        red = trace_reduce.reduce(pd, window=win)
        a = trace_reduce.anchor_ns(pd, ANCHOR)
        if red is not None and a is not None:
            off = self.anchor_wall_ns - a[0]
            red["to_wall"] = lambda ns: (ns + off) / 1e9
        if not self.keep:
            shutil.rmtree(os.path.dirname(self.dir), ignore_errors=True)
        return red


def label_by_spans(spans, outside: str):
    """label_at for trace_reduce.label_gaps: the innermost of the
    program's own completed spans (obs/timeline: name, start, end in
    wall seconds) that covers the gap's middle."""
    def label_at(t0, t1):
        mid = 0.5 * (t0 + t1)
        inside = [s for s in spans
                  if s["end"] is not None and s["start"] <= mid <= s["end"]]
        if not inside:
            return outside
        return min(inside, key=lambda s: s["end"] - s["start"])["name"]
    return label_at
