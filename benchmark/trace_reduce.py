"""trace_reduce.py — .xplane.pb -> device busy intervals, per-op and
per-XLA-module device time, longest idle gaps. Nothing but
`jax.profiler.ProfileData`; tested on the recorded trace under
benchmark/testdata/.

A TPU trace has one plane per chip (`/device:TPU:<n>`) whose line
`XLA Ops` holds one event per executed HLO op and whose line
`XLA Modules` holds one event per executed program (`jit_run(...)`). All
planes share one clock in nanoseconds. Ops nest (a `while` spans its
body's ops), so busy time is the UNION of the intervals and an op's time
is its self time; `anchor_ns` finds a host-side
`TraceAnnotation` so that the caller can map that clock onto its own.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(plane, line_name):
    for ln in plane.lines:
        if ln.name == line_name:
            return [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in ln.events]
    return []


def anchor_ns(pd, name: str):
    """(start, end) in trace ns of the first host event called `name`."""
    for pl in pd.planes:
        if DEVICE_PLANE.match(pl.name):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name == name:
                    s = float(e.start_ns)
                    return s, s + float(e.duration_ns)
    return None


def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_label(name: str) -> str:
    """The trace names an op by its whole HLO text; keep `%name kind`
    (`%while.20 while`, `%sbh_hist_pallas.26 custom-call`)."""
    m = re.match(r"^(%?[\w.\-]+) = .*?\s([\w\-]+)\(", name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def self_times(events):
    """{name: ns of SELF time} of (name, start, duration) events: an op's
    duration minus the ops nested inside it on the same line (a `while`
    holds its body's ops)."""
    out, stack = {}, []                       # stack of [end, label]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        lab = name
        out[lab] = out.get(lab, 0.0) + d
        if stack:
            out[stack[-1][1]] -= min(d, stack[-1][0] - s)
        stack.append([s + d, lab])
    return out


def module_base(name: str) -> str:
    """`jit_run(1234567)` -> `jit_run`: ids differ between processes."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(pd, window=None, top: int = 10) -> dict | None:
    """Per-chip reduction averaged over the device planes. `window` =
    (start, end) in trace ns; default the span of the device events.
    Returns None when no device plane holds an op."""
    planes = [pl for pl in pd.planes if DEVICE_PLANE.match(pl.name)]
    per = []
    for pl in planes:
        ops = _events(pl, OPS_LINE)
        if ops:
            per.append((ops, _events(pl, MODULES_LINE)))
    if not per:
        return None
    if window is None:
        window = (min(s for ops, _ in per for _, s, _ in ops),
                  max(s + d for ops, _ in per for _, s, d in ops))
    w0, w1 = window
    busy = 0.0
    op_s, mod_s, gaps = {}, {}, []
    for ops, mods in per:
        mods = sorted(mods, key=lambda m: m[1])
        starts = [m[1] for m in mods]

        def module_of(t):
            """The program an op ran in: ops carry anonymous names
            (`%fusion.16`), the module says whose they are."""
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < mods[i][1] + mods[i][2]:
                return module_base(mods[i][0]) + "/"
            return ""
        inside = [(module_of(s) + op_label(n), max(s, w0),
                   min(s + d, w1) - max(s, w0))
                  for n, s, d in ops if s + d > w0 and s < w1]
        merged = union([(s, s + d) for _, s, d in inside])
        busy += sum(e - s for s, e in merged)
        for lab, v in self_times(inside).items():
            op_s[lab] = op_s.get(lab, 0.0) + v
        for name, s, d in mods:
            if s + d > w0 and s < w1:
                k = module_base(name)
                mod_s[k] = mod_s.get(k, 0.0) + (min(s + d, w1) - max(s, w0))
        edges = [w0] + [t for se in merged for t in se] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    k = float(len(per))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "chips": len(per), "window": (w0, w1),
        "window_s": (w1 - w0) / 1e9, "busy_s": busy / k / 1e9,
        "op_s": {n: v / k / 1e9 for n, v in op_s.items()},
        "module_s": {n: v / k / 1e9 for n, v in mod_s.items()},
        "top_ops": [[n, v / k / 1e9] for n, v in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:top]],
        "gaps": gaps[:4 * top],       # (start, end) trace ns, longest first
    }


def label_gaps(gaps, label_at, top: int = 10):
    """Sum the idle gaps by what `label_at(start_ns, end_ns)` says the
    host was doing; the `top` labels by seconds."""
    by = {}
    for s, e in gaps:
        name = label_at(s, e)
        by[name] = by.get(name, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]


def describe(pd, n: int = 3) -> str:
    """What a trace holds — for looking at one by hand."""
    out = []
    for pl in pd.planes:
        out.append(f"PLANE {pl.name}")
        for ln in pl.lines:
            ev = list(ln.events)
            out.append(f"  LINE {ln.name!r} events={len(ev)}")
            for e in ev[:n]:
                out.append(f"    {e.name!r} start={e.start_ns} "
                           f"dur={e.duration_ns} stats={list(e.stats)[:6]}")
    return "\n".join(out)
