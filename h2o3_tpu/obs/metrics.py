"""Metrics registry — water/util/WaterMeter* rebuilt as a Prometheus-style
process registry.

Reference: WaterMeterCpuTicks.java / WaterMeterIo.java expose per-node
counters over REST for external scrapers; H2O has no first-class metric
types. Here the registry is explicit — counters, gauges and fixed-bucket
histograms with label support — because the TPU runtime's interesting
numbers (HBM in use, compile-cache hits, rows·trees/s) don't fall out of
/proc the way CPU ticks do.

Exposed at GET /metrics (text exposition format 0.0.4) and GET
/3/WaterMeter (JSON) by api/server.py. One registry per process; workers
in a multi-host cloud serve their own /metrics, and the span timeline (not
the registry) is what gets merged cloud-wide.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Callable, Optional

# Default latency buckets (seconds): sub-ms dispatches up to multi-minute
# jobs — one decade finer at the low end than Prometheus' defaults because
# device-program enqueues sit in the 0.1-10ms range.
DEFAULT_BUCKETS = (0.0001, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 300.0)


def _label_key(labels: dict) -> tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(key: tuple, extra: tuple = ()) -> str:
    items = list(key) + list(extra)
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"' for k, v in items) + "}"


def _fmt_num(v: float) -> str:
    f = float(v)
    return str(int(f)) if f.is_integer() and abs(f) < 2 ** 53 else repr(f)


class _Metric:
    kind = ""

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._series: dict = {}

    def clear(self):
        with self._lock:
            self._series.clear()


class Counter(_Metric):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels):
        if amount < 0:
            raise ValueError(f"counter {self.name}: negative increment")
        k = _label_key(labels)
        with self._lock:
            self._series[k] = self._series.get(k, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0.0)

    def series(self) -> list:
        """[(labels_dict, value)] for every live series."""
        with self._lock:
            return [(dict(k), v) for k, v in sorted(self._series.items())]

    def remove(self, **labels):
        """Drop one label series — the per-entity hygiene discipline
        (see Gauge.remove): a deleted model's counters must leave
        /metrics entirely, not linger as frozen series. Scrapers see a
        counter reset, which Prometheus-style rate() already handles."""
        with self._lock:
            self._series.pop(_label_key(labels), None)

    def _expose(self) -> list:
        with self._lock:
            items = sorted(self._series.items())
        return [f"{self.name}{_fmt_labels(k)} {_fmt_num(v)}"
                for k, v in items]

    def _json(self):
        with self._lock:
            return [{"labels": dict(k), "value": v}
                    for k, v in sorted(self._series.items())]


class Gauge(_Metric):
    """Settable gauge, or a callback gauge when `fn` is given: fn() returns
    a scalar or a {labels_dict: value}-style list of (labels, value) pairs,
    evaluated at scrape time (WaterMeter's read-on-request semantics)."""

    kind = "gauge"

    def __init__(self, name: str, help: str = "",
                 fn: Optional[Callable] = None):
        super().__init__(name, help)
        self._fn = fn

    def set(self, value: float, **labels):
        with self._lock:
            self._series[_label_key(labels)] = float(value)

    def inc(self, amount: float = 1.0, **labels):
        k = _label_key(labels)
        with self._lock:
            self._series[k] = self._series.get(k, 0.0) + amount

    def remove(self, **labels):
        """Drop one label series — for per-entity gauges (per-model HBM
        occupancy) whose entity was deleted: a freed model must leave
        /metrics entirely, not linger as a forever-zero series."""
        with self._lock:
            self._series.pop(_label_key(labels), None)

    def value(self, **labels) -> float:
        for k, v in self._collect():
            if k == _label_key(labels):
                return v
        return 0.0

    def _collect(self) -> list:
        if self._fn is not None:
            try:
                out = self._fn()
            except Exception:   # noqa: BLE001 — a dead probe must not 500 /metrics
                # the scrape stays alive (this gauge just emits no
                # series), but the failure is COUNTED — a silently dead
                # probe looks exactly like a healthy zero otherwise
                _note_collect_error(self.name)
                return []
            if isinstance(out, (int, float)):
                return [((), float(out))]
            return [(_label_key(dict(lbl)), float(v)) for lbl, v in out]
        with self._lock:
            return sorted(self._series.items())

    def _expose(self) -> list:
        return [f"{self.name}{_fmt_labels(k)} {_fmt_num(v)}"
                for k, v in self._collect()]

    def _json(self):
        return [{"labels": dict(k), "value": v} for k, v in self._collect()]


class Histogram(_Metric):
    """Fixed-bucket cumulative histogram (Prometheus semantics: _bucket
    series are cumulative counts with a +Inf catch-all, plus _sum/_count)."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", buckets=None):
        super().__init__(name, help)
        self.buckets = tuple(sorted(buckets or DEFAULT_BUCKETS))

    def observe(self, value: float, exemplar: Optional[str] = None,
                **labels):
        """Record one observation. `exemplar` is NOT a label: it is an
        OpenMetrics exemplar — typically the observing request's trace id
        — remembered per bucket and emitted by openmetrics_text() so a
        latency spike on a dashboard clicks through to a stored trace."""
        k = _label_key(labels)
        v = float(value)
        with self._lock:
            st = self._series.get(k)
            if st is None:
                st = self._series[k] = {
                    "counts": [0] * (len(self.buckets) + 1),
                    "sum": 0.0, "count": 0}
            for i, ub in enumerate(self.buckets):
                if v <= ub:
                    st["counts"][i] += 1
                    break
            else:
                i = len(self.buckets)
                st["counts"][-1] += 1
            st["sum"] += v
            st["count"] += 1
            if exemplar:
                # last-write-wins per bucket: the freshest exemplar is
                # the most likely to still be in the flight recorder
                st.setdefault("exemplars", {})[i] = (
                    str(exemplar), v, _time.time())

    def time(self, **labels):
        """Context manager: observe the block's wall time in seconds."""
        import contextlib
        import time as _time

        @contextlib.contextmanager
        def _cm():
            t0 = _time.perf_counter()
            try:
                yield
            finally:
                self.observe(_time.perf_counter() - t0, **labels)
        return _cm()

    def snapshot(self, **labels) -> dict:
        with self._lock:
            st = self._series.get(_label_key(labels))
            if st is None:
                return {"sum": 0.0, "count": 0,
                        "counts": [0] * (len(self.buckets) + 1)}
            return {"sum": st["sum"], "count": st["count"],
                    "counts": list(st["counts"])}

    def series_snapshots(self) -> list:
        """[(labels_dict, {"sum","count","counts"})] for every live
        series — the SLO engine's window sampler walks this."""
        with self._lock:
            return [(dict(k), {"sum": s["sum"], "count": s["count"],
                               "counts": list(s["counts"])})
                    for k, s in sorted(self._series.items())]

    def _expose(self, exemplars: bool = False) -> list:
        """Cumulative-bucket text exposition; with `exemplars` (the
        OpenMetrics renderer) each bucket a stored exemplar covers gets
        `... # {trace_id="<id>"} <value> <unix_ts>` appended."""
        with self._lock:
            items = sorted((k, {"counts": list(s["counts"]),
                                "sum": s["sum"], "count": s["count"],
                                "ex": dict(s.get("exemplars") or {})
                                if exemplars else {}})
                           for k, s in self._series.items())
        lines = []
        for k, st in items:
            cum = 0
            bounds = [(_fmt_num(ub), c)
                      for ub, c in zip(self.buckets, st["counts"])]
            bounds.append(("+Inf", st["counts"][-1]))
            for i, (le, c) in enumerate(bounds):
                cum += c
                line = (f"{self.name}_bucket"
                        f"{_fmt_labels(k, (('le', le),))} {cum}")
                ex = st["ex"].get(i)
                if ex is not None:
                    tid, v, ts = ex
                    line += (f' # {{trace_id="{_escape(tid)}"}} '
                             f"{_fmt_num(v)} {ts:.3f}")
                lines.append(line)
            lines.append(f"{self.name}_sum{_fmt_labels(k)}"
                         f" {_fmt_num(st['sum'])}")
            lines.append(f"{self.name}_count{_fmt_labels(k)} {st['count']}")
        return lines

    def _json(self):
        bounds = [_fmt_num(b) for b in self.buckets] + ["+Inf"]
        with self._lock:
            out = []
            for k, s in sorted(self._series.items()):
                d = {"labels": dict(k), "sum": s["sum"],
                     "count": s["count"],
                     "buckets": dict(zip(bounds, s["counts"]))}
                ex = s.get("exemplars")
                if ex:
                    # exemplars ride the JSON snapshot so the CLUSTER
                    # merge can re-emit them host-tagged (the ISSUE-7
                    # gap: the federated scrape used to strip them)
                    d["exemplars"] = [
                        {"le": bounds[i], "trace_id": tid,
                         "value": v, "ts": ts}
                        for i, (tid, v, ts) in sorted(ex.items())]
                out.append(d)
            return out


class MetricsRegistry:
    def __init__(self):
        # lockdep-instrumented (lock class "metrics.registry"): the
        # registry nests under every subsystem that declares or scrapes.
        # Local import — lockdep's own counters import THIS module, so a
        # top-level import would cycle; per-series _Metric._lock objects
        # stay plain threading.Lock (leaf locks on the counter hot path).
        from h2o3_tpu.analysis.lockdep import make_lock
        self._lock = make_lock("metrics.registry")
        self._metrics: dict[str, _Metric] = {}

    def _get_or_make(self, cls, name, help, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise TypeError(f"metric {name!r} already registered "
                                    f"as {m.kind}")
                return m
            m = cls(name, help, **kw)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_make(Counter, name, help)

    def gauge(self, name: str, help: str = "",
              fn: Optional[Callable] = None) -> Gauge:
        return self._get_or_make(Gauge, name, help, fn=fn)

    def histogram(self, name: str, help: str = "",
                  buckets=None) -> Histogram:
        return self._get_or_make(Histogram, name, help, buckets=buckets)

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def unregister(self, name: str):
        with self._lock:
            self._metrics.pop(name, None)

    def metrics(self) -> list:
        with self._lock:
            return sorted(self._metrics.values(), key=lambda m: m.name)

    # ---- exposition -----------------------------------------------------
    def prometheus_text(self) -> str:
        """Text exposition format 0.0.4 (the GET /metrics body)."""
        out = []
        for m in self.metrics():
            out.append(f"# HELP {m.name} {_escape(m.help)}")
            out.append(f"# TYPE {m.name} {m.kind}")
            out.extend(m._expose())
        return "\n".join(out) + "\n"

    def openmetrics_text(self) -> str:
        """OpenMetrics 1.0 exposition — what Prometheus negotiates (via
        Accept) when --enable-feature=exemplar-storage wants exemplars.
        Differences from 0.0.4 that matter here: counter families drop
        the _total suffix in metadata (samples keep it), histogram
        _bucket samples may carry `# {trace_id="..."} value ts`
        exemplars, and the body terminates with `# EOF`."""
        out = []
        for m in self.metrics():
            family = m.name
            if m.kind == "counter" and family.endswith("_total"):
                family = family[: -len("_total")]
            out.append(f"# HELP {family} {_escape(m.help)}")
            out.append(f"# TYPE {family} {m.kind}")
            if isinstance(m, Histogram):
                out.extend(m._expose(exemplars=True))
            else:
                out.extend(m._expose())
        out.append("# EOF")
        return "\n".join(out) + "\n"

    def to_dict(self) -> dict:
        """JSON exposition (the GET /3/WaterMeter body)."""
        return {m.name: {"kind": m.kind, "help": m.help,
                         "series": m._json()}
                for m in self.metrics()}


REGISTRY = MetricsRegistry()

COLLECT_ERRORS = REGISTRY.counter(
    "h2o3_metric_collect_errors_total",
    "gauge callback exceptions swallowed during a scrape (the scrape "
    "stays alive; the failing gauge emits no series)")


def _note_collect_error(gauge_name: str):
    """Count a gauge callback exception (Gauge._collect swallowed it so
    the scrape survives). A function, not an inline emit: Gauge is
    defined before the module-level REGISTRY/COLLECT_ERRORS exist."""
    COLLECT_ERRORS.inc(metric=gauge_name)


def counter(name: str, help: str = "") -> Counter:
    return REGISTRY.counter(name, help)


def gauge(name: str, help: str = "", fn: Optional[Callable] = None) -> Gauge:
    return REGISTRY.gauge(name, help, fn=fn)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    return REGISTRY.histogram(name, help, buckets=buckets)


# ---------------------------------------------------------------------------
# Cluster metrics federation (ISSUE 5). Workers ship REGISTRY.to_dict()
# snapshots over the replay channel (deploy/multihost._collect_local); the
# coordinator merges them here with a per-host `host=` label. Counters and
# histograms stay summable downstream (Prometheus `sum without (host)`);
# gauges stay per-host by construction — HBM on host 2 is not HBM on
# host 0. A host that outwaits the collect deadline is simply absent from
# the merge, counted in h2o3_cluster_scrape_timeouts_total by the caller.
CLUSTER_SCRAPE_TIMEOUTS = REGISTRY.counter(
    "h2o3_cluster_scrape_timeouts_total",
    "hosts absent from a cluster-scope metrics scrape — they outwaited "
    "the collect deadline (H2O3_OBS_COLLECT_TIMEOUT_S) or answered with "
    "an error; their series are missing from that merge")


def merge_cluster_snapshots(snapshots: list) -> dict:
    """[(host, REGISTRY.to_dict()-shaped dict)] → one merged dict of the
    same shape, every series labeled host=<id>. Kind/help come from the
    first host that declares the metric (hosts run the same code, so
    drift here would be a deploy skew, not a merge concern)."""
    merged: dict = {}
    for host, snap in snapshots:
        for name, m in (snap or {}).items():
            dst = merged.setdefault(name, {"kind": m.get("kind", "gauge"),
                                           "help": m.get("help", ""),
                                           "series": []})
            for s in m.get("series") or []:
                s2 = dict(s)
                s2["labels"] = dict(s.get("labels") or {}, host=str(host))
                if s.get("exemplars"):
                    # host-tag each exemplar too: the trace id resolves
                    # at GET /3/Trace/{id} on the coordinator either
                    # way, but Grafana shows WHICH host observed it
                    s2["exemplars"] = [dict(e, host=str(host))
                                       for e in s["exemplars"]]
                dst["series"].append(s2)
    return merged


def _exemplar_suffix(exemplars: list, le: str) -> str:
    """OpenMetrics exemplar suffix for one merged bucket line, or ""."""
    for e in exemplars or ():
        if e.get("le") == le and e.get("trace_id"):
            lbls = f'trace_id="{_escape(str(e["trace_id"]))}"'
            if e.get("host") is not None:
                lbls += f',host="{_escape(str(e["host"]))}"'
            return (f" # {{{lbls}}} {_fmt_num(e.get('value', 0.0))}"
                    f" {float(e.get('ts', 0.0)):.3f}")
    return ""


def _render_series(name: str, kind: str, series: list,
                   exemplars: bool = False) -> list:
    """Exposition lines for one metric's merged JSON series (the
    registry's _expose over live objects, re-done over snapshots that
    crossed the wire as JSON). With `exemplars` (the cluster OpenMetrics
    renderer) histogram bucket lines re-emit the host-tagged exemplars
    the snapshots carried."""
    lines = []
    for s in series:
        key = _label_key(s.get("labels") or {})
        ex = s.get("exemplars") if exemplars else None
        if kind == "histogram":
            buckets = s.get("buckets") or {}
            cum = 0
            for ub, c in buckets.items():
                if ub == "+Inf":
                    continue
                cum += int(c)
                lines.append(f"{name}_bucket"
                             f"{_fmt_labels(key, (('le', ub),))} {cum}"
                             + _exemplar_suffix(ex, ub))
            cum += int(buckets.get("+Inf", 0))
            lines.append(f"{name}_bucket"
                         f"{_fmt_labels(key, (('le', '+Inf'),))} {cum}"
                         + _exemplar_suffix(ex, "+Inf"))
            lines.append(f"{name}_sum{_fmt_labels(key)}"
                         f" {_fmt_num(s.get('sum', 0.0))}")
            lines.append(f"{name}_count{_fmt_labels(key)}"
                         f" {int(s.get('count', 0))}")
        else:
            lines.append(f"{name}{_fmt_labels(key)}"
                         f" {_fmt_num(s.get('value', 0.0))}")
    return lines


def cluster_prometheus_text(snapshots: list) -> str:
    """Text exposition 0.0.4 of the merged cluster view (the
    GET /metrics?scope=cluster body)."""
    merged = merge_cluster_snapshots(snapshots)
    out = []
    for name in sorted(merged):
        m = merged[name]
        out.append(f"# HELP {name} {_escape(m['help'])}")
        out.append(f"# TYPE {name} {m['kind']}")
        out.extend(_render_series(name, m["kind"], m["series"]))
    return "\n".join(out) + "\n"


def cluster_openmetrics_text(snapshots: list) -> str:
    """OpenMetrics 1.0 exposition of the merged cluster view — the
    GET /metrics?scope=cluster body when the scraper negotiates
    OpenMetrics: same merge as cluster_prometheus_text, but histogram
    buckets keep their (host-tagged) exemplars so Grafana click-through
    works on the federated scrape too."""
    merged = merge_cluster_snapshots(snapshots)
    out = []
    for name in sorted(merged):
        m = merged[name]
        family = name
        if m["kind"] == "counter" and family.endswith("_total"):
            family = family[: -len("_total")]
        out.append(f"# HELP {family} {_escape(m['help'])}")
        out.append(f"# TYPE {family} {m['kind']}")
        out.extend(_render_series(name, m["kind"], m["series"],
                                  exemplars=True))
    out.append("# EOF")
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# Runtime gauges: JAX device memory, DKV census, XLA compile cache.
def _device_memory_series():
    import jax
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if not stats:
            continue
        lbl = {"device": str(d.id)}
        if "bytes_in_use" in stats:
            out.append((dict(lbl, kind="bytes_in_use"),
                        stats["bytes_in_use"]))
        if "peak_bytes_in_use" in stats:
            out.append((dict(lbl, kind="peak_bytes_in_use"),
                        stats["peak_bytes_in_use"]))
        if "bytes_limit" in stats:
            out.append((dict(lbl, kind="bytes_limit"),
                        stats["bytes_limit"]))
    return out


def _dkv_series():
    from h2o3_tpu.core.kvstore import DKV
    st = DKV.stats()
    return [({"what": "keys"}, st["keys"]),
            ({"what": "frames"}, st["frames"]),
            ({"what": "frame_bytes"}, st["frame_bytes"]),
            ({"what": "write_locked"}, st["write_locked"])]


_JAX_LISTENERS_INSTALLED = False

# jax's three stages of building a program (jax/_src/dispatch.py
# log_elapsed_time): a scalar event as one starts, a time span as it ends
_BUILD_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                 "/jax/core/compile/backend_compile_duration": "compile"}
# what a build is booked to (`within`): the nearest of these open spans
_BUILD_OWNERS = ("job.run", "model.publish", "predict")


def _install_jax_listeners():
    """Count XLA compile-cache traffic via jax.monitoring events, and book
    every OUTERMOST program build of a thread (a jaxpr trace, a lowering,
    a backend compile or cache load) as one `xla.*` span in the timeline
    ring, parented to the span open at its start, plus its seconds in
    h2o3_xla_build_seconds_total{stage, within}. A build inside another
    (the jnp ops traced inside a jit's trace, the traces a lowering rule
    makes) is part of the outer one, so no second is counted twice. Safe
    to call before the backend initializes (listener registration imports
    jax but touches no devices)."""
    global _JAX_LISTENERS_INSTALLED
    if _JAX_LISTENERS_INSTALLED:
        return
    _JAX_LISTENERS_INSTALLED = True
    try:
        import jax as _jax
        import jax.monitoring as _mon
    except Exception:   # noqa: BLE001 — no jax, no compile metrics
        return
    from h2o3_tpu.obs.timeline import SPANS
    hits = counter("h2o3_xla_compile_cache_hits_total",
                   "persistent XLA compilation cache hits")
    misses = counter("h2o3_xla_compile_cache_misses_total",
                     "persistent XLA compilation cache misses")
    compiles = counter("h2o3_xla_compiles_total",
                       "XLA backend compilations in this process (every "
                       "new program x shape signature costs one)")
    compile_secs = counter("h2o3_xla_compile_seconds_total",
                           "cumulative wall time spent in XLA backend "
                           "compilation")
    build_secs = counter("h2o3_xla_build_seconds_total",
                         "seconds of outermost program builds (nested ones "
                         "are part of theirs) by stage (trace, lower or "
                         "compile, the last a load from the persistent "
                         "cache too) and by what paid for them: within = "
                         "the nearest open job.run, model.publish or "
                         "predict span, else other")
    tls = threading.local()     # depth, and the outermost build's owner

    def _on_event(event: str, **kw):
        # an xla.compile span's `cache`: hit (loaded), miss (looked up in a
        # cache directory and compiled; jax writes it back only past its min
        # compile time and entry size, and only such writes count as
        # cache_misses), off (no directory)
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            tls.cache = "miss" if _jax.config.jax_compilation_cache_dir \
                else "off"
        elif event == "/jax/compilation_cache/cache_hits":
            hits.inc()
            tls.cache = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            misses.inc()

    def _on_duration(event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.inc()
            compile_secs.inc(max(duration, 0.0))
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            tls.cache_s = duration

    def _on_start(event: str, value, **kw):
        if event not in _BUILD_STAGES:
            return
        depth = getattr(tls, "depth", 0)
        tls.depth = depth + 1
        if depth == 0:
            open_ = SPANS.open_spans()
            tls.parent = open_[-1].span_id if open_ else 0
            tls.within = next((sp.name for sp in reversed(open_)
                               if sp.name in _BUILD_OWNERS), "other")
            tls.cache, tls.cache_s = "off", None

    def _on_end(event: str, start: float, end: float, **kw):
        stage = _BUILD_STAGES.get(event)
        depth = getattr(tls, "depth", 0)
        if stage is None or depth == 0:   # not a build, or begun unseen
            return
        tls.depth = depth - 1
        if depth > 1:
            return
        build_secs.inc(max(end - start, 0.0), stage=stage, within=tls.within)
        attrs = {"fun": str(kw.get("fun_name", ""))}
        if stage == "compile":
            attrs["cache"] = tls.cache
            if tls.cache_s is not None:
                attrs["cache_s"] = tls.cache_s
        SPANS.add_finished("xla.trace" if stage == "trace" else
                           "xla.lower" if stage == "lower" else
                           "xla.compile", start, end, tls.parent, **attrs)

    try:
        _mon.register_event_listener(_on_event)
        _mon.register_event_duration_secs_listener(_on_duration)
        _mon.register_scalar_listener(_on_start)
        _mon.register_event_time_span_listener(_on_end)
    except Exception:   # noqa: BLE001
        pass


def xla_compile_count() -> float:
    """Current process-wide XLA backend-compile count — the serving fast
    path's regression metric (tests assert a warm bucket adds zero).
    Reads via get(): counter() here would be a second declaration site
    for the name (R005), racing the listener's help text."""
    m = REGISTRY.get("h2o3_xla_compiles_total")
    return m.value() if m is not None else 0.0


_BUILD_INFO = None


def _build_info_series():
    """h2o3_build_info callback: the identity labels are immutable for
    the process lifetime, so they resolve once (lazily — at the first
    scrape, never at import, where jax may still be initializing)."""
    global _BUILD_INFO
    if _BUILD_INFO is None:
        import h2o3_tpu as _pkg
        try:
            import jax as _jax
            backend = str(_jax.default_backend())
            jaxv = str(getattr(_jax, "__version__", "unknown"))
        except Exception:   # noqa: BLE001 — chip-less container: still expose
            backend, jaxv = "none", "none"
        _BUILD_INFO = ({"version": str(getattr(_pkg, "__version__", "0")),
                        "backend": backend, "jax": jaxv}, 1.0)
    return [_BUILD_INFO]


def install_runtime_gauges():
    """Register the default runtime gauges (idempotent; called by the API
    server at start and by /metrics scrapes)."""
    gauge("h2o3_device_memory_bytes",
          "JAX per-device HBM usage from device.memory_stats()",
          fn=_device_memory_series)
    gauge("h2o3_dkv_objects",
          "DKV registry census: live keys, frames, frame bytes",
          fn=_dkv_series)
    gauge("h2o3_build_info",
          "build/runtime identity info-gauge (value always 1): package "
          "version, JAX backend and jax version — correlates dashboards "
          "and bench trajectories across container/backend changes",
          fn=_build_info_series)
    # the usage ledger's pressure/attribution metrics register at its
    # import; pulling it in here makes them scrapeable even when the
    # serving path was never touched (bench, notebooks)
    try:
        from h2o3_tpu.obs import usage  # noqa: F401
    except ImportError:
        pass
    _install_jax_listeners()


# Registered at import: the registry must answer a scrape even if the
# server never called install explicitly (bench.py, tests, notebooks).
install_runtime_gauges()
