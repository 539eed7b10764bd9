"""Headline benchmark: GBM (bernoulli) training throughput on HIGGS-shaped
data — 11M rows x 28 features, depth 8, 255 value bins, sustained trees/s.

BASELINE.json metric: "HIGGS + airlines-1B GBM wall-clock vs H100 gpu_hist".
The reference publishes no absolute number ("published": {}); the comparison
point is XGBoost `gpu_hist` on HIGGS on one H100: ~11M rows x 28 features x
500 trees (depth 8, 256 bins) in ~35 s ~= 157M row*trees/s. We report
sustained row*trees/s of the binned tree engine (global quantile codes +
Pallas histogram kernel — the same `hist` algorithm family) at the SAME
shape: full 11M rows, depth 8, 255+NA bins, no extrapolation.

Prints ONE JSON line.
"""

import json
import os
import re
import subprocess
import sys
import time
import traceback

import numpy as np


def _registry():
    """The obs metrics registry — bench publishes its numbers there FIRST
    and builds the JSON line from it, so /metrics (a live server scraping
    the same process) and BENCH_*.json can never disagree."""
    from h2o3_tpu.obs import metrics as om
    return om.REGISTRY


def _short_cause(text: str, limit: int = 220) -> str:
    """Collapse a traceback (or an exception repr with escaped newlines)
    into ONE bounded line: the final exception line plus the deepest
    in-repo frame. BENCH_r09 lesson: `blocked_detail` must be a root
    cause a human can read in the record, never a raw traceback."""
    t = (text or "").replace("\\n", "\n")
    lines = [ln.strip() for ln in t.strip().splitlines() if ln.strip()]
    if not lines:
        return "unknown"
    exc = lines[-1]
    frame = ""
    for ln in reversed(lines):
        m = re.search(r'(h2o3_tpu/[\w/.]+)", line (\d+), in (\w+)', ln)
        if m:
            frame = f" (at {m.group(1)}:{m.group(2)} {m.group(3)})"
            break
    return (exc + frame)[:limit]


def _ingest_csv(path: str, mb: int, seed: int = 0) -> int:
    """Synthesize the r06-shaped ingest fixture (5 numeric cols,
    ~56 B/row); returns the row count."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n = mb * 18000
    with open(path, "w") as fh:
        fh.write("a,b,c,d,e\n")
        for i in range(0, n, 10000):
            blk = rng.normal(size=(min(10000, n - i), 5))
            fh.write("\n".join(
                ",".join(f"{v:.6f}" for v in row) for row in blk))
            fh.write("\n")
    return n


def ingest_bench(mb: int = 50) -> dict:
    """Single-host ingest throughput, now a HEADLINE metric (ISSUE 13):
    synthesize the same ~50MB CSV shape BENCH_r06 measured at 54.8 MB/s,
    time the byte-range pipelined parse (io/dparse + the rebuilt native
    tokenizer), best of 3 (first run pays page-cache + pool warmup)."""
    import tempfile
    from h2o3_tpu.io import dparse, fastcsv
    from h2o3_tpu.core.kvstore import DKV
    fd, path = tempfile.mkstemp(suffix=".csv")
    os.close(fd)
    try:
        n = _ingest_csv(path, mb)
        size_mb = os.path.getsize(path) / 1e6
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            fr = dparse.parse_files([path], chunk_bytes=8 << 20)
            dt = time.time() - t0
            best = min(best, dt)
            assert fr.nrows == n
            DKV.remove(fr.key)
        return {"mb": round(size_mb, 1), "seconds": round(best, 2),
                "mb_per_sec": round(size_mb / best, 1),
                "native_parser": fastcsv.available(),
                "cores": os.cpu_count()}
    finally:
        os.unlink(path)


def distributed_ingest_bench(single_host: dict | None,
                             timeout_s: int = 240) -> dict:
    """2-process distributed-ingest sample (ISSUE 13): form the real
    jax.distributed CPU cloud (tests/multiproc_runner.py), then drive
    POST /3/ParseDistributed — the coordinator fans byte-range shares to
    the worker over the replay channel (pure HOST work: tokenize +
    codec-pack, no device collectives) and merges the codec planes.
    Records cloud_size and MB/s; a container that cannot form the cloud
    yields a structured blocked record, and a box without ≥2 physical
    cores records the scaling claim as blocked with the root cause
    in-record (two processes time-slicing one core cannot scale)."""
    import socket
    import tempfile
    import urllib.parse
    import urllib.request

    here = os.path.dirname(os.path.abspath(__file__))
    deadline = time.time() + timeout_s

    def _free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def _req(port, path, data=None):
        url = f"http://127.0.0.1:{port}{path}"
        req = urllib.request.Request(
            url,
            data=urllib.parse.urlencode(data).encode() if data else None,
            method="POST" if data else "GET")
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    tmp = tempfile.mkdtemp(prefix="h2o3_bench_ingest_")
    csv = os.path.join(tmp, "dist_ingest.csv")
    mb = int(os.environ.get("BENCH_INGEST_MB", "50"))
    n = _ingest_csv(csv, mb, seed=2)
    size_mb = os.path.getsize(csv) / 1e6
    coord, rest = _free_port(), _free_port()
    env = dict(os.environ)
    env["H2O3_CLUSTER_SECRET"] = "bench-ingest-secret"
    env["H2O3_TPU_ICE_ROOT"] = os.path.join(tmp, "ice")
    # born-cold ingest: the coordinator of a multi-controller cloud must
    # not device_put globally sharded planes from one process
    env["H2O3_TPU_INGEST_COLD"] = "1"
    env["XLA_FLAGS"] = ""
    procs = []
    record = {"hosts": 2, "mb": round(size_mb, 1)}
    try:
        for pid in range(2):
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(here, "tests", "multiproc_runner.py"),
                 str(pid), "2", str(coord), str(rest)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env))
        cloud_size = 0
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                cloud_size = int(_req(rest, "/3/Cloud").get("cloud_size",
                                                            0))
                if cloud_size >= 2:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        record["cloud_size"] = cloud_size
        if cloud_size < 2:
            return {**record, "blocked": True,
                    "blocked_stage": "2proc-cloud-formation",
                    "blocked_detail": "2-process jax.distributed cloud "
                    "did not form in this container"}

        def _one_parse(dest):
            t0 = time.perf_counter()
            r = _req(rest, "/3/ParseDistributed",
                     {"source_frames": csv, "destination_frame": dest})
            jk = r["job"]["key"]
            while time.time() < deadline:
                j = _req(rest, f"/3/Jobs/{jk}")["jobs"][0]
                if j["status"] in ("DONE", "FAILED", "CANCELLED"):
                    if j["status"] != "DONE":
                        # the job's own exception repr IS the root cause —
                        # re-raising the whole job dict buried it in a
                        # traceback (BENCH_r09)
                        raise RuntimeError(
                            f"distributed parse {j['status']}: "
                            + _short_cause(str(j.get("exception") or "")))
                    return time.perf_counter() - t0
                time.sleep(0.1)
            raise TimeoutError("distributed parse did not finish")

        _one_parse("bench_dist_warm")       # warm: pools + page cache
        dt = min(_one_parse("bench_dist_1"), _one_parse("bench_dist_2"))
        record.update({"seconds": round(dt, 2),
                       "mb_per_sec": round(size_mb / dt, 1),
                       "rows": n})
        if single_host and single_host.get("mb_per_sec"):
            record["scaling_vs_single_host"] = round(
                record["mb_per_sec"] / single_host["mb_per_sec"], 2)
        cores = os.cpu_count() or 1
        if cores < 2:
            # the fan-out worked end-to-end, but a near-linear SCALING
            # claim is unmeasurable here: both processes time-slice one
            # physical core, so distributed MB/s ~= single-host MB/s by
            # construction — root cause, not a code limitation
            record["scaling_blocked"] = True
            record["scaling_blocked_detail"] = (
                f"container has {cores} CPU core(s); 2-process scaling "
                "needs >=2 cores to show >1x")
        return record
    except Exception:
        return {**record, "blocked": True,
                "blocked_stage": "2proc-distributed-ingest",
                "blocked_detail": _short_cause(traceback.format_exc())}
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)   # 50MB CSV + ice root


def scoring_bench() -> dict:
    """Warm-cache serving throughput: rows/sec through the shape-bucketed
    compiled-scorer cache (h2o3_tpu/serving) scoring a GBM at a
    serving-sized bucketed batch. The first call compiles the one resident
    program; the timed loop re-stages + dispatches it with zero compiles —
    what a steady-state /3/Predictions stream sees. Timed twice — without
    and WITH an active trace id (what a real REST request carries) — and
    the headline number is the traced run, so the reported throughput is
    what production serving actually sees; the delta is
    tracing_overhead_pct. A third interleaved mode additionally emits one
    structured log record per dispatch (utils/log: JSON build + ring +
    durable JSONL append — the per-request access-log worst case) and
    reports the delta over the traced run as logging_overhead_pct."""
    import numpy as np
    from h2o3_tpu.core.frame import Frame
    from h2o3_tpu.core.kvstore import DKV
    from h2o3_tpu.models import ESTIMATORS
    from h2o3_tpu import serving
    from h2o3_tpu.obs import metrics as om
    from h2o3_tpu.obs import tracing

    from h2o3_tpu.serving import scorer_cache as _scc
    from h2o3_tpu.serving import params as _sp

    rng = np.random.default_rng(3)
    ntr, batch, iters = 20_000, 4096, 25
    cols = {f"x{j}": rng.normal(size=ntr) for j in range(10)}
    hot = rng.random(ntr) < 1 / (1 + np.exp(-(cols["x0"] - cols["x1"])))
    cols["y"] = np.where(hot, "yes", "no").astype(object)
    fr = Frame.from_dict(cols)
    m = ESTIMATORS["gbm"](ntrees=10, max_depth=5, seed=1,
                          histogram_type="UniformAdaptive")
    m.train(x=[f"x{j}" for j in range(10)], y="y", training_frame=fr)
    sf = Frame.from_dict({f"x{j}": rng.normal(size=batch)
                          for j in range(10)})
    for _ in range(2):                     # warm: compile + settle
        serving.score_frame(m, sf)
    c0 = om.xla_compile_count()
    hits0 = _scc.HITS.value()
    fb0 = sum(e["value"] for e in _scc.FALLBACKS._json())

    def timed_loop():
        t0 = time.perf_counter()
        for _ in range(iters):
            r = serving.score_frame(m, sf)
        return time.perf_counter() - t0, r

    from h2o3_tpu.utils import log as _ulog

    def timed_loop_logged():
        t0 = time.perf_counter()
        for i in range(iters):
            r = serving.score_frame(m, sf)
            _ulog.info("bench scored batch %d rows=%d", i, batch)
        return time.perf_counter() - t0, r

    # alternating best-of-5 per mode: one span (or log record) per
    # iteration costs microseconds, so a naive single pair of loops
    # measures scheduler jitter, not instrumentation — min-of-N against
    # interleaved runs cancels it. BENCH_r09 regression root cause (1-core
    # container): the logged loop enqueues async records whose 0.5s-batch
    # DRAIN thread then fires DURING the next alternation's off/traced
    # loops, stealing the only core and inflating BOTH baselines — so the
    # drain is forced synchronously (log.flush) after every logged loop,
    # keeping each timed window drain-free.
    prev_trace = tracing.set_current(None)
    dt_off = dt_on = dt_log = float("inf")
    out = None
    _ulog.flush()
    for _ in range(5):
        tracing.set_current(None)                    # tracing off
        dt, out = timed_loop()
        dt_off = min(dt_off, dt)
        tracing.set_current(tracing.new_trace_id())  # traced, like REST
        dt, out = timed_loop()
        dt_on = min(dt_on, dt)
        # traced + one structured log record per dispatch (access-log
        # shape): the logging pillar's warm-path cost
        dt, out = timed_loop_logged()
        dt_log = min(dt_log, dt)
        _ulog.flush()            # drain NOW, outside the timed windows
    # usage-attribution overhead (ISSUE 16): the SAME warm traced loop
    # with the device-time ledger forced OFF vs ON (usage.set_enabled),
    # alternating best-of-5 like the pairs above. The ledger's warm-path
    # cost is one perf_counter pair + a counter inc + a dict update per
    # dispatch, so the bound is tight: <1% on >=2 cores. The ON pass
    # also yields the record's device_seconds (ledger delta across the
    # best loop) and utilization_pct — charged device seconds over wall
    # seconds x local device count.
    from h2o3_tpu.obs import usage as _usage
    import jax as _jax
    dt_led_off = dt_led_on = float("inf")
    device_seconds = 0.0
    for _ in range(5):
        tracing.set_current(tracing.new_trace_id())
        _usage.set_enabled(False)
        dt, out = timed_loop()
        dt_led_off = min(dt_led_off, dt)
        _usage.set_enabled(True)
        d0 = _usage.device_seconds_total()
        dt, out = timed_loop()
        if dt < dt_led_on:
            dt_led_on = dt
            device_seconds = _usage.device_seconds_total() - d0
    _usage.set_enabled(None)             # back to the env default
    # drift-monitor overhead (ISSUE 20): the SAME warm traced loop with
    # the modelmon serving tap forced OFF vs ON. The tap self-bounds —
    # one fold sees at most H2O3_MODELMON_TAP_ROWS stride-sampled rows
    # and the duty-cycle throttle defers the next fold until the
    # measured fold time amortizes under H2O3_MODELMON_TAP_PCT of wall
    # — so the bound matches the ledger's: <1% on >=2 cores.
    from h2o3_tpu.obs import modelmon as _mm
    dt_mon_off = dt_mon_on = float("inf")
    for _ in range(5):
        tracing.set_current(tracing.new_trace_id())
        _mm.set_enabled(False)
        dt, out = timed_loop()
        dt_mon_off = min(dt_mon_off, dt)
        _mm.set_enabled(True)
        dt, out = timed_loop()
        dt_mon_on = min(dt_mon_on, dt)
    _mm.set_enabled(None)                # back to the env default
    tracing.set_current(prev_trace)
    assert out is not None and len(out) >= batch
    warm_compiles = om.xla_compile_count() - c0
    rows_per_sec = batch * iters / dt_on
    overhead_pct = 100.0 * (dt_on - dt_off) / dt_off
    logging_overhead_pct = 100.0 * (dt_log - dt_on) / dt_on
    attribution_overhead_pct = 100.0 * (dt_led_on - dt_led_off) / dt_led_off
    drift_monitor_overhead_pct = 100.0 * (dt_mon_on - dt_mon_off) \
        / dt_mon_off
    devices = _jax.local_device_count()
    utilization_pct = (100.0 * device_seconds / (dt_led_on * devices)
                       if dt_led_on > 0 else 0.0)
    om.REGISTRY.gauge("h2o3_bench_scoring_rows_per_sec",
                      "warm-cache bucketed serving throughput"
                      ).set(rows_per_sec)
    # mesh-sharded fast-path evidence (ISSUE 11): every timed dispatch
    # must be a fast-path HIT (zero fallbacks), and the model's params
    # live as ONE shared HBM placement — bytes constant in buckets
    fast_hits = int(_scc.HITS.value() - hits0)
    fallbacks = int(sum(e["value"] for e in _scc.FALLBACKS._json()) - fb0)
    param_bytes = int(_sp.PARAMS.bytes_for(m.key))
    cores = os.cpu_count() or 1
    rec = {"rows_per_sec": round(rows_per_sec),
           "rows_per_sec_untraced": round(batch * iters / dt_off),
           "tracing_overhead_pct": round(overhead_pct, 2),
           "logging_overhead_pct": round(logging_overhead_pct, 2),
           # the overhead samples are only meaningful relative to the
           # core count they ran on: on 1 core ANY background thread
           # (span drain, GC) lands inside the measured loop
           "cores": cores,
           "batch_rows": batch, "iters": iters,
           "bucket": serving.row_bucket(batch),
           "warm_compiles": int(warm_compiles),
           "fast_path_hits": fast_hits,
           "fallbacks": fallbacks,
           "param_hbm_bytes": param_bytes,
           "params_shared": bool(_scc._shares_params(m)),
           # capacity attribution (ISSUE 16): what the usage ledger
           # charged for the best traced loop, and that charge as a
           # share of wall time across the local devices
           "device_seconds": round(device_seconds, 4),
           "utilization_pct": round(utilization_pct, 2),
           "attribution_overhead_pct": round(attribution_overhead_pct, 2),
           # drift observability (ISSUE 20): the serving tap's warm-path
           # cost — live-sketch folds per dispatch vs the tap disabled
           "drift_monitor_overhead_pct":
               round(drift_monitor_overhead_pct, 2)}
    if (overhead_pct > 5.0 or logging_overhead_pct > 1.0
            or attribution_overhead_pct > 1.0
            or drift_monitor_overhead_pct > 1.0) and cores < 2:
        # structured bound-waiver (ISSUE 14 satellite): with one physical
        # core the instrumented and baseline loops time-slice against
        # every background thread in the process, so the <5%/<1% bounds
        # are not measurable — record the cause instead of a silent miss
        rec["overhead_bound_waiver"] = {
            "cause": f"{cores}-core container: measured loop time-slices "
                     "against drain/GC threads; bounds need >=2 cores "
                     "(r06/r07 measured 0.09%/0.47% on 2 cores)",
            "bounds": {"tracing_pct": 5.0, "logging_pct": 1.0,
                       "attribution_pct": 1.0,
                       "drift_monitor_pct": 1.0}}
    for k in (fr.key, sf.key, m.key):
        DKV.remove(k)
    return rec


def qos_overload_bench(duration_s: float = 3.0) -> dict:
    """Multi-tenant QoS overload sample (ISSUE 15): a real REST server
    with two basic-auth tenants, one flooding unpaced from 3 threads and
    one well-behaved at ~10 rps. Records the victim's p50/p99, both
    tenants' outcome counts and the QoS shed/reject counters — the
    bounded, CI-sized version of the win-condition race harness. A
    server that can't form records a structured blocked record."""
    import base64
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    import numpy as np
    from h2o3_tpu.core.frame import Frame
    from h2o3_tpu.core.kvstore import DKV
    from h2o3_tpu.models import ESTIMATORS
    from h2o3_tpu.serving import qos as _qos

    try:
        from h2o3_tpu.api.server import H2OServer
        rng = np.random.default_rng(11)
        fr = Frame.from_dict(
            {"a": rng.normal(size=400), "b": rng.normal(size=400),
             "resp": rng.choice(["no", "yes"], size=400).astype(object)})
        m = ESTIMATORS["glm"](family="binomial")
        m.train(x=["a", "b"], y="resp", training_frame=fr)
        srv = H2OServer(port=0,
                        auth={"flood": "pw", "victim": "pw"}).start()
    except Exception:
        return {"blocked": True, "blocked_stage": "qos-server-formation",
                "blocked_detail": _short_cause(traceback.format_exc())}
    url = f"http://127.0.0.1:{srv.port}/3/Predictions/models/{m.key}"
    body = _json.dumps({"rows": [{"a": 0.1, "b": 0.2}]}).encode()

    def post(user, timeout=10.0):
        tok = base64.b64encode(f"{user}:pw".encode()).decode()
        req = urllib.request.Request(
            url, data=body, method="POST",
            headers={"Content-Type": "application/json",
                     "Authorization": f"Basic {tok}"})
        return urllib.request.urlopen(req, timeout=timeout)

    try:
        post("victim").read()               # warm: compile outside the clock
        stop = threading.Event()
        # one tally dict PER THREAD, summed after join — a shared dict's
        # read-modify-write increments from 3 threads can lose counts
        tallies = [{"ok": 0, "rejected": 0, "errors": 0}
                   for _ in range(3)]

        def flooder(tally):
            while not stop.is_set():
                try:
                    with post("flood") as r:
                        r.read()
                        tally["ok"] += 1
                except urllib.error.HTTPError as ex:
                    ex.read()
                    if ex.code in (429, 503):
                        tally["rejected"] += 1
                    else:
                        tally["errors"] += 1
                except Exception:
                    tally["errors"] += 1

        threads = [threading.Thread(target=flooder, args=(tally,))
                   for tally in tallies]
        for t in threads:
            t.start()
        lat, failures = [], 0
        t_end = time.time() + duration_s
        while time.time() < t_end:
            t0 = time.perf_counter()
            try:
                with post("victim") as r:
                    r.read()
                lat.append(time.perf_counter() - t0)
            except Exception:
                failures += 1
            time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(20)
        flood = {k: sum(t[k] for t in tallies)
                 for k in ("ok", "rejected", "errors")}
        shed = {reason: _qos.SHED.value(reason=reason)
                for reason in ("entry", "admission", "batch")}
        return {
            "victim_requests": len(lat),
            "victim_failures": failures,
            "victim_p50_ms": round(1e3 * float(np.percentile(lat, 50)), 2)
            if lat else None,
            "victim_p99_ms": round(1e3 * float(np.percentile(lat, 99)), 2)
            if lat else None,
            "flood_ok": flood["ok"], "flood_rejected": flood["rejected"],
            "flood_errors": flood["errors"],
            "flood_to_victim_ratio": round(
                (flood["ok"] + flood["rejected"]) / max(1, len(lat)), 1),
            "shed_total": shed,
            "gate_waits": sum(
                e["value"] for e in _qos.GATE_WAITS._json()),
        }
    except Exception:
        return {"blocked": True, "blocked_stage": "qos-overload-run",
                "blocked_detail": _short_cause(traceback.format_exc())}
    finally:
        try:
            srv.stop()
        except Exception:
            pass
        for k in (fr.key, m.key):
            DKV.remove(k)


def fleet_serving_bench(n_models: int | None = None) -> dict:
    """Fleet-scale serving sample (ISSUE 17): BENCH_FLEET_MODELS (default
    1024) registered stub models — 8 KB of f32 params each — against a
    deliberately single-chip-sized 1 MB HBM budget, through a PRIVATE
    ParamStore so the process's real serving placements are untouched.
    Reports resident models, warm p99 (hot set, HBM-resident dispatch
    lookup), cold-fault p99 (a demoted model promoted back through
    reserved admission), and the peak params-byte gauge against the
    budget — the '1000+ models on one chip' acceptance numbers. A
    failure yields a structured blocked record."""
    try:
        from h2o3_tpu.serving import params as _sp

        n = int(n_models or os.environ.get("BENCH_FLEET_MODELS", 1024))
        budget_mb = 1
        old = os.environ.get("H2O3_SERVE_HBM_BUDGET_MB")
        os.environ["H2O3_SERVE_HBM_BUDGET_MB"] = str(budget_mb)
        store = _sp.ParamStore()
        rng = np.random.default_rng(17)

        class _Stub:
            _partition_rules = ()

            def __init__(self, key, arr):
                self.key, self._arr = key, arr

            def _serving_params(self):
                return {"w": self._arr}

        try:
            models = [_Stub(f"bench/fleet{i}",
                            rng.normal(size=2048).astype(np.float32))
                      for i in range(n)]
            t0 = time.perf_counter()
            for m in models:
                store.acquire(m, 0)
            register_s = time.perf_counter() - t0
            hot = models[:16]              # warm path: HBM-resident
            for m in hot:
                store.placed(m, 0)
            warm = []
            for _ in range(30):
                for m in hot:
                    t0 = time.perf_counter()
                    store.placed(m, 0)
                    warm.append(time.perf_counter() - t0)
            cold = []                      # cold path: demote → promote
            for m in models[16:80]:
                store.demote_key(m.key, to_tier=_sp.TIER_HOST)
                t0 = time.perf_counter()
                store.placed(m, 0)
                cold.append(time.perf_counter() - t0)
            warm.sort()
            cold.sort()
            stats = store.stats()
            budget = budget_mb << 20
            peak = store.peak_hbm_bytes()
            return {
                "resident_models": store.resident(),
                "hbm_budget_bytes": budget,
                "params_hbm_peak_bytes": peak,
                "budget_respected": peak <= budget,
                "warm_p99_ms": round(
                    warm[int(0.99 * (len(warm) - 1))] * 1e3, 3),
                "cold_fault_p99_ms": round(
                    cold[int(0.99 * (len(cold) - 1))] * 1e3, 3),
                "register_models_per_sec": round(n / register_s, 1),
                "faults": stats["faults"],
                "evictions": sum(stats["evictions_by_tenant"].values()),
            }
        finally:
            store.clear()
            if old is None:
                os.environ.pop("H2O3_SERVE_HBM_BUDGET_MB", None)
            else:
                os.environ["H2O3_SERVE_HBM_BUDGET_MB"] = old
    except Exception:
        return {"blocked": True, "blocked_stage": "fleet-serving-run",
                "blocked_detail": _short_cause(traceback.format_exc())}


def multihost_scoring_bench(timeout_s: int = 240) -> dict:
    """2-process-cloud scaling sample (ISSUE 11): form the real
    jax.distributed CPU cloud (tests/multiproc_runner.py), train a GBM
    over REST, then time repeated predictions — the mesh-sharded fast
    path serving with params placed once per HOST instead of falling
    back to the legacy sharded scorer. Bounded end-to-end; a container
    that cannot form the 2-proc cloud (the known jax-CPU multiprocess
    limitation) yields a structured blocked record, not a hang."""
    import socket
    import tempfile
    import urllib.request

    here = os.path.dirname(os.path.abspath(__file__))
    deadline = time.time() + timeout_s

    def _free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    def _req(port, path, data=None):
        import urllib.parse
        url = f"http://127.0.0.1:{port}{path}"
        req = urllib.request.Request(
            url, data=urllib.parse.urlencode(data).encode() if data else None,
            method="POST" if data else "GET")
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    tmp = tempfile.mkdtemp(prefix="h2o3_bench_mp_")
    csv = os.path.join(tmp, "bench_mp.csv")
    rng = np.random.default_rng(5)
    n = 4000
    X = rng.normal(0, 1, (n, 3))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0)
    with open(csv, "w") as f:
        f.write("x0,x1,x2,y\n")
        for i in range(n):
            f.write(f"{X[i,0]:.6f},{X[i,1]:.6f},{X[i,2]:.6f},"
                    f"{'yes' if y[i] else 'no'}\n")
    coord, rest = _free_port(), _free_port()
    env = dict(os.environ)
    env["H2O3_CLUSTER_SECRET"] = "bench-mp-secret"
    env["H2O3_TPU_ICE_ROOT"] = os.path.join(tmp, "ice")
    env["XLA_FLAGS"] = ""
    procs, record = [], {"hosts": 2}
    try:
        for pid in range(2):
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(here, "tests", "multiproc_runner.py"),
                 str(pid), "2", str(coord), str(rest)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env))
        cloud_size = 0
        while time.time() < deadline:
            if any(p.poll() is not None for p in procs):
                break
            try:
                cloud_size = int(_req(rest, "/3/Cloud").get("cloud_size", 0))
                if cloud_size >= 2:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        record["cloud_size"] = cloud_size
        if cloud_size < 2:
            # a 1-host cloud must NOT masquerade as the 2-host scaling
            # sample — the record is evidence for a multihost claim
            return {"blocked": True, "cloud_size": cloud_size,
                    "blocked_stage": "2proc-cloud-formation",
                    "blocked_detail": "known jax-CPU multiprocess "
                    "limitation in this container"}
        r = _req(rest, "/3/Parse",
                 {"source_frames": csv, "destination_frame": "bench_mp"})
        jk = r["job"]["key"]
        while time.time() < deadline:
            j = _req(rest, f"/3/Jobs/{jk}")["jobs"][0]
            if j["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            time.sleep(0.3)
        r = _req(rest, "/3/ModelBuilders/gbm",
                 {"training_frame": "bench_mp", "response_column": "y",
                  "ntrees": "5", "max_depth": "4", "seed": "1",
                  "model_id": "bench_mp_gbm"})
        jk = r["job"]["key"]
        while time.time() < deadline:
            j = _req(rest, f"/3/Jobs/{jk}")["jobs"][0]
            if j["status"] in ("DONE", "FAILED", "CANCELLED"):
                if j["status"] != "DONE":
                    # known root cause on this image: the first device
                    # dispatch the 2-proc build reaches (the frame rollup
                    # kernel, a host-serialized collective) hits jax-CPU's
                    # "Multiprocess computations aren't implemented" — the
                    # rollup guard serializes dispatch, it did not break
                    # the run. Surface the job's OWN exception as a
                    # one-line cause, not the job dict's traceback.
                    raise RuntimeError(
                        f"gbm build {j['status']}: "
                        + _short_cause(str(j.get("exception") or "")))
                break
            time.sleep(0.3)
        # warm, then timed scoring round trips over the 2-host cloud
        for _ in range(2):
            _req(rest, "/3/Predictions/models/bench_mp_gbm/frames/bench_mp",
                 {"predictions_frame": "bench_mp_pred"})
        iters = 10
        t0 = time.perf_counter()
        for _ in range(iters):
            _req(rest, "/3/Predictions/models/bench_mp_gbm/frames/bench_mp",
                 {"predictions_frame": "bench_mp_pred"})
        dt = time.perf_counter() - t0
        record.update({"scoring_rows_per_sec": round(n * iters / dt),
                       "rows": n, "iters": iters})
        return record
    except Exception:
        return {"blocked": True, "blocked_stage": "2proc-cloud-run",
                "blocked_detail": _short_cause(traceback.format_exc())}
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def main():
    # --gbm-only (ISSUE 14 CI fast mode): train + AUC-gate the headline
    # GBM stage only, skipping the ingest / scoring / multihost stages
    gbm_only = "--gbm-only" in sys.argv
    # --serving-only (ISSUE 17 CI fast mode): the fleet-serving sample
    # alone — no data gen, no training — seconds instead of minutes
    serving_only = "--serving-only" in sys.argv

    import jax
    import jax.numpy as jnp

    from h2o3_tpu.utils import compile_cache
    compile_cache.enable()

    # the bench run carries its OWN trace id: every span it opens (tree
    # levels, parse stages, scoring dispatches) is fetchable afterward via
    # GET /3/Trace/{id} on a server scraping this process
    from h2o3_tpu.obs import tracing as _tracing
    bench_trace = _tracing.new_trace_id()
    _tracing.set_current(bench_trace)

    if serving_only:
        fleet_serving = fleet_serving_bench()
        if fleet_serving.get("blocked"):
            print("fleet serving sample blocked: "
                  f"{fleet_serving['blocked_stage']}", file=sys.stderr)
        else:
            print(f"fleet serving: {fleet_serving['resident_models']} "
                  f"models on {fleet_serving['hbm_budget_bytes'] >> 20}MB "
                  f"HBM, warm p99 {fleet_serving['warm_p99_ms']}ms, "
                  f"cold-fault p99 {fleet_serving['cold_fault_p99_ms']}ms",
                  file=sys.stderr)
        print(json.dumps({
            "metric": "fleet_serving_resident_models",
            "value": fleet_serving.get("resident_models"),
            "unit": "models",
            "serving_only": True,
            "backend": jax.default_backend(),
            "trace_id": bench_trace,
            "fleet_serving": fleet_serving,
        }))
        return

    from h2o3_tpu.models.tree import binned as BN

    N, C = int(os.environ.get("BENCH_N", 11_000_000)), 28
    DEPTH, NBINS = 8, 255
    WARM, CHUNK, NCHUNK = 10, 10, 4          # 10 warmup + 40 timed trees
    if N < 1_000_000:                        # CPU smoke mode: logic check only
        CHUNK, NCHUNK = 2, 2

    # generate HIGGS-like data ON DEVICE (the benchmark measures training,
    # not a 1.2GB host->device copy)
    key = jax.random.PRNGKey(7)
    kx, kn, ky = jax.random.split(key, 3)

    @jax.jit
    def gen(kx, kn, ky):
        X = jax.random.normal(kx, (N, C), jnp.float32)
        logit = (1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
                 + 0.4 * jnp.sin(X[:, 4]) + 0.3 * X[:, 5] * X[:, 6])
        y = (jax.random.uniform(ky, (N,)) <
             jax.nn.sigmoid(logit)).astype(jnp.float32)
        return X, y

    X, y = gen(kx, kn, ky)

    # ---- kernel parity gate (pre-step): a misrouting Pallas kernel must
    # not ship behind a good throughput number
    from h2o3_tpu.ops.parity import kernel_parity_check
    from h2o3_tpu.ops import hist_pallas as HP
    if HP.use_pallas():
        kernel_parity_check(seed=0)
        print("kernel parity: OK", file=sys.stderr)

    # bin spec from a host-side sample (29MB readback), codes on device:
    # uint8 planes end-to-end, packed to the i32 word layout for the
    # Pallas kernels (1 B/code in HBM — 4x less code-stream traffic)
    Xs = np.asarray(X[: 1 << 18])
    spec = BN.make_bins(Xs, np.zeros(C, bool), NBINS)
    codes = BN.prepare_codes(BN.quantize(X, spec))
    del X

    # ---- AUC: rank-sum (Mann-Whitney) on device; a broken histogram or
    # route kernel collapses this to ~0.5 regardless of throughput.
    @jax.jit
    def auc_dev(F, y):
        Fr = F[:N]
        order = jnp.argsort(Fr)
        ranks = jnp.zeros(N, jnp.float64).at[order].set(
            jnp.arange(1, N + 1, dtype=jnp.float64))
        pos = y.astype(jnp.float64)
        npos = pos.sum()
        nneg = N - npos
        return (ranks @ pos - npos * (npos + 1) / 2) / (npos * nneg)

    n_pad = BN.padded_rows(N)
    y1 = BN.pad_rows(y, n_pad)
    w1 = BN.pad_rows(jnp.ones(N, jnp.float32), n_pad)
    p0 = float(jnp.mean(y))
    f0 = float(np.log(p0 / (1 - p0)))

    def roofline_model(c_pad, np_rows, int8: bool):
        """Analytic MXU-MAC and HBM-byte counts per tree for the binned
        engine's executed program (mirrors grow()'s level loop: full hist
        at d=0, sibling-subtraction half windows after; windows of
        GW leaves x S_STATS sublanes; codes re-streamed per pass and per
        unfused route at ONE byte/code — the round-4 packed uint8 planes;
        levels the fused route+hist covers read the plane once). Counts
        the dot as written — lane padding below 128 counts AGAINST
        utilization, as it should."""
        from h2o3_tpu.ops import hist_pallas as _hp
        S, GW, nb = _hp.S_STATS, _hp.GW, NBINS + 1
        macs = b = 0
        stat_b = 1 if int8 else 4
        code_b = 1                                     # uint8/packed plane
        for d in range(DEPTH):
            l_eff = 1 if d == 0 else (1 << d) >> 1
            gwe = min(l_eff, GW)
            npass = -(-l_eff // gwe)
            macs += npass * c_pad * (gwe * S) * nb * np_rows
            b += npass * (c_pad * np_rows * code_b     # codes re-stream
                          + S * np_rows * stat_b + np_rows * 4)
            b += l_eff * c_pad * S * nb * 4            # hist writeback
            if d >= 1:
                # mirror the real dispatch gate (incl. the VMEM cap) so the
                # byte model can't claim fusion grow() would refuse
                fused = _hp._fused_applicable(1 << d, nb, c_pad)
                b += 2 * np_rows * 4                   # heap in/out
                if not fused:                          # unfused route re-
                    b += c_pad * np_rows * code_b      # streams the codes
        return macs, b

    # published per-chip peaks, keyed by jax's device_kind (Google Cloud
    # documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s
    # HBM). A device that is not in the table is an error, not a default:
    # mfu/hbm_frac against another chip's peaks would be a made-up number.
    PEAKS = {"TPU v5 lite": {"f32": 197e12, "int8": 393e12, "hbm": 819e9}}
    device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); this benchmark measures the chip")
    PEAK_FLOPS, PEAK_HBM = PEAKS[device_kind], PEAKS[device_kind]["hbm"]

    def run_mode(int8: bool):
        """Train WARM warmup + CHUNK*NCHUNK timed trees; returns
        (row*trees/s, auc, mfu, hbm_frac)."""
        grower = BN.BinnedGrower(spec, max_depth=DEPTH, min_rows=1.0,
                                 min_split_improvement=0.0,
                                 int8_stats=int8)
        trainer = BN.gbm_chunk_trainer(grower, N, dist="bernoulli",
                                       eta=0.1, sample_rate=1.0, mtries=0,
                                       k_trees=CHUNK)
        F = jnp.where(jnp.arange(n_pad) < N, f0, 0.0).astype(jnp.float32)
        k = jax.random.PRNGKey(0)
        # warmup: compile + first chunk
        k, kc = jax.random.split(k)
        F, _ = trainer(codes, y1, w1, F, kc)
        jax.block_until_ready(F)
        t0 = time.time()
        for _ in range(NCHUNK):
            k, kc = jax.random.split(k)
            F, _ = trainer(codes, y1, w1, F, kc)
        jax.block_until_ready(F)
        dt = time.time() - t0
        ntrees = CHUNK * NCHUNK
        from h2o3_tpu.models.tree.engine import ROW_TREES
        ROW_TREES.inc(N * ntrees, engine="binned")   # /metrics sees the bench
        # codes may be the packed (W_pad, n_pad) plane — column count for
        # the analytic model comes from the bin spec, not the plane shape
        macs, hbm_b = roofline_model(spec.c_pad, codes.shape[1], int8)
        mode = "int8" if int8 else "f32"
        mfu = 2 * macs * ntrees / dt / PEAK_FLOPS[mode]
        hbm_frac = hbm_b * ntrees / dt / PEAK_HBM
        return N * ntrees / dt, float(auc_dev(F, y)), mfu, hbm_frac

    tp_f32, auc_f32, mfu_f32, hbm_f32 = run_mode(False)
    # CPU smoke mode trains far fewer trees — gate correctness, not power
    auc_gate = 0.72 if N >= 1_000_000 else 0.60
    assert auc_f32 > auc_gate, \
        f"AUC gate failed: {auc_f32:.4f} — kernels mis-trained"
    print(f"f32: {tp_f32/1e6:.2f}M row*trees/s auc={auc_f32:.4f} "
          f"mfu={mfu_f32:.3f} hbm={hbm_f32:.3f}", file=sys.stderr)
    paths = {"f32": {"row_trees_per_sec": round(tp_f32),
                     "train_auc": round(auc_f32, 4),
                     "mfu": round(mfu_f32, 4),
                     "hbm_frac": round(hbm_f32, 4)}}

    # int8 stats path, on request (--int8): report as headline ONLY if it
    # both trains at parity (AUC within 2e-3 of f32 on the identical run)
    # and is actually faster. Not run by default: at this width Mosaic
    # refuses the int8 histogram kernel below a 64-leaf window (VMEM), so
    # the pass raises — which is the point of asking for it.
    throughput, auc, mode = tp_f32, auc_f32, "f32"
    mfu, hbm_frac = mfu_f32, hbm_f32
    if "--int8" in sys.argv:
        tp_i8, auc_i8, mfu_i8, hbm_i8 = run_mode(True)
        paths["int8"] = {"row_trees_per_sec": round(tp_i8),
                         "train_auc": round(auc_i8, 4),
                         "auc_delta_vs_f32": round(auc_i8 - auc_f32, 5),
                         "mfu": round(mfu_i8, 4),
                         "hbm_frac": round(hbm_i8, 4)}
        print(f"int8: {tp_i8/1e6:.2f}M row*trees/s auc={auc_i8:.4f} "
              f"mfu={mfu_i8:.3f} hbm={hbm_i8:.3f}", file=sys.stderr)
        if auc_i8 >= auc_f32 - 2e-3 and tp_i8 > tp_f32:
            throughput, auc, mode = tp_i8, auc_i8, "int8"
            mfu, hbm_frac = mfu_i8, hbm_i8

    # ---- per-level cost arbiter (ISSUE 14): ONE eagerly-dispatched tree
    # with a host sync per level fills h2o3_tree_level_seconds{engine=
    # "binned", level} and gives the record its per-level table — the
    # breakdown that names the residual cost whenever the on-chip 25M
    # row-trees/s target is missed
    g_lb = BN.BinnedGrower(spec, max_depth=DEPTH, min_rows=1.0,
                           min_split_improvement=0.0)
    stats_lb = jnp.stack(
        [w1, w1 * (y1 - p0), w1 * (p0 * (1 - p0)),
         jnp.zeros_like(w1)], axis=0)
    F_lb = jnp.where(jnp.arange(n_pad) < N, f0, 0.0) \
        .astype(jnp.float32)
    level_seconds = BN.measure_level_seconds(g_lb, codes, stats_lb, F_lb)
    print("level seconds: " + " ".join(
        f"L{r['level']}={r['seconds'] * 1e3:.0f}ms"
        for r in level_seconds), file=sys.stderr)

    # ---- kernel stamp: what the selection rules traced into the
    # programs above (HP.KERNEL_TRACES), not what a probe believed
    traced = {k for k, _ in HP.kernel_traces()}
    kernel_flags = {
        # uint8 code planes are END-TO-END: the binner emits uint8, the
        # XLA fallbacks consume it, the Pallas kernels stream the packed
        # word layout — true on every backend
        "int8_codes": True,
        "radix_shallow": bool(traced & {"radix", "fused_radix"}),
        "fused_level": "fused" in traced,
        "int8_stats": mode == "int8",
    }
    chip = None
    target = 25_000_000
    if throughput < target:
        chip = {"shortfall": True, "target_row_trees_per_sec": target,
                "level_seconds": level_seconds}

    ingest = None
    if not gbm_only:
        try:
            ingest = ingest_bench()
            print(f"ingest: {ingest['mb_per_sec']:.1f} MB/s "
                  f"({ingest['cores']} cores, "
                  f"native={ingest['native_parser']})", file=sys.stderr)
        except Exception:
            traceback.print_exc()

    distributed_ingest = None
    if not gbm_only:
        try:
            distributed_ingest = distributed_ingest_bench(ingest)
            if distributed_ingest.get("blocked"):
                print("2-proc ingest sample blocked: "
                      f"{distributed_ingest['blocked_stage']}",
                      file=sys.stderr)
            else:
                print(f"2-proc ingest: "
                      f"{distributed_ingest['mb_per_sec']:.1f} MB/s over "
                      f"REST (cloud_size {distributed_ingest['cloud_size']}"
                      f", scaling "
                      f"{distributed_ingest.get('scaling_vs_single_host')})",
                      file=sys.stderr)
        except Exception:
            traceback.print_exc()

    scoring = None
    if not gbm_only:
        try:
            scoring = scoring_bench()
            print(f"scoring: {scoring['rows_per_sec']/1e3:.1f}k rows/s warm "
                  f"(batch {scoring['batch_rows']}, "
                  f"{scoring['warm_compiles']} warm compiles, "
                  f"{scoring['fast_path_hits']} hits / "
                  f"{scoring['fallbacks']} fallbacks, "
                  f"params {scoring['param_hbm_bytes']}B shared)",
                  file=sys.stderr)
        except Exception:
            traceback.print_exc()

    qos_overload = None
    if not gbm_only:
        try:
            qos_overload = qos_overload_bench()
            if qos_overload.get("blocked"):
                print("qos overload sample blocked: "
                      f"{qos_overload['blocked_stage']}", file=sys.stderr)
            else:
                print(f"qos overload: victim p99 "
                      f"{qos_overload['victim_p99_ms']}ms / "
                      f"{qos_overload['victim_failures']} failures under "
                      f"{qos_overload['flood_to_victim_ratio']}x flood "
                      f"({qos_overload['flood_rejected']} flood rejects)",
                      file=sys.stderr)
        except Exception:
            traceback.print_exc()

    fleet_serving = None
    if not gbm_only:
        try:
            fleet_serving = fleet_serving_bench()
            if fleet_serving.get("blocked"):
                print("fleet serving sample blocked: "
                      f"{fleet_serving['blocked_stage']}", file=sys.stderr)
            else:
                print(f"fleet serving: {fleet_serving['resident_models']} "
                      f"models on "
                      f"{fleet_serving['hbm_budget_bytes'] >> 20}MB HBM, "
                      f"warm p99 {fleet_serving['warm_p99_ms']}ms, "
                      f"cold-fault p99 "
                      f"{fleet_serving['cold_fault_p99_ms']}ms",
                      file=sys.stderr)
        except Exception:
            traceback.print_exc()

    multihost_scoring = None
    if not gbm_only:
        try:
            multihost_scoring = multihost_scoring_bench()
            if multihost_scoring.get("blocked"):
                print("2-proc scoring sample blocked: "
                      f"{multihost_scoring['blocked_stage']}",
                      file=sys.stderr)
            else:
                print("2-proc scoring: "
                      f"{multihost_scoring['scoring_rows_per_sec']/1e3:.1f}k "
                      "rows/s over REST", file=sys.stderr)
        except Exception:
            traceback.print_exc()

    baseline = 157e6  # H100 gpu_hist row*trees/s reference point (header)
    # publish into the obs registry, then emit the JSON line FROM it —
    # one source of truth for the driver record and a /metrics scraper
    reg = _registry()
    g_tp = reg.gauge("h2o3_bench_row_trees_per_sec",
                     "headline GBM training throughput")
    g_tp.set(throughput)
    g = reg.gauge("h2o3_bench", "chip benchmark facts (labeled by stat)")
    g.set(auc, stat="train_auc")
    g.set(mfu, stat="mfu")
    g.set(hbm_frac, stat="hbm_frac")
    g.set(throughput / baseline, stat="vs_baseline")
    if ingest:
        g.set(ingest["mb_per_sec"], stat="ingest_mb_per_sec")
    if distributed_ingest and distributed_ingest.get("mb_per_sec"):
        g.set(distributed_ingest["mb_per_sec"],
              stat="distributed_ingest_mb_per_sec")
    if scoring:
        g.set(scoring["rows_per_sec"], stat="scoring_rows_per_sec")
    print(json.dumps({
        "metric": "gbm_hist_row_trees_per_sec",
        "value": round(g_tp.value()),
        "unit": "row*trees/s",
        "vs_baseline": round(g.value(stat="vs_baseline"), 4),
        "train_auc": round(g.value(stat="train_auc"), 4),
        "stats_mode": mode,
        "backend": jax.default_backend(),
        "device": {"platform": jax.devices()[0].platform,
                   "kind": device_kind, "count": len(jax.devices())},
        "mfu": round(g.value(stat="mfu"), 4),
        "hbm_frac": round(g.value(stat="hbm_frac"), 4),
        "radix_shallow": kernel_flags["radix_shallow"],
        "int8_codes": kernel_flags["int8_codes"],
        "fused_level": kernel_flags["fused_level"],
        "kernel_flags": kernel_flags,
        "cores": os.cpu_count(),
        "gbm_only": gbm_only,
        "level_seconds": level_seconds,
        "chip": chip,
        "scoring_rows_per_sec": (scoring or {}).get("rows_per_sec"),
        "fast_path_hits": (scoring or {}).get("fast_path_hits"),
        "fallbacks": (scoring or {}).get("fallbacks"),
        "param_hbm_bytes": (scoring or {}).get("param_hbm_bytes"),
        "tracing_overhead_pct": (scoring or {}).get("tracing_overhead_pct"),
        "logging_overhead_pct": (scoring or {}).get("logging_overhead_pct"),
        "device_seconds": (scoring or {}).get("device_seconds"),
        "utilization_pct": (scoring or {}).get("utilization_pct"),
        "attribution_overhead_pct":
            (scoring or {}).get("attribution_overhead_pct"),
        "trace_id": bench_trace,
        "paths": paths,
        "ingest_mb_per_sec": (ingest or {}).get("mb_per_sec"),
        "ingest": ingest,
        "distributed_ingest": distributed_ingest,
        "scoring": scoring,
        "qos_overload": qos_overload,
        "fleet_serving": fleet_serving,
        "multihost_scoring": multihost_scoring,
    }))


if __name__ == "__main__":
    # any failure is a failure: the traceback goes to stderr and the exit
    # code is non-zero — a record that says "blocked" with rc 0 hid three
    # rounds of missing numbers
    main()
