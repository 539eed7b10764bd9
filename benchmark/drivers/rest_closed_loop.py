"""rest_closed_loop — the server in this process, the callers in a child.

`api/server.start_server(port=0)` here; `callers` threads in ONE child
process (rest_client.py: no jax, no h2o3_tpu) each POST JSON row payloads
to /3/Predictions/models/<key> and wait for each answer. Every seed sends
the same deck of payload sizes (exact shares, `deck` requests long),
reshuffled by each caller every time round; the rows of a payload are a seed-drawn slice of
the training arrays, encoded in set-up. Warm-up sends one request for each
power-of-two row bucket the micro-batcher can form from `callers`
payloads, and no other shape. Answers are kept (`keep_share` of each size,
drawn from the seed; every non-200 too) and compared after the window.

Parameters (traffic file): callers, payload_rows, payload_share, deck,
bodies_per_size, keep_share, min_bucket, trace_seconds.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark import profile, trace_reduce
from benchmark.drivers import rest_client as wire

STAGES = ("edge", "queue", "gate", "decode", "device", "readback", "app")


def _body(names, rows):
    return json.dumps({"columns": names, "rows": rows.tolist()}).encode()


def prepare(ctx):
    from h2o3_tpu.api.server import start_server
    mix, X, seed = ctx["mix"], ctx["X"], ctx["seed"]
    names = [f"f{j}" for j in range(X.shape[1])]
    rng = np.random.default_rng(seed ^ 0x5EED)
    sizes = [int(k) for k in mix["payload_rows"]]
    # the deck: exact shares, the same multiset for every seed
    counts = np.rint(np.array(mix["payload_share"]) * mix["deck"]).astype(int)
    base = np.repeat(np.arange(len(sizes)), counts)
    decks = [rng.permutation(base).tolist() for _ in range(mix["callers"])]
    ids, bodies = [], []
    for k in sizes:
        starts = rng.integers(0, X.shape[0] - k + 1, mix["bodies_per_size"])
        ids.append([np.arange(s, s + k) for s in starts])
        bodies.append([_body(names, X[i]) for i in ids[-1]])
    srv = start_server(port=0)
    url = (f"http://127.0.0.1:{srv.port}/3/Predictions/models/"
           f"{ctx['model'].key}")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(wire.__file__)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    state = {"srv": srv, "child": child, "url": url, "ids": ids,
             "sizes": sizes, "cmd": {
                 "cmd": "run", "url": url, "callers": mix["callers"],
                 "decks": decks, "bodies": bodies, "seed": seed,
                 "keep": [float(s) for s in mix["keep_share"]]}}
    # one request per bucket the batcher can form: min_bucket .. the
    # power of two that holds `callers` of the largest payload
    warm, b = [], int(mix["min_bucket"])
    while b < 2 * mix["callers"] * max(sizes):
        n = min(b, X.shape[0])
        warm.append(_body(names, X[:n]))
        b <<= 1
    wire.write_frame(child.stdin, {"cmd": "warm", "url": url, "bodies": warm})
    got = wire.read_frame(child.stdout)
    if got != [200] * len(warm):
        finish(ctx, state, None)
        raise RuntimeError(f"warm-up requests answered {got}")
    return state


def _stages(header):
    out = {}
    for part in header.split(","):
        name, _, dur = part.strip().partition(";dur=")
        if dur:
            out[name] = float(dur) / 1e3
    return out


def window(ctx, state, seconds, trace):
    child, mix = state["child"], ctx["mix"]
    if trace:
        seconds = min(seconds, float(mix["trace_seconds"]))
    tr = profile.Trace(ctx["root"], copy_to=ctx["keep_trace"]) if trace \
        else None
    with tr or contextlib.nullcontext():
        wire.write_frame(child.stdin, dict(state["cmd"], seconds=seconds))
        got = wire.read_frame(child.stdout)
    red = tr.reduce() if tr else None
    reqs = got["requests"]
    ok = [r for r in reqs if r[6] == 200]
    lat = np.array([r[5] if r[6] == 200 else np.inf for r in reqs])
    rows = sum(state["sizes"][r[2]] for r in ok)
    timing = {}
    for r in ok:
        for k, v in _stages(r[7]).items():
            timing[k] = timing.get(k, 0.0) + v
    out = {"seconds": got["seconds"], "attempted": len(reqs),
           "failed": len(reqs) - len(ok), "requests": reqs,
           "server_timing": timing, "trace": red,
           "end_to_end": {
               "score_rows_per_s": rows / got["seconds"],
               "serve_p50_ms": 1e3 * float(np.percentile(lat, 50)),
               "serve_p95_ms": 1e3 * float(np.percentile(lat, 95))}}
    by_size = {k: int(sum(r[2] == i for r in reqs))
               for i, k in enumerate(state["sizes"])}
    ctx["log"](f"rest_closed_loop: {len(reqs)} requests {by_size}, "
               f"{len(reqs) - len(ok)} failed, median "
               f"{1e3 * float(np.median(lat)):.2f} ms, stages {timing}")
    if red is not None and "to_wall" in red:
        spans = [{"name": "requests_in_flight", "start": r[4],
                  "end": r[4] + r[5]} for r in reqs]
        label = profile.label_by_spans(spans, "no_request_in_flight")
        out["idle_gaps"] = trace_reduce.label_gaps(
            red["gaps"], lambda s, e: label(red["to_wall"](s),
                                            red["to_wall"](e)))
    return out


def finish(ctx, state, window):
    """Stop the child and the server; the kept answers, parsed."""
    child = state["child"]
    try:
        wire.write_frame(child.stdin, {"cmd": "quit"})
        child.stdin.close()
    except OSError:
        pass
    try:
        child.wait(timeout=30)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
    state["srv"].stop()
    if window is None:
        return None
    answers = []
    for n, r in enumerate(window.pop("requests")):
        if r[8] is None:
            continue
        try:
            doc = json.loads(r[8]) if r[6] == 200 else {}
            preds = doc.get("predictions")
            if doc.get("row_count") != len(preds):
                preds = None
        except (ValueError, TypeError):
            preds = None
        answers.append((n, state["ids"][r[2]][r[3]], preds))
    return answers
