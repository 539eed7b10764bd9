#!/usr/bin/env python3
"""rest_client — the load generator of the REST mixes, as a child process.

It imports neither jax nor h2o3_tpu nor numpy: threads + urllib only, so
the callers do not share the server's interpreter lock. It speaks pickle
frames on stdin/stdout with rest_closed_loop.py:

    {"cmd": "warm", "url", "bodies": [bytes]}        -> [status, ...]
    {"cmd": "run", "url", "seconds", "callers", "decks", "bodies", "keep",
     "seed"}                                          -> {"requests": [...]}
    {"cmd": "quit"}

A closed loop: each caller sends its next request only after it has read
the whole answer to the previous one, going round its deck of payload
sizes in a fresh order each time; a request is started while the
window is open and the one in flight finishes. The clock of a request
stops when its response body has been read.
"""

from __future__ import annotations

import pickle
import random
import struct
import sys
import threading
import time
import urllib.error
import urllib.request

TIMEOUT_S = 120.0


def read_frame(fh):
    head = fh.read(8)
    if len(head) < 8:
        return None
    return pickle.loads(fh.read(struct.unpack("<Q", head)[0]))


def write_frame(fh, obj):
    blob = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    fh.write(struct.pack("<Q", len(blob)))
    fh.write(blob)
    fh.flush()


def post(url, body):
    """(status, Server-Timing header, response bytes)."""
    req = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=TIMEOUT_S) as r:
            return r.status, r.headers.get("Server-Timing", ""), r.read()
    except urllib.error.HTTPError as e:
        return e.code, "", e.read()
    except Exception as e:                       # noqa: BLE001
        return -1, repr(e), b""


def caller(k, cmd, t_end, out):
    rng = random.Random(cmd["seed"] * 1009 + k)
    deck, bodies, keep = list(cmd["decks"][k]), cmd["bodies"], cmd["keep"]
    i = 0
    while time.perf_counter() < t_end:
        if i % len(deck) == 0:
            rng.shuffle(deck)       # a new order each time round the deck,
        size = deck[i % len(deck)]  # so no seed keeps one unlucky overlap
        b = rng.randrange(len(bodies[size]))
        wall = time.time()
        t0 = time.perf_counter()
        status, timing, data = post(cmd["url"], bodies[size][b])
        lat = time.perf_counter() - t0
        kept = data if (status != 200 or rng.random() < keep[size]) else None
        out.append((k, i, size, b, wall, lat, status, timing, kept))
        i += 1


def run(cmd):
    outs = [[] for _ in range(cmd["callers"])]
    t0 = time.perf_counter()
    threads = [threading.Thread(target=caller, daemon=True,
                                args=(k, cmd, t0 + cmd["seconds"], outs[k]))
               for k in range(cmd["callers"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"seconds": time.perf_counter() - t0,
            "requests": [r for o in outs for r in o]}


def main():
    fin, fout = sys.stdin.buffer, sys.stdout.buffer
    while True:
        cmd = read_frame(fin)
        if cmd is None or cmd["cmd"] == "quit":
            return 0
        if cmd["cmd"] == "warm":
            write_frame(fout, [post(cmd["url"], b)[0] for b in cmd["bodies"]])
        elif cmd["cmd"] == "run":
            write_frame(fout, run(cmd))


if __name__ == "__main__":
    sys.exit(main())
