#!/usr/bin/env python3
"""How benchmark/testdata/small.xplane.pb was made: a few short jitted
steps with idle host time between them, traced on the chip through
profile.Trace, copied out with what trace_reduce.reduce read from it
(benchmark/testdata/small.expected.json). Run on a TPU:

    python benchmark/tools/record_testdata.py <out_dir>
"""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402

from benchmark import profile, trace_reduce  # noqa: E402


def main(out_dir):
    os.makedirs(out_dir, exist_ok=True)

    @jax.jit
    def run(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.float32)
    run(x).block_until_ready()
    tr = profile.Trace(ROOT, keep=True)
    with tr:
        for _ in range(4):
            run(x).block_until_ready()
            time.sleep(0.02)
    path = trace_reduce.newest_xplane(tr.dir)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    pd = trace_reduce.load(path)
    with open(os.path.join(out_dir, "small.describe.txt"), "w") as fh:
        fh.write(trace_reduce.describe(pd, n=6))
    red = tr.reduce()
    red.pop("to_wall", None)
    with open(os.path.join(out_dir, "small.expected.json"), "w") as fh:
        json.dump(red, fh, indent=1)
    print(json.dumps({k: red[k] for k in ("chips", "window_s", "busy_s",
                                          "module_s")}))


if __name__ == "__main__":
    main(sys.argv[1])
