"""In-product self-benchmarks — water/init/{NetworkBench,Linpack,
MemoryBandwidth}.java rebuilt for TPU hardware.

Reference: NetworkBench.java:16-18 (all-to-all + MRTask message
latency/throughput across the cloud), Linpack.java (per-node FLOPS),
MemoryBandwidth.java (per-node memory bandwidth), exposed over REST and used
to sanity-check a cluster before long jobs.

TPU equivalents: the "network" is ICI — measured with psum/all_gather
round-trips over the mesh; "Linpack" is an MXU matmul FLOPs probe in
bfloat16 and float32; "memory bandwidth" is an HBM triad stream. CLI:
`python -m h2o3_tpu.utils.selfbench`."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, repeats=5):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def network_bench(sizes=(1 << 10, 1 << 16, 1 << 22)) -> list:
    """ICI collective latency/bandwidth: psum + all_gather per payload size
    (NetworkBench's all-to-all matrix collapses to mesh collectives)."""
    from h2o3_tpu.parallel import mesh as M
    cloud = M.cloud()
    mesh = cloud.mesh
    axis = M.ROWS
    n_dev = cloud.n_rows_shards
    from jax.sharding import NamedSharding, PartitionSpec as P
    results = []
    for size in sizes:
        n = size // 4  # f32 elements per device
        x = jax.device_put(
            jnp.ones((n_dev, max(n, 1)), jnp.float32),
            NamedSharding(mesh, P(axis, None)))

        @jax.jit
        def allreduce(x):
            return jax.shard_map(
                lambda s: jax.lax.psum(s, axis),
                mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None)
            )(x)

        dt = _timeit(allreduce, x)
        results.append({
            "op": "psum", "payload_bytes_per_device": int(n * 4),
            "latency_us": dt * 1e6,
            "algo_bw_gbps": (n * 4 * 2 * (n_dev - 1) / max(n_dev, 1))
                            / max(dt, 1e-12) / 1e9,
        })
    return results


def linpack(n: int = 4096, dtype="bfloat16") -> dict:
    """MXU FLOPs probe (Linpack.java analog): C = A @ B throughput."""
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    a = jnp.ones((n, n), dt)
    b = jnp.ones((n, n), dt)

    @jax.jit
    def mm(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    t = _timeit(mm, a, b)
    flops = 2.0 * n * n * n
    return {"n": n, "dtype": dtype, "seconds": t,
            "gflops": flops / max(t, 1e-12) / 1e9}


def memory_bandwidth(n: int = 1 << 24) -> dict:
    """HBM stream triad (MemoryBandwidth.java analog): a = b + 2·c."""
    b = jnp.ones(n, jnp.float32)
    c = jnp.ones(n, jnp.float32)

    @jax.jit
    def triad(b, c):
        return b + 2.0 * c

    t = _timeit(triad, b, c)
    bytes_moved = n * 4 * 3
    return {"elements": n, "seconds": t,
            "gbps": bytes_moved / max(t, 1e-12) / 1e9}


def publish(results: dict) -> dict:
    """Emit selfbench numbers into the obs registry so /metrics and
    bench.py report the same hardware facts (the WaterMeter contract:
    one source of truth for scrapers and humans)."""
    from h2o3_tpu.obs import metrics as om
    g = om.gauge("h2o3_selfbench", "in-product hardware self-benchmarks "
                 "(linpack gflops, HBM triad GB/s, ICI collectives)")
    # one label schema for every probe (R005): absent dimensions are "",
    # so the series aggregate instead of splitting per probe family
    lp = results.get("linpack")
    if lp:
        g.set(lp["gflops"], probe="linpack_gflops", dtype=lp["dtype"],
              payload_bytes="")
    mb = results.get("memory_bandwidth")
    if mb:
        g.set(mb["gbps"], probe="hbm_triad_gbps", dtype="",
              payload_bytes="")
    for row in results.get("network") or []:
        pb = str(row["payload_bytes_per_device"])
        g.set(row["latency_us"], probe="ici_latency_us", dtype="",
              payload_bytes=pb)
        g.set(row["algo_bw_gbps"], probe="ici_bw_gbps", dtype="",
              payload_bytes=pb)
    return results


def run_all() -> dict:
    return publish({"network": network_bench(), "linpack": linpack(),
                    "memory_bandwidth": memory_bandwidth(),
                    "backend": jax.default_backend(),
                    "n_devices": len(jax.devices())})


if __name__ == "__main__":
    import json
    import h2o3_tpu
    h2o3_tpu.init()
    print(json.dumps(run_all(), indent=2, default=float))   # h2o3-ok: R012 `python -m ...selfbench` CLI: the JSON report on stdout IS the interface
