"""Entry point for the real multi-process SPMD test (one invocation per
process). Forms a 2-process jax.distributed CPU cloud, then runs the
deploy/multihost serve() path: process 0 serves REST + broadcasts, worker
replays — the multiNodeUtils.sh 4-JVM local-cloud analog, reduced to 2.

Usage: python multiproc_runner.py <process_id> <num_procs> <coord_port> \
           <rest_port>
"""

import os
import sys


def main():
    pid, nproc, coord_port, rest_port = (int(a) for a in sys.argv[1:5])
    join = len(sys.argv) > 5 and sys.argv[5] == "join"
    # CPU children by construction, whatever the environment says: the
    # parent (a test on a machine with a chip) may hold the
    # accelerator, and a chip belongs to one process at a time
    import jax
    jax.config.update("jax_platforms", "cpu")
    os.environ.setdefault("H2O3_CLUSTER_SECRET", "multiproc-test-secret")
    os.environ["H2O3_PROCESS_ID"] = str(pid)
    os.environ["H2O3_INSECURE_BIND_ALL"] = "1"   # loopback-only test

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from h2o3_tpu.deploy import multihost
    if join:
        # replacement worker: the dead process's slot in the fixed jax
        # runtime is gone — join the REPLAY CHANNEL only (single-process
        # jax), sync epoch + snapshot, serve replays
        import h2o3_tpu
        h2o3_tpu.init()
        multihost.join_cloud("127.0.0.1", rest_port, pid)
        return
    os.environ["H2O3_COORDINATOR_ADDRESS"] = f"127.0.0.1:{coord_port}"
    os.environ["H2O3_NUM_PROCESSES"] = str(nproc)
    multihost.serve(rest_port)


if __name__ == "__main__":
    main()
