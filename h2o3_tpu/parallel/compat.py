"""Host-mesh collective guard — THE one serialization point for concurrent
multi-replica dispatch on host (CPU) meshes. XLA's CPU client shares ONE
collective thread pool across concurrently launched programs: two
in-flight multi-replica executions each park a subset of their
participants at the rendezvous (collective_ops_utils.h "may be stuck")
and starve each other forever. The fix is to keep AT MOST ONE collective
program in flight: every dispatch funnel acquires the guard, launches,
and `block_until_ready`s BEFORE releasing — scoped to device execution
only, so host-side work (staging, binning prep, numpy solves) between
dispatches overlaps freely across threads. This hoists the whole-train
lock H2OGridSearch used to carry (models/grid.py) into the shared
dispatch layer: wired at mrtask dispatch (map_reduce/map_chunks/
cached_jit), the tree engine's per-level launches, and GLM's IRLS device
passes. Accelerator runtimes queue per-device and interleave fine, so
the guard is a no-op there (and on single-device CPU).
"""

from __future__ import annotations

import contextlib
import threading

import jax

from h2o3_tpu.utils import env as _uenv


# ---------------------------------------------------------------------------
# host-mesh collective serialization
# RLock: a guarded region may re-enter (tracing a guarded dispatch can
# evaluate nested cached_jit call sites on the same thread)
_HOST_COLLECTIVE_LOCK = threading.RLock()
_NEEDS_SERIALIZATION: bool | None = None


def needs_host_serialization() -> bool:
    """True on multi-device host (CPU) meshes, where XLA's shared
    collective thread pool makes concurrent multi-replica programs
    deadlock-prone. Memoized after the first backend probe;
    H2O3_HOST_SERIALIZE=0|1 overrides."""
    global _NEEDS_SERIALIZATION
    env = _uenv.env_str("H2O3_HOST_SERIALIZE", "")
    if env in ("0", "1"):
        return env == "1"
    if _NEEDS_SERIALIZATION is None:
        try:
            _NEEDS_SERIALIZATION = (jax.default_backend() == "cpu"
                                    and jax.device_count() > 1)
        except Exception:   # noqa: BLE001 — no backend: nothing to guard
            _NEEDS_SERIALIZATION = False
    return _NEEDS_SERIALIZATION


def host_collective_guard():
    """Context manager for a launch→block region on host meshes (a
    shared nullcontext elsewhere). Callers that hold device results
    across host-side work should prefer `run_host_serialized`, which
    also drains the launched program before releasing."""
    if needs_host_serialization():
        return _HOST_COLLECTIVE_LOCK
    return contextlib.nullcontext()


def _block_concrete(out):
    """block_until_ready on every CONCRETE array leaf (tracers pass
    through — a guarded dispatch evaluated under an outer trace must not
    try to force an abstract value)."""
    for leaf in jax.tree_util.tree_leaves(out):
        if isinstance(leaf, jax.Array) \
                and not isinstance(leaf, jax.core.Tracer):
            leaf.block_until_ready()
    return out


def run_host_serialized(fn):
    """Run `fn()` (a device launch) with at most one collective program
    in flight on host meshes: acquire the guard, launch, block until the
    result is ready, release. On accelerators: just `fn()` — async
    dispatch stays fully pipelined."""
    if not needs_host_serialization():
        return fn()
    with _HOST_COLLECTIVE_LOCK:
        # h2o3-ok: R008 the block IS the guard's contract — at most one collective program in flight means holding the lock through launch→ready; a stall here is exactly what the watchdog's device watch diagnoses
        return _block_concrete(fn())


_usage_mod = None


def _usage():
    """Lazy obs/usage handle (same shape as mrtask's lazy qos import):
    the metrics registry pulls usage in at its own import, so a
    module-level import here would cycle through obs during bootstrap;
    by the first guarded launch the graph is settled."""
    global _usage_mod
    if _usage_mod is None:
        from h2o3_tpu.obs import usage
        _usage_mod = usage
    return _usage_mod


def guard_collective(jfn):
    """Wrap an already-jitted callable so every invocation runs under
    the host-mesh collective guard. The decorator spelling of
    run_host_serialized, for module-level jits the dispatch layer cannot
    see (the tree engine's level programs, GLM's gram passes).

    Also the bottom of the usage-attribution funnel: every guarded
    launch meters its wall seconds to the ambient principal (kind
    `jit`) unless an outer meter — mrtask's traced dispatch, the scorer
    cache — already owns the charge."""
    import functools

    @functools.wraps(jfn)
    def _guarded(*a, **k):
        with _usage().meter("jit"):
            return run_host_serialized(lambda: jfn(*a, **k))

    _guarded.__wrapped__ = jfn
    return _guarded


def guarded_jit(fn, **jit_kwargs):
    """jax.jit + guard_collective in one step (the analyzer's rules_jax
    treats this as a jit-maker, so R001/R004 coverage is preserved)."""
    return guard_collective(jax.jit(fn, **jit_kwargs))


# Whole-train serialization on host meshes. The fine-grained guard above
# covers every JIT launch, but a training body also runs EAGER ops on
# sharded arrays (e.g. shared_tree._binned_setup's row slicing → gather
# collectives) that no call-site wrapper can reach — two concurrent
# trains' eager collectives still rendezvous-starve (reproduced: the
# parallel grid probe hangs ~50% without this). So concurrent TRAINS
# serialize end-to-end on host meshes, exactly the protection the old
# models/grid.py lock gave — now owned by the shared layer so any
# concurrent-train driver (grid, future tuners) gets it. Accelerator
# runtimes keep full overlap (nullcontext). RLock: nested drivers
# (AutoML → grid → train) re-enter on one thread.
_TRAIN_LOCK = threading.RLock()


def train_guard():
    """Context manager serializing one whole model-train body against
    concurrent trains on host meshes; nullcontext elsewhere."""
    if needs_host_serialization():
        return _TRAIN_LOCK
    return contextlib.nullcontext()
