"""The compute substrate: H2O's MRTask re-imagined for XLA.

Reference: water/MRTask.java:65 — serialize a task, fan it out over nodes in a
binary RPC tree (MRTask.java:690-754), fork-join down to one chunk per task,
run `map(Chunk[])`, then `reduce` partial POJOs back up two trees
(MRTask.java:850-921).

TPU-native design: there is no task serialization, no RPC tree and no explicit
reduce plumbing. A "map over chunks + tree reduce" is exactly what XLA compiles
a jitted computation over a row-sharded array into: the map runs shard-local,
and any cross-shard reduction (sum/min/max/…) lowers to an ICI collective
(all-reduce) with optimal scheduling. Two entry points:

  * map_reduce(fn, ...)  — jit `fn` over sharded inputs with replicated (small)
    outputs. The common case: XLA inserts the collectives. This is the moral
    equivalent of `new MRTask(){map;reduce}.doAll(frame)`.
  * map_chunks(fn, ...)  — `shard_map` when per-shard (per-"node") semantics
    are required: fn sees its local row block and may call lax.psum etc.
    Equivalent of MRTask with setupLocal/postLocal node-level hooks.
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from h2o3_tpu.deploy import chaos as _chaos
from h2o3_tpu.deploy import membership as _mb
from h2o3_tpu.obs import tracing as _tracing
from h2o3_tpu.obs import watchdog as _wd
from h2o3_tpu.obs.timeline import span as _span
from h2o3_tpu.parallel import compat as _compat
from h2o3_tpu.parallel import mesh as _mesh

# ---------------------------------------------------------------------------
# cached_jit: jax.jit keyed by CODE + closure VALUES, not function identity.
#
# jax's trace/compile cache is keyed on the function object, so
# `jax.jit(lambda x: ...)` (or a nested def) inside a function body mints a
# fresh identity per call and recompiles every invocation — the R001 bug
# class the static analyzer (h2o3_tpu/analysis) now rejects. A lambda
# EXPRESSION, however, compiles to one code object shared by every
# evaluation; keying the wrapper on (code, defaults, closure values) makes
# call-site closures hit one resident wrapper as long as their captured
# values are equal. Unhashable captures (arrays, models) fall back to a
# plain uncached jit — exactly today's behavior, never worse.
_JIT_CACHE: OrderedDict = OrderedDict()
_JIT_CACHE_MAX = 512
_JIT_CACHE_LOCK = threading.Lock()


class _Uncacheable(Exception):
    """Function cannot be keyed safely — caller must fall back to a
    plain (uncached) jax.jit."""


def _typed(v):
    """Cell/default values keyed WITH their type: 1, 1.0 and True hash
    equal but trace to different programs."""
    return (type(v), v)


def _fn_key(fn, _seen=None):
    """Identity-free cache key for a function: code + defaults + closure
    cell values, resolving function-valued cells recursively (a per-call
    lambda captured by another per-call closure must not leak identity
    back into the key). Raises _Uncacheable for bound methods (two
    instances share code + cells, but trace different state) and for
    cyclic closures (recursive nested defs)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn                      # builtin / C function: stable object
    if getattr(fn, "__self__", None) is not None:
        # bound method: two instances share code + cells but trace
        # different state — cannot be keyed identity-free
        raise _Uncacheable("bound method: state lives on __self__")
    if _seen is None:
        _seen = set()
    if id(fn) in _seen:
        raise _Uncacheable("cyclic closure")
    _seen.add(id(fn))
    cells = tuple(
        _fn_key(c.cell_contents, _seen) if callable(c.cell_contents)
        else _typed(c.cell_contents)
        for c in (fn.__closure__ or ()))
    defaults = tuple(_typed(v) for v in (fn.__defaults__ or ()))
    kwdefaults = tuple(sorted((k, _typed(v)) for k, v in
                              (fn.__kwdefaults__ or {}).items()))
    return (code, defaults, kwdefaults, cells)


def cached_jit(fn, **jit_kwargs):
    """jax.jit with a wrapper cache keyed by _fn_key + jit kwargs.

    The per-call-closure fix: `cached_jit(lambda x: x @ R)` at a call site
    re-evaluated per request resolves to ONE wrapper (and one compiled
    program per shape) as long as the captured values hash equal.
    """
    try:
        key = (_fn_key(fn),
               tuple(sorted(jit_kwargs.items())))
        hash(key)
    except (TypeError, ValueError, _Uncacheable):
        # unhashable captures, bound methods, cyclic closures, or an
        # uninitialized cell (ValueError): uncached fallback — exactly
        # the pre-cached_jit behavior, never wrong results. Guarded like
        # the cached path: every cached_jit call site is a potential
        # multi-replica launch on a host mesh.
        return _compat.guard_collective(jax.jit(fn, **jit_kwargs))
    with _JIT_CACHE_LOCK:
        jfn = _JIT_CACHE.get(key)
        if jfn is not None:
            _JIT_CACHE.move_to_end(key)
            return jfn
    # the host-mesh collective guard rides INSIDE the cached wrapper, so
    # every call site of a cached_jit program serializes its launch→ready
    # window on CPU meshes (see parallel/compat.py)
    jfn = _compat.guard_collective(jax.jit(fn, **jit_kwargs))
    with _JIT_CACHE_LOCK:
        cur = _JIT_CACHE.setdefault(key, jfn)
        _JIT_CACHE.move_to_end(key)
        while len(_JIT_CACHE) > _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
    return cur


def _dispatch_once(jfn, arrays):
    """One device launch. The host-mesh collective guard rides INSIDE
    `jfn` (cached_jit / map_chunks wrap their jits with guard_collective
    at creation), so this funnel adds no second lock acquisition. The
    chaos hook lets the fault harness fail a seeded dispatch with
    EpochChanged."""
    _chaos.maybe_raise("mrtask.dispatch", exc=_mb.EpochChanged)
    return jfn(*arrays)


def _dispatch_retrying(jfn, arrays, retryable: bool):
    """Membership-aware dispatch: an execution that straddles a cloud
    epoch bump (a worker excised mid-collective) retries ONCE against
    the new epoch with jittered backoff instead of failing the caller.
    Single-host clouds and donated-buffer dispatches (whose inputs are
    consumed by the first attempt) skip straight through."""
    if retryable and _mb.MEMBERSHIP.multi:
        return _mb.retry_once(lambda: _dispatch_once(jfn, arrays),
                              op="mrtask")
    return _dispatch_once(jfn, arrays)


_qos_mod = None
_usage_mod = None


def _qos():
    """Lazy, cached serving/qos handle: the serving package imports this
    module at load time, so a module-level import here would cycle; by
    the first device dispatch the import graph is settled and the cost
    is one `is None` check per call."""
    global _qos_mod
    if _qos_mod is None:
        from h2o3_tpu.serving import qos
        _qos_mod = qos
    return _qos_mod


def _usage():
    """Lazy obs/usage handle — same cycle-avoidance shape as _qos()."""
    global _usage_mod
    if _usage_mod is None:
        from h2o3_tpu.obs import usage
        _usage_mod = usage
    return _usage_mod


def _traced_dispatch(name: str, jfn, arrays, fn, retryable=True):
    """Dispatch `jfn(*arrays)`, recording an mrtask phase span when the
    calling thread is inside an active trace (obs/tracing). Untraced
    callers — training inner loops, bench — pay the trace TLS read plus
    one watchdog registration (a slotted dict insert/remove under a
    leaf lock, a few microseconds).

    Priority lanes (serving/qos): a dispatch issued from a Job thread is
    BATCH work — it defers (bounded by H2O3_QOS_BATCH_YIELD_S) while
    interactive scoring requests are pending in the micro-batch queue,
    so training never steals device slots out from under a waiting
    user. Preemption happens here, at the scheduler; an in-flight
    device program always runs to completion.

    Every dispatch is watchdog-watched: a device program blocked past
    H2O3_WATCHDOG_STALL_S (the XLA:CPU collective-rendezvous deadlock —
    two in-flight multi-replica executions starving each other's
    thread-pool slots) trips a pinned diagnostic trace with a cluster
    JStack instead of hanging the process silently."""
    _qos().batch_yield()
    fname = getattr(fn, "__name__", "<fn>")
    # usage attribution: the dispatch wall charges the ambient principal
    # under this op's kind; the guarded jit's own meter inside jfn is
    # suppressed (outermost meter wins), so the seconds charge once
    with _wd.watch("device", desc=f"{name}:{fname}"), \
            _usage().meter(name):
        if _tracing.current() is not None:
            with _span(name, fn=fname):
                return _dispatch_retrying(jfn, arrays, retryable)
        return _dispatch_retrying(jfn, arrays, retryable)


def prefetch_chunks(handles):
    """Start tier-up of DKV chunk handles (Vecs or TierChunks) on the
    pager's I/O worker — fire-and-forget, so a later fault finds the
    planes already HBM-resident. The MRTask lookahead primitive."""
    if not handles:
        return
    from h2o3_tpu.core import tiering as _tiering
    _tiering.PAGER.prefetch(handles)


def map_chunked(fn, chunks, *, lookahead: int = 1):
    """Sequential MRTask over out-of-core chunk handles: run `fn(chunk)`
    per handle, prefetching the NEXT `lookahead` handles' tier-up on the
    pager's I/O thread overlapped with the current handle's compute —
    the Cleaner-era "reload while the map runs" pipelining, chunk-shaped.
    Returns the list of per-chunk results (reduce is the caller's fold)."""
    seq = list(chunks)
    out = []
    queued = 0          # high-water mark: windows overlap, enqueue once
    for i, c in enumerate(seq):
        if lookahead > 0 and i + 1 < len(seq):
            lo = max(queued, i + 1)
            hi = i + 1 + lookahead
            if hi > lo:
                prefetch_chunks(seq[lo:hi])
                queued = hi
        out.append(fn(c))
    return out


def map_reduce(fn, *arrays, donate=(), prefetch=()):
    """Jit `fn` over row-sharded arrays; outputs get whatever sharding XLA
    propagates (scalars/small reductions come back replicated).

    `fn` is traced once and cached per shape/dtype signature by jax.jit.
    `prefetch` takes chunk handles (Vecs) whose tier-up should overlap
    this dispatch — typically the NEXT iteration's columns.
    """
    prefetch_chunks(prefetch)
    jfn = cached_jit(fn, donate_argnums=donate)
    # donated inputs are consumed by the first attempt — never retryable
    # across an epoch bump
    return _traced_dispatch("mrtask.map_reduce", jfn, arrays, fn,
                            retryable=not donate)


def map_chunks(fn, *arrays, in_specs=None, out_specs=None, check_vma=False,
               prefetch=()):
    """shard_map `fn` over the rows axis: fn runs once per shard ("node"),
    seeing only its local rows, and may use lax.psum/ppermute over "rows".

    in_specs/out_specs default to row-sharded in, replicated out. The
    jitted shard_map wrapper is cached by (fn code+closure, mesh, specs):
    shard_map returns a fresh object per call, so an uncached jit here
    re-traced on every invocation (R001). `prefetch` overlaps the next
    chunk handles' tier-up with this dispatch (see map_chunked).
    """
    prefetch_chunks(prefetch)
    c = _mesh.cloud()
    if in_specs is None:
        in_specs = tuple(P(_mesh.ROWS, *([None] * (a.ndim - 1))) for a in arrays)
    in_specs = tuple(in_specs)

    def smapped(*arrs):
        return jax.shard_map(fn, mesh=c.mesh, in_specs=in_specs,
                             out_specs=out_specs if out_specs is not None
                             else P(), check_vma=check_vma)(*arrs)

    try:
        key = ("map_chunks", _fn_key(fn), c.mesh, in_specs,
               out_specs, check_vma)
        hash(key)
    except (TypeError, ValueError, _Uncacheable):
        return _traced_dispatch(   # h2o3-ok: R001,R011 unhashable specs fall back to the uncached legacy path; same map_chunks stage either way
            "mrtask.map_chunks",
            _compat.guard_collective(jax.jit(smapped)), arrays, fn)
    with _JIT_CACHE_LOCK:
        jfn = _JIT_CACHE.get(key)
        if jfn is None:
            jfn = _JIT_CACHE[key] = _compat.guard_collective(
                jax.jit(smapped))
        _JIT_CACHE.move_to_end(key)
        while len(_JIT_CACHE) > _JIT_CACHE_MAX:
            _JIT_CACHE.popitem(last=False)
    return _traced_dispatch("mrtask.map_chunks", jfn, arrays, fn)


def shard_sum(x, axis_name=_mesh.ROWS):
    """psum helper for use inside map_chunks bodies."""
    return jax.lax.psum(x, axis_name)


def host_fetch(x) -> "np.ndarray":
    """np.asarray of a possibly globally-sharded jax.Array.

    In a multi-controller runtime (deploy/multihost), fetching an array
    whose shards live on other processes' devices raises; gather it to
    every host first (the MRTask result-collection hop). Single-process
    arrays take the plain fast path."""
    import contextlib
    import numpy as np
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils
        # the allgather IS the work here (the MRTask result-collection
        # hop) — a traced request's remote fragment shows it
        ctx = _span("mrtask.host_fetch",
                    shape=[int(d) for d in getattr(x, "shape", ())]) \
            if _tracing.current() is not None else contextlib.nullcontext()
        with ctx:
            return np.asarray(
                multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


def device_put_rows(host_array, ndim=None):
    """Place a host array onto the mesh row-sharded (dim 0 over "rows").

    Multi-controller (deploy/multihost SPMD replay): every host holds the
    FULL host array (requests replay identically), so each process builds
    its addressable shards from its own copy via make_array_from_callback
    — plain device_put cannot target non-addressable devices."""
    c = _mesh.cloud()
    nd = host_array.ndim if ndim is None else ndim
    sh = c.rows_sharding(nd)
    if jax.process_count() > 1:
        import numpy as _np
        arr = _np.asarray(host_array)
        return jax.make_array_from_callback(arr.shape, sh,
                                            lambda idx: arr[idx])
    return jax.device_put(host_array, sh)


def device_put_replicated(host_array):
    c = _mesh.cloud()
    if jax.process_count() > 1:
        import numpy as _np
        arr = _np.asarray(host_array)
        return jax.make_array_from_callback(arr.shape, c.replicated(),
                                            lambda idx: arr[idx])
    return jax.device_put(host_array, c.replicated())


def jit_rows(fn=None, *, static_argnums=(), donate_argnums=()):
    """Decorator: jit a function whose first args are row-sharded arrays.

    Just jax.jit — named for intent at call sites (an "MRTask definition").
    """
    if fn is None:
        return functools.partial(jit_rows, static_argnums=static_argnums,
                                 donate_argnums=donate_argnums)
    return _compat.guard_collective(
        jax.jit(fn, static_argnums=static_argnums,
                donate_argnums=donate_argnums))


def row_mask(padded_len: int, nrows: int, dtype=jnp.float32):
    """1.0 for real rows, 0.0 for padding — the ESPC-padding guard.

    Built inside jit from scalars so it fuses into consumers.
    """
    return (jnp.arange(padded_len) < nrows).astype(dtype)
