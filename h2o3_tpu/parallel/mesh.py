"""The "cloud": a device mesh replacing H2O-3's gossip/Paxos cluster.

Reference: water/Paxos.java, water/H2O.java:1845 (startLocalNode),
water/HeartBeatThread.java. H2O forms a cloud of symmetric JVM peers via UDP
gossip and freezes membership at the first DKV write (Paxos.java:145).

TPU-native design: JAX is single-controller — one Python process drives every
chip. "Cloud formation" is simply constructing a `jax.sharding.Mesh` over the
visible devices; there is no consensus protocol to run, no heartbeats, no
flatfiles. Membership is fixed by construction (the moral equivalent of
`Paxos.lockCloud`), and "nodes" are mesh shards addressed by named axes.

Axes:
  * "rows"  — the data axis. Frames are row-sharded over it; every MRTask-like
              reduce becomes a psum over this axis riding ICI.
  * "model" — optional second axis for tensor/model parallelism (DeepLearning
              wide layers, batched tree-building, grid-search fan-out).
"""

from __future__ import annotations

import math
import os
import re
import threading
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

ROWS = "rows"
MODEL = "model"

# RLock: cloud() calls init() while already holding the lock (first-use
# formation path) — a plain Lock deadlocks every standalone server start
_lock = threading.RLock()
_CLOUD: "Cloud | None" = None


@dataclass
class Cloud:
    """A formed cloud == a live device mesh plus derived shardings."""

    mesh: Mesh
    name: str = "h2o3-tpu"
    # elastic membership (deploy/membership) epoch this mesh was built
    # for. The jax device runtime is fixed-size (ROADMAP gap), so an
    # epoch bump rebuilds the mesh over the SAME visible devices — but a
    # fresh Mesh object per epoch gives downstream placement caches (the
    # serving param store) an identity to invalidate against.
    epoch: int = 1

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    @property
    def n_rows_shards(self) -> int:
        return self.mesh.shape[ROWS]

    @property
    def n_model_shards(self) -> int:
        return self.mesh.shape.get(MODEL, 1)

    # ---- shardings ------------------------------------------------------
    def rows_sharding(self, ndim: int = 1) -> NamedSharding:
        """Row-sharded: dim 0 split over the data axis, rest replicated."""
        spec = P(ROWS, *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ---- row padding ----------------------------------------------------
    # H2O lays rows out via ESPC (Vec.java:163-171): uneven chunks per node.
    # XLA wants even, static shapes: we pad the row count up to a multiple of
    # (row-shards × sublane granule) and carry the logical nrows separately.
    ROW_GRANULE = 8  # f32 sublane granularity on TPU

    def padded_rows(self, nrows: int) -> int:
        g = self.n_rows_shards * self.ROW_GRANULE
        return max(g, int(math.ceil(nrows / g)) * g)

    def describe(self) -> dict:
        return {
            "cloud_name": self.name,
            "cloud_size": self.n_devices,
            "mesh_shape": dict(self.mesh.shape),
            "devices": [str(d) for d in self.mesh.devices.flat],
            "platform": self.mesh.devices.flat[0].platform if self.n_devices else "?",
            "consensus": "locked",  # single-controller: always formed, always locked
        }


def init(n_rows_shards: int | None = None, n_model_shards: int = 1,
         devices=None, name: str | None = None) -> Cloud:
    """Form the cloud (h2o.init analog). Idempotent unless shape changes.
    Name: explicit arg > ai.h2o.cloud.name property (-name flag) >
    default."""
    global _CLOUD
    if name is None:
        from h2o3_tpu.utils import config as _cfg
        name = str(_cfg.get_property("cloud.name", None) or "h2o3-tpu")
    with _lock:
        devices = list(devices if devices is not None else jax.devices())
        from h2o3_tpu.utils import compile_cache as _cc
        _cc.enable()
        total = len(devices)
        if n_rows_shards is None:
            n_rows_shards = total // n_model_shards
        use = n_rows_shards * n_model_shards
        if use > total:
            raise ValueError(
                f"requested {use} devices ({n_rows_shards}x{n_model_shards}) "
                f"but only {total} visible")
        dev_grid = np.array(devices[:use]).reshape(n_rows_shards, n_model_shards)
        mesh = Mesh(dev_grid, (ROWS, MODEL))
        _CLOUD = Cloud(mesh=mesh, name=name)
        # extension lifecycle (ExtensionManager onLocalNodeStarted analog)
        try:
            from h2o3_tpu.ext import load_configured_extensions
            load_configured_extensions(_CLOUD)
        except Exception:   # an extension failure must not kill the cloud
            import traceback
            traceback.print_exc()
        return _CLOUD


def cloud() -> Cloud:
    """Return the formed cloud, forming a default one on first use."""
    global _CLOUD
    if _CLOUD is None:
        with _lock:
            if _CLOUD is None:
                init()
    return _CLOUD


def shutdown():
    """Tear down the cloud and the registry (h2o.cluster().shutdown())."""
    global _CLOUD
    from h2o3_tpu.core.kvstore import DKV
    with _lock:
        DKV.clear()
        _CLOUD = None


def cluster_info() -> dict:
    """REST /3/Cloud analog."""
    return cloud().describe()


def note_epoch(epoch: int) -> "Cloud":
    """Adopt a cloud-membership epoch (deploy/membership listener hook):
    when it moves past the formed mesh's epoch, rebuild the mesh — same
    shape, same visible devices (the jax runtime is fixed-size), but a
    NEW Mesh object stamped with the epoch, so placement caches keyed on
    mesh identity (serving/params) re-place instead of serving arrays
    laid out for a dead membership. Idempotent for old/equal epochs."""
    global _CLOUD
    with _lock:
        c = cloud()
        if epoch <= c.epoch:
            return c
        mesh = Mesh(c.mesh.devices, c.mesh.axis_names)
        _CLOUD = Cloud(mesh=mesh, name=c.name, epoch=int(epoch))
        return _CLOUD


# ---------------------------------------------------------------------------
# Regex-rule partitioner: param pytrees → PartitionSpec pytrees →
# NamedSharding placements (the match_partition_rules / shard_params /
# make_shard_and_gather_fns pattern, re-keyed for model serving).
#
# A rule set is ((regex, PartitionSpec), ...). Each leaf of a param
# pytree is named by its '/'-joined tree path ("_trees/value",
# "_params_net/1/0", …); the FIRST rule whose regex `re.search`-matches
# the name wins. Scalars and unmatched leaves replicate (P()) — serving
# must never refuse a model because a rule is missing; replication is
# the always-correct default and still yields ONE shared copy per model
# (the HBM win is vs. per-bucket baked constants, not vs. replication).


def _leaf_name(path) -> str:
    """'/'-joined jax KeyPath → rule-matchable leaf name."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def named_tree_map(fn, tree):
    """tree_map with the '/'-joined path name as the first argument."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(_leaf_name(path), leaf), tree)


def match_partition_rules(rules, params):
    """Pytree of PartitionSpec, one per leaf of `params`, by first-match
    regex over the leaf's path name. Scalar leaves and leaves no rule
    matches get P() (replicated)."""
    rules = tuple(rules or ())

    def spec_for(name, leaf):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()            # never partition scalars
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        return P()
    return named_tree_map(spec_for, params)


def _canon_host_leaf(leaf) -> np.ndarray:
    """Serving dtype canonicalization for HOST leaves: params reach the
    scorer in the dtypes its traced math uses — f32 floats, i32 ints.
    Matches the jnp.asarray(..., jnp.float32) casts inside every
    _score_matrix, so passing params as device args instead of baked
    constants cannot change a single bit of the result."""
    a = np.asarray(leaf)
    if a.dtype == np.float64:
        a = a.astype(np.float32)
    elif a.dtype == np.int64:
        a = a.astype(np.int32)
    return a


def shard_params(params, specs=None, *, cld: "Cloud | None" = None,
                 rules=None):
    """device_put every leaf of a param pytree with its NamedSharding —
    ONE resident copy per model, shared by every compiled row-bucket
    program that takes it as an argument. `specs` is a PartitionSpec
    pytree (from match_partition_rules); passing `rules` computes it.
    Device-resident leaves (trained ensembles) reshard device-to-device
    — no host round trip, transfer-guard clean. Multi-controller
    runtimes build each process's addressable shards from its own
    (replay-identical) host copy, exactly like mrtask.device_put_rows."""
    c = cld or cloud()
    if specs is None:
        specs = match_partition_rules(rules, params)
    multi = jax.process_count() > 1

    def place(leaf, spec):
        sh = NamedSharding(c.mesh, spec)
        if multi:
            from h2o3_tpu.parallel import mrtask as _mrt
            arr = _canon_host_leaf(
                _mrt.host_fetch(leaf) if isinstance(leaf, jax.Array)
                else leaf)
            return jax.make_array_from_callback(arr.shape, sh,
                                                lambda idx: arr[idx])
        if isinstance(leaf, jax.Array):
            return jax.device_put(leaf, sh)
        return jax.device_put(_canon_host_leaf(leaf), sh)
    return jax.tree_util.tree_map(place, params, specs)


def make_shard_and_gather_fns(specs, cld: "Cloud | None" = None):
    """(shard_fn, gather_fn) pytrees for a PartitionSpec pytree:
    shard_fn(leaf) places one leaf with its NamedSharding; gather_fn
    fetches it back to a host numpy array (the checkpoint/export hop)."""
    c = cld or cloud()

    def mk_shard(spec):
        return lambda leaf: shard_params(leaf, specs=spec, cld=c)

    def mk_gather(spec):
        del spec
        from h2o3_tpu.parallel import mrtask as _mrt
        return lambda leaf: _mrt.host_fetch(leaf)
    return (jax.tree_util.tree_map(mk_shard, specs,
                                   is_leaf=lambda s: isinstance(s, P)),
            jax.tree_util.tree_map(mk_gather, specs,
                                   is_leaf=lambda s: isinstance(s, P)))


def params_nbytes(params) -> int:
    """Logical bytes of ONE copy of a (placed or host) param pytree —
    the h2o3_scorer_params_bytes gauge's unit: per-model HBM occupancy
    that must stay CONSTANT in the number of compiled row-buckets."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        n = getattr(leaf, "nbytes", None)
        if n is None:
            n = np.asarray(leaf).nbytes
        total += int(n)
    return total
