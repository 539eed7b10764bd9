"""Shared serving-param placements — model params under the tier pager.

The pre-mesh scorer cache traced a model's parameters (tree arrays, GLM
coefficients, net weights, centroids, …) into each per-bucket XLA
program as closure constants: N row-buckets × M models duplicated every
ensemble in HBM, and any model bigger than one host's HBM simply could
not ride the fast path. This store is the other half of the rebuild:

  * A model family exports a param PYTREE (`ModelBase._serving_params`)
    plus regex partition rules; `parallel.mesh.match_partition_rules`
    maps each leaf to a `PartitionSpec` and `mesh.shard_params` places
    it once as `NamedSharding`-committed device arrays.
  * Every compiled row-bucket program takes the placed pytree as its
    FIRST argument (not a baked constant), so all buckets — and on a
    multi-controller cloud, all hosts — share the same single copy.
  * Placements are REFCOUNTED by the cache entries that dispatch them:
    each resident (model, bucket) program holds one reference; the last
    eviction (LRU, stale-generation purge, model DELETE) frees the
    placement exactly once. `h2o3_scorer_params_bytes{model}` tracks the
    per-model HBM occupancy, which is constant in the number of buckets.
  * A cloud-epoch bump (deploy/membership) rebuilds the mesh
    (`mesh.note_epoch`); placements record the epoch they were placed
    for and transparently re-place on the next dispatch.

Fleet-scale tiering (H2O-3's water/Cleaner.java memory manager, rebuilt
for the serving hot path): with `H2O3_SERVE_HBM_BUDGET_MB` set, a
placement's refcount keeps it REGISTERED but no longer keeps it
DEVICE-RESIDENT. Params ride the same three-tier ladder as chunk planes
(core/tiering.py):

    HBM (placed pytree)  ⇄  host canonical numpy  ⇄  npz under ice_root

  * PROMOTE is the ISSUE-11 placement primitive: the per-spec shard_fns
    from `mesh.make_shard_and_gather_fns` place the canonical host
    pytree; admission is reserved ATOMICALLY before any device_put
    lands (the ISSUE-6 in-flight-reservation discipline), so the
    `h2o3_scorer_params_bytes` sum can never exceed the budget even
    under concurrent cold faults.
  * DEMOTE is the matching gather_fns pass + `mesh._canon_host_leaf`
    (f64→f32, i64→i32) — the same canonicalization `shard_params`
    applies on the way in, so a demote→promote round trip is bit-exact.
  * EVICTION is same-tenant-first LRU: victims are chosen first among
    the faulting tenant's own cold placements, then cross-tenant in
    ascending `qos.eviction_standing` (heaviest QoS consumers first),
    then by the per-model hotness clock — one tenant's model churn
    cannot evict another tenant's hot set, and every eviction is
    CHARGED to the tenant whose fault forced it. `pin()` marks a
    model's placements never-victim (SLO hot sets).
  * `H2O3_SERVE_HOST_BUDGET_MB` bounds the host tier the same way;
    overflow spills to an npz artifact under ice_root (io/spill.py),
    freed exactly once on release/DELETE/retrain.

With no budget set, behavior is the pre-tiering fast path: eager
device placement at acquire, nothing demotes, no host mirrors.
"""

from __future__ import annotations

import itertools

import jax

from h2o3_tpu.analysis.lockdep import make_lock
from h2o3_tpu.obs import metrics as _om
from h2o3_tpu.parallel import mesh as _mesh
from h2o3_tpu.utils.env import env_int

# tier names (string-compatible with core.tiering's ladder)
TIER_HBM = "hbm"
TIER_HOST = "host"
TIER_DISK = "disk"
_TIERS = (TIER_HBM, TIER_HOST, TIER_DISK)

PARAM_BYTES = _om.gauge(
    "h2o3_scorer_params_bytes",
    "HBM-resident bytes of ONE shared serving-param copy per model "
    "(constant in the number of compiled row-buckets; demoted "
    "placements leave the gauge — it is bounded by "
    "H2O3_SERVE_HBM_BUDGET_MB when set)")
PLACEMENTS = _om.counter(
    "h2o3_scorer_param_placements_total",
    "serving param pytrees placed on the mesh (one per model generation "
    "per cloud epoch; re-places after an epoch bump are counted too)")
PARAM_FAULTS = _om.counter(
    "h2o3_serve_param_faults_total",
    "model-param promotions into HBM by source tier — a cold model "
    "faulting in from its host mirror or ice_root npz artifact")
PARAM_EVICTIONS = _om.counter(
    "h2o3_serve_param_evictions_total",
    "model-param demotions by destination tier, charged to the tenant "
    "whose cold fault forced the eviction")


def _hbm_budget_bytes() -> int:
    """H2O3_SERVE_HBM_BUDGET_MB — byte budget for DEVICE-resident model
    params (0 = unbudgeted eager placement). Read per call so serving
    tests and operators can retune without a restart."""
    return env_int("H2O3_SERVE_HBM_BUDGET_MB", 0) * (1 << 20)


def _host_budget_bytes() -> int:
    """H2O3_SERVE_HOST_BUDGET_MB — byte budget for the host tier of
    demoted model params (0 = unbounded host tier)."""
    return env_int("H2O3_SERVE_HOST_BUDGET_MB", 0) * (1 << 20)


def _standing(principal: str) -> float:
    """Cross-tenant victim ordering key — qos.eviction_standing in
    [0, 1], lower = heavier consumer = evicted first. Looked up OUTSIDE
    the store lock (qos takes its own locks)."""
    try:
        from h2o3_tpu.serving import qos as _qos
        return _qos.eviction_standing(principal)
    except Exception:   # noqa: BLE001 — victim order must never fail
        return 1.0


class Placement:
    """One model generation's params, resident on exactly the tiers its
    non-None slots say: `placed` (device pytree), `host` (canonical
    numpy pytree), `path` (npz spill artifact). `specs` is the
    PartitionSpec pytree and `treedef` the param tree structure — both
    mesh-independent, so a placement can demote off one cloud epoch and
    promote onto the next (jax interns Mesh objects — same devices and
    axis names give the SAME Mesh back — so the epoch, not mesh
    identity, is the staleness signal). `tenant` is the principal that
    faulted it in last; `last` is the hotness-clock tick. `_io` is the
    per-placement transfer lock (one lockdep class), ordered BEFORE the
    store lock exactly like tiering.io → tiering.residency."""

    __slots__ = ("key", "placed", "specs", "host", "treedef", "path",
                 "nbytes", "epoch", "refs", "tenant", "last", "_io",
                 "_acct")

    def __init__(self, placed, specs, nbytes, epoch, host=None,
                 treedef=None):
        self.key = None
        self.placed = placed
        self.specs = specs
        self.host = host
        self.treedef = treedef
        self.path = None
        self.nbytes = nbytes
        self.epoch = epoch
        self.refs = 0
        self.tenant = "anonymous"
        self.last = 0
        self._io = make_lock("serving.params.io")
        self._acct = None

    @property
    def tier(self) -> str:
        """Best (fastest) tier this placement is resident on."""
        if self.placed is not None:
            return TIER_HBM
        if self.host is not None:
            return TIER_HOST
        return TIER_DISK


class ParamStore:
    """(model key, generation token) → refcounted, TIERED Placement."""

    def __init__(self):
        self._lock = make_lock("serving.params")
        self._placements: dict = {}
        self._pinned: set = set()
        self._bytes = {t: 0 for t in _TIERS}
        self._reserved = 0
        self._peak_hbm = 0
        self._ticks = itertools.count(1)
        self._fault_count = 0
        self._evictions_by_tenant: dict = {}

    # -- tenancy / clocks --------------------------------------------------
    @property
    def tiering_active(self) -> bool:
        return bool(_hbm_budget_bytes() or _host_budget_bytes())

    def _tick(self) -> int:
        return next(self._ticks)

    @staticmethod
    def _tenant() -> str:
        """The QoS principal of the request on this thread — the tenant
        a fault's evictions are charged to. Never called with the store
        lock held (qos/tracing take their own locks)."""
        try:
            from h2o3_tpu.obs import tracing as _tracing
            from h2o3_tpu.serving import qos as _qos
            return _qos.resolve_principal(_tracing.principal() or "")
        except Exception:   # noqa: BLE001 — attribution must not break serving
            return "anonymous"

    # -- accounting (presence-based, mirrors ChunkPager) -------------------
    def _account_locked(self, p: "Placement"):
        # h2o3-ok: R003 _locked helper — every caller holds self._lock
        present = (p.placed is not None, p.host is not None,
                   p.path is not None)
        prev = p._acct
        if prev is not None:
            for t, had in zip(_TIERS, prev):
                if had:
                    self._bytes[t] -= p.nbytes
        p._acct = present
        for t, has in zip(_TIERS, present):
            if has:
                self._bytes[t] += p.nbytes
        if present[0] and self._bytes[TIER_HBM] > self._peak_hbm:
            # h2o3-ok: R003 _locked helper — caller holds self._lock
            self._peak_hbm = self._bytes[TIER_HBM]
        self._gauge_locked(p.key[0])

    def _gauge_locked(self, model_key: str):
        # h2o3-ok: R003 _locked helper — every caller holds self._lock
        # (the per-series metric lock is a leaf, same as the pager's)
        total = sum(pp.nbytes for (mk, _t), pp in self._placements.items()
                    if mk == model_key and pp.placed is not None)
        PARAM_BYTES.set(total, model=model_key)

    def _forget_locked(self, p: "Placement"):
        # h2o3-ok: R003 _locked helper — every caller holds self._lock.
        # Un-account a placement leaving the store. Its in-memory
        # pytrees stay intact for in-flight holders (reattach/GC), but
        # the DISK artifact is owned by the store and freed exactly
        # once: the path is popped here and unlinked by the caller
        # outside the lock.
        prev = p._acct
        if prev is not None:
            for t, had in zip(_TIERS, prev):
                if had:
                    self._bytes[t] -= p.nbytes
        p._acct = None
        path, p.path = p.path, None
        return path

    def _registered_locked(self, p: "Placement") -> bool:
        # h2o3-ok: R003 _locked helper — every caller holds self._lock
        return p.key is not None and self._placements.get(p.key) is p

    # -- admission (ISSUE-6 in-flight reservation discipline) --------------
    def _try_reserve(self, nbytes: int, force: bool = False) -> bool:
        """Reserve HBM headroom BEFORE any device_put lands — resident
        + reserved never exceeds the budget, so concurrent cold faults
        cannot overshoot between transfer and accounting. `force` admits
        unconditionally (nothing left to demote — correctness over
        budget, exactly like the chunk pager)."""
        with self._lock:
            budget = _hbm_budget_bytes()
            if (force or not budget or
                    self._bytes[TIER_HBM] + self._reserved + nbytes
                    <= budget):
                self._reserved += nbytes
                return True
        return False

    def _release_reservation(self, nbytes: int):
        with self._lock:
            self._reserved -= nbytes

    # -- victim selection / eviction ---------------------------------------
    def _victim(self, tenant: str, exclude=None):
        """The next placement to demote for `tenant`'s fault: snapshot
        candidates under the lock, order OUTSIDE it (qos standing takes
        qos locks). Same-tenant cold placements go first, then other
        tenants in ascending QoS standing (heaviest consumers first),
        then coldest by the hotness clock — churn stays in its lane."""
        with self._lock:
            cands = [(p, p.tenant, p.last)
                     for k, p in self._placements.items()
                     if p.placed is not None and p is not exclude
                     and k[0] not in self._pinned]
        if not cands:
            return None

        def order(item):
            _p, owner, last = item
            if owner == tenant:
                return (0, 0.0, last)
            return (1, _standing(owner), last)
        cands.sort(key=order)
        return cands[0][0]

    def _make_room(self, incoming: int, tenant: str, exclude=None) -> bool:
        """Demote victims until `incoming` bytes fit under the HBM
        budget. False = nothing demotable (caller force-admits)."""
        budget = _hbm_budget_bytes()
        if not budget:
            return True
        while True:
            with self._lock:
                if (self._bytes[TIER_HBM] + self._reserved + incoming
                        <= budget):
                    return True
            vic = self._victim(tenant, exclude)
            if vic is None:
                return False
            self.demote(vic, charge=tenant)

    def demote(self, p: "Placement", charge: str | None = None,
               to_tier: str = TIER_HOST):
        """The DEMOTE primitive: gather the placed pytree back to host
        through `make_shard_and_gather_fns` gather_fns, canonicalize
        with `mesh._canon_host_leaf` (the same pass shard_params applies
        promoting — the bit-exact round-trip contract), drop the device
        copy; `to_tier="disk"` additionally spills the host pytree to an
        npz artifact under ice_root. The eviction is charged to the
        tenant whose fault forced it (`charge`), not the victim's owner."""
        tenant = charge if charge is not None else self._tenant()
        moved = False
        with p._io:
            if p.placed is not None:
                host = p.host
                if host is None:
                    host = self._gather_host(p)
                with self._lock:
                    p.host = host
                    p.placed = None
                    if self._registered_locked(p):
                        self._account_locked(p)
                moved = True
            if (to_tier == TIER_DISK and p.host is not None
                    and p.placed is None and p.path is None):
                from h2o3_tpu.io import spill as _spill
                leaves = jax.tree_util.tree_leaves(p.host)
                mk, tok = p.key if p.key is not None else ("params", 0)
                path = _spill.write_params(f"{mk}@{tok}", leaves)
                with self._lock:
                    p.path = path
                    p.host = None
                    if self._registered_locked(p):
                        self._account_locked(p)
                moved = True
        if moved:
            PARAM_EVICTIONS.inc(tier=to_tier, tenant=tenant)
            with self._lock:
                self._evictions_by_tenant[tenant] = \
                    self._evictions_by_tenant.get(tenant, 0) + 1

    @staticmethod
    def _gather_host(p: "Placement"):
        _shard_fns, gather_fns = _mesh.make_shard_and_gather_fns(p.specs)
        fetched = jax.tree_util.tree_map(lambda fn, leaf: fn(leaf),
                                         gather_fns, p.placed)
        return jax.tree_util.tree_map(_mesh._canon_host_leaf, fetched)

    def _spill_host_tier(self, tenant: str):
        """Enforce the host-tier budget after a fault/demote grew it:
        HBM-resident placements drop their (re-gatherable) host mirror
        first — free to reconstruct — then cold placements spill to
        disk, coldest first."""
        budget = _host_budget_bytes()
        if not budget:
            return
        while True:
            with self._lock:
                if self._bytes[TIER_HOST] <= budget:
                    return
                cands = [p for k, p in self._placements.items()
                         if p.host is not None and k[0] not in self._pinned]
                cands.sort(key=lambda pp: pp.last)
                vic = cands[0] if cands else None
            if vic is None:
                return
            if vic.placed is not None:
                with vic._io:
                    with self._lock:
                        if vic.placed is not None and vic.host is not None:
                            vic.host = None
                            if self._registered_locked(vic):
                                self._account_locked(vic)
            else:
                self.demote(vic, charge=tenant, to_tier=TIER_DISK)

    # -- promotion (fault) -------------------------------------------------
    def fault(self, p: "Placement"):
        """The PROMOTE primitive: place the canonical host pytree (read
        back from its npz artifact first when disk-resident) through the
        per-spec shard_fns, with admission reserved atomically BEFORE
        the device transfer starts. Mirrors ChunkPager.fault: reserve →
        transfer → account under the lock → release reservation; on a
        full device, demote victims and retry, force-admitting only
        when nothing is left to demote."""
        tenant = self._tenant()
        src = p.tier
        forced = False
        while True:
            with p._io:
                if p.placed is not None:
                    placed = p.placed
                    with self._lock:
                        p.last = self._tick()
                    return placed
                if self._try_reserve(p.nbytes, force=forced):  # h2o3-ok: R022 the commit CONVERTS the reservation to accounted bytes (self._reserved -= nbytes, reserved=False) inside its critical section; the finally releases exactly the uncommitted case — condition-variable pairing the path analysis cannot prove
                    stale_path = None
                    replaced_epoch = False
                    reserved = True
                    try:
                        host = p.host
                        if host is None:
                            from h2o3_tpu.io import spill as _spill
                            leaves = _spill.read_params(p.path)
                            host = jax.tree_util.tree_unflatten(
                                p.treedef, leaves)
                        cld = _mesh.cloud()
                        shard_fns, _g = _mesh.make_shard_and_gather_fns(
                            p.specs, cld)
                        placed = jax.tree_util.tree_map(
                            lambda fn, leaf: fn(leaf), shard_fns, host)
                        with self._lock:
                            p.placed = placed
                            replaced_epoch = p.epoch != cld.epoch
                            p.epoch = cld.epoch
                            p.host = host if self.tiering_active else None
                            stale_path, p.path = p.path, None
                            p.last = self._tick()
                            p.tenant = tenant
                            self._fault_count += 1
                            if self._registered_locked(p):
                                self._account_locked(p)
                            # convert the reservation to accounted bytes
                            # IN the commit's critical section, so
                            # admitted_bytes() (resident + reserved)
                            # never double-counts an in-flight fault at
                            # any observable instant
                            self._reserved -= p.nbytes
                            reserved = False
                    finally:
                        if reserved:
                            self._release_reservation(p.nbytes)
                    if stale_path is not None:
                        from h2o3_tpu.io import spill as _spill
                        _spill.delete_params(stale_path)
                    break
            forced = not self._make_room(p.nbytes, tenant, exclude=p)
        if src != TIER_HBM:
            PARAM_FAULTS.inc(tier=src)
        if replaced_epoch:
            PLACEMENTS.inc()    # epoch bump re-place (see _publish)
        self._spill_host_tier(tenant)
        return placed

    # -- placement ---------------------------------------------------------
    def _build_placement(self, model):
        """Compute a Placement WITHOUT the store lock held — the
        device_put of a large ensemble must not stall every other
        model's warm dispatches (which read the store per call). Returns
        None for families without a param export. Under a budget the
        build stops at the canonical HOST pytree (the demote
        primitive's output), so the initial device placement goes
        through the same reserved admission as any cold fault."""
        params = model._serving_params()
        if params is None:
            return None
        cld = _mesh.cloud()
        specs = _mesh.match_partition_rules(
            getattr(model, "_partition_rules", ()), params)
        treedef = jax.tree_util.tree_structure(params)
        if not self.tiering_active:
            placed = _mesh.shard_params(params, specs=specs, cld=cld)
            return Placement(placed, specs, _mesh.params_nbytes(placed),
                             cld.epoch, treedef=treedef)
        from h2o3_tpu.parallel import mrtask as _mrt
        host = jax.tree_util.tree_map(
            lambda leaf: _mesh._canon_host_leaf(
                _mrt.host_fetch(leaf) if isinstance(leaf, jax.Array)
                else leaf),
            params)
        return Placement(None, specs, _mesh.params_nbytes(host),
                         cld.epoch, host=host, treedef=treedef)

    def _publish(self, key, p: "Placement") -> "Placement":
        """Install a freshly built Placement under the lock; a racing
        builder's copy wins first-publish (the loser's arrays are
        GC'd). Returns the placement now in the store."""
        tenant = self._tenant()
        stale_path = None
        with self._lock:
            cur = self._placements.get(key)
            if cur is not None and cur.epoch == p.epoch:
                return cur
            if cur is not None:
                p.refs = cur.refs         # epoch re-place keeps the refs
                stale_path = self._forget_locked(cur)
            p.key = key
            p.tenant = tenant
            p.last = self._tick()
            self._placements[key] = p
            PLACEMENTS.inc()
            self._account_locked(p)
        if stale_path is not None:
            from h2o3_tpu.io import spill as _spill
            _spill.delete_params(stale_path)
        return p

    def acquire(self, model, token: int):
        """Place (or re-reference) the model's params; bumps the
        refcount. Called once per cache-entry build; each resident
        compiled bucket program holds exactly one reference. Returns the
        Placement, or None for families without a param export. Under a
        budget the first device placement rides `fault` (reserved
        admission, eviction on pressure)."""
        key = (model.key, token)
        with self._lock:
            p = self._placements.get(key)
            if p is not None:
                p.refs += 1
                p.last = self._tick()
                return p
        built = self._build_placement(model)        # outside the lock
        if built is None:
            return None
        p = self._publish(key, built)
        if p.placed is None:
            self.fault(p)
        with self._lock:
            p.refs += 1
        return p

    def reattach(self, model_key: str, token: int, p: "Placement"):
        """Re-install a placement an in-flight build acquired but a
        concurrent invalidate_key swept before the entry published —
        the entry's reference is live, so the store must know the
        placement again (or every dispatch would re-place one-shot)."""
        with self._lock:
            if (model_key, token) not in self._placements:
                p.key = (model_key, token)
                self._placements[(model_key, token)] = p
                self._account_locked(p)

    def placed(self, model, token: int):
        """The CURRENT placed pytree for a dispatch — faulting the
        placement back into HBM first when it was demoted, and
        re-placing when the mesh was rebuilt for a new cloud epoch (the
        old placement's arrays are laid out for a dead membership; the
        demote→fault hop gathers off the old mesh and places onto the
        new one, bit-exact by the canonicalization contract). Does not
        change the refcount; the calling cache entry already holds one."""
        key = (model.key, token)
        epoch = _mesh.cloud().epoch
        with self._lock:
            p = self._placements.get(key)
            if p is not None:
                p.last = self._tick()
                if p.placed is not None and p.epoch == epoch:
                    return p.placed
        if p is None or (p.placed is None and p.host is None
                         and p.path is None):
            # Placement gone while a dispatch was in flight: the entry
            # was evicted/invalidated (retrain purge, model DELETE)
            # between the cache lookup and this call — or swept with its
            # disk artifact already freed. Serve THIS request with a
            # one-shot placement that is never stored — storing it would
            # re-register the freed model with refs nothing will ever
            # release (a permanent HBM leak and a ghost gauge series for
            # a deleted model). One-shot placement is GC'd with the
            # dispatch.
            params = model._serving_params()
            if params is None:
                return None
            return _mesh.shard_params(
                params,
                rules=getattr(model, "_partition_rules", ()))
        if p.placed is not None and p.epoch != epoch:
            # stale epoch: gather off the old mesh, fault onto the new
            self.demote(p, charge=self._tenant())
        return self.fault(p)

    # -- pinning / explicit tier moves -------------------------------------
    def pin(self, model_key: str, on: bool = True):
        """Pin (or unpin) a model's placements against eviction — the
        tenant hot-set guard. Pinned placements still count against the
        budget; they are simply never victims."""
        with self._lock:
            if on:
                self._pinned.add(model_key)
            else:
                self._pinned.discard(model_key)

    def demote_key(self, model_key: str, to_tier: str = TIER_HOST):
        """Demote every device-resident placement of a model (tests and
        operator tooling)."""
        with self._lock:
            ps = [p for k, p in self._placements.items()
                  if k[0] == model_key]
        for p in ps:
            self.demote(p, to_tier=to_tier)

    # -- release -----------------------------------------------------------
    def release(self, model_key: str, token: int):
        """One cache entry dropped its reference; the LAST release frees
        the placement — every tier, exactly once (the npz artifact is
        unlinked outside the lock; device/host arrays free by GC)."""
        path = None
        with self._lock:
            p = self._placements.get((model_key, token))
            if p is None:
                return
            p.refs -= 1
            if p.refs <= 0:
                del self._placements[(model_key, token)]
                path = self._forget_locked(p)
                if not any(k[0] == model_key for k in self._placements):
                    PARAM_BYTES.remove(model=model_key)
                else:
                    self._gauge_locked(model_key)
        if path is not None:
            from h2o3_tpu.io import spill as _spill
            _spill.delete_params(path)

    def invalidate_key(self, model_key: str):
        """Model DELETE / retrain purge: drop every generation's
        placement for the DKV key regardless of refcount (the cache
        drops its entries in the same breath — see
        ScorerCache.invalidate_key), freeing all tiers exactly once."""
        paths = []
        with self._lock:
            for k in [k for k in self._placements if k[0] == model_key]:
                p = self._placements.pop(k)
                path = self._forget_locked(p)
                if path is not None:
                    paths.append(path)
            self._pinned.discard(model_key)
            PARAM_BYTES.remove(model=model_key)
        from h2o3_tpu.io import spill as _spill
        for path in paths:
            _spill.delete_params(path)

    def clear(self):
        paths = []
        with self._lock:
            keys = {k[0] for k in self._placements}
            for p in self._placements.values():
                path = self._forget_locked(p)
                if path is not None:
                    paths.append(path)
            self._placements.clear()
            self._pinned.clear()
            for mk in keys:
                PARAM_BYTES.remove(model=mk)
        from h2o3_tpu.io import spill as _spill
        for path in paths:
            _spill.delete_params(path)

    # -- introspection -----------------------------------------------------
    def bytes_for(self, model_key: str) -> int:
        """Logical bytes of the model's placements across all tiers."""
        with self._lock:
            return sum(p.nbytes for k, p in self._placements.items()
                       if k[0] == model_key)

    def total_bytes(self) -> int:
        with self._lock:
            return sum(p.nbytes for p in self._placements.values())

    def by_model(self) -> dict:
        """{model_key: placement bytes} across resident generations —
        the /3/Usage HBM-attribution feed."""
        with self._lock:
            out: dict = {}
            for (mk, _tok), p in self._placements.items():
                out[mk] = out.get(mk, 0) + p.nbytes
            return out

    def by_model_tier(self) -> dict:
        """{model_key: {tier: bytes}} — which rung of the ladder each
        model's generations sit on."""
        with self._lock:
            out: dict = {}
            for (mk, _tok), p in self._placements.items():
                d = out.setdefault(mk, {t: 0 for t in _TIERS})
                d[p.tier] += p.nbytes
            return out

    def resident(self) -> int:
        with self._lock:
            return len(self._placements)

    def hbm_bytes(self) -> int:
        with self._lock:
            return self._bytes[TIER_HBM]

    def reserved_bytes(self) -> int:
        with self._lock:
            return self._reserved

    def admitted_bytes(self) -> int:
        """Resident + in-flight-reserved HBM bytes in ONE lock hold —
        the quantity the admission check bounds; ≤ budget at every
        instant (summing hbm_bytes() + reserved_bytes() from two
        separate calls can double-count a fault committing between
        them)."""
        with self._lock:
            return self._bytes[TIER_HBM] + self._reserved

    def tier_bytes(self) -> dict:
        with self._lock:
            return dict(self._bytes)

    def peak_hbm_bytes(self) -> int:
        with self._lock:
            return self._peak_hbm

    def reset_peak(self):
        with self._lock:
            self._peak_hbm = self._bytes[TIER_HBM]

    def stats(self) -> dict:
        with self._lock:
            return {
                "tier_bytes": dict(self._bytes),
                "reserved": self._reserved,
                "hbm_budget": _hbm_budget_bytes(),
                "host_budget": _host_budget_bytes(),
                "peak_hbm_bytes": self._peak_hbm,
                "faults": self._fault_count,
                "resident": len(self._placements),
                "pinned": sorted(self._pinned),
                "evictions_by_tenant": dict(self._evictions_by_tenant),
            }


PARAMS = ParamStore()

_om.gauge("h2o3_scorer_param_models",
          "model generations with a live shared serving-param placement",
          fn=lambda: float(PARAMS.resident()))


def _param_tier_series():
    return [({"tier": t}, float(b))
            for t, b in sorted(PARAMS.tier_bytes().items())]


_om.gauge("h2o3_serve_param_tier_bytes",
          "resident model-param bytes per tier of the serving ladder "
          "(hbm / host / disk)",
          fn=_param_tier_series)
