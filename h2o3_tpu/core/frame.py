"""Columnar Frame/Vec data plane — H2O's "Fluid Vectors" rebuilt for TPU HBM.

Reference: water/fvec/Frame.java:64 (named set of Vecs), water/fvec/Vec.java:157
(typed distributed column; ESPC row layout Vec.java:163-171; type system
Vec.java:207-212), water/fvec/Chunk.java + ~20 compression codecs
(C0D/C0L/C1/C1S/C2/C2S/C4/C8/CBS/CStr/CXI/…), water/fvec/NewChunk.java (write
buffer that picks the best codec on close), water/fvec/RollupStats.java:30
(lazy per-Vec min/max/mean/sigma/NA stats).

TPU-native design:
  * A Vec is ONE row-sharded, padded jax.Array in HBM, dtype-packed by a codec
    chosen at ingest (const / int8 / int16 / int32 / float32, with integer
    bias), plus an optional uint8 NA mask side-plane. This keeps the codec
    benefits of Chunk compression (HBM footprint, bandwidth) while staying a
    dense static-shape array XLA can tile.  Decoding (cast·scale+bias, NA→NaN)
    happens inside consumer jits, where XLA fuses it into the first kernel
    for free — the moral equivalent of Chunk.atd() inlined into the map loop.
  * Rows are padded to a multiple of (row-shards × 8) — H2O's uneven ESPC
    chunking becomes even tiling + a padding mask.
  * Strings live on DEVICE as a dictionary-coded plane (StrVec below:
    int32 codes in HBM + a host-side unique-string table), so string
    munging (strlen/toupper/substring/…) runs O(unique) host-side and
    O(rows) on device; UUIDs remain host numpy object arrays (C16Chunk
    has no device analog yet); numeric / categorical / time columns live
    in HBM.
  * Rollups are computed lazily in one fused jit pass and cached, invalidated
    on write — same contract as RollupStats.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.analysis.lockdep import make_lock
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.core import tiering as _tiering
from h2o3_tpu.parallel import mesh as _mesh
from h2o3_tpu.parallel import mrtask as _mr

# ---------------------------------------------------------------------------
# Vec types (Vec.java:207-212)
T_NUM = "num"
T_CAT = "enum"
T_TIME = "time"
T_STR = "str"
T_UUID = "uuid"
T_BAD = "bad"  # all-NA column


# ---------------------------------------------------------------------------
# Codecs (the NewChunk "pick best compression on close" logic)
@dataclasses.dataclass(frozen=True)
class Codec:
    kind: str           # "const" | "i8" | "i16" | "i32" | "f32"
    bias: float = 0.0   # value = stored + bias   (integer kinds)
    const_val: float = float("nan")  # for kind == "const"

    @property
    def np_dtype(self):
        return {"i8": np.int8, "i16": np.int16, "i32": np.int32,
                "f32": np.float32, "const": np.int8}[self.kind]


def _choose_codec(col: np.ndarray, mask: np.ndarray):
    """Pick the narrowest storage for a float64 host column (NewChunk.close).

    Returns (packed ndarray, Codec). NAs are stored as 0 in packed form; the
    mask side-plane is authoritative. Pass-frugal (this is the ingest
    pack hot path): the masked-value copy is skipped when there are no
    NAs, and scalar min/max pre-checks short-circuit the all-integral
    scan for ordinary float columns — results are identical.
    """
    has_na = bool(mask.any())
    valid = col[mask == 0] if has_na else col
    if valid.size == 0:
        return np.zeros(col.shape, np.int8), Codec("const", const_val=float("nan"))
    vmin, vmax = float(valid.min()), float(valid.max())
    if vmin == vmax:  # constant col; NAs (incl. padding) live in the mask
        return np.zeros(col.shape, np.int8), Codec("const", const_val=vmin)
    filled = np.where(mask, 0.0, col) if has_na else col
    is_int = math.isfinite(vmin) and math.isfinite(vmax) \
        and math.floor(vmin) == vmin and math.floor(vmax) == vmax \
        and bool(np.all(np.floor(valid) == valid))
    if is_int:
        span = vmax - vmin
        for kind, lim, dt in (("i8", 254, np.int8), ("i16", 65534, np.int16)):
            if span <= lim:
                bias = math.floor(vmin + span // 2 + 1)  # center into signed range
                packed = np.where(mask, 0, filled - bias).astype(dt)
                return packed, Codec(kind, bias=bias)
        if -2**31 < vmin and vmax < 2**31 - 1:
            packed = np.where(mask, 0, filled).astype(np.int32)
            return packed, Codec("i32")
    packed = filled.astype(np.float32)  # NAs already zeroed in filled
    return packed, Codec("f32")


def _decode_f32(data: jax.Array, codec: Codec, mask: Optional[jax.Array]):
    """Decode packed storage to f32 with NaN NAs. Call inside jit; fuses."""
    if codec.kind == "const":
        x = jnp.full(data.shape, codec.const_val, jnp.float32)
    else:
        x = data.astype(jnp.float32)
        if codec.bias:
            x = x + jnp.float32(codec.bias)
    if mask is not None:
        x = jnp.where(mask != 0, jnp.float32(jnp.nan), x)
    return x


class DevicePlanes(NamedTuple):
    """One column as a device program packed it, ready to be a Vec's
    storage: `data` (padded,) in the codec's dtype with NAs zeroed, `mask`
    (padded,) uint8 with NAs AND the padding rows (>= `nrows`) set."""
    data: jax.Array
    mask: jax.Array
    codec: Codec
    nrows: int


# one resident wrapper: a per-call jax.jit(_decode_f32) in as_f32 rebuilt
# the wrapper on every decoded read (R001)
_DECODE_F32_JIT = jax.jit(_decode_f32, static_argnums=1)


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Rollups:
    """RollupStats.java:30 — cached per-Vec stats."""
    min: float
    max: float
    mean: float
    sigma: float
    nas: int
    zeros: int
    is_int: bool


# one rollup device dispatch in flight at a time, process-wide: leaf
# lock (nothing else is acquired under it; mrtask's jit-wrapper cache
# lock below it is itself a leaf)
_ROLLUP_LOCK = make_lock("vec.rollups")


class Vec:
    """A typed, row-sharded, dtype-packed column resident in TPU HBM."""

    def __init__(self, data, codec: Codec, mask, nrows: int, type: str = T_NUM,
                 domain: Optional[np.ndarray] = None, host_data=None,
                 packed_host=None, packed_mask=None):
        # the packed planes live behind the DKV tier pager: `data`/`mask`
        # are fault-on-read properties over one TierChunk (HBM → host
        # codec bytes → disk), None for str/uuid/sparse layouts. A chunk
        # may be born cold (data=None + packed_host): budgeted ingest
        # parks codec bytes in the host tier and faults on first access.
        if data is not None or packed_host is not None:
            host = (packed_host, packed_mask) \
                if packed_host is not None else None
            self._chunk = _tiering.PAGER.new_chunk(data, mask, host=host,
                                                   label=type)
        else:
            self._chunk = None
        self.codec = codec
        self.nrows = nrows
        self.type = type
        self.domain = domain        # np.ndarray[str] for T_CAT
        self.host_data = host_data  # np object array for T_STR/T_UUID
        self._rollups: Optional[Rollups] = None

    @property
    def data(self):
        """Packed jax.Array (padded,) — faults the chunk to HBM."""
        ch = self._chunk
        return ch.device()[0] if ch is not None else None

    @property
    def mask(self):
        """uint8 NA plane (padded,) or None — faults alongside data."""
        ch = self._chunk
        return ch.device()[1] if ch is not None else None

    # ---- construction ---------------------------------------------------
    @staticmethod
    def from_numpy(col: np.ndarray, type: Optional[str] = None,
                   domain=None) -> "Vec":
        """Ingest one host column, inferring type (ParseSetup column typing)."""
        c = _mesh.cloud()
        if col.dtype == object or col.dtype.kind in "US":
            return Vec._from_strings(col, force_type=type, domain=domain)
        if np.issubdtype(col.dtype, np.datetime64):
            ms = col.astype("datetime64[ms]").astype(np.int64).astype(np.float64)
            nat = np.isnat(col.astype("datetime64[ms]"))
            return Vec._from_floats(np.where(nat, 0.0, ms), nat, T_TIME)
        if col.dtype == bool:
            col = col.astype(np.float64)
        col = col.astype(np.float64, copy=False)
        mask = np.isnan(col)
        vtype = type or (T_CAT if domain is not None else T_NUM)
        return Vec._from_floats(col, mask, vtype, domain)

    @staticmethod
    def _from_floats(col, mask, vtype, domain=None) -> "Vec":
        c = _mesh.cloud()
        n = len(col)
        pad = c.padded_rows(n)
        colp = np.zeros(pad, np.float64)
        colp[:n] = np.where(mask, 0.0, col) if mask.any() else col
        maskp = np.ones(pad, bool)       # padding rows are NA
        maskp[:n] = mask
        packed, codec = _choose_codec(colp, maskp)
        mask_np = maskp.astype(np.uint8) if maskp.any() else None
        if mask_np is None and n < pad:  # padding must always be masked
            mask_np = np.zeros(pad, np.uint8)
            mask_np[n:] = 1
        dom = np.asarray(domain, dtype=object) if domain is not None else None
        if _tiering.PAGER.ingest_cold:
            # budgeted/cold ingest: park the codec bytes in the HOST
            # tier and let first access fault them — an eager device_put
            # here would spike HBM past the budget before the pager
            # could act (H2O3_TPU_INGEST_COLD forces this without a
            # budget for spike-free bulk ingest)
            return Vec(None, codec, None, n, vtype, dom,
                       packed_host=packed, packed_mask=mask_np)
        data = _mr.device_put_rows(packed)
        dmask = _mr.device_put_rows(mask_np) if mask_np is not None else None
        # packed/mask_np are the codec bytes the pager's host tier keeps
        return Vec(data, codec, dmask, n, vtype, dom,
                   packed_host=packed, packed_mask=mask_np)

    @staticmethod
    def from_device_floats(col_j, vtype=T_NUM, domain=None) -> "Vec":
        """Device-resident construction — the hand-off point for device
        mungers (sort/merge/group_by): no host round trip. Stores with the
        f32 codec (re-running the codec chooser would need host stats)."""
        c = _mesh.cloud()
        n = int(col_j.shape[0])
        pad = c.padded_rows(n)

        def pack(col_j):
            full = jnp.full(pad, jnp.nan, jnp.float32) \
                .at[:n].set(col_j.astype(jnp.float32))
            mask = jnp.isnan(full)
            return jnp.where(mask, 0.0, full), mask.astype(jnp.uint8)

        sh = c.rows_sharding(1)
        # cached_jit: pack's closure is (pad, n) ints, so repeated
        # device-munger hand-offs at one size reuse one program
        packed, dmask = _mr.cached_jit(pack, out_shardings=(sh, sh))(col_j)
        return Vec.from_device_planes(
            DevicePlanes(packed, dmask, Codec("f32"), n), vtype, domain)

    @staticmethod
    def from_device_planes(planes: DevicePlanes, vtype=T_NUM,
                           domain=None) -> "Vec":
        """Adopt planes a device program already packed (the program that
        made them may have made a whole frame's in one dispatch): no
        dispatch here, no host copy, no host mirror — the pager fetches
        codec bytes only if it ever demotes the chunk."""
        dom = np.asarray(domain, dtype=object) if domain is not None else None
        return Vec(planes.data, planes.codec, planes.mask, planes.nrows,
                   vtype, dom)

    @staticmethod
    def _from_strings(col: np.ndarray, force_type=None, domain=None) -> "Vec":
        """Strings parse to categorical by default (CsvParser enum detection);
        T_STR keeps raw host strings."""
        n = len(col)
        sarr = np.asarray(col, dtype=object)
        na = np.array([s is None or (isinstance(s, float) and math.isnan(s))
                       or (isinstance(s, str) and s == "") for s in sarr])
        if force_type == T_STR:
            # device string plane: dictionary codes on device (CStrChunk
            # analog; see StrVec) — no n-sized host object array retained
            return StrVec.encode(sarr)
        if domain is None:
            uniq = sorted({str(s) for s, bad in zip(sarr, na) if not bad})
            domain = np.asarray(uniq, dtype=object)
        lookup = {s: i for i, s in enumerate(domain)}
        codes = np.array([-1 if bad else lookup.get(str(s), -1)
                          for s, bad in zip(sarr, na)], np.float64)
        mask = codes < 0
        return Vec._from_floats(np.where(mask, 0.0, codes), mask, T_CAT, domain)

    # ---- access ---------------------------------------------------------
    @property
    def padded_len(self) -> int:
        # chunk metadata, NOT .data: reading the shape must never fault a
        # demoted chunk back into HBM
        if self._chunk is not None:
            return self._chunk.rows
        return len(self.host_data)

    def as_f32(self) -> jax.Array:
        """Decoded f32 view (NaN NAs, padding = NaN). Materializes; prefer
        Frame.matrix() for multi-column consumers."""
        if self.type == T_STR:
            raise TypeError("string Vec has no numeric view")
        return _DECODE_F32_JIT(self.data, self.codec, self.mask)

    def to_numpy(self) -> np.ndarray:
        if self.type == T_STR:
            return self.host_data.copy()
        # host_fetch: in a multi-controller cloud the decoded column spans
        # every process's shards — gather before fetching
        x = _mr.host_fetch(self.as_f32())[: self.nrows]
        return x

    def levels(self):
        return list(self.domain) if self.domain is not None else None

    @property
    def cardinality(self) -> int:
        return len(self.domain) if self.domain is not None else 0

    # ---- rollups (lazy, cached) -----------------------------------------
    def rollups(self) -> Rollups:
        r = self._rollups
        if r is None:
            # compute-once, process-wide: parallel model builds (grid
            # search) all roll up the shared training frame's vecs at
            # the same instant, and N simultaneous dispatches of the
            # same sharded program can rendezvous-deadlock XLA:CPU on
            # small hosts — at most one rollup kernel may be in flight,
            # and N-1 of the stampede's results were discarded anyway
            with _ROLLUP_LOCK:
                r = self._rollups
                if r is None:
                    r = self._rollups = self._compute_rollups()  # h2o3-ok: R008 intentional: the whole point of the lock is one rollup device dispatch in flight at a time
        return r

    def _compute_rollups(self) -> Rollups:
        if self.type == T_STR:
            na = sum(1 for s in self.host_data if s is None)
            return Rollups(math.nan, math.nan, math.nan, math.nan, na, 0, False)
        stats = _rollup_kernel(self.data, self.codec, self.mask)
        cnt, s, s2, mn, mx, nas, zeros, frac = (float(v) for v in stats)
        n_real_na = int(nas) - (self.padded_len - self.nrows)
        mean = s / cnt if cnt else math.nan
        var = max(0.0, s2 / cnt - mean * mean) if cnt > 1 else 0.0
        # sample sigma like RollupStats (n-1)
        sigma = math.sqrt(var * cnt / (cnt - 1)) if cnt > 1 else 0.0
        return Rollups(mn if cnt else math.nan, mx if cnt else math.nan,
                       mean, sigma, n_real_na, int(zeros), frac == 0.0)

    def invalidate_rollups(self):
        with _ROLLUP_LOCK:
            self._rollups = None

    # convenience accessors (Vec.min()/max()/mean()/sigma()/naCnt())
    def min(self): return self.rollups().min
    def max(self): return self.rollups().max
    def mean(self): return self.rollups().mean
    def sigma(self): return self.rollups().sigma
    def na_cnt(self): return self.rollups().nas
    def is_int(self): return self.rollups().is_int

    def __len__(self):
        return self.nrows


@jax.jit
def _rollup_kernel_impl(x):
    """One fused pass: count, sum, sum², min, max, NA count, zeros, frac-part."""
    isna = jnp.isnan(x)
    w = (~isna).astype(jnp.float32)
    xz = jnp.where(isna, 0.0, x)
    cnt = w.sum()
    s = xz.sum()
    s2 = (xz * xz).sum()
    mn = jnp.where(isna, jnp.inf, x).min()
    mx = jnp.where(isna, -jnp.inf, x).max()
    nas = isna.sum()
    zeros = ((xz == 0.0) & ~isna).sum()
    frac = jnp.abs(xz - jnp.round(xz)).sum()
    return jnp.stack([cnt, s, s2, mn, mx, nas.astype(jnp.float32),
                      zeros.astype(jnp.float32), frac])


def _rollup_kernel(data, codec, mask):
    # cached_jit: the closures capture only the (frozen, hashable) codec,
    # so every vec sharing a codec replays one resident program per shape
    def f(d, m):
        return _rollup_kernel_impl(_decode_f32(d, codec, m))
    if mask is None:
        return _mr.cached_jit(
            lambda d: _rollup_kernel_impl(_decode_f32(d, codec, None)))(data)
    return _mr.cached_jit(f)(data, mask)


@functools.partial(jax.jit, static_argnames=("pad", "n"))
def _sparse_densify(rows, vals, *, pad, n):
    """One cached program per (pad, n): a fresh closure here would
    recompile per call and per column."""
    base = jnp.where(jnp.arange(pad) < n, 0.0, jnp.nan)
    return base.at[rows].set(vals, mode="drop")


# ---------------------------------------------------------------------------
class StrVec(Vec):
    """Device-resident string column — the CStrChunk analog
    (water/fvec/CStrChunk.java stores string bytes + per-row offsets in the
    chunk; string Rapids prims are MRTasks over those chunks,
    water/rapids/ast/prims/string/).

    TPU-native representation: DICTIONARY ENCODING. Rows live on device as
    int32 dictionary codes (row-sharded over the mesh; -1 = NA/padding);
    the dictionary of unique strings is host metadata, typically ≪ n.
    The op classes map as:
      * value transforms (toupper/trim/gsub/substring/…): applied to the
        DICTIONARY — O(unique) host work — then codes remap through one
        device gather. A 2M-row gsub with 1k unique values costs 1k regex
        calls + one (n,)-gather, never an n-sized host object array.
      * per-row measures (strlen, countmatches): per-level table built
        host-side (O(unique)), then one device gather codes→value.
      * predicates (grep/match/==): per-level bool mask → device gather.
    The legacy n-sized host object array materializes ONLY if a consumer
    explicitly asks (`to_numpy`/`host_data`)."""

    def __init__(self, codes_dev, levels, nrows: int, host_codes=None):
        # the (padded,) i32 code plane (-1 = NA) lives behind its own
        # TierChunk, so string-heavy frames demote exactly like numeric
        # planes: HBM → host i32 bytes → disk spill file. `codes_dev`
        # may be None for a chunk born cold with `host_codes` (budgeted
        # ingest); passing BOTH gives the pager a free demote (the host
        # mirror is already canonical).
        host = (host_codes, None) if host_codes is not None else None
        self._codes_chunk = _tiering.PAGER.new_chunk(
            codes_dev, None, host=host, label="strcodes")
        self._levels = np.asarray(levels, dtype=object)
        super().__init__(None, Codec("const"), None, nrows, T_STR)

    @property
    def codes(self):
        """(padded,) i32 device codes — faults the plane to HBM."""
        return self._codes_chunk.device()[0]

    @staticmethod
    def encode(col: np.ndarray) -> "StrVec":
        """Dictionary-encode a host object array into device codes."""
        c = _mesh.cloud()
        n = len(col)
        na = np.array([s is None or (isinstance(s, float) and math.isnan(s))
                       for s in col])
        strs = np.asarray(["" if bad else str(s)
                           for s, bad in zip(col, na)], dtype=object)
        levels, inv = np.unique(strs[~na], return_inverse=True)
        codes = np.full(n, -1, np.int64)
        codes[~na] = inv
        pad = c.padded_rows(n)
        cp = np.full(pad, -1, np.int32)
        cp[:n] = codes
        if _tiering.PAGER.ingest_cold:
            # budgeted/cold ingest: park the codes in the host tier and
            # fault on first access (same contract as Vec._from_floats)
            return StrVec(None, levels, n, host_codes=cp)
        return StrVec(_mr.device_put_rows(cp), levels, n, host_codes=cp)

    # ---- Vec surface -----------------------------------------------------
    @property
    def padded_len(self) -> int:
        return int(self._codes_chunk.rows)   # shape read must not fault

    @property
    def levels_arr(self) -> np.ndarray:
        return self._levels

    @property
    def host_data(self):
        """Back-compat decode: n-sized object array ON DEMAND only."""
        codes = _mr.host_fetch(self.codes)[: self.nrows]
        out = np.empty(self.nrows, object)
        ok = codes >= 0
        out[ok] = self._levels[codes[ok]]
        return out

    @host_data.setter
    def host_data(self, v):  # Vec.__init__ assigns None; ignore
        if v is not None:
            raise AttributeError("StrVec host_data is derived")

    def to_numpy(self) -> np.ndarray:
        return self.host_data

    # ---- device string ops ----------------------------------------------
    def map_values(self, fn) -> "StrVec":
        """Value transform through the dictionary: O(unique) host calls,
        one device gather to remap codes (levels may merge)."""
        mapped = np.asarray([fn(s) for s in self._levels], dtype=object)
        new_levels, remap = (np.unique(mapped, return_inverse=True)
                             if len(mapped) else (mapped, mapped))
        tbl = jnp.asarray(np.asarray(remap, np.int32).reshape(-1)
                          if len(mapped) else np.zeros(1, np.int32))
        codes2 = _remap_codes(self.codes, tbl)
        return StrVec(codes2, new_levels, self.nrows)

    def map_values_opt(self, fn) -> "StrVec":
        """Like map_values but fn may return None (→ NA), e.g. a strsplit
        part a level doesn't have."""
        mapped = [fn(s) for s in self._levels]
        keep = [m for m in mapped if m is not None]
        new_levels, inv = (np.unique(np.asarray(keep, object),
                                     return_inverse=True)
                           if keep else (np.asarray([], object), []))
        lut = {s: i for i, s in enumerate(new_levels)}
        remap = np.asarray([-1 if m is None else lut[m] for m in mapped]
                           or [-1], np.int32)
        codes2 = _remap_codes(self.codes, jnp.asarray(remap))
        return StrVec(codes2, new_levels, self.nrows)

    def per_level_f32(self, fn) -> jax.Array:
        """(padded,) f32 measure: per-level host table + device gather
        (NaN at NA/padding rows)."""
        tbl = jnp.asarray(np.asarray(
            [float(fn(s)) for s in self._levels] or [0.0], np.float32))
        return _gather_level_f32(self.codes, tbl)

    def level_mask(self, pred) -> jax.Array:
        """(padded,) f32 0/1 predicate through the dictionary."""
        return self.per_level_f32(lambda s: 1.0 if pred(s) else 0.0)

    def _compute_rollups(self) -> Rollups:
        codes = _mr.host_fetch(self.codes)[: self.nrows]
        nas = int((codes < 0).sum())
        return Rollups(min=math.nan, max=math.nan, mean=math.nan,
                       sigma=math.nan, nas=nas, zeros=0, is_int=False)


@jax.jit
def _remap_codes(codes, tbl):
    safe = jnp.clip(codes, 0, tbl.shape[0] - 1)
    return jnp.where(codes >= 0, jnp.take(tbl, safe), -1)


@jax.jit
def _gather_level_f32(codes, tbl):
    safe = jnp.clip(codes, 0, tbl.shape[0] - 1)
    return jnp.where(codes >= 0, jnp.take(tbl, safe), jnp.nan)


# ---------------------------------------------------------------------------
class UuidVec(Vec):
    """Device-resident UUID column — the C16Chunk analog
    (water/fvec/C16Chunk.java stores each UUID as two longs in the chunk).

    TPU-native representation: the 128-bit value lives ON DEVICE as four
    row-sharded int32 lanes (padded, 4) — XLA has no native u128 and TPU
    x64 is off by default, so the C16 "two longs" become four words. NA is
    a separate device i32 mask lane (C16's NA sentinel is a reserved
    bit-pattern; a mask lane avoids stealing one of the 2^128 values).
    Supported compute is what the reference supports on UUIDs: equality /
    NA predicates (device-side lane compares) and pass-through storage;
    arithmetic intentionally raises, as in water.fvec.Vec."""

    def __init__(self, words, na, nrows: int):
        # both lanes ride ONE TierChunk (data=(padded,4) word lanes,
        # mask=(padded,) NA lane) so a UUID column demotes HBM → host
        # i32 bytes → disk as a unit, like dense planes. "flat"
        # placement: the (padded, 4) word matrix is not a 1-D packed
        # plane, so the row-shard put does not apply; consumers compare
        # whole rows and a default-device placement keeps the four
        # lanes of each row colocated.
        words_host = np.ascontiguousarray(np.asarray(words, np.int32))
        na_host = np.ascontiguousarray(np.asarray(na, np.int32))
        if _tiering.PAGER.ingest_cold:
            words_dev = na_dev = None    # born cold: fault on first use
        else:
            words_dev = jnp.asarray(words_host)
            na_dev = jnp.asarray(na_host)
        self._uuid_chunk = _tiering.PAGER.new_chunk(
            words_dev, na_dev, host=(words_host, na_host),
            label="uuid_words", put="flat")
        super().__init__(None, Codec("const"), None, nrows, T_UUID)

    @property
    def words(self):
        """(padded, 4) i32 device word lanes — faults the chunk to HBM."""
        return self._uuid_chunk.device()[0]

    @property
    def na(self):
        """(padded,) i32 NA lane (1 = NA/padding) — faults with words."""
        return self._uuid_chunk.device()[1]

    @staticmethod
    def encode(col: np.ndarray) -> "UuidVec":
        """Host UUID strings/objects -> device word lanes."""
        import uuid as _uuidlib
        c = _mesh.cloud()
        n = len(col)
        pad = c.padded_rows(n)
        words = np.zeros((pad, 4), np.int32)
        na = np.ones(pad, np.int32)
        for i, s in enumerate(col):
            if s is None or (isinstance(s, float) and math.isnan(s)) \
                    or (isinstance(s, str) and not s.strip()):
                continue
            try:
                v = (_uuidlib.UUID(str(s).strip()).int
                     if not isinstance(s, _uuidlib.UUID) else s.int)
            except (ValueError, AttributeError):
                continue                 # malformed token -> NA (C16 NA)
            for w in range(4):
                u = (v >> (32 * (3 - w))) & 0xFFFFFFFF
                words[i, w] = np.int64(u - (1 << 32) if u >= (1 << 31)
                                       else u)
            na[i] = 0
        return UuidVec(words, na, n)

    # ---- Vec surface -----------------------------------------------------
    @property
    def padded_len(self) -> int:
        return int(self._uuid_chunk.rows)   # shape read must not fault

    @property
    def host_data(self):
        """Decode to an object array of uuid.UUID (on demand only).
        staging_view: decoding a demoted column must not promote it."""
        import uuid as _uuidlib
        words_np, na_np = self._uuid_chunk.staging_view()
        W = np.asarray(words_np)[: self.nrows]
        na = np.asarray(na_np)[: self.nrows]
        out = np.empty(self.nrows, object)
        for i in range(self.nrows):
            if na[i]:
                continue
            v = 0
            for w in range(4):
                v = (v << 32) | (int(W[i, w]) & 0xFFFFFFFF)
            out[i] = _uuidlib.UUID(int=v)
        return out

    @host_data.setter
    def host_data(self, v):
        if v is not None:
            raise AttributeError("UuidVec host_data is derived")

    def to_numpy(self) -> np.ndarray:
        return self.host_data

    def as_f32(self):
        raise TypeError("UUID Vec has no numeric view (C16Chunk atd "
                        "throws in the reference too)")

    def eq(self, other: "UuidVec") -> jax.Array:
        """(padded,) f32 0/1 row equality, computed on device."""
        return _uuid_eq(self.words, self.na, other.words, other.na)

    def isna_f32(self) -> jax.Array:
        return jnp.asarray(self.na, jnp.float32)

    def na_cnt(self) -> int:
        # staging_view: rollups on a demoted column must not promote it
        na_np = self._uuid_chunk.staging_view()[1]
        return int(np.asarray(na_np)[: self.nrows].sum())

    def _compute_rollups(self) -> Rollups:
        return Rollups(min=math.nan, max=math.nan, mean=math.nan,
                       sigma=math.nan, nas=self.na_cnt(), zeros=0,
                       is_int=False)


@jax.jit
def _uuid_eq(wa, na_a, wb, na_b):
    same = jnp.all(wa == wb, axis=1)
    ok = (na_a == 0) & (na_b == 0)
    return jnp.where(ok & same, 1.0, 0.0).astype(jnp.float32)


# ---------------------------------------------------------------------------
class SparseVec(Vec):
    """Sparse numeric column — the CXIChunk/CXFChunk analog
    (water/fvec/CXIChunk.java: compressed sparse chunks storing only
    nonzero (offset, value) pairs; the overwhelming majority of values are
    an implicit zero).

    Device representation: sorted nonzero row indices (i32) + values (f32).
    NAs are stored as explicit NaN values at their rows. `as_f32()`
    densifies on demand (small frames / fallback consumers); wide-sparse
    compute paths (GLM sparse rows, hex/DataInfo.java:23) consume
    (nz_rows, nz_vals) directly via Frame.sparse_coo and never densify.
    """

    def __init__(self, nz_rows, nz_vals, nrows: int, type: str = T_NUM):
        c = _mesh.cloud()
        # both nz planes live behind TierChunks (the StrVec code-plane
        # pattern), so wide-sparse frames demote HBM → host i32/f32
        # bytes → disk exactly like dense planes. Construction sites
        # pass host arrays (npz import, parser CSC split), so the host
        # mirror is canonical for free and demote never re-fetches.
        rows_host = np.ascontiguousarray(np.asarray(nz_rows, np.int32))
        vals_host = np.ascontiguousarray(np.asarray(nz_vals, np.float32))
        if _tiering.PAGER.ingest_cold:
            rows_dev = vals_dev = None    # born cold: fault on first use
        else:
            rows_dev = jnp.asarray(rows_host)
            vals_dev = jnp.asarray(vals_host)
        self._nzr_chunk = _tiering.PAGER.new_chunk(
            rows_dev, None, host=(rows_host, None), label="sparse_rows",
            put="flat")
        self._nzv_chunk = _tiering.PAGER.new_chunk(
            vals_dev, None, host=(vals_host, None), label="sparse_vals",
            put="flat")
        self._pad = c.padded_rows(nrows)
        super().__init__(None, Codec("const", const_val=0.0), None,
                         nrows, type)

    # ---- Vec surface -----------------------------------------------------
    @property
    def nz_rows(self):
        """(nnz,) i32 device row indices — faults the plane to HBM."""
        return self._nzr_chunk.device()[0]

    @property
    def nz_vals(self):
        """(nnz,) f32 device values — faults the plane to HBM."""
        return self._nzv_chunk.device()[0]

    @property
    def nnz(self) -> int:
        return int(self._nzr_chunk.rows)   # shape read must not fault

    @property
    def padded_len(self) -> int:
        return self._pad

    def as_f32(self) -> jax.Array:
        return _sparse_densify(self.nz_rows, self.nz_vals,
                               pad=self._pad, n=self.nrows)

    def _compute_rollups(self) -> Rollups:
        # staging_view: rollups on a demoted column must not promote it
        v = np.asarray(self._nzv_chunk.staging_view()[0])
        ok = v[~np.isnan(v)]
        n = self.nrows
        nas = int(np.isnan(v).sum())
        implicit_zeros = n - len(v)          # rows absent from nz storage
        zeros = implicit_zeros + int((ok == 0).sum())
        cnt = max(n - nas, 1)
        mean = ok.sum() / cnt
        var = (ok * ok).sum() / cnt - mean * mean
        var *= cnt / max(cnt - 1, 1)         # sample sigma like RollupStats
        if len(ok) == 0:
            mn = mx = 0.0
        elif implicit_zeros > 0:             # implicit zeros exist only
            mn = float(min(ok.min(), 0.0))   # when some row is absent
            mx = float(max(ok.max(), 0.0))
        else:
            mn, mx = float(ok.min()), float(ok.max())
        return Rollups(
            min=mn, max=mx,
            mean=float(mean), sigma=float(math.sqrt(max(var, 0.0))),
            nas=nas, zeros=int(zeros),
            is_int=bool(len(ok) == 0 or np.all(ok == np.floor(ok))))


# ---------------------------------------------------------------------------
class Frame:
    """A named, ordered set of equal-length Vecs (Frame.java:64)."""

    def __init__(self, names: Sequence[str], vecs: Sequence[Vec],
                 key: Optional[str] = None):
        assert len(names) == len(vecs)
        ns = {v.nrows for v in vecs}
        assert len(ns) <= 1, f"ragged frame: row counts {ns}"
        self.names = list(names)
        self.vecs = list(vecs)
        self.key = key or DKV.make_key("frame")
        self._matrix_cache: dict = {}
        DKV.put(self.key, self)
        # Cleaner wakeup point: account this frame, spill cold ones if the
        # HBM budget is exceeded (water/Cleaner.java:11)
        from h2o3_tpu.core.memory import MANAGER
        MANAGER.touch(self.key)
        MANAGER.maybe_clean()

    # ---- construction ---------------------------------------------------
    @staticmethod
    def from_dict(cols: dict, key: Optional[str] = None,
                  column_types: Optional[dict] = None) -> "Frame":
        names, vecs = [], []
        for name, col in cols.items():
            t = (column_types or {}).get(name)
            names.append(str(name))
            vecs.append(Vec.from_numpy(np.asarray(col), type=t))
        return Frame(names, vecs, key)

    @staticmethod
    def from_numpy(mat: np.ndarray, names: Optional[Sequence[str]] = None,
                   key: Optional[str] = None) -> "Frame":
        mat = np.asarray(mat)
        if mat.ndim == 1:
            mat = mat[:, None]
        names = list(names) if names else [f"C{i+1}" for i in range(mat.shape[1])]
        return Frame(names, [Vec.from_numpy(mat[:, j]) for j in range(mat.shape[1])], key)

    @staticmethod
    def from_pandas(df, key=None) -> "Frame":
        return Frame.from_dict({c: df[c].to_numpy() for c in df.columns}, key)

    # ---- shape ----------------------------------------------------------
    @property
    def nrows(self) -> int:
        return self.vecs[0].nrows if self.vecs else 0

    @property
    def ncols(self) -> int:
        return len(self.vecs)

    @property
    def padded_len(self) -> int:
        return self.vecs[0].padded_len if self.vecs else 0

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    @property
    def types(self) -> dict:
        return {n: v.type for n, v in zip(self.names, self.vecs)}

    def vec(self, name) -> Vec:
        """Column by name or positional index (h2o-py frames accept both)."""
        if isinstance(name, (int, np.integer)):
            return self.vecs[int(name)]
        return self.vecs[self.names.index(name)]

    def col_idx(self, name: str) -> int:
        return self.names.index(name)

    # ---- column select / mutation ---------------------------------------
    def __getitem__(self, sel):
        if isinstance(sel, str):
            return Frame([sel], [self.vec(sel)])
        if isinstance(sel, (list, tuple)):
            if all(isinstance(s, str) for s in sel):
                return Frame(list(sel), [self.vec(s) for s in sel])
            return Frame([self.names[i] for i in sel], [self.vecs[i] for i in sel])
        raise KeyError(sel)

    def __setitem__(self, name: str, value):
        if isinstance(value, Frame):
            value = value.vecs[0]
        if isinstance(value, np.ndarray):
            value = Vec.from_numpy(value)
        if not isinstance(value, Vec):
            value = Vec.from_numpy(np.asarray(value))
        assert value.nrows == self.nrows or self.ncols == 0
        if name in self.names:
            self.vecs[self.names.index(name)] = value
        else:
            self.names.append(name)
            self.vecs.append(value)
        self._matrix_cache.clear()

    def drop(self, names) -> "Frame":
        if isinstance(names, str):
            names = [names]
        keep = [n for n in self.names if n not in names]
        return self[keep]

    # ---- dense matrix view (the DataInfo feed) --------------------------
    def matrix(self, cols: Optional[Sequence[str]] = None,
               dtype=jnp.float32) -> jax.Array:
        """(padded_rows, k) row-sharded dense matrix; NAs/padding → NaN.

        Cached per column-tuple. This is the hand-off point from the packed
        columnar store to MXU-shaped compute.
        """
        cols = tuple(cols if cols is not None else self.names)
        ck = (cols, str(dtype))
        hit = self._matrix_cache.get(ck)
        if hit is not None:
            return hit
        vs = [self.vec(c) for c in cols]
        # bounded-lookahead faulting, ONE device() per column (both
        # planes from a single fault — touching .data then .mask would
        # fault a demoted chunk twice): the I/O worker tiers up the next
        # couple of columns while the main thread faults the current one.
        # sparse columns densify through as_f32 (already decoded f32 with
        # NaN padding) — _decode_f32 cannot read their data=None layout
        planes = _mr.map_chunked(
            lambda v: (v.as_f32(), None) if isinstance(v, SparseVec)
            else v._chunk.device(),
            vs, lookahead=2)
        datas = [p[0] for p in planes]
        masks = [p[1] for p in planes]
        codecs = tuple(Codec("f32") if isinstance(v, SparseVec) else v.codec
                       for v in vs)

        def build(datas, masks):
            cols_f32 = [_decode_f32(d, c, m)
                        for d, c, m in zip(datas, codecs, masks)]
            return jnp.stack(cols_f32, axis=1).astype(dtype)

        out_sh = _mesh.cloud().rows_sharding(2)
        # cached_jit: build captures (codecs, dtype) — both hashable — so
        # re-materializing a same-schema matrix reuses one program
        m = _mr.cached_jit(build, out_shardings=out_sh)(datas, masks)
        self._matrix_cache[ck] = m
        return m

    def is_sparse(self, cols=None) -> bool:
        cols = cols if cols is not None else self.names
        return all(isinstance(self.vec(c), SparseVec) for c in cols)

    def sparse_coo(self, cols=None):
        """Global COO of sparse columns: (row_idx, col_idx, vals, (n, C))
        device arrays — the hand-off to sparse-rows compute (the
        hex/DataInfo.java:23 sparse iterator analog). NaN values mean NA;
        consumers decide their NA policy (GLM's sparse mode zero-imputes,
        matching its implicit zeros; mean-centering would densify)."""
        cols = list(cols if cols is not None else self.names)
        rows_l, cols_l, vals_l = [], [], []
        for j, c in enumerate(cols):
            v = self.vec(c)
            assert isinstance(v, SparseVec), f"{c} is not sparse"
            rows_l.append(v.nz_rows)
            cols_l.append(jnp.full(v.nnz, j, jnp.int32))
            vals_l.append(v.nz_vals)
        return (jnp.concatenate(rows_l), jnp.concatenate(cols_l),
                jnp.concatenate(vals_l), (self.nrows, len(cols)))

    # ---- host round-trip -------------------------------------------------
    def to_numpy(self, cols=None) -> np.ndarray:
        cols = cols if cols is not None else self.names
        return np.column_stack([self.vec(c).to_numpy() for c in cols])

    def as_data_frame(self):
        import pandas as pd
        out = {}
        for n, v in zip(self.names, self.vecs):
            x = v.to_numpy()
            if v.type == T_CAT:
                dom = v.domain
                x = np.array([None if np.isnan(c) else dom[int(c)] for c in x],
                             dtype=object)
            out[n] = x
        return pd.DataFrame(out)

    def head(self, n=10):
        return self.as_data_frame().head(n)

    # ---- summary (REST /3/Frames summary) --------------------------------
    def summary(self) -> dict:
        # chunked iteration with lookahead: rollups fault one column at a
        # time, so the pager tiers up column j+1 while j's kernel runs
        rolls = _mr.map_chunked(
            lambda v: None if v.type == T_STR else v.rollups(),
            self.vecs, lookahead=2)
        out = {}
        for n, v, r in zip(self.names, self.vecs, rolls):
            if r is None:
                out[n] = {"type": v.type}
                continue
            out[n] = {"type": v.type, "min": r.min, "max": r.max,
                      "mean": r.mean, "sigma": r.sigma, "missing": r.nas,
                      "zeros": r.zeros,
                      "cardinality": v.cardinality}
        return out

    def _tier_on_get(self):
        """DKV.get hook: LRU-touch this frame's chunks — numeric planes,
        StrVec dictionary code planes, SparseVec nz planes and UuidVec
        word lanes alike; a whole-frame spill (every chunk on disk)
        promotes its codec bytes back to host RAM, HBM faults stay lazy
        (raw_get never calls this)."""
        chunks = []
        for v in self.vecs:
            for attr in ("_chunk", "_codes_chunk", "_nzr_chunk",
                         "_nzv_chunk", "_uuid_chunk"):
                ch = getattr(v, attr, None)
                if ch is not None:
                    chunks.append(ch)
        _tiering.PAGER.on_frame_get(chunks)

    def _on_remove(self):
        # Vecs may be shared with other frames (column slices, adapted test
        # frames) — drop only our caches; device arrays (and their pager
        # chunks + spill files) are freed by refcount/GC.
        self._matrix_cache.clear()

    def __repr__(self):
        return f"<Frame {self.key} {self.nrows}x{self.ncols} {self.names[:8]}>"


# ---------------------------------------------------------------------------
def rebalance_frame(frame: "Frame", key: Optional[str] = None) -> "Frame":
    """RebalanceDataSet.java analog: rebuild every Vec against the CURRENT
    cloud sharding/padding. H2O re-chunks to re-spread work across nodes;
    here re-sharding matters after the mesh shape changed (frames created
    under an old mesh keep their old layout) or to defragment after slicing."""
    names, vecs = [], []
    for n, v in zip(frame.names, frame.vecs):
        if v.type == T_STR:
            vecs.append(Vec.from_numpy(v.host_data, type=T_STR))
        else:
            col = v.to_numpy()
            mask = np.isnan(col) if v.type != T_CAT else np.isnan(col)
            vecs.append(Vec._from_floats(np.where(mask, 0.0, col), mask,
                                         v.type, v.domain))
        names.append(n)
    return Frame(names, vecs, key)
