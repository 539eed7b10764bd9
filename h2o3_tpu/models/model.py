"""ModelBuilder / Model framework — hex/ModelBuilder.java + hex/Model.java.

Reference: hex/ModelBuilder.java (param validation `init(expensive)` :1319,
n-fold CV orchestration `computeCrossValidation` :597, Driver :228),
hex/Model.java (score :1764, BigScore MRTask :2077, per-row score0 :2244,
adaptTestForTrain), hex/DataInfo.java:23 (row codec: one-hot expansion,
standardization, NA imputation).

TPU-native design:
  * A builder's Driver is a controller loop launching jitted device programs;
    "BigScore" is one jitted batch scorer over the row-sharded matrix — there
    is no per-row score0; scoring is vectorized by construction.
  * DataInfo becomes a matrix-builder: it materializes the (padded_rows ×
    nfeatures) f32 design matrix ONCE per train/score (one-hot on device via
    jax.nn.one_hot, standardization/imputation fused in the same jit).
  * CV builds fold models sequentially on the controller (each a full-mesh
    jitted program — the TPU analog of H2O building CV models in parallel on
    idle cluster CPU is keeping the chips busy with one model at a time).
"""

from __future__ import annotations

import copy
import functools
import math
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.core.frame import (Codec, DevicePlanes, Frame, Vec, T_CAT,
                                 T_NUM)
from h2o3_tpu.core.jobs import Job
from h2o3_tpu.core.kvstore import DKV
from h2o3_tpu.models import metrics as M
from h2o3_tpu.obs import metrics as _om
from h2o3_tpu.obs.timeline import SPANS as _SPANS, span as _span
from h2o3_tpu.parallel import mesh as _mesh
from h2o3_tpu.parallel import compat as _compat

# the operator's scoring rate by rate() on /metrics — the companion of
# h2o3_gbm_row_trees_total for training. path = "bucket" (the compiled-
# scorer cache served it) or "frame" (the model's own whole-frame path)
_PREDICT_CALLS = _om.counter(
    "h2o3_predict_calls_total",
    "completed Model.predict() calls, by algorithm and scoring path")
_PREDICT_ROWS = _om.counter(
    "h2o3_predict_rows_total",
    "rows scored by completed Model.predict() calls, by algorithm and "
    "scoring path")
# how often the prediction frame is built without a host round trip
_FRAME_COLUMNS = _om.counter(
    "h2o3_predict_frame_columns_total",
    "prediction frames built, by algorithm and by where their columns were "
    "made: columns=device (planes from the device scores, one program) or "
    "host (float64 host columns, packed and put)")


def _predict_root(fn):
    """Wrap a family's predict() in the `predict` root span and the two
    counters; ModelBase applies it to its own predict and, through
    __init_subclass__, to every override."""
    @functools.wraps(fn)
    def predict(self, test_data, *args, **kwargs):
        rows = int(getattr(test_data, "nrows", 0) or 0)
        # h2o3-ok: R011 a family's own attrs are a fixed set it notes at publish (tree models: cat_levels, set_nodes)
        with _span("predict", model=self.key, algo=self.algo,
                   frame=getattr(test_data, "key", None), rows=rows,
                   cols=int(getattr(test_data, "ncols", 0) or 0),
                   path="frame",
                   **(getattr(self, "_predict_attrs", None) or {})) as sp:
            out = fn(self, test_data, *args, **kwargs)
        _PREDICT_CALLS.inc(algo=self.algo, path=sp.attrs["path"])
        _PREDICT_ROWS.inc(rows, algo=self.algo, path=sp.attrs["path"])
        return out
    return predict


def _prediction_planes(n: int, label: Optional[str]):
    """The device program that packs raw scores into a prediction frame's
    planes, [(data, mask), ...] in column order, each as long as the scores
    (the scored frame's padded length) — what `_prediction_columns`
    computes on the host, in the storage `Vec._from_floats` would choose
    for it: `label` None (regression, `out` is (rows,)) gives the one score
    plane; "i8" / "f32" (a classifier, `out` is (rows, K)) the label in
    that storage — argmax with NumPy's rules: the first maximum, a NaN
    counts as one — then a plane per class. A score is its own float32
    with NaN as NA; rows >= n are NA."""
    def planes(out):
        padding = jnp.arange(out.shape[0]) >= n

        def num(p):
            p = p.astype(jnp.float32)
            na = padding | jnp.isnan(p)
            return jnp.where(na, 0.0, p), na.astype(jnp.uint8)
        if label is None:
            return [num(out)]
        lab = jnp.where(padding, 0, jnp.argmax(out, axis=1))
        lab = lab.astype(jnp.int8 if label == "i8" else jnp.float32)
        return [(lab, padding.astype(jnp.uint8))] \
            + [num(out[:, k]) for k in range(out.shape[1])]
    return planes


# ===========================================================================
class DataInfo:
    """Design-matrix codec (hex/DataInfo.java:23).

    cat_mode:
      * "onehot" — expand categoricals to indicator columns (GLM/DL/KMeans/PCA)
      * "label"  — keep categorical codes as one numeric column (tree algos,
                   which bin them natively)
    """

    def __init__(self, frame: Frame, x: Sequence[str], y: Optional[str],
                 cat_mode: str = "onehot", standardize: bool = False,
                 impute_missing: bool = True, weights: Optional[str] = None,
                 offset: Optional[str] = None,
                 interactions: Optional[Sequence[str]] = None):
        self.cat_mode = cat_mode
        self.standardize = standardize
        self.impute_missing = impute_missing
        self.response_name = y
        self.weights_name = weights
        self.offset_name = offset
        self.predictors = [c for c in x if c != y and frame.vec(c).type != "str"]
        self.cat_cols = [c for c in self.predictors if frame.vec(c).type == T_CAT]
        self.num_cols = [c for c in self.predictors if c not in self.cat_cols]
        self.domains = {c: list(frame.vec(c).domain) for c in self.cat_cols}
        self.cardinalities = {c: len(self.domains[c]) for c in self.cat_cols}
        # response metadata
        self.response_domain = None
        if y is not None and frame.vec(y).type == T_CAT:
            self.response_domain = list(frame.vec(y).domain)
        # normalization stats from the TRAINING frame
        self.means = {c: frame.vec(c).mean() for c in self.num_cols}
        self.sigmas = {c: frame.vec(c).sigma() or 1.0 for c in self.num_cols}
        # interactions (hex/DataInfo.java interactions / makeInteraction /
        # InteractionWrappedVec): pairwise interaction columns over the
        # listed predictors. num x num -> product column (standardized with
        # its own training stats); cat x cat -> interaction categorical
        # whose indicator block spans the level CROSS; cat x num -> one
        # numeric column per level of the categorical (the wrapped-vec
        # expansion: num value in the active level's slot, 0 elsewhere).
        self.inter_pairs: list = []      # (num_a, num_b, name)
        self.inter_catcat: list = []     # (cat_a, cat_b, name)
        self.inter_catnum: list = []     # (cat_a, num_b, name)
        if interactions:
            if cat_mode != "onehot":
                raise ValueError(
                    "interactions are only supported with the one-hot "
                    "design matrix (GLM-family models)")
            # dedupe, order-preserving: a repeated entry would emit a
            # degenerate self-pair product
            interactions = list(dict.fromkeys(interactions))
            unknown = [c for c in interactions if c not in self.predictors]
            if unknown:
                raise ValueError(
                    f"interactions reference unknown predictors: "
                    f"{unknown} (GLM interaction-column validation)")
            import itertools as _it
            for a, b in _it.combinations(interactions, 2):
                a_cat, b_cat = a in self.cat_cols, b in self.cat_cols
                if a_cat and b_cat:
                    cross = (self.cardinalities[a] * self.cardinalities[b])
                    if cross > 10_000:
                        raise ValueError(
                            f"categorical interaction {a}x{b} expands to "
                            f"{cross} indicator columns (cap 10000)")
                    self.inter_catcat.append((a, b, f"{a}_{b}"))
                elif a_cat or b_cat:
                    ca, nb = (a, b) if a_cat else (b, a)
                    self.inter_catnum.append((ca, nb, f"{ca}:{nb}"))
                else:
                    name = f"{a}:{b}"
                    self.inter_pairs.append((a, b, name))
                    prod = (frame.vec(a).as_f32()[: frame.nrows]
                            * frame.vec(b).as_f32()[: frame.nrows])
                    pn = np.asarray(prod, np.float64)
                    ok = pn[~np.isnan(pn)]
                    self.means[name] = float(ok.mean()) if len(ok) else 0.0
                    self.sigmas[name] = float(ok.std(ddof=1)) or 1.0 \
                        if len(ok) > 1 else 1.0
        # expanded feature names (coefficient_names order: cats first like H2O)
        self.feature_names: list[str] = []
        if cat_mode == "onehot":
            for c in self.cat_cols:
                self.feature_names += [f"{c}.{l}" for l in self.domains[c]]
            self.feature_names += self.num_cols
            self.feature_names += [n for _, _, n in self.inter_pairs]
            for a, b, name in self.inter_catcat:
                self.feature_names += [
                    f"{name}.{la}_{lb}" for la in self.domains[a]
                    for lb in self.domains[b]]
            for a, b, name in self.inter_catnum:
                self.feature_names += [f"{a}.{la}:{b}"
                                       for la in self.domains[a]]
        else:
            self.feature_names = list(self.predictors)

    @property
    def n_features(self) -> int:
        return len(self.feature_names)

    # ---- device-side matrix build --------------------------------------
    def raw_columns(self) -> list:
        """Column order of the RAW (pre-expansion) staging matrix consumed
        by assemble_design: cat codes first, then numerics — the serving
        fast path stages exactly these into its bucket buffer."""
        if self.cat_mode == "label":
            return list(self.predictors)
        return self.cat_cols + self.num_cols

    def _assemble(self, raw_cat, raw_num):
        """Expand raw columns into the design matrix (one-hot, standardize,
        impute, interactions). Pure traceable jnp — callable eagerly, under
        matrix()'s jit, or inside a serving scorer program."""
        cards = tuple(self.cardinalities[c] for c in self.cat_cols)
        means = np.array([self.means[c] for c in self.num_cols], np.float32)
        sigmas = np.array([max(self.sigmas[c], 1e-10)
                           for c in self.num_cols], np.float32)
        standardize = self.standardize
        inter_idx = tuple(
            (self.num_cols.index(a), self.num_cols.index(b),
             np.float32(self.means[n]),
             np.float32(max(self.sigmas[n], 1e-10)))
            for a, b, n in self.inter_pairs)
        catcat_idx = tuple(
            (self.cat_cols.index(a), self.cat_cols.index(b),
             self.cardinalities[a], self.cardinalities[b])
            for a, b, _ in self.inter_catcat)
        catnum_idx = tuple(
            (self.cat_cols.index(a), self.num_cols.index(b),
             self.cardinalities[a], np.float32(self.means[b]),
             np.float32(max(self.sigmas[b], 1e-10)))
            for a, b, _ in self.inter_catnum)
        parts = []
        if raw_cat is not None:
            for j, k in enumerate(cards):
                col = raw_cat[:, j]
                code = jnp.where(jnp.isnan(col), -1, col).astype(jnp.int32)
                parts.append(jax.nn.one_hot(code, k, dtype=jnp.float32))
        if raw_num is not None:
            x = raw_num
            if standardize:
                x = (x - means) / sigmas
            if self.impute_missing:
                fill = jnp.zeros_like(means) if standardize else means
                x = jnp.where(jnp.isnan(x), fill, x)
            parts.append(x)
        for (ia, ib, im, isg) in inter_idx:
            p = raw_num[:, ia] * raw_num[:, ib]     # RAW product
            if standardize:
                p = (p - im) / isg
            if self.impute_missing:
                p = jnp.where(jnp.isnan(p),
                              0.0 if standardize else im, p)
            parts.append(p[:, None])
        for (ia, ib, ka, kb) in catcat_idx:
            # interaction categorical: indicator over the level cross;
            # NA in either factor -> all-zero row (InteractionWrappedVec)
            ca = raw_cat[:, ia]
            cb = raw_cat[:, ib]
            bad = jnp.isnan(ca) | jnp.isnan(cb)
            code = jnp.where(
                bad, -1,
                jnp.nan_to_num(ca) * kb + jnp.nan_to_num(cb)
            ).astype(jnp.int32)
            parts.append(jax.nn.one_hot(code, ka * kb,
                                        dtype=jnp.float32))
        for (ia, ib, ka, im, isg) in catnum_idx:
            # cat x num wrapped vec: num value in the active level slot
            ca = raw_cat[:, ia]
            code = jnp.where(jnp.isnan(ca), -1, ca).astype(jnp.int32)
            x = raw_num[:, ib]
            if standardize:
                x = (x - im) / isg
            if self.impute_missing:
                x = jnp.where(jnp.isnan(x), 0.0 if standardize else im,
                              x)
            parts.append(jax.nn.one_hot(code, ka, dtype=jnp.float32)
                         * x[:, None])
        return jnp.concatenate(parts, axis=1)

    def assemble_design(self, raw):
        """raw (rows, len(raw_columns())) f32 NaN-NA → design matrix.
        Traceable; the serving scorer cache compiles it together with the
        model's _score_matrix into ONE program per (model, bucket)."""
        if self.cat_mode == "label":
            return raw
        ncat = len(self.cat_cols)
        raw_cat = raw[:, :ncat] if ncat else None
        raw_num = raw[:, ncat:] if self.num_cols else None
        return self._assemble(raw_cat, raw_num)

    def __getstate__(self):
        # the jit wrapper is derived state, rebuilt on demand; never pickled
        state = dict(self.__dict__)
        state.pop("_assemble_jit", None)
        return state

    def matrix(self, frame: Frame) -> jax.Array:
        """(padded, n_features) f32 row-sharded design matrix. NaN padding rows
        remain NaN in "label" mode; in onehot mode NAs are imputed/zeroed and
        callers must use weights() to exclude padding."""
        frame = self.adapt(frame)
        if self.cat_mode == "label":
            return frame.matrix(self.predictors)
        raw_cat = frame.matrix(self.cat_cols) if self.cat_cols else None
        raw_num = frame.matrix(self.num_cols) if self.num_cols else None
        # ONE jit wrapper per DataInfo: a fresh jax.jit(self._assemble)
        # here would have a new identity (and empty trace cache) per call
        # — the same per-call recompile hazard fixed in weights()/engine
        fn = self.__dict__.get("_assemble_jit")
        if fn is None:
            out_sh = _mesh.cloud().rows_sharding(2)
            fn = self._assemble_jit = _compat.guard_collective(
                jax.jit(self._assemble, out_shardings=out_sh))
        return fn(raw_cat, raw_num)

    def response(self, frame: Frame) -> jax.Array:
        """(padded,) f32 response; class index for categorical; NaN padding."""
        return frame.matrix([self.response_name])[:, 0]

    def weights(self, frame: Frame) -> jax.Array:
        """(padded,) f32 observation weights; 0 on padding rows and rows with
        missing response (the BigScore skip-NA contract)."""
        if self.weights_name:
            w = frame.matrix([self.weights_name])[:, 0]
            w = jnp.where(jnp.isnan(w), 0.0, w)
        else:
            w = jnp.ones(frame.padded_len, jnp.float32)
        # n is a traced scalar: the old closure-over-n jit had a fresh
        # function identity per call and recompiled on every invocation
        return _mask_padding(w, frame.nrows)

    def offset(self, frame: Frame):
        if not self.offset_name:
            return None
        o = frame.matrix([self.offset_name])[:, 0]
        return jnp.where(jnp.isnan(o), 0.0, o)

    # ---- test-frame adaptation (Model.adaptTestForTrain) ----------------
    def adapt(self, frame: Frame) -> Frame:
        """Remap categorical domains to training domains; add missing columns
        as all-NA. Returns the original frame when nothing needs adapting."""
        needed = list(self.predictors)
        if self.response_name and self.response_name in frame.names:
            needed.append(self.response_name)
        for extra in (self.weights_name, self.offset_name):
            if extra and extra in frame.names:
                needed.append(extra)
        changed = False
        names, vecs = [], []
        for c in needed:
            if c not in frame.names:
                v = Vec.from_numpy(np.full(frame.nrows, np.nan))
                changed = True
            else:
                v = frame.vec(c)
                want = self.domains.get(c) or (
                    self.response_domain if c == self.response_name else None)
                if v.type == T_CAT and want is not None and v.levels() != want:
                    v = _remap_domain(v, want)
                    changed = True
                elif v.type == T_CAT and want is None and c in self.num_cols:
                    # train saw numeric, test has cat → NA out
                    v = Vec.from_numpy(np.full(frame.nrows, np.nan))
                    changed = True
            names.append(c)
            vecs.append(v)
        if not changed and names == frame.names[: len(names)]:
            return frame
        f = Frame(names, vecs)
        DKV.remove(f.key)  # adaptation product is transient, not registered
        return f


@_compat.guard_collective


@jax.jit
def _mask_padding(w, n):
    """Zero weights on padding rows; n traced, so one compile per shape."""
    idx = jnp.arange(w.shape[0])
    return jnp.where(idx < n, w, 0.0)


def _fold_custom_metric(udf, mapped):
    """Apply the CMetricFunc 3-phase contract (water/udf): map emits per-row
    component tuples; reduce is an associative combiner folded down to the
    final aggregate. Vectorized: pairwise binary-tree halving, so jnp-math
    combiners run on device. Pre-aggregated scalars pass through unchanged."""
    tup = mapped if isinstance(mapped, tuple) else (mapped,)
    if jnp.asarray(tup[0]).ndim == 0:
        return mapped                      # map already produced the aggregate
    comps = tuple(jnp.atleast_1d(jnp.asarray(c)) for c in tup)
    while comps[0].shape[0] > 1:
        n = comps[0].shape[0]
        even = n - (n % 2)
        red = udf.reduce(tuple(c[0:even:2] for c in comps),
                         tuple(c[1:even:2] for c in comps))
        red = tuple(jnp.atleast_1d(jnp.asarray(a)) for a in red)
        if n % 2:
            red = tuple(jnp.concatenate([a, c[-1:]])
                        for a, c in zip(red, comps))
        comps = red
    agg = tuple(c[0] for c in comps)
    return agg if isinstance(mapped, tuple) else agg[0]


def _remap_domain(v: Vec, want: list) -> Vec:
    lookup = {l: i for i, l in enumerate(want)}
    src = v.to_numpy()
    dom = v.domain
    out = np.full(len(src), np.nan)
    for i, code in enumerate(src):
        if not math.isnan(code):
            out[i] = lookup.get(str(dom[int(code)]), np.nan)
    return Vec._from_floats(np.where(np.isnan(out), 0.0, out),
                            np.isnan(out), T_CAT, np.asarray(want, object))


# ===========================================================================
@dataclass
class ModelOutput:
    """hex/Model.Output analog: everything the training run learned."""
    model_id: str = ""
    algo: str = ""
    names: list = field(default_factory=list)
    domains: dict = field(default_factory=dict)
    response_domain: Optional[list] = None
    training_metrics: Optional[object] = None
    validation_metrics: Optional[object] = None
    cross_validation_metrics: Optional[object] = None
    scoring_history: list = field(default_factory=list)
    model_summary: dict = field(default_factory=dict)
    variable_importances: Optional[list] = None
    run_time_ms: int = 0
    cv_predictions_key: Optional[str] = None
    cv_fold_assignment_key: Optional[str] = None


class ModelBase:
    """Shared estimator/model surface (mirrors h2o-py H2OEstimator)."""

    algo = "base"
    supervised = True
    _defaults: dict = {}
    _COMMON = {
        "model_id": None, "seed": -1, "nfolds": 0, "weights_column": None,
        "offset_column": None, "fold_assignment": "AUTO", "fold_column": None,
        "keep_cross_validation_predictions": False,
        "keep_cross_validation_fold_assignment": False,
        "ignored_columns": None, "ignore_const_cols": True,
        "max_runtime_secs": 0.0, "standardize": True,
        "categorical_encoding": "AUTO", "distribution": "AUTO",
        "checkpoint": None, "export_checkpoints_dir": None,
        "custom_metric_func": None, "custom_distribution_func": None,
    }

    def __init__(self, **params):
        self.params = dict(self._COMMON)
        self.params.update(self._defaults)
        unknown = set(params) - set(self.params)
        if unknown:
            raise ValueError(f"{self.algo}: unknown parameters {sorted(unknown)}")
        self.params.update(params)
        self._output: Optional[ModelOutput] = None
        self._dinfo: Optional[DataInfo] = None
        self.key: Optional[str] = None

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "predict" in cls.__dict__:
            cls.predict = _predict_root(cls.__dict__["predict"])

    # ---- public training entrypoint (H2OEstimator.train) ----------------
    def train(self, x=None, y=None, training_frame=None, validation_frame=None,
              **overrides) -> "ModelBase":
        self.params.update(overrides)
        frame = training_frame
        assert isinstance(frame, Frame), "training_frame must be a Frame"
        if self.supervised:
            assert y is not None, f"{self.algo} requires a response column y"
        x = self._resolve_predictors(frame, x, y)
        self._dinfo = self._make_data_info(frame, x, y)
        self.key = self.params.get("model_id") or DKV.make_key(self.algo)
        self._output = ModelOutput(model_id=self.key, algo=self.algo,
                                   names=list(x),
                                   domains=self._dinfo.domains,
                                   response_domain=self._dinfo.response_domain)
        job = Job(description=f"{self.algo} on {frame.key}", dest=self.key)
        t0 = time.time()
        mrs = float(self.params.get("max_runtime_secs") or 0.0)
        if mrs > 0:
            job.deadline = t0 + mrs
        # early stopping scores the validation frame when one is given
        # (ScoreKeeper uses validation metrics over training metrics)
        self._valid_for_scoring = validation_frame

        def work(job: Job):
            if int(self.params["nfolds"] or 0) > 1 or self.params.get("fold_column"):
                self._run_cross_validation(frame, x, y, job)
            self._fit(frame, job)
            with job.phase("metrics"):
                self._score_train_valid(frame, validation_frame)
            self._output.run_time_ms = int(1000 * (time.time() - t0))
            # release validation scoring state: the margins/design matrix
            # would otherwise pin device memory for the model's lifetime
            # (and a retrain on this instance must never see stale state)
            self._vstate = None
            self._valid_for_scoring = None
            return self

        job.start(work, background=False)
        job.join()
        # drift baseline: profile the training distribution (features +
        # predictions) and register the model for live monitoring BEFORE
        # publish, so a retrain rotates generations before any request
        # can score the new one (modelmon owns the try/except — a failed
        # profile must never fail the train)
        from h2o3_tpu.obs import modelmon as _modelmon
        from h2o3_tpu import serving
        with _span("model.publish", model=self.key):
            self._note_published()
            _modelmon.install_baseline(self, frame)
            DKV.put(self.key, self)
            # optional serving pre-warm on publish (H2O3_SCORER_PREWARM=1):
            # compile the most common row bucket in the background so the
            # first real request warm-hits instead of paying the compile
            if serving.prewarm_enabled():
                serving.prewarm(self)
        return self

    def _note_published(self):
        """What a family counts about a model as it is published (tree
        models: their SET-split nodes); `_predict_attrs` = attrs its
        `predict` root span then carries."""

    def _resolve_predictors(self, frame, x, y):
        if x is None:
            skip = {y, self.params.get("weights_column"),
                    self.params.get("offset_column"),
                    self.params.get("fold_column")}
            skip |= set(self.params.get("ignored_columns") or [])
            x = [c for c in frame.names if c not in skip]
        else:
            x = [frame.names[i] if isinstance(i, int) else i for i in x]
        if self.params.get("ignore_const_cols"):
            # SparseVec reuses the "const" codec for its implicit zeros:
            # it is constant only when it has NO nonzeros at all
            x = [c for c in x
                 if frame.vec(c).type == "str"
                 or getattr(frame.vec(c), "nnz", 0) > 0
                 or not (frame.vec(c).codec.kind == "const"
                         and frame.vec(c).na_cnt() == 0)]
        return x

    def _make_data_info(self, frame, x, y) -> DataInfo:
        return DataInfo(frame, x, y,
                        cat_mode=self._cat_mode(),
                        standardize=bool(self.params.get("standardize")),
                        weights=self.params.get("weights_column"),
                        offset=self.params.get("offset_column"),
                        interactions=self.params.get("interactions"))

    def _cat_mode(self) -> str:
        return "onehot"

    # ---- algo hooks ------------------------------------------------------
    def _fit(self, frame: Frame, job: Job):
        raise NotImplementedError

    def _score_matrix(self, X: jax.Array):
        """Batch score0: return regression preds (n,) or class probs (n,K)."""
        raise NotImplementedError

    # ---- mesh-sharded serving params -------------------------------------
    # Families list the instance attributes whose (pytree-of-arrays)
    # values should enter the serving scorer as SHARED DEVICE ARGUMENTS
    # instead of baked closure constants: the serving param store places
    # them once per model generation (NamedSharding over the cloud mesh,
    # PartitionSpecs from the regex rules below) and every row-bucket
    # program dispatches against that single HBM copy. Attributes that
    # are missing or None are skipped (e.g. `_trees` vs `_trees_k`
    # depending on the trained distribution). Anything the scorer
    # CONCRETIZES at trace time (float(self._f0[c]), static index lists)
    # must stay OUT of this tuple — it traces as a constant like before.
    _serving_param_attrs: tuple = ()
    # ((regex, PartitionSpec), ...) matched against '/'-joined leaf paths
    # ("_trees/value", "_params_net/0/0", …) by mesh.match_partition_rules;
    # first match wins, unmatched leaves and scalars replicate.
    _partition_rules: tuple = ()

    def _serving_params(self):
        """Param pytree for the serving fast path, or None when this
        family's scorer must close over its state (legacy baked build)."""
        attrs = self._serving_param_attrs
        if not attrs:
            return None
        p = {a: getattr(self, a, None) for a in attrs}
        p = {a: v for a, v in p.items() if v is not None}
        return p or None

    # ---- DKV lifecycle hooks ---------------------------------------------
    def _on_remove(self):
        """DKV.remove(model key): drop the model's serving residency —
        compiled programs, shared param placements on EVERY tier (HBM,
        host mirror, ice_root npz) — exactly once. Runs outside the
        `dkv` mutex (kvstore contract), so cache/param locks never nest
        under it. Idempotent: the REST DELETE handler calls
        CACHE.invalidate_key in the same breath."""
        if not self.key:
            return
        try:
            from h2o3_tpu import serving
            serving.CACHE.invalidate_key(self.key)
        except Exception:   # noqa: BLE001 — removal must not fail the DKV op
            pass
        # per-model observability series leave /metrics exactly once:
        # drift sketches + gauges (modelmon) and the usage ledger's
        # attribution rows/counters. Both are idempotent no-ops when the
        # model was never monitored/charged.
        try:
            from h2o3_tpu.obs import modelmon as _mm
            _mm.forget(self.key)
        except Exception:   # noqa: BLE001
            pass
        try:
            from h2o3_tpu.obs import usage as _usage
            _usage.forget_model(self.key)
        except Exception:   # noqa: BLE001
            pass

    def _on_replace(self):
        """A retrain overwriting this key frees the old generation's
        serving tiers like a remove — but KEEPS the monitoring series:
        modelmon retains the outgoing generation's live sketch for the
        shadow-compare (rotation happened in install_baseline), and the
        usage ledger keeps attributing to the key across generations."""
        if not self.key:
            return
        try:
            from h2o3_tpu import serving
            serving.CACHE.invalidate_key(self.key)
        except Exception:   # noqa: BLE001 — removal must not fail the DKV op
            pass

    def _score_with_params(self, params, X):
        """_score_matrix with `params` (a `_serving_params()`-shaped
        pytree, possibly of tracers) standing in for the exported
        attributes. The default grafts the params onto a SHALLOW COPY of
        the model and runs the family's own `_score_matrix` — the same
        code path as legacy scoring, so fast-path and legacy predictions
        are bit-identical by construction. The copy keeps concurrent
        legacy scorers (reading concrete attrs off `self`) safe while a
        build thread traces."""
        clone = copy.copy(self)
        for a, v in params.items():
            setattr(clone, a, v)
        return type(self)._score_matrix(clone, X)

    # ---- scoring / metrics ----------------------------------------------
    @property
    def _is_classifier(self) -> bool:
        return self.supervised and self._dinfo.response_domain is not None

    @property
    def nclasses(self) -> int:
        d = self._dinfo.response_domain if self._dinfo else None
        return len(d) if d else 1

    @_predict_root
    def predict(self, test_data: Frame) -> Frame:
        n = test_data.nrows
        # large frame: the columns, made on the device ahead of the wait;
        # bucket path: the scorer cache's host scores as they are
        out = self._score_device(
            test_data, then=lambda out: self._prediction_columns(out, n))
        return self._prediction_frame(out, n)

    def _score_device(self, test_data: Frame, then=None):
        """Score a frame and leave the result where it was computed.
        Serving-sized frames ride the compiled-scorer cache (no recompile
        per row count), which answers with HOST scores: those come back
        as they are. Large frames take the legacy sharded path, whose
        compile cost amortizes over the batch, and come back as the device
        array, computed: `then(out)` — what the caller makes of the scores
        on the device — is enqueued behind the walk inside
        `predict.dispatch`, and `predict.wait` blocks on its result. The
        stages of that path are the children of the `predict` root span;
        the cache's own are scorer.warm_hit / scorer.compile."""
        from h2o3_tpu import serving
        out = serving.score_frame(self, test_data)
        if out is not None:
            root = _SPANS.current()
            if root is not None and root.name == "predict":
                root.attrs["path"] = "bucket"
            return out
        with _span("predict.matrix"):       # adapt + stack + concat dispatch
            X = self._dinfo.matrix(test_data)
        with _span("predict.dispatch"):     # returns at enqueue
            out = self._score_matrix(X)
            if then is not None:
                out = then(out)
        with _span("predict.wait"):
            # the host blocked on the device: the caller gets results,
            # not promises (predict() returns a finished frame)
            # h2o3-ok: R002 the span IS the wait: it splits device time from the host work around it
            jax.block_until_ready(out)
        return out

    def _score_host(self, test_data: Frame) -> np.ndarray:
        """_score_device for the callers that compute on host scores: the
        result fetched in ONE device→host transfer."""
        from h2o3_tpu.parallel import mrtask as _mrt
        out = self._score_device(test_data)
        if isinstance(out, np.ndarray):
            return out
        with _span("predict.fetch") as sp:
            # h2o3-ok: R002,R015 the span IS the device→host fetch
            out = _mrt.host_fetch(out)
            sp.attrs["bytes"] = int(out.nbytes)
        return out

    def _prediction_columns(self, out, n: int) -> list:
        """Prediction column assembly — the ONE place that maps raw
        scores to (name, values, domain-or-None) columns. Shared by
        predict(), _prediction_frame and the REST row-payload route, so
        the serving answers can never diverge. The columns are made where
        the scores already live: a device array stays on the device
        (`DevicePlanes` out of one program, nothing of frame size crosses
        the host link); host scores become float64 host columns, every
        p<level> sliced out of the ONE fetched copy."""
        dom = self._dinfo.response_domain if self._is_classifier else None
        if isinstance(out, jax.Array):
            return self._device_columns(out, n, dom)
        if dom is not None:
            probs = np.asarray(out, np.float64)[:n]
            pred = probs.argmax(axis=1).astype(np.float64)
            cols = [("predict", pred, dom)]
            cols += [(f"p{lvl}", probs[:, k], None)
                     for k, lvl in enumerate(dom)]
            return cols
        return [("predict", np.asarray(out, np.float64)[:n], None)]

    @staticmethod
    def _device_columns(out: jax.Array, n: int, dom) -> list:
        """The host columns' values as device planes, out of ONE program
        (`_prediction_planes`). The planes come out row-sharded like every
        Vec's; over a row-sharded `out` that takes no collective."""
        from h2o3_tpu.parallel import mrtask as _mrt
        c = _mesh.cloud()
        label = None if dom is None else "i8" if len(dom) <= 127 else "f32"
        # cached_jit: the program's closure is (n, label), so every frame
        # of one size replays one resident program
        got = _mrt.cached_jit(_prediction_planes(n, label),
                              out_shardings=c.rows_sharding(1))(out)
        f32 = Codec("f32")
        if label is None:
            return [("predict", DevicePlanes(*got[0], f32, n), None)]
        return [("predict", DevicePlanes(*got[0], Codec(label), n), dom)] \
            + [(f"p{lvl}", DevicePlanes(*got[1 + k], f32, n), None)
               for k, lvl in enumerate(dom)]

    def _prediction_frame(self, out, n: int) -> Frame:
        """Build the predictions Frame from raw scores — or from their
        _prediction_columns, where the caller made those already (a list:
        predict() enqueues them ahead of its wait). Each Vec is made from
        what its column is: device planes are adopted as they are (host
        bookkeeping only), a host column is packed and put."""
        names, vecs = [], []
        with _span("predict.frame") as sp:
            cols = out if isinstance(out, list) \
                else self._prediction_columns(out, n)
            for name, vals, dom in cols:
                vtype = T_CAT if dom is not None else T_NUM
                if isinstance(vals, DevicePlanes):
                    vecs.append(Vec.from_device_planes(vals, vtype, dom))
                elif dom is not None:
                    vecs.append(Vec._from_floats(
                        vals, np.zeros(n, bool), T_CAT,
                        np.asarray(dom, object)))
                else:
                    vecs.append(Vec.from_numpy(vals))
                names.append(name)
            where = "device" if all(isinstance(v, DevicePlanes)
                                    for _, v, _ in cols) else "host"
            sp.attrs["cols"] = len(names)
            sp.attrs["columns"] = where
            _FRAME_COLUMNS.inc(algo=self.algo, columns=where)
            return Frame(names, vecs)

    def model_performance(self, test_data: Optional[Frame] = None):
        if test_data is None:
            return self._output.training_metrics
        return self._compute_metrics(test_data)

    def _compute_metrics(self, frame: Frame):
        from h2o3_tpu import serving
        di = self._dinfo
        fast = serving.score_frame_with_response(self, frame)
        if fast is not None:
            # bucketed fast path: host (bucket,)-shaped y/w with w=0 on
            # padding AND missing-response rows — padded rows can never
            # poison the aggregates
            out, y, w = fast
        else:
            X = di.matrix(frame)
            y = di.response(frame)
            w = di.weights(frame)
            w = jnp.where(jnp.isnan(y), 0.0, w)
            out = self._score_matrix(X)
        m = self._metrics_from_preds(y, out, w)
        cmf = self.params.get("custom_metric_func")
        if cmf and m is not None:
            # water/udf CMetricFunc 3-phase contract, traced in one program
            from h2o3_tpu.udf import resolve_udf
            udf = resolve_udf(cmf)
            # rows with w=0 (padding / missing response) must not poison the
            # aggregate: neutralize y there (0·NaN would propagate)
            ysafe = jnp.where(w > 0, jnp.nan_to_num(y), 0.0)
            agg = _fold_custom_metric(udf, udf.map(jnp.nan_to_num(out),
                                                   ysafe, w))
            m.custom_metric = {"name": udf.name,
                               "value": float(udf.metric(agg))}
        return m

    def _metrics_from_preds(self, y, out, w):
        if not self.supervised:
            return None
        if self._is_classifier and self.nclasses == 2:
            return M.binomial_metrics(y, out[:, 1], w,
                                      domain=self._dinfo.response_domain)
        if self._is_classifier:
            return M.multinomial_metrics(y, out, w,
                                         domain=self._dinfo.response_domain)
        return M.regression_metrics(y, out, w)

    def _score_train_valid(self, frame, valid):
        if not self.supervised:
            return
        self._output.training_metrics = self._compute_metrics(frame)
        if valid is not None:
            self._output.validation_metrics = self._compute_metrics(valid)

    # ---- cross-validation (ModelBuilder.computeCrossValidation :597) -----
    def _run_cross_validation(self, frame: Frame, x, y, job: Job):
        nfolds = int(self.params["nfolds"] or 0)
        fold_col = self.params.get("fold_column")
        n = frame.nrows
        if fold_col:
            fa = frame.vec(fold_col).to_numpy().astype(int)
            folds = sorted(set(fa.tolist()))
        else:
            seed = int(self.params.get("seed") or -1)
            rng = np.random.default_rng(seed if seed > 0 else None)
            if self.params.get("fold_assignment", "AUTO") in ("AUTO", "Random"):
                fa = rng.integers(0, nfolds, size=n)
            elif self.params["fold_assignment"] == "Modulo":
                fa = np.arange(n) % nfolds
            else:  # Stratified — per-class modulo on shuffled order
                yv = frame.vec(y).to_numpy()
                fa = np.zeros(n, int)
                for cls in np.unique(yv[~np.isnan(yv)]):
                    idx = np.where(yv == cls)[0]
                    rng.shuffle(idx)
                    fa[idx] = np.arange(len(idx)) % nfolds
            folds = list(range(nfolds))
        host = frame.to_numpy()
        col_data = {c: host[:, j] for j, c in enumerate(frame.names)}
        cat_doms = {c: frame.vec(c).domain for c in frame.names
                    if frame.vec(c).type == T_CAT}
        holdout_pred = None
        cv_models = []
        for fi, f in enumerate(folds):
            tr_idx = fa != f
            te_idx = ~tr_idx
            tr = _subframe(frame, col_data, cat_doms, tr_idx)
            te = _subframe(frame, col_data, cat_doms, te_idx)
            mb = self.__class__(**{k: v for k, v in self.params.items()
                                   if k not in ("nfolds", "model_id",
                                                "fold_column")})
            mb.params["nfolds"] = 0
            # the budget is shared by ALL folds + the final build — give
            # each fold what remains of the parent deadline, not a fresh
            # full allowance (ModelBuilder CV time allocation)
            if job.deadline is not None:
                mb.params["max_runtime_secs"] = max(
                    1.0, job.deadline - time.time())
            mb.train(x=x, y=y, training_frame=tr)
            cv_models.append(mb)
            pf = mb.predict(te)
            if holdout_pred is None:
                ncols_p = pf.ncols
                holdout_pred = np.full((n, ncols_p), np.nan)
            holdout_pred[te_idx] = pf.to_numpy()
            for k in (tr.key, te.key, pf.key):
                DKV.remove(k)
            job.update(0.5 * (fi + 1) / len(folds), f"CV fold {fi+1}")
        # CV metrics on the combined holdout predictions
        yv = self._dinfo.response(frame)
        w = self._dinfo.weights(frame)
        pad = frame.padded_len
        if self._is_classifier:
            probs = np.zeros((pad, self.nclasses), np.float32)
            probs[:n] = holdout_pred[:, 1:]
            out = jnp.asarray(probs)
        else:
            pr = np.zeros(pad, np.float32)
            pr[:n] = holdout_pred[:, 0]
            out = jnp.asarray(pr)
        self._output.cross_validation_metrics = self._metrics_from_preds(yv, out, w)
        self._cv_models = cv_models
        if self.params.get("keep_cross_validation_predictions"):
            cvp = Frame.from_numpy(holdout_pred[:, 1:] if self._is_classifier
                                   else holdout_pred)
            self._output.cv_predictions_key = cvp.key
        if self.params.get("keep_cross_validation_fold_assignment"):
            cvf = Frame.from_numpy(fa.astype(np.float64))
            self._output.cv_fold_assignment_key = cvf.key

    # ---- introspection ---------------------------------------------------
    def auc(self, valid=False):
        m = (self._output.validation_metrics if valid
             else self._output.training_metrics)
        return getattr(m, "auc", None)

    def logloss(self, valid=False):
        m = (self._output.validation_metrics if valid
             else self._output.training_metrics)
        return getattr(m, "logloss", None)

    def mse(self, valid=False):
        m = (self._output.validation_metrics if valid
             else self._output.training_metrics)
        return getattr(m, "mse", None)

    def rmse(self, valid=False):
        m = (self._output.validation_metrics if valid
             else self._output.training_metrics)
        return getattr(m, "rmse", None)

    @property
    def model_id(self):
        return self.key

    def summary(self):
        return self._output.model_summary if self._output else {}

    def scoring_history(self):
        return self._output.scoring_history if self._output else []

    def varimp(self, use_pandas=False):
        vi = self._output.variable_importances if self._output else None
        if vi and use_pandas:
            import pandas as pd
            return pd.DataFrame(vi)
        return vi

    # ---- explanation surface (h2o-py explain module) ---------------------
    def partial_plot(self, frame, cols=None, nbins: int = 20, plot=False):
        """h2o model.partial_plot: PDP tables for the given columns."""
        from h2o3_tpu import explain_data as EX
        cols = cols or [r["variable"] for r in (self.varimp() or [])[:2]] \
            or self._dinfo.predictors[:2]
        return [EX.partial_dependence(self, frame, c, nbins=nbins)
                for c in cols]

    def permutation_importance(self, frame, metric="AUTO", n_repeats=1,
                               seed=42):
        """h2o model.permutation_importance (PermutationVarImp.java)."""
        from h2o3_tpu import explain_data as EX
        return EX.permutation_varimp(self, frame, metric=metric,
                                     n_repeats=n_repeats, seed=seed)

    def ice_plot(self, frame, column, nbins: int = 20):
        """ICE figure (h2o-py model.ice_plot renders matplotlib)."""
        from h2o3_tpu import explain_plots as EP
        return EP.ice_plot(self, frame, column, nbins=nbins)

    def pd_plot(self, frame, column, nbins: int = 20):
        from h2o3_tpu import explain_plots as EP
        return EP.pd_plot(self, frame, column, nbins=nbins)

    def varimp_plot(self, num_of_features: int = 10):
        from h2o3_tpu import explain_plots as EP
        return EP.varimp_plot(self, num_of_features=num_of_features)

    def shap_summary_plot(self, frame, top_n: int = 20):
        from h2o3_tpu import explain_plots as EP
        return EP.shap_summary_plot(self, frame, top_n=top_n)

    def shap_explain_row_plot(self, frame, row_index: int, top_n: int = 10):
        from h2o3_tpu import explain_plots as EP
        return EP.shap_explain_row_plot(self, frame, row_index,
                                        top_n=top_n)

    def learning_curve_plot(self):
        from h2o3_tpu import explain_plots as EP
        return EP.learning_curve_plot(self)

    def explain(self, frame, columns: int = 3):
        from h2o3_tpu import explain_plots as EP
        return EP.explain(self, frame, columns=columns)

    # ---- export (h2o-genmodel surface) -----------------------------------
    def download_mojo(self, path: str, format: str = "native") -> str:
        """format="native": this framework's npz-zip artifact.
        format="h2o3": genuine reference-layout MOJO zip (tree models) that
        the stock h2o-genmodel JAR scores unmodified
        (hex/tree/SharedTreeMojoWriter.java layout)."""
        if format == "h2o3":
            from h2o3_tpu.genmodel.h2o_mojo import export_h2o_mojo
            return export_h2o_mojo(self, path)
        from h2o3_tpu.genmodel.mojo import export_mojo
        return export_mojo(self, path)

    save_mojo = download_mojo

    def download_pojo(self, path: str) -> str:
        """Generate a dependency-free Java scoring class
        (water/util/JCodeGen.java analog)."""
        from h2o3_tpu.genmodel.pojo import export_pojo
        return export_pojo(self, path)

    def save_model_details(self, path: str) -> str:
        import json
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, default=str)
        return path

    def to_dict(self):
        o = self._output
        d = {
            "model_id": self.key, "algo": self.algo,
            "params": {k: v for k, v in self.params.items() if v is not None},
            "training_metrics": o.training_metrics.to_dict() if o and o.training_metrics else None,
            "validation_metrics": o.validation_metrics.to_dict() if o and o.validation_metrics else None,
            "model_summary": o.model_summary if o else {},
        }
        # ModelOutputSchemaV3 extras the clients read off the model JSON:
        # varimp table, GLM coefficients, KMeans centers
        if o and o.variable_importances:
            d["variable_importances"] = o.variable_importances
        if o and o.scoring_history:
            d["scoring_history"] = o.scoring_history
        out = {}
        if getattr(self, "_coefficients", None):
            out["coefficients_table"] = self._coefficients
            out["coefficients_std"] = getattr(self, "_coefficients_std",
                                              None)
        if getattr(self, "_centroids", None) is not None:
            out["centers"] = np.asarray(self._centroids,
                                        np.float64).tolist()
        if out:
            d["output"] = out
        return d


def _subframe(frame: Frame, col_data, cat_doms, idx: np.ndarray) -> Frame:
    """Row-subset a frame on the host (CV fold splitting)."""
    names, vecs = [], []
    for c in frame.names:
        v = frame.vec(c)
        if v.type == "str":
            vecs.append(Vec.from_numpy(v.host_data[idx], type="str"))
        else:
            col = col_data[c][idx]
            mask = np.isnan(col)
            vecs.append(Vec._from_floats(np.where(mask, 0.0, col), mask,
                                         v.type, cat_doms.get(c)))
        names.append(c)
    return Frame(names, vecs)
