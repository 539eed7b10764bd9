"""SharedTree driver — hex/tree/SharedTree.java + gbm/GBM.java + drf/DRF.java.

Reference: SharedTree.java:208 (Driver), :440 (scoreAndBuildTrees), :507
(buildLayer — K concurrent MRTasks, one per tree/class), GBM.java:452
(buildNextKTrees), :981 (ComputePredAndRes), :1235 (GammaPass leaf refit),
:776 (fitBestConstants), DRF.java (mtries column sampling, 0.632 sampling).

TPU-native design: the driver is a controller loop dispatching async device
programs; each tree is max_depth fused level-programs + one residual pass +
one GammaPass — nothing synchronizes to the host except periodic scoring
(score_tree_interval), so the chips never idle on controller round-trips.
The K trees of a multinomial iteration run sequentially (one tree's
histograms already saturate the chips; H2O's tree-level concurrency bought
idle-CPU utilization, not algorithmic speedup). Training-frame predictions
are maintained incrementally: each grown tree's per-row terminal node comes
back from the router (val[heap]), so F-updates are gathers, not tree walks.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.core.frame import Frame
from h2o3_tpu.models.model import (Margins, ModelBase, link_span,
                                   probabilities)
from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.obs import metrics as _om
from h2o3_tpu.obs.timeline import span as _span
from h2o3_tpu.ops import walk_pallas as WP


SET_NODES = _om.counter(
    "h2o3_tree_set_split_nodes_total",
    "categorical SET-split nodes of the tree models published, by algo")


class SharedTreeEstimator(ModelBase):
    """Common driver for GBM / DRF (and the histogram machinery IF shares)."""

    # mesh-sharded serving: ensembles (TreeArrays pytrees — `_trees`: GBM's
    # ONE ensemble, a K-class one too (`tree_class`); `_trees_k` per class
    # where a family keeps its classes apart: DRF, the XGBoost-style
    # booster) enter the scorer as shared device args. The per-node
    # arrays shard their TREE axis over the optional "model" mesh axis
    # (each model shard walks its tree slice; XLA inserts the cross-shard
    # sum); on the default rows-only mesh that spec degenerates to one
    # replicated copy. `_f0` stays a baked constant of the scorer.
    _serving_param_attrs = ("_trees", "_trees_k")
    _partition_rules = (
        (r"^_trees", jax.sharding.PartitionSpec("model")),
    )

    _tree_defaults = {
        "ntrees": 50, "max_depth": 5, "min_rows": 10.0, "nbins": 20,
        "nbins_cats": 1024, "learn_rate": 0.1, "sample_rate": 1.0,
        "col_sample_rate": 1.0, "col_sample_rate_per_tree": 1.0,
        "min_split_improvement": 1e-5, "mtries": -2,
        "score_tree_interval": 5, "stopping_rounds": 0,
        "stopping_metric": "AUTO", "stopping_tolerance": 1e-3,
        "build_tree_one_node": False, "histogram_type": "AUTO",
        "calibrate_model": False, "balance_classes": False,
        "monotone_constraints": None,
        # nbins_top_level (DHistogram nbins halving): the binned engine
        # uses GLOBAL quantile codes, so an explicit top-level resolution
        # maps to the global bin count: b_val = max(nbins, value/4) capped
        # at 255 (a root histogram at 1024 bins halved 2 levels ≈ 256).
        # None = derive from nbins alone (the engine's own default).
        "nbins_top_level": None,
    }

    def _cat_mode(self):
        return "label"  # trees bin label-encoded categoricals natively

    @property
    def _trees_k(self):
        """The classes' ensembles apart. A family that keeps them so (DRF,
        the XGBoost-style booster) stores the list; of a model whose ONE
        ensemble holds every class's trees (`_trees.tree_class`) this is a
        HOST view derived on demand for the readers that want a class at a
        time (genmodel/, the tree route) — never a second device copy."""
        own = self.__dict__.get("_trees_k")
        trees = self.__dict__.get("_trees")
        if own is None and getattr(trees, "tree_class", None) is not None:
            return E.class_ensembles(trees)
        return own

    @_trees_k.setter
    def _trees_k(self, value):
        self.__dict__["_trees_k"] = value

    def _serving_params(self):
        # the ensembles the instance itself holds: the derived view above
        # is no parameter
        p = {a: self.__dict__.get(a) for a in self._serving_param_attrs}
        return {a: v for a, v in p.items() if v is not None} or None

    def _note_published(self):
        """Count the published ensembles' categorical SET-split nodes
        (h2o3_tree_set_split_nodes_total{algo}) and note what the `predict`
        root span says of the walk: `cat_levels`, the levels the dense walk
        matches (engine._cat_layout), `set_rows`, the depth of its SET
        product (walk_pallas.set_rows: eight levels a byte row, filled up
        to whole MXU tiles), and `set_nodes`."""
        trees = [t for t in [self.__dict__.get("_trees")]
                 + list(self.__dict__.get("_trees_k") or []) if t is not None]
        sets = rows = depth = 0
        for ta in trees:
            cats = E._cat_layout(ta, len(self._dinfo.predictors))
            if cats:
                rows, depth = sum(k for _, k in cats), WP.set_rows(cats)
                col = np.asarray(ta.col)
                flags = np.asarray(ta.col_is_cat, bool)
                sets += int(((col >= 0) & flags[np.clip(
                    col, 0, flags.size - 1)]).sum())
        if sets:
            SET_NODES.inc(sets, algo=self.algo)
        self._predict_attrs = {"cat_levels": rows, "set_rows": depth,
                               "set_nodes": sets}

    def _validate_early_stopping(self):
        """Fail fast on an unusable stopping_metric (H2O validates at
        build-parameter time, not 2*stopping_rounds scoring events in)."""
        if int(self.params.get("stopping_rounds") or 0) <= 0:
            return
        want = str(self.params.get("stopping_metric") or "AUTO").lower()
        want = {"aucpr": "pr_auc"}.get(want, want)
        if want in ("auto", ""):
            return
        known = {"auc", "pr_auc", "logloss", "rmse", "mae", "r2",
                 "classification_error"}
        cls_only = {"auc", "pr_auc", "logloss", "classification_error"}
        reg_only = {"mae", "r2"}
        if want not in known:
            raise ValueError(f"unknown stopping_metric {want!r}; "
                             f"supported: {sorted(known)}")
        if self._is_classifier and want in reg_only:
            raise ValueError(f"stopping_metric={want!r} is a regression "
                             "metric but the response is categorical")
        if not self._is_classifier and want in cls_only:
            raise ValueError(f"stopping_metric={want!r} is a "
                             "classification metric but the response is "
                             "numeric")

    # ---- shared plumbing -------------------------------------------------
    def _prep(self, frame: Frame):
        self._validate_early_stopping()
        di = self._dinfo
        X = di.matrix(frame)           # (pad, C) f32 NaN-NA (label cats)
        y = di.response(frame)
        w = di.weights(frame)
        w = jnp.where(jnp.isnan(y), 0.0, w)
        yz = jnp.where(jnp.isnan(y), 0.0, y)
        # balance_classes (hex/ModelBuilder class-balancing): reweight so
        # every class carries equal total weight — the weight-based
        # equivalent of the reference's minority over-sampling, with no
        # row duplication on device
        if self.params.get("balance_classes") and self._is_classifier:
            K = self.nclasses
            yi = yz.astype(jnp.int32)
            totals = jax.ops.segment_sum(w, yi, num_segments=K)
            wsum = totals.sum()
            factor = jnp.where(totals > 0, wsum / (K * totals), 1.0)
            w = w * factor[yi]
        return X, yz, w

    def _grower(self):
        p = self.params
        return E.TreeGrower(nbins=int(p["nbins"]),
                            max_depth=int(p["max_depth"]),
                            min_rows=float(p["min_rows"]),
                            min_split_improvement=float(p["min_split_improvement"]))

    def _sample_weights(self, w, key, rate):
        """Per-tree row sampling — on device (no host RNG round-trip)."""
        if rate >= 1.0:
            return w
        u = jax.random.uniform(key, w.shape)
        return w * (u < rate)

    def _col_mask(self, C, key):
        rate = float(self.params.get("col_sample_rate_per_tree") or 1.0)
        if rate >= 1.0:
            return None
        k = max(1, int(round(rate * C)))
        r = jax.random.uniform(key, (C,))
        kth = jnp.sort(r)[k - 1]
        return r <= kth

    def _per_level_mtries(self, C) -> int:
        """col_sample_rate (GBM) / colsample_bylevel (XGBoost) → per-level
        column subsampling, realized as the engine's per-(level,leaf) mtries
        draw. 0 = disabled."""
        rate = float(self.params.get("col_sample_rate") or 1.0)
        if rate >= 1.0:
            return 0
        return max(1, int(round(rate * C)))

    # ---- binned-engine shared setup (GBM + DRF + IF share the histogram
    # machinery, SharedTree.java:507 buildLayer) --------------------------
    def _binned_setup(self, frame: Frame, job=None):
        """Quantize the frame ONCE, form the mesh wiring and the grower.
        Returns a context dict used by the per-algo binned drivers. `job`:
        where a categorical column past a code byte books its binning
        (phase `setup.cats`, inside the caller's `setup`)."""
        from h2o3_tpu.models.tree import binned as BN
        from h2o3_tpu.parallel import mesh as MESH
        p = self.params
        di = self._dinfo
        X, y, w = self._prep(frame)
        n = int(frame.nrows)
        X, y, w = X[:n], y[:n], w[:n]
        C = X.shape[1]
        is_cat = np.array([c in di.cat_cols for c in di.predictors], bool)
        cards = [di.cardinalities[c] for c in di.cat_cols]
        nbins = int(p["nbins"])
        nbins_cats = int(p.get("nbins_cats") or 1024)
        nbins_top = int(p.get("nbins_top_level") or 0)
        b_val = max(nbins, nbins_top // 4,
                    min(nbins_cats, max(cards, default=0)))
        b_val = int(min(255, max(b_val, 4)))
        # bin edges come from a row sample: STRIDED device slice (a head
        # slice would bias quantiles on ordered data), tiny readback
        stride = max(1, n >> 18)
        from h2o3_tpu.parallel import mrtask as _mr
        Xs = _mr.host_fetch(X[::stride][: 1 << 18])
        levels = np.array([di.cardinalities[c] if c in di.cat_cols else 0
                           for c in di.predictors], np.int64)
        with _span("gbm.bin.spec", rows=int(Xs.shape[0]), bins=b_val):
            # every level of a categorical column its own bin up to
            # nbins_cats (DHistogram); a column past a code byte takes
            # several byte planes, and the numeric columns keep b_val
            spec = BN.make_bins(Xs, is_cat, b_val, cat_levels=levels,
                                nbins_cats=nbins_cats)
        grouped = {} if spec.planes is None else {
            di.predictors[c]: {"levels": k, "bins": b}
            for c, (k, b) in spec.planes.grouped.items()}
        if grouped:
            from h2o3_tpu.utils import log as _log
            _log.warn(f"{self.algo}: more levels than nbins_cats="
                      f"{nbins_cats}, consecutive levels share a bin: "
                      f"{grouped}")

        cl = MESH.cloud()
        shards = cl.n_rows_shards
        multi = shards > 1

        mono = np.zeros(spec.c_pad, np.int32)
        mc = p.get("monotone_constraints") or {}
        for cname, v in mc.items():
            if cname in di.predictors:
                mono[di.predictors.index(cname)] = int(np.sign(v))
        grower = BN.BinnedGrower(
            spec, max_depth=int(p["max_depth"]),
            min_rows=float(p["min_rows"]),
            min_split_improvement=float(p["min_split_improvement"]),
            monotone=mono if mc else None,
            axis_name=MESH.ROWS if multi else None)
        n_pad = grower.layout(n, shards=shards if multi else 1)
        # uint8 code plane (1 byte/code in HBM), packed to the Pallas
        # kernels' i32 word layout on TPU — the row axis is untouched so
        # one rows sharding applies to either layout. On a multi-device
        # cloud the plane is BORN row-sharded (each device quantizes its
        # own rows): unconstrained, a partitioner may replicate it, the
        # whole matrix gathered onto every device first (BN._quantize).
        codes_sh = None
        if multi:
            from jax.sharding import PartitionSpec as P
            codes_sh = cl.sharding(P(None, MESH.ROWS))
        # the DISPATCH of the binning programs (a retrace or an executable
        # load shows here); the device's share ends the caller's `setup`
        with _span("gbm.bin.codes", rows=n, cols=C):
            if spec.planes is None:
                codes = BN.prepare_codes(BN.quantize(X, spec, n_pad=n_pad,
                                                     sharding=codes_sh))
            else:
                with (job.phase("setup.cats") if job is not None
                      else contextlib.nullcontext()), \
                        _span("gbm.bin.cats", columns=int(is_cat.sum()),
                              levels=int(max(cards)), bins=spec.b_val,
                              planes=int(spec.planes.cp_pad)):
                    codes = BN.prepare_codes(BN.quantize(
                        X, spec, n_pad=n_pad, sharding=codes_sh))
                    # h2o3-ok: R002 the span ends when the byte planes exist; the caller's `setup` phase waits on the same array next
                    jax.block_until_ready(codes)
            y1 = BN.pad_rows(y, n_pad)
            w1 = BN.pad_rows(w, n_pad)
            if multi:
                codes = jax.device_put(codes, codes_sh)
                y1 = jax.device_put(y1, cl.rows_sharding(1))
                w1 = jax.device_put(w1, cl.rows_sharding(1))
        # register the code plane with the DKV tier pager: training
        # re-streams it every level, so it is pinned (never an LRU victim
        # mid-build) but now VISIBLE to the HBM accounting that budget
        # demotions are judged against (h2o3_dkv_tier_bytes) — and at
        # uint8/packed size it is 4x smaller than the old i32 planes.
        # The chunk dies with the training context (weakref reaping).
        codes_chunk = None
        from h2o3_tpu.core.tiering import PAGER
        if PAGER.enabled:
            codes_chunk = PAGER.new_chunk(codes, None, label="tree_codes",
                                          pinned=1)
        return dict(BN=BN, X=X, y=y, w=w, y1=y1, w1=w1, codes=codes, n=n,
                    C=C, is_cat=is_cat, spec=spec, grower=grower,
                    n_pad=n_pad, cl=cl, multi=multi,
                    mesh=cl.mesh if multi else None,
                    codes_chunk=codes_chunk, levels=levels, grouped=grouped)

    def _binned_tree_arrays(self, ctx, chunks, prev=None, lead=None):
        """Assemble E.TreeArrays from trainer chunk outputs (+ an optional
        checkpoint model's arrays prepended). `lead` flattens extra leading
        scan dims (the multinomial (iters, K) case: iteration-major)."""
        spec, C = ctx["spec"], ctx["C"]
        sel = (lambda a: a) if lead is None else lead
        colT = jnp.concatenate([sel(c[0]) for c in chunks])
        binT = jnp.concatenate([sel(c[1]) for c in chunks])
        nalT = jnp.concatenate([sel(c[2]) for c in chunks])
        wordsT = jnp.concatenate([sel(c[3]) for c in chunks])
        valT = jnp.concatenate([sel(c[4]) for c in chunks])
        gainsT = jnp.concatenate([sel(c[5]) for c in chunks]).sum(0)
        coverT = jnp.concatenate([sel(c[6]) for c in chunks])
        edges_j = jnp.asarray(spec.edges)
        safe_col = jnp.clip(colT, 0, C - 1)
        safe_bin = jnp.clip(binT, 0, spec.edges.shape[1] - 1)
        thrT = edges_j[safe_col, safe_bin]
        any_cat = bool(ctx["is_cat"].any())
        if prev is not None:
            colT = jnp.concatenate([prev.col, colT])
            thrT = jnp.concatenate([prev.thr, thrT])
            nalT = jnp.concatenate([prev.na_left, nalT])
            valT = jnp.concatenate([prev.value, valT])
            coverT = jnp.concatenate([prev.cover, coverT])
            if any_cat:
                pw = prev.catbits if prev.catbits is not None else \
                    jnp.zeros((prev.col.shape[0],) + wordsT.shape[1:],
                              wordsT.dtype)
                wordsT = jnp.concatenate([pw, wordsT])
        ta = E.TreeArrays(
            col=colT, thr=thrT, na_left=nalT, value=valT,
            depth=ctx["grower"].D, cover=coverT,
            catbits=wordsT if any_cat else None,
            col_is_cat=(np.pad(ctx["is_cat"],
                               (0, spec.c_pad - C)) if any_cat else None),
            cat_levels=(np.pad(ctx["levels"],
                               (0, spec.c_pad - C)) if any_cat else None))
        return ta, gainsT

    # ---- SHAP contributions (Model.PredictContributions analog) ----------
    def predict_contributions(self, test_data: Frame) -> Frame:
        """Per-row TreeSHAP feature contributions + BiasTerm, in margin
        space; rows sum to the margin prediction (genmodel parity)."""
        from h2o3_tpu.models.tree import contrib
        assert getattr(self, "_trees", None) is not None, \
            "contributions supported for regression/binomial tree models"
        X = np.asarray(self._dinfo.matrix(test_data),
                       np.float64)[: test_data.nrows]
        phi = contrib.ensemble_shap(self._trees, X)
        scale, bias0 = self._contrib_scale_bias()
        phi *= scale
        phi[:, -1] += bias0
        names = list(self._dinfo.feature_names) + ["BiasTerm"]
        from h2o3_tpu.core.frame import Vec
        return Frame(names, [Vec.from_numpy(phi[:, j])
                             for j in range(phi.shape[1])])

    def _contrib_scale_bias(self):
        return 1.0, 0.0

    # ---- scoring history / early stopping -------------------------------
    def _record_history(self, ntrees, F, y, w, dist):
        mu = _link_inv_dist(dist, F, udf=getattr(self, "_udf_dist", None))
        from h2o3_tpu.models import metrics as M
        if self._is_classifier:
            m = M.binomial_metrics(y, mu[:, 1], w)
            h = {"number_of_trees": ntrees, "training_logloss": m.logloss,
                 "training_auc": m.auc, "training_pr_auc": m.pr_auc,
                 "training_rmse": m.rmse}
        else:
            m = M.regression_metrics(y, mu, w)
            h = {"number_of_trees": ntrees, "training_rmse": m.rmse,
                 "training_mae": m.mae, "training_r2": m.r2}
        h.update(self._valid_history_entry(dist))
        self._output.scoring_history.append(h)

    # ---- incremental validation scoring (ScoreKeeper valid series) -------
    def _valid_setup(self, f0):
        """Prepare incremental validation margins: the in-progress model
        scores the validation frame at every scoring event
        (SharedTree.doScoringAndSaveModel), so the margins are maintained
        chunk-by-chunk rather than rebuilt from the final ensemble."""
        vf = getattr(self, "_valid_for_scoring", None)
        self._vstate = None
        if vf is None:
            return
        di = self._dinfo
        nv = int(vf.nrows)
        Xv = di.matrix(vf)[:nv]
        yv = di.response(vf)[:nv]
        wv = di.weights(vf)[:nv]
        wv = jnp.where(jnp.isnan(yv), 0.0, wv)
        yv = jnp.where(jnp.isnan(yv), 0.0, yv)
        Fv = jnp.full(nv, float(np.asarray(f0).ravel()[0]), jnp.float32) \
            if np.ndim(f0) == 0 or np.size(f0) == 1 else \
            jnp.tile(jnp.asarray(f0, jnp.float32)[None, :], (nv, 1))
        self._vstate = {"X": Xv, "y": yv, "w": wv, "F": Fv}

    def _valid_advance(self, new_trees, lr):
        """Add a just-trained tree batch's contribution to the validation
        margins (one batched heap-walk over the valid rows)."""
        if self._vstate is None or new_trees.ntrees == 0:
            return
        self._vstate["F"] = self._vstate["F"] + \
            lr * E.predict_ensemble(self._vstate["X"], new_trees)

    def _valid_history_entry(self, dist="gaussian") -> dict:
        if getattr(self, "_vstate", None) is None:
            return {}
        vs = self._vstate
        mu = _link_inv_dist(dist, vs["F"],
                            udf=getattr(self, "_udf_dist", None))
        if self._is_classifier and mu.ndim == 1:
            mu = jnp.stack([1.0 - mu, mu], axis=1)
        vm = self._metrics_from_preds(vs["y"], mu, vs["w"])
        out = {}
        for k in ("logloss", "auc", "pr_auc", "rmse", "mae", "r2"):
            v = getattr(vm, k, None)
            if v is not None:
                out[f"validation_{k}"] = v
        return out

    def _record_history_multi(self, ntrees, F, y, w):
        from h2o3_tpu.models import metrics as M
        P = jax.nn.softmax(F, axis=1)
        m = M.multinomial_metrics(y, P, w)
        h = {"number_of_trees": ntrees, "training_logloss": m.logloss,
             "training_classification_error": m.error}
        h.update(self._valid_history_entry())
        self._output.scoring_history.append(h)

    def _should_stop(self) -> bool:
        """ScoreKeeper.stopEarly: stop when the chosen stopping_metric has
        not improved over the last `stopping_rounds` scoring events."""
        k = int(self.params.get("stopping_rounds") or 0)
        if k <= 0 or len(self._output.scoring_history) < 2 * k:
            return False
        hist = self._output.scoring_history
        want = str(self.params.get("stopping_metric") or "AUTO").lower()
        want = {"aucpr": "pr_auc"}.get(want, want)
        maximize = want in ("auc", "pr_auc", "r2")
        metric = None
        explicit = want not in ("auto", "")
        if explicit:
            # validation series wins when a validation frame was scored
            for prefix in ("validation_", "training_"):
                if prefix + want in hist[-1]:
                    metric = prefix + want
                    break
            if metric is None:
                for key in hist[-1]:
                    if key.endswith("_" + want):
                        metric = key
                        break
            if metric is None:
                raise ValueError(
                    f"stopping_metric={want!r} is not recorded for this "
                    f"problem type (available: {sorted(hist[-1])})")
        if metric is None:
            maximize = False
            for cand in ("validation_logloss", "validation_rmse",
                         "training_logloss", "training_rmse"):
                if cand in hist[-1]:
                    metric = cand
                    break
        if metric is None:
            return False
        vals = [h[metric] for h in hist]
        # tolerance 0 is a VALID value (stop on any non-improvement):
        # no falsy-or fallback; inclusive comparisons so an exact plateau
        # stops; tol scales with |past| so negative metrics (r2 < 0) keep
        # the intended direction (ScoreKeeper.stopEarly semantics)
        tol_raw = self.params.get("stopping_tolerance")
        tol = 1e-3 if tol_raw is None else float(tol_raw)
        if maximize:
            recent = max(vals[-k:])
            past = max(vals[:-k])
            return recent <= past + tol * abs(past)
        recent = min(vals[-k:])
        past = min(vals[:-k])
        return recent >= past - tol * abs(past)

    def _varimp_from_gains(self, gains: np.ndarray):
        names = self._dinfo.feature_names
        tot = gains.sum() or 1.0
        order = np.argsort(-gains)
        self._output.variable_importances = [
            {"variable": names[i], "relative_importance": float(gains[i]),
             "scaled_importance": float(gains[i] / (gains[order[0]] or 1.0)),
             "percentage": float(gains[i] / tot)}
            for i in order]


# ===========================================================================
class H2OGradientBoostingEstimator(SharedTreeEstimator):
    algo = "gbm"
    _defaults = dict(SharedTreeEstimator._tree_defaults)

    # ---- distributions (ComputePredAndRes + GammaPass per family) --------
    def _resolve_dist(self) -> str:
        d = (self.params.get("distribution") or "AUTO").lower()
        if d != "auto":
            return d
        dom = self._dinfo.response_domain
        if dom is None:
            return "gaussian"
        return "bernoulli" if len(dom) == 2 else "multinomial"

    def _fit(self, frame: Frame, job):
        dist = self._resolve_dist()
        self._dist = dist
        # custom distribution UDF (water/udf CDistributionFunc)
        self._udf_dist = None
        if dist == "custom":
            from h2o3_tpu.udf import resolve_udf
            self._udf_dist = resolve_udf(
                self.params.get("custom_distribution_func"))
        if self._binned_ok(dist):
            return self._fit_binned(frame, job, dist)
        X, y, w = self._prep(frame)
        if dist == "multinomial":
            return self._fit_multinomial(X, y, w, job)
        ntrees = int(self.params["ntrees"])
        lr = float(self.params["learn_rate"])
        seed = int(self.params.get("seed") or -1)
        key = jax.random.PRNGKey(seed if seed > 0 else 42)
        grower = self._grower()
        wsum = float(np.asarray(jnp.sum(w)))
        ysum = float(np.asarray(jnp.sum(w * y)))
        ybar = ysum / max(wsum, 1e-30)
        # init F0 (SharedTree init + DistributionFactory links)
        if dist == "custom":
            f0 = float(self._udf_dist.init_f0(ybar))
        elif dist == "bernoulli":
            p0 = min(max(ybar, 1e-10), 1 - 1e-10)
            f0 = math.log(p0 / (1 - p0))
        elif dist in ("poisson", "gamma", "tweedie"):
            f0 = math.log(max(ybar, 1e-10))
        else:
            f0 = ybar
        self._f0 = f0
        F = jnp.full(X.shape[0], f0, jnp.float32)
        sample_rate = float(self.params["sample_rate"])
        trees = []
        # checkpoint restart (ModelBuilder.java:1401, SharedTree.java:132):
        # resume boosting from a prior model's trees
        ckpt = self.params.get("checkpoint")
        if ckpt:
            from h2o3_tpu.core.kvstore import DKV
            prev = DKV.get(ckpt) if isinstance(ckpt, str) else ckpt
            assert prev is not None and prev.algo == self.algo, \
                f"checkpoint {ckpt} not found or wrong algo"
            pt = prev._trees
            assert pt.depth == grower.D, \
                "checkpoint restart requires identical max_depth"
            if pt.cover is not None:
                pcov = pt.cover
            else:
                # prior model predates cover recording: rebuild covers by
                # routing the current training rows through its trees (an
                # approximation of the original in-sample weights, but keeps
                # TreeSHAP's sum-to-margin property intact)
                heaps, _ = E.predict_leaf_ids(X, pt)
                pcov = [E.node_covers(heaps[i], w, nodes=grower.nodes,
                                      D=grower.D) for i in range(pt.ntrees)]
            for i in range(pt.ntrees):
                trees.append((jnp.asarray(pt.col[i]), jnp.asarray(pt.thr[i]),
                              jnp.asarray(pt.na_left[i]),
                              jnp.asarray(pt.value[i]),
                              jnp.asarray(pcov[i])))
            self._f0 = f0 = prev._f0
            F = f0 + lr * E.predict_ensemble(X, pt)
        gains_tot = jnp.zeros(X.shape[1], jnp.float32)
        interval = max(1, int(self.params.get("score_tree_interval") or 5))
        self._valid_setup(f0)
        if trees:   # checkpoint restart: prior ensemble scores valid too
            self._valid_advance(E.stack_trees(trees, grower.D), lr)
        last_scored = len(trees)
        for t in range(len(trees), ntrees):
            with job.phase("grow"):
                key, k1, k2, k3 = jax.random.split(key, 4)
                res, hess = _grad_hess(dist, F, y, udf=self._udf_dist)
                wt = self._sample_weights(w, k1, sample_rate)
                cmask = self._col_mask(X.shape[1], k2)
                col, thr, nal, val, heap, g = grower.grow(
                    X, wt, res, col_mask=cmask, key=k3,
                    mtries=self._per_level_mtries(X.shape[1]))
                gains_tot = gains_tot + g
                if dist != "gaussian":   # GammaPass Newton refit (device)
                    val = E.gamma_pass(heap, wt, res, hess, val,
                                       nodes=grower.nodes)
                cover = E.node_covers(heap, wt, nodes=grower.nodes,
                                      D=grower.D)
                trees.append((col, thr, nal, val, cover))
                F = F + lr * val[heap]
            if (t + 1) % interval == 0 or t == ntrees - 1:
                with job.phase("score"):
                    if self._vstate is not None and len(trees) > last_scored:
                        self._valid_advance(
                            E.stack_trees(trees[last_scored:], grower.D), lr)
                        last_scored = len(trees)
                    self._record_history(t + 1, F, y, w, dist)
                if self._should_stop():
                    break
            job.update(0.1 + 0.8 * (t + 1) / ntrees, f"tree {t+1}")
        self._trees = E.stack_trees(trees, grower.D)
        self._varimp_from_gains(np.asarray(gains_tot, np.float64))
        self._output.model_summary = {
            "number_of_trees": self._trees.ntrees, "max_depth": grower.D,
            "distribution": dist, "learn_rate": lr, "init_f": f0,
        }

    # ---- binned fast path (GlobalQuantilesCalc / tree_method=hist) -------
    def _binned_ok(self, dist) -> bool:
        """Default engine: globally pre-binned codes + the Pallas histogram
        kernel (SURVEY §2.4 row 1). `histogram_type="UniformAdaptive"`
        selects the H2O-exact per-level adaptive engine instead.
        Multinomial, checkpoint restart and col_sample_rate_per_tree all
        run on the binned path now (VERDICT r2 weak #5)."""
        ht = str(self.params.get("histogram_type") or "AUTO").lower()
        if ht not in ("auto", "quantilesglobal", "binned"):
            return False
        if dist not in ("gaussian", "bernoulli", "quasibinomial", "poisson",
                        "gamma", "tweedie", "laplace", "multinomial"):
            return False
        if int(self.params["max_depth"]) > 10:
            return False      # static 2^D leaf arrays: deep trees adaptive
        ckpt = self.params.get("checkpoint")
        if ckpt:
            prev = self._resolve_checkpoint(ckpt)
            # binned restart needs a binned prior (array-stacked trees)
            if (prev._output.model_summary or {}).get("engine") \
                    != "binned_pallas":
                return False
        return True

    def _resolve_checkpoint(self, ckpt):
        from h2o3_tpu.core.kvstore import DKV
        prev = DKV.get(ckpt) if isinstance(ckpt, str) else ckpt
        assert prev is not None and prev.algo == self.algo, \
            f"checkpoint {ckpt} not found or wrong algo"
        return prev

    def _binned_setup_phase(self, frame: Frame, job):
        """`_binned_setup` as the job's `setup` phase, ended when binning
        HAS RUN: the phase clock used to stop at the asynchronous dispatch
        of `_quantize` and read 1.7 % where binning cost 25 %. One sync a
        train(), where the Σw readback that follows synced anyway."""
        with job.phase("setup"):   # quantile spec + codes + device_put
            ctx = self._binned_setup(frame, job)
            # h2o3-ok: R002 the phase ends when the device has binned, not when the host has enqueued it
            jax.block_until_ready(ctx["codes"])
        return ctx

    @staticmethod
    def _run_chunk(trainer, *args):
        """One chunk of trees: build, dispatch, wait — each under its name
        inside `gbm.chunk`. A new grower is a new jit identity, so every
        train() traces and lowers the K-tree program again and loads its
        executable from the cache (seconds at HIGGS size, the device idle
        meanwhile): `gbm.chunk.build`; lowering and executable are
        memoized on the trainer per argument signature, as its own call
        would. `gbm.chunk.wait` ends the `grow` phase when the trees HAVE
        grown: the history readback of the `score` phase that follows
        blocked on the same margins, and booked the device's growing
        time as scoring."""
        with _span("gbm.chunk.build"):
            program = trainer.lower(*args).compile()
        F, trees = program(*args)
        with _span("gbm.chunk.wait"):
            # h2o3-ok: R002 the span IS the wait for the chunk's program
            jax.block_until_ready(F)
        return F, trees

    def _fit_binned(self, frame: Frame, job, dist):
        if dist == "multinomial":
            return self._fit_binned_multinomial(frame, job)
        p = self.params
        ctx = self._binned_setup_phase(frame, job)
        BN, grower, cl = ctx["BN"], ctx["grower"], ctx["cl"]
        X, y, w, y1, w1 = ctx["X"], ctx["y"], ctx["w"], ctx["y1"], ctx["w1"]
        n, C, n_pad = ctx["n"], ctx["C"], ctx["n_pad"]

        ntrees = int(p["ntrees"])
        lr = float(p["learn_rate"])
        seed = int(p.get("seed") or -1)
        key = jax.random.PRNGKey(seed if seed >= 0 else 42)
        wsum = float(np.asarray(jnp.sum(w)))
        ybar = float(np.asarray(jnp.sum(w * y))) / max(wsum, 1e-30)
        if dist == "bernoulli":
            p0 = min(max(ybar, 1e-10), 1 - 1e-10)
            f0 = math.log(p0 / (1 - p0))
        elif dist in ("poisson", "gamma", "tweedie"):
            f0 = math.log(max(ybar, 1e-10))
        else:
            f0 = ybar

        prev = None
        ckpt = p.get("checkpoint")
        if ckpt:
            # binned restart (SharedTree.java:132): resume margins from the
            # prior ensemble's predictions on the training rows
            prev_model = self._resolve_checkpoint(ckpt)
            prev = prev_model._trees
            assert prev.depth == grower.D, \
                "checkpoint restart requires identical max_depth"
            f0 = prev_model._f0
            Fp = f0 + lr * E.predict_ensemble(X, prev)
            F = BN.pad_rows(Fp.astype(jnp.float32), n_pad)
        else:
            F = jnp.where(jnp.arange(n_pad) < n, f0, 0.0) \
                .astype(jnp.float32)
        self._f0 = f0
        if ctx["multi"]:
            F = jax.device_put(F, cl.rows_sharding(1))

        interval = max(1, int(p.get("score_tree_interval") or 5))
        mtries = self._per_level_mtries(C)
        sample_rate = float(p["sample_rate"])
        col_rate_tree = float(p.get("col_sample_rate_per_tree") or 1.0)
        self._valid_setup(f0)
        if prev is not None:
            # validation margins must include the checkpoint ensemble too
            self._valid_advance(prev, lr)
        chunks = []
        done = prev.ntrees if prev is not None else 0
        if prev is not None and done >= ntrees:
            raise ValueError(
                f"checkpoint model already has {done} trees; ntrees "
                f"({ntrees}) must exceed it to continue training "
                "(ModelBuilder checkpoint validation)")
        while done < ntrees:
            k = min(interval, ntrees - done)
            with job.phase("grow"), \
                    _span("gbm.chunk", trees=k, rows=n, engine="binned"):
                trainer = BN.gbm_chunk_trainer(
                    grower, n, dist=dist, eta=lr, sample_rate=sample_rate,
                    mtries=mtries, k_trees=k, col_rate_tree=col_rate_tree,
                    mesh=ctx["mesh"])
                key, kc = jax.random.split(key)
                # h2o3-ok: R015 its wait IS the rest of the phase: `grow` ends when the trees have grown
                F, trees = self._run_chunk(trainer, ctx["codes"], y1, w1,
                                           F, kc)
            E.ROW_TREES.inc(n * k, engine="binned")
            chunks.append(trees)
            done += k
            with job.phase("score"):
                if self._vstate is not None:
                    ta_chunk, _ = self._binned_tree_arrays(ctx, [trees])
                    self._valid_advance(ta_chunk, lr)
                self._record_history(done, F[:n], y, w, dist)
            job.update(0.1 + 0.8 * done / ntrees, f"tree {done}")
            if self._should_stop() or job.budget_exhausted:
                break

        with job.phase("finish"):
            self._trees, gainsT = self._binned_tree_arrays(ctx, chunks,
                                                           prev=prev)
            self._varimp_from_gains(np.asarray(gainsT[:C], np.float64))
            self._output.model_summary = {
                "number_of_trees": int(self._trees.ntrees),
                "max_depth": grower.D, "distribution": dist,
                "learn_rate": lr, "init_f": f0, "engine": "binned_pallas",
                "nbins_effective": ctx["spec"].b_val,
            }
            if ctx["grouped"]:      # never silently: which levels share bins
                self._output.model_summary["categorical_levels_grouped"] = \
                    ctx["grouped"]

    def _fit_binned_multinomial(self, frame: Frame, job):
        """K class trees per iteration through ONE jitted binned program
        (the SharedTree.java:548-561 K-tree layer)."""
        self._vstate = None   # no multinomial validation series (yet)
        p = self.params
        ctx = self._binned_setup_phase(frame, job)
        BN, grower, cl = ctx["BN"], ctx["grower"], ctx["cl"]
        y, w, y1, w1 = ctx["y"], ctx["w"], ctx["y1"], ctx["w1"]
        n, C, n_pad = ctx["n"], ctx["C"], ctx["n_pad"]
        K = self.nclasses
        ntrees = int(p["ntrees"])
        lr = float(p["learn_rate"])
        seed = int(p.get("seed") or -1)
        key = jax.random.PRNGKey(seed if seed >= 0 else 42)
        from h2o3_tpu.parallel import mrtask as _mr
        wn = _mr.host_fetch(w).astype(np.float64)
        yin = _mr.host_fetch(y.astype(jnp.int32))
        f0 = np.zeros(K, np.float32)
        for c in range(K):
            pc = (wn * (yin == c)).sum() / max(wn.sum(), 1e-30)
            f0[c] = math.log(max(pc, 1e-10))

        prev = None
        ckpt = p.get("checkpoint")
        if ckpt:
            prev_model = self._resolve_checkpoint(ckpt)
            prev = prev_model._trees
            assert prev.depth == grower.D, \
                "checkpoint restart requires identical max_depth"
            f0 = prev_model._f0
            Fc = jnp.asarray(f0)[None, :] \
                + lr * E.predict_ensemble(ctx["X"], prev)
            F = jnp.zeros((n_pad, K), jnp.float32).at[:n].set(Fc)
        else:
            F = jnp.where((jnp.arange(n_pad) < n)[:, None],
                          jnp.asarray(f0)[None, :], 0.0) \
                .astype(jnp.float32)
        self._f0 = f0
        if ctx["multi"]:
            from jax.sharding import PartitionSpec as P
            from h2o3_tpu.parallel import mesh as MESH
            F = jax.device_put(F, cl.sharding(P(MESH.ROWS, None)))

        interval = max(1, int(p.get("score_tree_interval") or 5))
        mtries = self._per_level_mtries(C)
        sample_rate = float(p["sample_rate"])
        col_rate_tree = float(p.get("col_sample_rate_per_tree") or 1.0)
        chunks = []
        done = prev.ntrees // K if prev is not None else 0
        if prev is not None and done >= ntrees:
            raise ValueError(
                f"checkpoint model already has {done} trees per class; "
                f"ntrees ({ntrees}) must exceed it to continue training")
        while done < ntrees:
            k = min(interval, ntrees - done)
            with job.phase("grow"), \
                    _span("gbm.chunk", trees=k * K, rows=n,  # h2o3-ok: R011 same stage as binomial path, engine= attr disambiguates
                          engine="binned_multinomial"):
                trainer = BN.gbm_multi_chunk_trainer(
                    grower, n, n_classes=K, eta=lr, sample_rate=sample_rate,
                    mtries=mtries, k_iters=k, col_rate_tree=col_rate_tree,
                    mesh=ctx["mesh"])
                key, kc = jax.random.split(key)
                # h2o3-ok: R015 its wait IS the rest of the phase: `grow` ends when the trees have grown
                F, trees = self._run_chunk(trainer, ctx["codes"], y1, w1,
                                           F, kc)
            E.ROW_TREES.inc(n * k * K, engine="binned")
            chunks.append(trees)
            done += k
            with job.phase("score"):
                self._record_history_multi(done, F[:n], y, w)
            job.update(0.1 + 0.8 * done / ntrees, f"iter {done}")
            if self._should_stop() or job.budget_exhausted:
                break

        # chunks hold (iters, K, ...) arrays: iteration-major as they come,
        # ONE ensemble with each tree's class beside it
        with job.phase("finish"):
            self._trees, gains = self._binned_tree_arrays(
                ctx, chunks, prev=prev,
                lead=lambda a: a.reshape((-1,) + a.shape[2:]))
            self._trees.tree_class = np.tile(
                np.arange(K, dtype=np.int32), self._trees.ntrees // K)
            self._varimp_from_gains(np.asarray(gains[:C], np.float64))
            self._output.model_summary = {
                "number_of_trees": int(self._trees.ntrees),
                "max_depth": grower.D, "distribution": "multinomial",
                "learn_rate": lr, "engine": "binned_pallas",
                "nbins_effective": ctx["spec"].b_val,
            }
            if ctx["grouped"]:      # never silently: which levels share bins
                self._output.model_summary["categorical_levels_grouped"] = \
                    ctx["grouped"]

    def _fit_multinomial(self, X, y, w, job):
        self._vstate = None   # no multinomial validation series (yet)
        K = self.nclasses
        ntrees = int(self.params["ntrees"])
        lr = float(self.params["learn_rate"])
        seed = int(self.params.get("seed") or -1)
        key = jax.random.PRNGKey(seed if seed > 0 else 42)
        grower = self._grower()
        yi = y.astype(jnp.int32)
        from h2o3_tpu.parallel import mrtask as _mr
        wn = _mr.host_fetch(w).astype(np.float64)
        yin = _mr.host_fetch(yi)
        f0 = np.zeros(K, np.float32)
        for c in range(K):
            pc = (wn * (yin == c)).sum() / max(wn.sum(), 1e-30)
            f0[c] = math.log(max(pc, 1e-10))
        self._f0 = f0
        F = jnp.tile(jnp.asarray(f0)[None, :], (X.shape[0], 1))
        trees = []              # iteration-major: iteration t, class c
        gains_tot = jnp.zeros(X.shape[1], jnp.float32)
        interval = max(1, int(self.params.get("score_tree_interval") or 5))
        onehot = jax.nn.one_hot(yi, K)
        sample_rate = float(self.params["sample_rate"])
        for t in range(ntrees):
            key, k1, k2 = jax.random.split(key, 3)
            P = jax.nn.softmax(F, axis=1)
            R = onehot - P                       # (n, K) residuals
            wt = self._sample_weights(w, k1, sample_rate)
            cmask = self._col_mask(X.shape[1], k2)
            newF = []
            for c in range(K):
                key, kc = jax.random.split(key)
                res = R[:, c]
                col, thr, nal, val, heap, g = grower.grow(
                    X, wt, res, col_mask=cmask, key=kc,
                    mtries=self._per_level_mtries(X.shape[1]))
                gains_tot = gains_tot + g
                absr = jnp.abs(res)
                val = E.gamma_pass(heap, wt, res, absr * (1 - absr), val,
                                   nodes=grower.nodes, scale=(K - 1) / K)
                cover = E.node_covers(heap, wt, nodes=grower.nodes,
                                      D=grower.D)
                trees.append((col, thr, nal, val, cover))
                newF.append(F[:, c] + lr * val[heap])
            F = jnp.stack(newF, axis=1)
            if (t + 1) % interval == 0 or t == ntrees - 1:
                self._record_history_multi(t + 1, F, y, w)
                if self._should_stop():
                    break
            job.update(0.1 + 0.8 * (t + 1) / ntrees, f"iter {t+1}")
        self._trees = E.stack_trees(trees, grower.D)
        self._trees.tree_class = np.tile(np.arange(K, dtype=np.int32),
                                         len(trees) // K)
        self._varimp_from_gains(np.asarray(gains_tot, np.float64))
        self._output.model_summary = {
            "number_of_trees": int(self._trees.ntrees),
            "max_depth": grower.D, "distribution": "multinomial",
        }

    # ---- scoring ---------------------------------------------------------
    def _score_matrix(self, X):
        if self._dist == "multinomial":
            # ONE walk sums every tree into its class's row, one link after
            margins = self._margins(X)
            with link_span():
                return margins.probabilities()
        lr = float(self.params["learn_rate"])
        F = self._f0 + lr * E.predict_ensemble(X, self._trees)
        return _link_inv_dist(self._dist, F,
                              udf=getattr(self, "_udf_dist", None))

    def _margins(self, X):
        """The walk's sums with the link (`model.Margins`)."""
        return Margins(E.predict_ensemble(X, self._trees), self._link_f0(),
                       float(self.params["learn_rate"]),
                       _LINKS.get(self._dist, "identity"))

    def _score_linkable(self, X):
        """The walk's sums with the link: a prediction frame's planes take
        the link into their one program. A user distribution's link is
        user code, and a family that scores its own way (XGBoost's
        `_score_matrix`) has its own: those stay `_score_matrix`'s."""
        own = type(self)._score_matrix is \
            H2OGradientBoostingEstimator._score_matrix
        if not own or getattr(self, "_udf_dist", None) is not None:
            return self._score_matrix(X)
        return self._margins(X)

    def _link_f0(self):
        """The initial margin(s) on the device, placed ONCE a model: a host
        value handed to the jitted link is a transfer of its own ahead of
        every frame (as `engine._walk_tables` has it of the trees). Under a
        trace the placed copy is a constant of that trace and is not kept."""
        hit = self.__dict__.get("_f0_placed")
        if hit is not None and hit[0] is self._f0:
            return hit[1]
        f0 = jnp.asarray(self._f0, jnp.float32)
        if not isinstance(f0, jax.core.Tracer):
            self._f0_placed = (self._f0, f0)
        return f0

    def _contrib_scale_bias(self):
        return float(self.params["learn_rate"]), float(self._f0)



# ---------------------------------------------------------------------------
@jax.jit
def _bernoulli_grad(F, y):
    p = jax.nn.sigmoid(F)
    return y - p, p * (1 - p)


def _grad_hess(dist, F, y, udf=None):
    """ComputePredAndRes (GBM.java:981): per-row pseudo-residual + hessian."""
    if udf is not None:
        return udf.grad_hess(F, y)
    if dist == "gaussian":
        return y - F, jnp.ones_like(F)
    if dist == "bernoulli" or dist == "quasibinomial":
        return _bernoulli_grad(F, y)
    if dist == "poisson":
        mu = jnp.exp(F)
        return y - mu, mu
    if dist == "gamma":
        mu = jnp.exp(F)
        return y / mu - 1.0, y / mu
    if dist == "tweedie":
        mu = jnp.exp(F)
        return y * jnp.power(mu, -0.5) - jnp.power(mu, 0.5), \
            0.5 * (y * jnp.power(mu, -0.5) + jnp.power(mu, 0.5))
    if dist == "laplace":
        return jnp.sign(y - F), jnp.ones_like(F)
    raise NotImplementedError(f"GBM distribution {dist}")


# a distribution's inverse link as `model.Margins` names it (what
# `_link_inv_dist` applies); the others are the identity
_LINKS = {"bernoulli": "logistic", "quasibinomial": "logistic",
          "multinomial": "softmax", "poisson": "exp", "gamma": "exp",
          "tweedie": "exp"}


def _link_inv_dist(dist, F, udf=None):
    if udf is not None:
        return udf.link_inv(F)
    return probabilities(_LINKS.get(dist, "identity"), F)
