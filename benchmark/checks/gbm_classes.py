"""checks/gbm_classes.py — the comparison that decides `correct` for a
multinomial GBM (K classes, numeric and categorical SET splits), against
reference/gbm_classes_plain.py.

Frame scoring (`compare("scores")`): a sample, drawn from the seed, of the
rows of every prediction frame the window left, against the reference
scorer on the trees set-up's train() produced — ALL K probability columns:

  score_gap         worst |p - p_ref| over the K columns of the sampled
                    rows of every kept frame
  score_bad         rows missing, not finite, with probabilities that do
                    not sum to 1 within 1e-6, or whose label is not a
                    largest of the row's own probabilities (limit 0)
  classes_unscored  classes of the domain with no probability column in a
                    kept frame, or with no tree in the model (limit 0)
  cat_levels_lost   as checks/gbm_sets.py has it (limit 0)

The limits live in the configuration's file (`check.limits`), and beside
them the raw table's column names (`check.names`): train() drops a constant
column by H2O-3's default, and the reference finds the model's columns in
the table by name.
"""

from __future__ import annotations

import numpy as np

from benchmark.checks.gbm_sets import levels_lost
from benchmark.reference import gbm_classes_plain as ref


def _program_sums_classes():
    """A program whose trees carry no class scores a K-class model as K
    ensembles and publishes none the harness can hold (`m._trees`): it
    cannot run this configuration, and says so at once (run.py imports
    this module before it makes any data)."""
    from h2o3_tpu.models.tree import engine
    if "tree_class" not in getattr(engine.TreeArrays,
                                   "__dataclass_fields__", {}):
        raise ImportError(
            "checks/gbm_classes.py: this program's trees carry no class "
            "(TreeArrays.tree_class): a multinomial model is K ensembles "
            "there, not the one the harness holds")


_program_sums_classes()


def read_model(m) -> dict:
    """What the program ANSWERED, as plain arrays for the reference: the
    ONE ensemble, each tree's class, the K initial margins (and, as
    checks/gbm_sets.py reads them, the columns' levels and bins)."""
    tr, di = m._trees, m._dinfo
    levels = np.array([di.cardinalities.get(c, 0) if c in di.cat_cols else 0
                       for c in di.predictors], np.int64)
    col = np.asarray(tr.col)
    sets = np.zeros(col.shape + (1,), np.uint32) if tr.catbits is None \
        else np.asarray(tr.catbits)
    summary = dict(m._output.model_summary or {})
    grouped = summary.get("categorical_levels_grouped") or {}
    bins = np.array([grouped[c]["bins"] if c in grouped else
                     min(int(k), int(summary.get("nbins_effective", k)))
                     for c, k in zip(di.predictors, levels)], np.int64)
    return {"col": col, "thr": np.asarray(tr.thr),
            "na_left": np.asarray(tr.na_left), "value": np.asarray(tr.value),
            "sets": sets, "is_cat": levels > 0, "levels": levels,
            "bins": bins, "depth": int(tr.depth),
            "tree_class": np.asarray(tr.tree_class, np.int64),
            "f0": np.asarray(m._f0, np.float64),
            "predictors": list(di.predictors),
            "learn_rate": float(m.params["learn_rate"]),
            "domain": list(di.response_domain)}


def compare(what: str, *, X, y, params, model, produced, opts) -> dict:
    """The readings of one thing a mix `compares`. X, y: the host arrays
    the frames were made from; produced: what the driver's finish() kept;
    opts: the configuration's `check` block."""
    if what == "scores":
        # the model's columns are the table's less what train() dropped (a
        # constant column, by H2O-3's default `ignore_const_cols`): the
        # reference walks the raw table's columns of the same names
        names = list(opts["names"])
        at = np.array([names.index(c) for c in model["predictors"]])
        return dict(check_scores(produced, X, model, columns=at),
                    cat_levels_lost=levels_lost(model))
    raise ValueError(f"checks/gbm_classes.py compares no {what!r}")


def check_scores(scores, X, model, columns=None, **control) -> dict:
    """scores: [(row ids into X, P (rows, K) in the domain's order, label
    codes)] — the sampled rows of each prediction frame, as read back
    after the window; a frame that could not be read is (ids, None, None),
    a column that could not a column of NaN. `columns`: the table's columns
    the model's are, in the model's order (None: all of them); `control`:
    the reference's own options, for tools/controls_classes.py."""
    columns = slice(None) if columns is None else columns
    K = len(model["domain"])
    has_tree = np.bincount(model["tree_class"], minlength=K)[:K] > 0
    has_column = np.ones(K, bool)
    bad, gap, rows = 0, 0.0, 0
    for ids, P, lab in scores:
        rows += len(ids)
        if P is None or P.shape != (len(ids), K):
            bad += len(ids)
            has_column[:] = False
            continue
        has_column &= ~np.isnan(P).all(axis=0)
        want = ref.predict_proba(X[ids][:, columns], model, **control)
        ok = np.isfinite(P).all(axis=1) & (np.abs(P.sum(axis=1) - 1.0) < 1e-6)
        # the label is a largest probability (a tie may go either way)
        at = np.clip(np.nan_to_num(lab).astype(np.int64), 0, K - 1)
        ok &= (at == lab) & (P[np.arange(len(ids)), at] == P.max(axis=1))
        bad += int((~ok).sum())
        gap = max(gap, float(np.abs(P - want)[ok].max()) if ok.any() else 1.0)
    if not rows:
        bad = 1                      # a window that left nothing to compare
    return {"score_gap": gap, "score_bad": bad, "score_rows": rows,
            "classes_unscored": int((~(has_tree & has_column)).sum())}
