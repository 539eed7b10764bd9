"""checks/gbm_sets.py — the comparison that decides `correct` for a
bernoulli GBM over a table with categorical columns, against
reference/gbm_sets_plain.py (numeric and SET splits walked from the raw
host table: numeric values and level ids).

Frame scoring (`compare("scores")`), as checks/gbm.py has it: a sample,
drawn from the seed, of the rows of every prediction frame the window
left, against the reference scorer on the trees set-up's train()
produced: `score_gap` = worst |p - p_ref|, `score_bad` = rows missing,
mislabelled, not finite or with probabilities that do not sum to 1
(limit 0). And of the trained model itself:

  cat_levels_lost   levels of any categorical column that share a bin
                    with another level in the trained model (limit 0):
                    a column's levels less the bins the model says it
                    gave it (`model_summary`: `nbins_effective`, and
                    `categorical_levels_grouped` where it grouped), and
                    less the bits a node's set holds. A program that caps
                    a column's codes at a byte reads levels - 255 here.

The limits live in the configuration's file (`check.limits`).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import gbm_sets_plain as ref


def _program_keeps_levels():
    """A program whose trees carry no per-column levels caps a categorical
    column's codes at a byte: it cannot run this configuration, and says
    so at once (run.py imports this module before it makes any data)."""
    from h2o3_tpu.models.tree import engine
    if "cat_levels" not in getattr(engine.TreeArrays,
                                   "__dataclass_fields__", {}):
        raise ImportError(
            "checks/gbm_sets.py: this program's trees carry no per-column "
            "levels (TreeArrays.cat_levels): it caps a categorical "
            "column's codes at a byte and cannot run a configuration that "
            "gives every level its own bin")


_program_keeps_levels()


def read_model(m) -> dict:
    """What the program ANSWERED, as plain arrays for the reference."""
    tr, di = m._trees, m._dinfo
    C = len(di.predictors)
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
            "sets": sets, "is_cat": levels[:C] > 0, "levels": levels,
            "bins": bins, "depth": int(tr.depth), "f0": float(m._f0),
            "learn_rate": float(m.params["learn_rate"]),
            "domain": list(di.response_domain)}


def compare(what: str, *, X, y, params, model, produced, opts) -> dict:
    """The readings of one thing a mix `compares`. X, y: the host arrays
    the frames were made from; produced: what the driver's finish() kept;
    opts: the configuration's `check` block."""
    if what == "scores":
        return dict(check_scores(produced, X, model),
                    cat_levels_lost=levels_lost(model))
    raise ValueError(f"checks/gbm_sets.py compares no {what!r}")


def levels_lost(model) -> int:
    """Levels that share a bin, over the categorical columns."""
    lv, bits = model["levels"], 32 * model["sets"].shape[-1]
    kept = np.minimum(model["bins"], bits)
    return int(np.maximum(lv - kept, 0)[model["is_cat"]].sum())


def set_nodes(model) -> tuple:
    """(SET-split nodes, split nodes) of the model."""
    col = model["col"]
    split = col >= 0
    return int((split & model["is_cat"][np.maximum(col, 0)]).sum()), \
        int(split.sum())


def check_scores(scores, X, model) -> dict:
    """scores: [(row ids into X, p0, p1, label codes)] — the sampled rows
    of each prediction frame, as read back after the window; a frame that
    could not be read is (ids, None, None, None)."""
    bad, gap, rows = 0, 0.0, 0
    for ids, p0, p1, lab in scores:
        rows += len(ids)
        if p1 is None or len(p1) != len(ids):
            bad += len(ids)
            continue
        want = ref.predict_proba(X[ids], model)
        ok = np.isfinite(p1) & (np.abs(p0 + p1 - 1.0) < 1e-6)
        ok &= (lab == (p1 >= p0)) | (p1 == p0)
        bad += int((~ok).sum())
        gap = max(gap, float(np.abs(p1 - want)[ok].max()) if ok.any() else 1.0)
    if not rows:
        bad = 1                      # a window that left nothing to compare
    return {"score_gap": gap, "score_bad": bad, "score_rows": rows}
