"""checks/gbm.py — the comparison that decides `correct` for a bernoulli
histogram GBM, against reference/gbm_plain.py.

Every number compared is printed beside its limit. The limits live in the
configuration's file (`check.limits`), set from the readings listed in
PERF.md: above the largest that sound runs of the program gave, below the
smallest the bfloat16 control and the planted faults gave. A traffic mix
names what its cells compare (`compares`): "model", "answers", "scores".

Training (`check_model`): the reference follows the model the timed path
produced, teacher-forced on that model's own earlier trees, so a near-tie
split that rounds the other way does not read as a fault:

  edges_off       program thresholds that are not one of the reference's
                  own cut points of that column (exact: limit 0) — binning
  gain_gap        first `check_trees` trees, worst node: how far the
                  program's split lies below the reference's best split of
                  that node, over that best or the tree's median best,
                  whichever is larger — histograms + split search
  leaf_gap        every tree, worst node: |program value - Newton step the
                  reference computes from the rows that reach the node|,
                  over |that step| or the tree's median leaf, whichever is
                  larger — leaf statistics and, through the margins the
                  next tree's residuals come from, the margin update
  history_gap     the program's scoring-history logloss (from the margins
                  training maintains) against the reference's logloss of
                  the same trees, relative, worst entry
  final_gap       the program's final training logloss (its own scoring
                  walk over the frame) against the reference's, relative

Serving (`check_answers`): every answered request of the window, each row
of it, against the reference scorer walking the same trees from the raw
payload values: `served_gap` = worst |p - p_ref|, and `served_bad` = rows
missing, mislabelled or not finite (limit 0).

Frame scoring (`check_scores`): a sample, drawn from the seed, of the rows
of every prediction frame the window left, against the same scorer:
`score_gap` = worst |p - p_ref|, `score_bad` = rows missing, mislabelled,
not finite or with probabilities that do not sum to 1 (limit 0).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import gbm_plain as ref


def read_model(m) -> dict:
    """What the program ANSWERED, as plain arrays for the reference."""
    tr = m._trees
    return {"col": np.asarray(tr.col), "thr": np.asarray(tr.thr),
            "na_left": np.asarray(tr.na_left), "value": np.asarray(tr.value),
            "depth": int(tr.depth), "f0": float(m._f0),
            "learn_rate": float(m.params["learn_rate"]),
            "domain": list(m._dinfo.response_domain),
            "history": [(int(h["number_of_trees"]),
                         float(h["training_logloss"]))
                        for h in m._output.scoring_history],
            "final_logloss": float(m._output.training_metrics.logloss)}


def compare(what: str, *, X, y, params, model, produced, opts) -> dict:
    """The readings of one thing a mix `compares`. X, y: the host arrays
    the frames were made from; produced: what the driver's finish() kept;
    opts: the configuration's `check` block."""
    if what == "model":
        n = int(opts["train_rows"])
        return check_model(X[:n], y[:n], params, model,
                           check_trees=int(opts["trees"]))
    if what == "answers":
        return check_answers(produced, lambda ids: X[ids], model,
                             model["domain"])
    if what == "scores":
        return check_scores(produced, X, model, model["domain"])
    raise ValueError(f"checks/gbm.py compares no {what!r}")


def check_model(X, y, params, model, check_trees: int = 3) -> dict:
    """X (n, C) f32, y (n,) 0/1, params the configuration's estimator
    parameters, model the dict `read_model` reads off the program's
    model. Returns {name: reading}."""
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float64)
    n = X.shape[0]
    D, T = int(model["depth"]), int(model["col"].shape[0])
    nodes = 2 ** (D + 1) - 1
    nbins, min_rows = int(params["nbins"]), float(params.get("min_rows", 10.0))
    msi = max(float(params.get("min_split_improvement", 1e-5)), 0.0)
    lr = float(model["learn_rate"])
    col, thr, nal, val = (model[k] for k in ("col", "thr", "na_left", "value"))

    edges = ref.quantile_edges(X, nbins)
    split = col >= 0
    cut_of = np.stack([edges[np.maximum(col[t], 0)] for t in range(T)])
    edges_off = int((split & ~(cut_of == thr[..., None]).any(-1)).sum())

    f0 = ref.init_margin(y)
    out = {"edges_off": edges_off,
           "f0_gap": abs(float(model["f0"]) - f0) / max(abs(f0), 1e-3)}
    codes = ref.bin_codes(X, edges) if check_trees > 0 else None
    F = np.full(n, f0, np.float64)
    gain_gap = leaf_gap = 0.0
    losses = {}
    for t in range(T):
        g, h = ref.grad_hess(F, y)
        node = np.zeros(n, np.int64)
        best = np.full(nodes, -np.inf)
        for d in range(D):
            if t < check_trees:
                L, base = 1 << d, (1 << d) - 1
                at = node >= base
                best[base:base + L] = ref.level_best_splits(
                    codes[at], node[at] - base, g[at], L, nbins, min_rows)[0]
            node = ref.route(X, node, col[t], thr[t], nal[t])
        tot = ref.node_totals(node, g, h, nodes)
        w, sg, sh = tot
        # ---- leaf values: every node a row reached -----------------------
        want = np.clip(sg / np.maximum(sh, 1e-30), -ref.CLIP, ref.CLIP)
        seen = w > 0
        is_leaf = seen & ~split[t]
        scale = np.maximum(np.abs(want), np.median(np.abs(want[is_leaf])))
        leaf_gap = max(leaf_gap, float(
            (np.abs(val[t] - want) / scale)[seen].max()))
        # ---- splits: program's gain, by the reference's sums, against the
        # reference's best for that node ----------------------------------
        if t < check_trees:
            sc = ref.score(w, sg)
            inner = np.arange((nodes - 1) // 2)
            got = np.where(split[t][inner],
                           sc[2 * inner + 1] + sc[2 * inner + 2] - sc[inner],
                           0.0)
            b = best[inner]
            can = seen[inner] & (b > msi)      # the reference would split
            if can.any():
                scale = np.maximum(b, np.median(b[can]))
                gain_gap = max(gain_gap, float(
                    ((b - got) / scale)[can].max()))
            # a split where the reference sees no admissible cut at all
            gain_gap = max(gain_gap, float((split[t][inner] & seen[inner]
                                            & ~(b > msi)).any()))
        F += lr * val[t][node].astype(np.float64)
        losses[t + 1] = ref.logloss(F, y)
    out["gain_gap"], out["leaf_gap"] = gain_gap, leaf_gap
    out["history_gap"] = max(
        [abs(ll - losses[k]) / losses[k] for k, ll in model["history"]
         if k in losses], default=1.0)
    out["final_gap"] = abs(model["final_logloss"] - losses[T]) / losses[T]
    return out


def check_answers(answers, rows_of, model, domain) -> dict:
    """answers: [(request index, payload row ids, predictions list or
    None)], rows_of(ids) -> (k, C) f32 payload rows as they were sent."""
    bad, gap, rows = 0, 0.0, 0
    ids = np.concatenate([a[1] for a in answers]) if answers else \
        np.zeros(0, np.int64)
    p_ref = ref.predict_proba(rows_of(ids), model) if ids.size else ids
    at = 0
    for _, rid, preds in answers:
        k = len(rid)
        want = p_ref[at:at + k]
        at += k
        rows += k
        if preds is None or len(preds) != k:
            bad += k
            continue
        try:
            p1 = np.array([p["p" + domain[1]] for p in preds], np.float64)
            p0 = np.array([p["p" + domain[0]] for p in preds], np.float64)
            lab = [p["predict"] for p in preds]
        except (KeyError, TypeError):
            bad += k
            continue
        ok = np.isfinite(p1) & (np.abs(p0 + p1 - 1.0) < 1e-6)
        # the label is the larger probability (a tie may go either way)
        ok &= np.array([(l == domain[int(a >= b)]) or a == b
                        for l, a, b in zip(lab, p1, p0)])
        bad += int((~ok).sum())
        gap = max(gap, float(np.abs(p1 - want)[ok].max()) if ok.any() else 1.0)
    return {"served_gap": gap, "served_bad": bad, "served_rows": rows}


def check_scores(scores, X, model, domain) -> dict:
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
