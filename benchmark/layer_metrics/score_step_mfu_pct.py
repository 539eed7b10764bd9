"""score_step_mfu_pct — the whole step's share of the chip's peak: least
time the chip could take to score one frame (work_model.score_call: the
features read once, the probabilities written; a compare per tree and
level) = max(ops / peak FLOP/s, bytes / peak bytes/s), over the mean wall
time of a predict() call in the window. Bounds every kernel roofline that
moves score_rows_per_s: a kernel taken off the path leaves this standing."""

from benchmark import work_model


def read(rec):
    w = rec["window"]
    walls = w.get("call_walls")
    if not walls or rec["peak"] is None:
        return None
    p = rec["params"]
    ops, byts = work_model.score_call(
        int(w["call_rows"]), int(rec["config"]["table"]["columns"]),
        int(p["max_depth"]), int(p["ntrees"]))
    least = work_model.least_seconds(ops, byts, rec["peak"])
    return work_model.share_pct(least, sum(walls) / len(walls),
                                "score_step_mfu_pct")
