"""train_step_mfu_pct — the whole step's share of the chip's peak: least
time the chip could take for everything one train() must do
(work_model.train_call: bin the frame, grow the trees, one scoring pass)
= max(ops / peak FLOP/s, bytes / peak bytes/s), over the mean wall time
of a call in the window. Bounds every kernel roofline that moves
train_rowtrees_per_s: a kernel taken off the path leaves this standing."""

from benchmark import work_model


def read(rec):
    walls = rec["window"].get("call_walls")
    if not walls or rec["peak"] is None:
        return None
    p, s = rec["params"], rec["sizes"]
    ops, byts = work_model.train_call(
        int(s["train_rows"]), int(rec["config"]["table"]["columns"]),
        int(p["max_depth"]), int(p["ntrees"]), int(p["nbins"]))
    least = work_model.least_seconds(ops, byts, rec["peak"])
    return work_model.share_pct(least, sum(walls) / len(walls),
                                "train_step_mfu_pct")
