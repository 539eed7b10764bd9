"""tree_hbm_roofline — least time the chip could take for the trees grown
in the traced stretch (work_model.tree_bytes / peak HBM bytes/s: the
algorithm is BYTES-bound) over the summed device time of the K-tree
trainer's XLA module(s) in the trace. The trainer's jitted function is
literally named `run` (binned.gbm_chunk_trainer), so its module is
`jit_run`. No trace (the CPU) -> nothing; a device trace WITHOUT that
module is an error: the trainer was renamed, and this reader has to
follow."""

from benchmark import work_model

MODULE = "jit_run"


def read(rec):
    tr = rec["window"].get("trace")
    if not tr or rec["peak"] is None:
        return None
    calls = len(rec["window"].get("call_walls") or ())
    if not calls:
        return None
    dev_s = tr["module_s"].get(MODULE, 0.0)
    if dev_s <= 0:
        raise LookupError(f"tree_hbm_roofline: no device time under "
                          f"{MODULE!r}; modules: {sorted(tr['module_s'])}")
    p, s = rec["params"], rec["sizes"]
    byts = work_model.tree_bytes(int(s["train_rows"]),
                                 int(rec["config"]["table"]["columns"]),
                                 int(p["max_depth"]), int(p["ntrees"]) * calls)
    least = byts / rec["peak"]["hbm_bytes_per_s"]
    return work_model.share_pct(least, dev_s, "tree_hbm_roofline")
