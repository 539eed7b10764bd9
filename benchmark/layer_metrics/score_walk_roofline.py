"""score_walk_roofline — least time the chip could take to score the
frames of the traced stretch (work_model.score_call's bytes / peak HBM
bytes/s: scoring is BYTES-bound) over the summed device time of the
ensemble walk's XLA module(s) in the trace. The walk's jitted function is
`engine._ensemble_walk`, so its module is `jit__ensemble_walk`. No trace
(the CPU) -> nothing; a device trace WITHOUT that module is an error: the
walk was renamed or taken off the path, and this reader has to follow."""

from benchmark import work_model

MODULE = "jit__ensemble_walk"


def read(rec):
    w = rec["window"]
    tr = w.get("trace")
    calls = len(w.get("call_walls") or ())
    if not tr or rec["peak"] is None or not calls:
        return None
    dev_s = tr["module_s"].get(MODULE, 0.0)
    if dev_s <= 0:
        raise LookupError(f"score_walk_roofline: no device time under "
                          f"{MODULE!r}; modules: {sorted(tr['module_s'])}")
    p = rec["params"]
    _, byts = work_model.score_call(
        int(w["call_rows"]) * calls, int(rec["config"]["table"]["columns"]),
        int(p["max_depth"]), int(p["ntrees"]))
    return work_model.share_pct(byts / rec["peak"]["hbm_bytes_per_s"], dev_s,
                                "score_walk_roofline")
