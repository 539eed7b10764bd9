"""score_walk_mxu_pct — the dense walk's share of ITS roofline: the least
time the chip's MXU could take for the passes the dense block formulation
needs for the frames of the traced stretch (work_model_dense.walk_flops:
from the configuration alone — trees = params.ntrees x table.classes, the
blocks they fill, 2 select + 1 path pass a block in bf16, an int8
half-pass a 128 level rows), over the summed device time of the ensemble
walk's XLA module in the trace (`jit__ensemble_walk`, as
score_walk_roofline reads it). No trace (the CPU), or a program whose
trace holds no such module -> nothing."""

from benchmark import work_model, work_model_dense
from benchmark.layer_metrics.score_walk_roofline import MODULE


def read(rec):
    w = rec["window"]
    tr = w.get("trace")
    calls = len(w.get("call_walls") or ())
    if not tr or rec["peak"] is None or not calls:
        return None
    dev_s = tr["module_s"].get(MODULE, 0.0)
    if dev_s <= 0:
        return None
    p, table = rec["params"], rec["config"]["table"]
    flops = work_model_dense.walk_flops(
        int(w["call_rows"]) * calls,
        int(p["ntrees"]) * int(table.get("classes", 1)),
        int(p["max_depth"]), int(table["columns"]),
        sum(int(k) for k in table.get("levels", ())))
    return work_model.share_pct(flops / rec["peak"]["flops_per_s"], dev_s,
                                "score_walk_mxu_pct")
