"""train_setup_pct — share of the window's train() wall time the program's
own phase clock books under Job.phases["setup"] (quantile spec + codes +
device_put; `shared_tree.py _fit_binned`). Source: the /3/Jobs document."""

PHASE = "setup"


def phase_pct(rec, phase):
    w = rec["window"]
    phases, walls = w.get("job_phases"), w.get("call_walls")
    if not phases or not walls or len(phases) != len(walls):
        return None
    ms = sum(p.get(phase, 0.0) for p in phases)
    return 100.0 * ms / 1e3 / sum(walls)


def read(rec):
    return phase_pct(rec, PHASE)
