"""Tests of the benchmark itself — CPU only, nothing touches the TPU
library. Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They live under benchmark/ (BENCHMARK.json `paths`), so a later PR cannot
quietly change the yardstick they pin: names resolve by file, the work
model knows shapes only, the trace reduction reads the recorded trace as
written beside it, the plain reference agrees with the program at a tiny
size, its lower-precision control and every planted fault come out NOT
correct, and `--rehearse` drives each cell end to end.
"""

from __future__ import annotations

import importlib
import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import checks, run, trace_reduce, work_model  # noqa: E402
from benchmark.checks import gbm as check                    # noqa: E402
from benchmark.datasets import higgs_like as data            # noqa: E402
from benchmark.reference import gbm_plain as ref             # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    B = json.load(_fh)
HELD_FILE = os.path.join(HERE, "held_cells.json")
with open(HELD_FILE) as _fh:
    HELD = json.load(_fh)        # cells held out of BENCHMARK.json (PERF.md §7)
CELLS = [w["name"] for w in B["workloads"]]


# ---- names resolve by file -------------------------------------------------
@pytest.mark.parametrize("bench", [B, HELD], ids=["BENCHMARK.json", "held"])
def test_names_resolve_by_file(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = [w["name"] for w in bench["workloads"]]
    for w in bench["workloads"]:
        cfg = run.load_json("configs", w["config"] + ".json")
        importlib.import_module("benchmark.datasets." + cfg["data"])
        chk = importlib.import_module(
            "benchmark.checks." + cfg["check"]["module"])
        assert all(hasattr(chk, f) for f in ("read_model", "compare"))
        mix = run.load_json("traffic", w["traffic"] + ".json")
        drv = importlib.import_module("benchmark.drivers." + mix["driver"])
        assert all(hasattr(drv, f) for f in ("prepare", "window", "finish"))
        assert mix["compares"] and mix["source"]
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in bench["per_layer"]:
        assert callable(run.reader_of(m["name"])), m["name"]
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= set(cells)
    for w in bench["workloads"]:
        mine = [m["name"] for m in bench["end_to_end"]
                if run.e2e_in_cell(m, w)]
        assert "setup_s" in mine and len(mine) >= 2, w["name"]
        assert any(run.layer_in_cell(m, w, bench) for m in bench["per_layer"])


def test_benchmark_json_configs_and_bounds():
    cfgs = {c["name"]: c for c in B["configs"]}
    assert {w["config"] for w in B["workloads"]} == set(cfgs)
    for name, c in cfgs.items():
        cfg = run.load_json("configs", name + ".json")
        assert c["file"] == f"benchmark/configs/{name}.json"
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["sizes"]["table_rows"] == cfg["published"]["rows"]
    for m in B["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1


# ---- the work model knows shapes only --------------------------------------
def test_work_model_takes_algorithm_shapes_only():
    for fn in (work_model.tree_bytes, work_model.tree_ops):
        assert list(inspect.signature(fn).parameters) == \
            ["rows", "real_cols", "depth", "trees"]
    # 28 real columns count as 28 whatever the kernels pad them to
    assert work_model.tree_bytes(1000, 28, 8, 10) == \
        10 * 1000 * (8 * (28 + 16) + 20)
    peak = run.load_json("peaks.json")["TPU v5 lite"]
    # bytes-bound, as the readers of the two rooflines say
    for ops, byts in (work_model.train_call(2_750_000, 28, 8, 10, 255),
                      work_model.score_call(2_750_000, 28, 8, 10)):
        assert byts / peak["hbm_bytes_per_s"] > ops / peak["flops_per_s"]
    assert work_model.score_call(1000, 28, 5, 20)[1] == 1000 * (4 * 28 + 12)


def test_share_over_100_fails_instead_of_clipping():
    assert work_model.share_pct(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError):
        work_model.share_pct(1.01, 1.0, "x")
    with pytest.raises(ValueError):
        work_model.share_pct(1.0, float("nan"), "x")


def test_unknown_device_is_not_in_peaks():
    assert "cpu" not in run.load_json("peaks.json")


# ---- trace reduction on the recorded chip trace -----------------------------
def test_trace_reduce_reads_the_recorded_trace_as_written_beside_it():
    pd = trace_reduce.load(os.path.join(BENCH, "testdata", "small.xplane.pb"))
    with open(os.path.join(BENCH, "testdata", "small.expected.json")) as fh:
        want = json.load(fh)
    got = trace_reduce.reduce(pd, window=tuple(want["window"]))
    assert got["chips"] == want["chips"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["module_s"].keys() == want["module_s"].keys()
    for k, v in want["module_s"].items():
        assert got["module_s"][k] == pytest.approx(v, rel=1e-9)
    assert "jit_run" in got["module_s"]          # ids stripped from the name
    # four steps with the host asleep between them: the gaps are the sleeps
    assert sum(e - s for s, e in got["gaps"]) / 1e9 == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert trace_reduce.anchor_ns(pd, "bench.anchor") is not None


def test_union_and_gap_labels():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace_reduce.module_base("jit_run(123)") == "jit_run"
    lab = trace_reduce.label_gaps(
        [(0, 2e9), (4e9, 5e9), (7e9, 7.5e9)],
        lambda s, e: "a" if s < 3e9 or s > 6e9 else "b")
    assert lab == [["a", 2.5], ["b", 1.0]]


# ---- the reference against the program, its control and the faults ---------
PARAMS = dict(ntrees=6, max_depth=4, nbins=20, learn_rate=0.1, min_rows=10.0)
LIMITS = run.load_json("configs", "gbm_higgs.json")["check"]["limits"]
SEED = 2987654321                  # past 2**31, as the driver's seeds are


def _failed(readings):
    return [n for n, *_, ok in checks.verdict(
        readings, {k: v for k, v in LIMITS.items() if k in readings})
        if not ok]


@pytest.fixture(scope="module")
def table():
    return data.host_arrays(20_000, 28, 3_000_000_017)


@pytest.fixture(scope="module")
def program_model(table):
    import h2o3_tpu
    h2o3_tpu.init()
    X, y = table
    ctx = {"config": {"estimator": "H2OGradientBoostingEstimator"},
           "params": dict(PARAMS, distribution="bernoulli"), "seed": 11,
           "frame": data.frame(X, y), "data": data}
    return check.read_model(run.train_once(ctx))


def test_seeded_data_is_prefix_stable_and_takes_large_seeds():
    X, y = data.host_arrays(300_000, 7, SEED)
    X2, y2 = data.host_arrays(260_000, 7, SEED)
    assert np.array_equal(X[:260_000], X2) and np.array_equal(y[:260_000], y2)
    assert not np.array_equal(X[:9], data.host_arrays(9, 7, SEED + 1)[0])
    assert 0.4 < y.mean() < 0.6


def test_reference_agrees_with_the_program_at_a_tiny_size(table, program_model):
    X, y = table
    got = check.check_model(X, y, PARAMS, program_model, check_trees=2)
    assert _failed(got) == [], got
    mine = ref.grow(X, y, **PARAMS)
    # same splits from independent code (a near-tie may flip: most, not all)
    same = (mine["col"] == program_model["col"]) & (
        (mine["thr"] == program_model["thr"]) | (mine["col"] < 0))
    assert same.mean() > 0.95


@pytest.mark.parametrize("how", [
    dict(precision="bf16"),
    dict(fault="half_batch"),
    dict(fault="state_unchanged"), dict(fault="altered_leaf")])
def test_training_control_and_planted_faults_are_not_correct(table, how):
    X, y = table
    got = check.check_model(X, y, PARAMS, ref.grow(X, y, **PARAMS, **how),
                            check_trees=2)
    assert _failed(got), (how, got)


def test_sound_reference_in_the_programs_place_is_correct(table):
    X, y = table
    got = check.check_model(X, y, PARAMS, ref.grow(X, y, **PARAMS),
                            check_trees=2)
    assert _failed(got) == [], got


def _scored(X, model, ids, precision="f32"):
    p1 = ref.predict_proba(X[ids], model, precision)
    return [ids, 1.0 - p1, p1, p1 >= 0.5]


@pytest.mark.parametrize("how,number", [
    ("sound", None), ("bf16", "score_gap"), ("altered", "score_gap"),
    ("half_unscored", "score_gap"), ("mislabelled", "score_bad"),
    ("unreadable", "score_bad"), ("nothing_kept", "score_bad")])
def test_scores_check(table, program_model, how, number):
    """The scoring control (the reference scorer at bfloat16 in the
    program's place) and each fault of an answer come out NOT correct."""
    X, _ = table
    ids = np.arange(0, 20_000, 7)
    got = _scored(X, program_model, ids, "bf16" if how == "bf16" else "f32")
    if how == "altered":
        got[2][5] += 1e-3
        got[1][5] -= 1e-3
    elif how == "half_unscored":
        got[1][len(ids) // 2:], got[2][len(ids) // 2:] = got[1][0], got[2][0]
        got[3] = got[2] >= 0.5
    elif how == "mislabelled":
        got[3][:3] = ~got[3][:3]
    elif how == "unreadable":
        got[1:] = [None] * 3
    frames = [] if how == "nothing_kept" else [tuple(got)]
    r = check.check_scores(frames, X, program_model, ["b", "s"])
    assert _failed(r) == ([number] if number else []), r


def test_served_answers_check(table, program_model):
    X, _ = table
    ids = np.arange(50)
    p = ref.predict_proba(X[ids], program_model)
    preds = [{"predict": "s" if q >= 0.5 else "b", "pb": 1 - q, "ps": q}
             for q in p]
    ok = check.check_answers([(0, ids, preds)], lambda i: X[i],
                             program_model, ["b", "s"])
    assert ok["served_bad"] == 0 and ok["served_gap"] < 1e-12
    preds[7] = dict(preds[7], ps=preds[7]["ps"] + 1e-3,
                    pb=preds[7]["pb"] - 1e-3)
    bad = check.check_answers([(0, ids, preds)], lambda i: X[i],
                              program_model, ["b", "s"])
    assert bad["served_gap"] > 5e-4
    assert check.check_answers([(0, ids, preds[:-1])], lambda i: X[i],
                               program_model, ["b", "s"])["served_bad"] == 50


# ---- a whole run, rehearsed -------------------------------------------------
def _rehearse(capsys, cell, trace=0, seconds=0.5):
    held = cell not in CELLS
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse"]
                  + (["--bench", HELD_FILE] if held else []))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS + [
    "gbm_higgs_defaults.train", "gbm_higgs.serve"])
def test_rehearse_prints_the_contracts_last_line(capsys, cell):
    line = _rehearse(capsys, cell)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    bench = B if cell in CELLS else HELD
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    want = {m["name"] for m in bench["end_to_end"] if run.e2e_in_cell(m, w)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert len(line["compared"]) >= 2
    for c in line["compared"].values():
        assert set(c) == {"value", "limit", "ok"}


def test_rehearsed_trace_run_reports_layers_and_no_device_metric(capsys):
    line = _rehearse(capsys, CELLS[1], trace=1)
    # nothing read from a device trace or a peak on the CPU: no roofline,
    # no mfu, no idle share, no busy_s — and no reader returns 0 instead
    assert line["metrics"] == {} and line["correct"] is True
    assert "busy_s" not in line["device"]


def test_a_roofline_reader_fails_when_its_module_left_the_trace():
    from benchmark.layer_metrics import score_walk_roofline, tree_hbm_roofline
    peak = run.load_json("peaks.json")["TPU v5 lite"]
    rec = {"peak": peak, "params": {"max_depth": 8, "ntrees": 10},
           "config": {"table": {"columns": 28}},
           "sizes": {"train_rows": 1000},
           "window": {"call_walls": [1.0], "call_rows": 1000,
                      "trace": {"module_s": {"jit_something_else": 1.0}}}}
    for reader in (score_walk_roofline, tree_hbm_roofline):
        with pytest.raises(LookupError):
            reader.read(rec)
        assert reader.read(dict(rec, window={"call_walls": [1.0]})) is None
    rec["window"]["trace"]["module_s"] = {"jit__ensemble_walk": 1e-9}
    with pytest.raises(ValueError):          # over 100 % of the roofline
        score_walk_roofline.read(rec)


def test_without_rehearse_it_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout.strip() == ""


# ---- the timed path broken underneath: `correct` must come out false -------
def _plant(variant):
    """The faults, planted in the program as tools/controls.py plants
    them on the chip."""
    def plant(request):
        from benchmark.tools import controls
        request.addfinalizer(controls.plant(variant))
    return plant


@pytest.mark.parametrize("cell,variant,number", [
    (CELLS[1], "altered", "score_gap"),
    (CELLS[1], "half_unscored", "score_gap"),
    ("gbm_higgs_defaults.train", "state_unchanged", "leaf_gap"),
])
def test_a_broken_timed_path_is_not_correct(capsys, request, cell, variant,
                                            number):
    _plant(variant)(request)
    line = _rehearse(capsys, cell)
    assert line["correct"] is False
    assert line["compared"][number]["ok"] is False
