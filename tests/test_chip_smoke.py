"""chip_smoke.py, off the chip.

The script itself must FAIL here (no accelerator) and gets no option that
lets it pass; what can be rehearsed on the CPU is rehearsed by importing
its phase functions at a tiny size — widths (28 columns, 255 bins, depth
8) stay as on the chip. The kernels phase has no CPU form (the Pallas
kernels carry no interpret path); tests/test_chip_compile.py compiles
them for a described chip instead.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from h2o3_tpu.utils import compile_cache  # noqa: E402

SEED = 7
MAX_FAST_ROWS = 8192


@pytest.fixture(scope="module")
def trained(cloud8):
    return cs.phase_train(20_000, 3, SEED, on_chip=False)


@pytest.fixture(scope="module")
def predicted(trained, tmp_path_factory):
    _, model, fr, X = trained
    with pytest.MonkeyPatch.context() as mp:
        # the frame must exceed the fast path's row ceiling
        mp.setenv("H2O3_SCORE_FASTPATH_MAX_ROWS", str(MAX_FAST_ROWS))
        return cs.phase_predict(model, fr, X, SEED,
                                str(tmp_path_factory.mktemp("mojo")),
                                slice_rows=4096, check_rows=500,
                                parity_rows=9001)


def test_ingest_phase(cloud8, tmp_path):
    rec = cs.phase_ingest(5_000, SEED, str(tmp_path))
    assert rec["tokenized_bytes"] == rec["csv_bytes"] > 0
    assert not os.listdir(tmp_path)         # the CSV is removed again


def test_walk_phase():
    rec = cs.phase_walk_exact(20_000, ((3, 8), (5, 5)), SEED, on_chip=False)
    assert rec["cols"] == 28 and rec["nonfinite_cells"] > 0
    assert [(r["depth"], r["block"]) for r in rec["shapes"]] == \
        [(8, "0.5x256"), (5, "4x32")]
    assert rec["shapes"][0]["nodes_reached"] > 127
    assert rec["shapes"][1]["nodes_reached"] > 15
    assert rec["pallas_kernels_traced"] == []   # the XLA twin ran here


@pytest.mark.parametrize("shape,rows", [((5, 5), 6_000), ((2, 8), 20_000),
                                        ((1, 9), 30_000)])
def test_walk_sets_phase(shape, rows):
    rec = cs.phase_walk_sets(rows, cs.WALK_SET_LEVELS, shape, SEED,
                             on_chip=False)
    assert rec["level_rows"] == sum(cs.WALK_SET_LEVELS) == 759
    assert rec["set_rows"] == 128           # 97 byte rows, one MXU tile
    assert rec["set_words"] == 12 and rec["rows_past_a_byte"] > 0
    assert rec["walk_ms"] == [] and rec["time_rows"] == 0
    assert 0 < rec["set_nodes"] < rec["split_nodes"]
    assert rec["nodes_reached"] > 15 and rec["nonfinite_cells"] > 0
    assert rec["block"] == ("4x32" if shape[1] == 5 else "0.5x256")
    assert rec["pallas_kernels_traced"] == []   # the XLA twin ran here
    assert {d for _, d in cs.WALK_SET_SHAPES} == {5, 8, 9}


def test_walk_sets_phase_at_the_bytes_edges_and_timed():
    rec = cs.phase_walk_sets(6_000, cs.WALK_EDGE_LEVELS, (5, 5), SEED,
                             on_chip=False, edges=True, time_rows=3_000,
                             reads=2)
    assert rec["level_rows"] == sum(cs.WALK_EDGE_LEVELS) == 367
    assert rec["set_rows"] == 128 and rec["set_words"] == 12
    assert rec["nodes_reached"] > 15 and rec["nonfinite_cells"] > 0
    assert rec["time_rows"] == 3_000 and len(rec["walk_ms"]) == 2
    assert cs.WALK_TIME_ROWS == 7_720_935


@pytest.mark.parametrize("shape,rows", [((2, 5), 6_000), ((1, 8), 8_000),
                                        ((1, 9), 8_000)])
def test_walk_classes_phase(shape, rows):
    rec = cs.phase_walk_classes(rows, cs.WALK_CLASS_LEVELS, shape, 5, SEED,
                                on_chip=False)
    assert rec["level_rows"] == sum(cs.WALK_CLASS_LEVELS) == 84
    assert (rec["cols"], rec["classes"], rec["ntrees"]) == (41, 5, 5 * shape[0])
    assert rec["distinct_sums"] > 100 and rec["nonfinite_cells"] > 0
    assert rec["pallas_kernels_traced"] == []   # the XLA twin ran here
    assert rec["epilogue_planes"] == 1 + 5      # the label and a plane a class
    assert cs.WALK_CLASSES == 23 and cs.WALK_CLASS_SHAPES[0] == (10, 5)
    assert {d for _, d in cs.WALK_CLASS_SHAPES} == {5, 8, 9}


def test_train_phase(trained):
    rec = trained[0]
    assert rec["train_auc"] > cs.AUC_MIN
    assert (rec["cols"], rec["depth"], rec["nbins"]) == (28, 8, 255)
    assert rec["pallas_kernels_traced"] == []   # the XLA twins ran here


def test_predict_phase(predicted):
    for path in ("large_frame_path", "fast_path"):
        assert predicted[path]["compared"] == 500
        assert predicted[path]["max_abs_dev"] < 2e-5


def test_predict_phase_sets_the_device_built_frame_against_the_hosts(
        predicted):
    rec = predicted["frame_parity"]
    assert rec["rows"] == 9001 and rec["padded"] == 9024
    assert rec["nonfinite_cells"] > 1000
    assert (rec["columns_compared"], rec["planted_rows"]) == (3, 3)


def test_serve_phase(trained, predicted):
    _, model, _, X = trained
    rec = cs.phase_serve(model, X, predicted["p_full"], sizes=(1, 64, 300),
                         repeats=3, one_row_repeats=4)
    assert rec["requests"]["1"]["count"] == 4
    assert rec["requests"]["1"]["median_warm_ms"] > 0
    assert rec["one_row_median_warm_ms_pr28"] == [7.41, 7.66]
    assert rec["trace_error_fallbacks"] == 0
    assert sorted(rec["requests"]) == ["1", "300", "64"]


def test_parity_harness_with_the_twins_standing_in(monkeypatch):
    """The kernels phase cannot run here, but its harness can: with each
    Pallas entry point replaced by unpack + its XLA twin, every case must
    line up (argument order, slicing, shapes) and deviate by exactly 0 —
    found on the CPU, not on chip time."""
    from h2o3_tpu.ops import hist_pallas as HP, parity

    def unpack(cp):
        return HP.unpack_codes(cp, c_pad=cp.shape[0] * HP.PACK)

    def hist(cp, heap, stats, **kw):
        return HP.sbh_hist_xla(unpack(cp), heap, stats, **kw)

    def route(cp, heap, tbl, rf, valtab=None, F=None, **kw):
        h, f = HP.sbh_route_xla(unpack(cp), heap, tbl, rf, valtab, F, **kw)
        return h, (f if kw.get("emit_f") else None)

    def fused(cp, heap, tbl, rf, stats, *, base_r, L_r, base_h, L_h,
              n_bins, any_cat, na_code, planes=1):
        nh, _ = route(cp, heap, tbl, rf, base=base_r, L=L_r,
                      any_cat=any_cat, na_code=na_code, planes=planes)
        return nh, hist(cp, nh, stats, base=base_h, L=L_h, n_bins=n_bins,
                        half=True)

    monkeypatch.setattr(HP, "sbh_hist_pallas", hist)
    monkeypatch.setattr(HP, "sbh_route_pallas", route)
    monkeypatch.setattr(HP, "sbh_route_hist_fused_pallas", fused)
    devs = parity.kernel_parity_check(SEED)
    assert len(devs) >= 17 and max(devs.values()) == 0


def _run(args, cwd=REPO, **env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_four_chip_phase_on_four_virtual_devices():
    """Rehearsal (b): the --chips 4 path on a 4-device CPU mesh, in a
    process of its own (the suite's cloud has 8)."""
    r = _run(["-c", "import json, chip_smoke as cs; print(json.dumps("
              f"cs.phase_four_chips(20000, 2, {SEED}, on_chip=False)))"],
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["all_reduces_per_tree"] == cs.DEPTH
    assert min(rec["split_agreement_per_tree"]) > 0.95
    assert rec["train_4"]["train_auc"] > cs.AUC_MIN


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_script_fails_without_an_accelerator(args):
    r = _run(["chip_smoke.py", *args])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr


# ---- the compile-cache helper ---------------------------------------------
def test_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")


def test_cache_dir_honours_the_variable(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enable() == str(tmp_path)
    # JAX read the variable itself; code set nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_enable_places_the_cache_only_off_the_cpu(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() is None       # the suite's CPU backend
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.enable() == compile_cache.cache_dir()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
