"""Compile, for a DESCRIBED v5e, every kernel the default GBM path selects
at HIGGS width — no chip attached, nothing executed.

The TPU's compiler is installed in the CPU sandbox and compiles for a
topology that is described rather than attached, so what Mosaic refuses
on the chip (a misaligned slice, too much VMEM) is refused here too, at
no chip time. Widths are the ones users train at: 28 columns -> 32 padded
-> 8 packed words, 255 bins + NA in a 256-bin plane, 11,001,856 padded
rows, depth 8. The kernel functions are called directly: `use_pallas()`
sees the CPU here and the dispatchers would take the XLA branch.

Rules for this file (several xdist workers import every test file, and
only one process may hold the TPU library): the topology is described
inside a module-scoped, non-autouse fixture; nothing touches the TPU
library at import, in a skipif condition or in a parametrize argument;
and every such compile lives in THIS one file.

Also here: the scoring walk's dense body (engine._ensemble_walk) at the
benchmark's frame, 2,750,000 x 28, for its two ensembles — the fused kernel
`walk_dense_tile` (ops/walk_pallas.py) — on one chip and row-sharded over
the host's four.

And the KDD Cup 1999 configuration (benchmark/configs/gbm_kddcup99.json):
a 23-class ensemble through the kernel's class variant
(`walk_dense_tile_sets_classes`), the K-tree multinomial trainer, and the
prediction planes of a 23-class frame.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from h2o3_tpu.models.tree import binned as BN
from h2o3_tpu.models.tree import engine as E
from h2o3_tpu.ops import hist_pallas as HP
from h2o3_tpu.ops import walk_pallas as WP
from h2o3_tpu.ops import parity
from h2o3_tpu.parallel import mesh as MESH

N_ROWS = 11_000_000
N_PAD = -(-(N_ROWS + 1) // HP.BLOCK_ROWS) * HP.BLOCK_ROWS   # 11,001,856
C_REAL, DEPTH = 28, 8
C_PAD, N_BINS, B_VAL = parity.C_PAD, parity.N_BINS, parity.B_VAL
W_PAD = HP.packed_words(C_PAD)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler: skip, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory pinned to chip 0 of the described host."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next one warns and
    recompiles) — keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _plane(sds):
    return (sds((W_PAD, N_PAD), jnp.int32), sds((N_PAD,), jnp.int32),
            sds((HP.S_STATS, N_PAD), jnp.float32))


def _tables(sds, L):
    Lp = max(8, L)
    return sds((8, Lp), jnp.float32), sds((Lp, N_BINS), jnp.float32)


def _hist(sds, L, half):
    return _plane(sds), functools.partial(
        HP.sbh_hist_pallas, base=L - 1, L=L, n_bins=N_BINS, half=half)


def _route(sds, L, emit_f, any_cat=False):
    codes, heap, _ = _plane(sds)
    extra = ()
    if emit_f:
        nodes_p = -(-(2 ** (DEPTH + 1)) // 128) * 128
        extra = (sds((8, nodes_p), jnp.float32), sds((N_PAD,), jnp.float32))
    return (codes, heap, *_tables(sds, L), *extra), functools.partial(
        HP.sbh_route_pallas, base=L - 1, L=L, eta=0.1, emit_f=emit_f,
        any_cat=any_cat, na_code=B_VAL)


def _fused(sds, L_h):
    codes, heap, stats = _plane(sds)
    L_r = L_h >> 1
    return (codes, heap, *_tables(sds, L_r), stats), functools.partial(
        HP.sbh_route_hist_fused_pallas, base_r=L_r - 1, L_r=L_r,
        base_h=L_h - 1, L_h=L_h, n_bins=N_BINS, any_cat=False,
        na_code=B_VAL)


# One case per kernel family, each a list of (abstract args, kernel call):
# a family's instantiations go into ONE program, whose Mosaic kernels the
# compiler builds in parallel (five fused kernels cost ~20 s together
# against ~17 s each alone). A refusal names its kernel. Built from plain
# ints only — no TPU library in a parametrize argument.
LAST = 2 ** (DEPTH - 1)
FAMILIES = {
    "pack_codes": lambda sds: [
        ((sds((C_PAD, N_PAD), jnp.uint8),), HP.pack_codes)],
    "hist_full": lambda sds: [
        _hist(sds, L, False) for L in parity.LEVELS],
    "hist_half": lambda sds: [
        _hist(sds, L, True) for L in parity.LEVELS],
    "route": lambda sds: [
        _route(sds, 1, False), _route(sds, LAST, False),
        _route(sds, LAST, False, any_cat=True)],
    "route_terminal": lambda sds: [
        _route(sds, 1, True), _route(sds, LAST, True)],
    "fused": lambda sds: [
        _fused(sds, L_h) for L_h in parity.fusable_levels()],
}


def _higgs_trainer(monkeypatch, sds, mesh=None, k_trees=2):
    """(trainer, abstract args): the K-tree program exactly as
    `_fit_binned` builds it for a HIGGS frame — on one chip, or with
    `mesh` row-sharded over the described host's chips — with the
    dispatchers steered onto their TPU branch (they ask the default
    backend, which is the CPU in this sandbox)."""
    monkeypatch.setattr(HP, "use_pallas", lambda: True)
    rng = np.random.default_rng(0)
    spec = BN.make_bins(rng.normal(size=(4096, C_REAL)).astype(np.float32),
                        np.zeros(C_REAL, bool), B_VAL)
    assert (spec.c_pad, spec.n_bins, spec.b_val) == (C_PAD, N_BINS, B_VAL)
    grower = BN.BinnedGrower(spec, max_depth=DEPTH, min_rows=10.0,
                             min_split_improvement=1e-5,
                             axis_name=MESH.ROWS if mesh is not None
                             else None)
    n_pad = grower.layout(N_ROWS, shards=mesh.devices.size if mesh else 1)
    trainer = BN.gbm_chunk_trainer(
        grower, N_ROWS, dist="bernoulli", eta=0.1, sample_rate=1.0,
        mtries=0, k_trees=k_trees, mesh=mesh)
    if mesh is None:
        assert n_pad == N_PAD
        row = sds((n_pad,), jnp.float32)
        return trainer, (sds((W_PAD, n_pad), jnp.int32), row, row, row,
                         sds((2,), jnp.uint32))

    def on_mesh(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    row = on_mesh((n_pad,), jnp.float32, P(MESH.ROWS))
    return trainer, (on_mesh((W_PAD, n_pad), jnp.int32, P(None, MESH.ROWS)),
                     row, row, row, on_mesh((2,), jnp.uint32, P()))


# what grow() selects per level of a depth-8 tree at 32 columns / 256
# bins: full hist at the root, the fused route+hist while the level's
# histogram fits VMEM, the route + half-hist pair below, terminal route
DEFAULT_KERNELS = ({("hist", 1), ("hist", 64), ("hist", 128),
                    ("route", 32), ("route", 64), ("route_f", 128)}
                   | {("fused", L_h) for L_h in parity.fusable_levels()})


@pytest.mark.parametrize("name", sorted(FAMILIES) + [
    "trainer", "trainer_4chips", "default_kernels"])
def test_compiles_for_v5e_at_higgs_width(name, topo, sds,
                                         no_persistent_cache, monkeypatch):
    if name == "default_kernels":
        # tracing alone shows which kernels the default rules pick; the
        # cleared caches make every inner jit run its Python body again
        jax.clear_caches()
        before = HP.kernel_traces()
        trainer, args = _higgs_trainer(monkeypatch, sds)
        jax.eval_shape(trainer, *args)
        picked = {k for k, v in HP.kernel_traces().items()
                  if v > before.get(k, 0)}
        assert picked == DEFAULT_KERNELS
        return
    if name == "trainer":
        trainer, args = _higgs_trainer(monkeypatch, sds)
        lowered = trainer.lower(*args)
    elif name == "trainer_4chips":
        # the row-sharded cloud: one program over the host's four chips
        mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                    (MESH.ROWS, MESH.MODEL))
        trainer, args = _higgs_trainer(monkeypatch, sds, mesh=mesh)
        lowered = trainer.lower(*args)
    else:
        argsets, calls = zip(*FAMILIES[name](sds))
        lowered = jax.jit(
            lambda sets: [f(*a) for f, a in zip(calls, sets)]
        ).lower(argsets)
    compiled = lowered.compile()    # raises what the chip's compiler would
    text = compiled.as_text()
    if name != "pack_codes":
        assert "tpu_custom_call" in text
    if name == "trainer_4chips":
        # the design's collectives: ONE histogram all-reduce per level in
        # the per-tree scan body, nothing else crossing chips
        n_ar = len(re.findall(r" all-reduce(?:-start)?\(", text))
        assert n_ar == DEPTH, n_ar
        assert "all-gather" not in text and "all-to-all" not in text


SCORE_ROWS = 2_750_000      # a quarter of HIGGS: the benchmark's frame


def _walk_args(sds_of, rows, ntrees, depth):
    nodes = 2 ** (depth + 1) - 1
    tbl = [sds_of((ntrees, nodes), d, P())
           for d in (jnp.int32, jnp.float32, jnp.bool_, jnp.float32)]
    return (sds_of((rows, C_REAL), jnp.float32, P(MESH.ROWS)), *tbl,
            sds_of((ntrees,), jnp.float32, P()),
            sds_of((1, 1, 1), jnp.uint32, P()), sds_of((1,), jnp.bool_, P()))


@pytest.mark.parametrize("ntrees,depth,chips", [(10, 8, 1), (20, 5, 1),
                                                (10, 8, 4), (50, 5, 1),
                                                (3, 9, 1)])
def test_dense_walk_compiles_for_v5e_at_frame_size(ntrees, depth, chips,
                                                   topo, sds,
                                                   no_persistent_cache,
                                                   monkeypatch):
    """gbm_higgs (10 x depth 8: two blocks a tree), gbm_higgs_defaults (20 x
    depth 5: four trees a block), the defaults' published 50 trees (12.5
    groups) and a tree with a level under the path-matched eight: the shape
    picks the dense body, on the TPU the fused kernel
    (`walk_dense_tile`, which Mosaic must accept at these shapes); the
    program holds no gather, keeps its name, and nothing of (rows x slots)
    size is left outside the kernel (2,750,000 x 256 f32 is 2.8 GB)."""
    assert E._walk_path(depth, C_REAL, False) == "dense"
    # the dispatcher asks the default backend, which is the CPU here
    monkeypatch.setattr(WP, "use_pallas", lambda: True)
    mesh = None
    if chips == 1:
        args = _walk_args(lambda shape, dt, _: sds(shape, dt), SCORE_ROWS,
                          ntrees, depth)
    else:
        mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                    (MESH.ROWS, MESH.MODEL))
        args = _walk_args(
            lambda shape, dt, spec: jax.ShapeDtypeStruct(
                shape, dt, sharding=NamedSharding(mesh, spec)),
            MESH.Cloud(mesh).padded_rows(SCORE_ROWS), ntrees, depth)
    before = HP.kernel_traces()
    compiled = E._ensemble_walk.__wrapped__.lower(
        *args, depth=depth, has_cat=False, mesh=mesh).compile()
    picked = {k for k, v in HP.kernel_traces().items()
              if v > before.get(k, 0)}
    assert picked == {("walk_dense_tile", 1 << depth)}
    text = compiled.as_text()
    assert text.startswith("HloModule jit__ensemble_walk")
    assert "tpu_custom_call" in text
    assert not re.search(r" gather\(", text)
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op       # each chip walks its own rows
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


@pytest.mark.parametrize("chips", [1, 4])
def test_prediction_planes_compile_for_v5e_at_frame_size(chips, topo, sds,
                                                         no_persistent_cache):
    """The one program that makes a prediction frame's planes from the
    walk's output (ISSUE 31), at the benchmark's frame: three (data, mask)
    pairs, the label int8; over a row-sharded `out` on the 2x2 host every
    plane comes out row-sharded and the program holds no collective — each
    chip packs its own rows."""
    from h2o3_tpu.models.model import _prediction_planes
    if chips == 1:
        pad, out_sh = SCORE_ROWS, None
        out = sds((pad, 2), jnp.float32)
    else:
        mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                    (MESH.ROWS, MESH.MODEL))
        pad = MESH.Cloud(mesh).padded_rows(SCORE_ROWS - 5)
        out_sh = NamedSharding(mesh, P(MESH.ROWS))
        out = jax.ShapeDtypeStruct(
            (pad, 2), jnp.float32,
            sharding=NamedSharding(mesh, P(MESH.ROWS, None)))
    compiled = jax.jit(_prediction_planes(SCORE_ROWS - 5, "i8"),
                       out_shardings=out_sh).lower(out).compile()
    text = compiled.as_text()
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
    planes = compiled.out_info
    assert [[p.shape for p in col] for col in planes] == [[(pad,)] * 2] * 3
    assert [[str(p.dtype) for p in col] for col in planes] == \
        [["int8", "uint8"], ["float32", "uint8"], ["float32", "uint8"]]
    if chips == 4:
        for sh in jax.tree_util.tree_leaves(compiled.output_shardings):
            assert sh.is_equivalent_to(out_sh, 1), sh
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("link,chips", [("logistic", 1), ("softmax", 1),
                                        ("softmax", 4)])
def test_linked_planes_compile_for_v5e_at_frame_size(link, chips, topo, sds,
                                                     no_persistent_cache,
                                                     monkeypatch):
    """The one program from the walk's sums to a prediction frame's planes,
    the link inside (`model._linked_planes`), at the cells' frames: the
    HIGGS cells' (2,750,000,) sums of two classes, which never become a
    (rows, 2) matrix, and the KDD cell's (2,449,215 + 1, 23) — on the TPU
    ONE kernel (`class_planes`, which Mosaic must accept: a (23, rows) tile
    of the sums' own bytes in, 48 one-dimensional planes out), and over
    the host's four chips a kernel a chip with no collective."""
    from h2o3_tpu.models.model import _linked_planes
    from h2o3_tpu.ops import planes_pallas as PP
    monkeypatch.setattr(PP, "use_pallas", lambda: True)
    K = 2 if link == "logistic" else KDD_CLASSES
    n = SCORE_ROWS - 5 if link == "logistic" else KDD_ROWS
    mesh, pad, out_sh = None, n + 5 if link == "logistic" else n + 1, None
    shape = (pad,) if link == "logistic" else (pad, K)
    sums, f0 = sds(shape, jnp.float32), sds(shape[1:], jnp.float32)
    if chips == 4:
        mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                    (MESH.ROWS, MESH.MODEL))
        pad = MESH.Cloud(mesh).padded_rows(n)
        out_sh = NamedSharding(mesh, P(MESH.ROWS))
        sums = jax.ShapeDtypeStruct((pad, K), jnp.float32,
                                    sharding=NamedSharding(mesh,
                                                           P(MESH.ROWS, None)))
        f0 = jax.ShapeDtypeStruct((K,), jnp.float32,
                                  sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(_linked_planes(n, "i8", 0.1, link, mesh),
                       out_shardings=out_sh).lower(sums, f0).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_linked_planes")
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
    planes = compiled.out_info
    assert [[p.shape for p in col] for col in planes] == \
        [[(pad,)] * 2] * (K + 1)
    assert [[str(p.dtype) for p in col] for col in planes] == \
        [["int8", "uint8"]] + [["float32", "uint8"]] * K
    if link == "logistic":
        assert "tpu_custom_call" not in text
        assert f"f32[{pad},2]" not in text
    else:
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              text)) == 1
        assert not re.search(r" (fusion|slice|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# ---- the airline configuration (benchmark/configs/gbm_airline.json): eight
# columns, six categorical, Origin and Dest past a code byte -------------------
AIR_ROWS = 7_720_935
AIR_LEVELS = (12, 31, 7, 0, 29, 340, 340, 0)


def _airline_spec():
    levels = np.array(AIR_LEVELS)
    spec = BN.make_bins(np.zeros((64, 8), np.float32), levels > 0, 255,
                        cat_levels=levels)
    return spec, spec.planes


# the scoring walk's set shapes: (ntrees, depth, chips, the columns' levels
# known) — the cell's own, two blocks a tree's top, a position level under
# them, the cell's over the host's four chips, and a MOJO of the same table
# (384 bits a column, 48 byte rows: K' = 384)
WALK_SETS = {"walk_sets": (20, 5, 1, True), "walk_sets_d8": (10, 8, 1, True),
             "walk_sets_d9": (3, 9, 1, True),
             "walk_sets_4chips": (20, 5, 4, True),
             "walk_sets_mojo": (20, 5, 1, False)}


@pytest.mark.parametrize("name", ["route_planes", "fused_planes",
                                  "quantize_planes", *WALK_SETS])
def test_airline_programs_compile_for_v5e(name, topo, sds,
                                          no_persistent_cache, monkeypatch):
    """What a frame with a column past a code byte adds to the programs
    (models/tree/binned.py `Planes`): the route kernels reading a split
    column's code from one of two byte planes, at 16 plane columns of 256
    bins and 7,720,935 rows; the program that makes the planes; and the
    scoring walk with the set match INSIDE the fused kernel
    (`walk_dense_tile_sets`, which Mosaic must accept at these shapes),
    which must stay `jit__ensemble_walk`, hold no gather and no collective,
    and leave nothing of (rows x byte rows) size outside VMEM (7,720,936 x
    128 int8 would be 0.99 GB; the program's temp is under 16 MB)."""
    spec, pl = _airline_spec()
    assert (pl.cp_pad, pl.per, pl.n_search, spec.n_bins) == (16, 2, 384, 256)
    n_pad = -(-(AIR_ROWS + 1) // HP.BLOCK_ROWS) * HP.BLOCK_ROWS
    w_pad = HP.packed_words(pl.cp_pad)
    codes, heap = sds((w_pad, n_pad), jnp.int32), sds((n_pad,), jnp.int32)
    stats = sds((HP.S_STATS, n_pad), jnp.float32)

    def tables(L):
        Lp = max(8, L)
        return sds((8, Lp), jnp.float32), \
            sds((Lp, pl.per * spec.n_bins), jnp.float32)
    if name == "route_planes":
        nodes_p = -(-(2 ** 6) // 128) * 128
        # depth 5 routes its first four levels in the fused kernel: the
        # terminal route is the one route kernel its trainer holds
        lowered = jax.jit(lambda a: HP.sbh_route_pallas(
            *a, base=15, L=16, eta=0.1, emit_f=True, any_cat=True,
            na_code=spec.b_val, planes=pl.per)).lower(
            (codes, heap, *tables(16), sds((8, nodes_p), jnp.float32),
             sds((n_pad,), jnp.float32)))
    elif name == "fused_planes":
        assert HP._fused_applicable(16, spec.n_bins, w_pad * HP.PACK)
        lowered = jax.jit(lambda a: HP.sbh_route_hist_fused_pallas(
            *a, base_r=7, L_r=8, base_h=15, L_h=16, n_bins=spec.n_bins,
            any_cat=True, na_code=spec.b_val, planes=pl.per)).lower(
            (codes, heap, *tables(8), stats))
    elif name == "quantize_planes":
        lowered = BN._quantize_planes.lower(
            sds((AIR_ROWS, 8), jnp.float32),
            sds(spec.edges.shape, jnp.float32), sds((8,), jnp.int32),
            *(sds((pl.cp_pad,), d) for d in (jnp.int32, jnp.int32,
                                             jnp.bool_)), n_pad=n_pad)
    else:
        ntrees, depth, chips, known = WALK_SETS[name]
        nodes, words = 2 ** (depth + 1) - 1, 12
        cats = tuple((c, k if known else 32 * words)
                     for c, k in enumerate(AIR_LEVELS) if k)
        assert E._walk_path(depth, 8, sum(k for _, k in cats)) == "dense"
        # the dispatcher asks the default backend, which is the CPU here
        monkeypatch.setattr(WP, "use_pallas", lambda: True)
        assert E._dense_body(cats) == "kernel"
        mesh, rows = None, AIR_ROWS + 1
        if chips == 4:
            mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                        (MESH.ROWS, MESH.MODEL))
            rows = MESH.Cloud(mesh).padded_rows(AIR_ROWS)

        def of(shape, dt, spec):
            return sds(shape, dt) if mesh is None else jax.ShapeDtypeStruct(
                shape, dt, sharding=NamedSharding(mesh, spec))
        tbl = [of((ntrees, nodes), d, P()) for d in
               (jnp.int32, jnp.float32, jnp.bool_, jnp.float32)]
        before = HP.kernel_traces()
        lowered = E._ensemble_walk.__wrapped__.lower(
            of((rows, 8), jnp.float32, P(MESH.ROWS)), *tbl,
            of((ntrees,), jnp.float32, P()),
            of((ntrees, nodes, words), jnp.uint32, P()),
            of((8,), jnp.bool_, P()),
            depth=depth, has_cat=True, cats=cats, mesh=mesh)
        picked = {k for k, v in HP.kernel_traces().items()
                  if v > before.get(k, 0)}
        assert picked == {("walk_dense_tile_sets", 1 << depth)}
    compiled = lowered.compile()    # raises what the chip's compiler would
    text = compiled.as_text()
    if name in WALK_SETS:
        assert text.startswith("HloModule jit__ensemble_walk")
        assert "tpu_custom_call" in text
        assert not re.search(r" gather\(", text)
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute"):
            assert op not in text, op       # each chip walks its own rows
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    elif name != "quantize_planes":
        assert "tpu_custom_call" in text


# ---- the KDD Cup 1999 configuration (benchmark/configs/gbm_kddcup99.json):
# 41 columns, three categorical (K' = 128 level rows), 23 classes ------------
KDD_ROWS, KDD_COLS, KDD_CLASSES = 2_449_215, 41, 23
KDD_LEVELS = (0, 3, 70, 11) + (0,) * 37

# the class walk's shapes: (ntrees, depth, chips) — the cell's own (10
# iterations x 23 classes: 58 blocks of four trees), two blocks a tree's
# top, the cell's over the host's four chips
WALK_CLASSES = {"walk_classes": (230, 5, 1), "walk_classes_d8": (46, 8, 1),
                "walk_classes_4chips": (230, 5, 4)}


@pytest.mark.parametrize("name", [*WALK_CLASSES, "gbm_multi_trainer",
                                  "planes_24"])
def test_kddcup99_programs_compile_for_v5e(name, topo, sds,
                                           no_persistent_cache, monkeypatch):
    """What a response of 23 classes adds to the programs: the scoring walk
    with the accumulator a row a class (`walk_dense_tile_sets_classes`,
    whose dynamic-sublane update Mosaic must accept), still
    `jit__ensemble_walk`, no gather, no collective, nothing of (rows x
    slots) size outside the kernel — its (rows, 23) output is all it
    leaves; the K-tree trainer `gbm_multi_chunk_trainer` as
    `_fit_binned_multinomial` builds it (23 trees an iteration in one
    program, the Pallas histogram kernels inside); and the one program
    that makes a frame's 24 prediction planes."""
    cats = tuple((c, k) for c, k in enumerate(KDD_LEVELS) if k)
    if name == "planes_24":
        from h2o3_tpu.models.model import _prediction_planes
        compiled = jax.jit(_prediction_planes(KDD_ROWS, "i8")).lower(
            sds((KDD_ROWS + 1, KDD_CLASSES), jnp.float32)).compile()
        planes = compiled.out_info
        assert [[str(p.dtype) for p in col] for col in planes] == \
            [["int8", "uint8"]] + [["float32", "uint8"]] * KDD_CLASSES
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
        return
    if name == "gbm_multi_trainer":
        monkeypatch.setattr(HP, "use_pallas", lambda: True)
        levels = np.array(KDD_LEVELS)
        spec = BN.make_bins(
            np.random.default_rng(0).normal(size=(4096, KDD_COLS))
            .astype(np.float32), levels > 0, 70, cat_levels=levels)
        assert (spec.c_pad, spec.n_bins, spec.planes) == (48, 128, None)
        grower = BN.BinnedGrower(spec, max_depth=5, min_rows=10.0,
                                 min_split_improvement=1e-5)
        n_pad = grower.layout(KDD_ROWS)
        trainer = BN.gbm_multi_chunk_trainer(
            grower, KDD_ROWS, n_classes=KDD_CLASSES, eta=0.1,
            sample_rate=1.0, mtries=0, k_iters=5)
        row = sds((n_pad,), jnp.float32)
        compiled = trainer.lower(
            sds((HP.packed_words(spec.c_pad), n_pad), jnp.int32), row, row,
            sds((n_pad, KDD_CLASSES), jnp.float32),
            sds((2,), jnp.uint32)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        # the margins, residuals and one-hot labels of 23 classes
        assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
        return
    ntrees, depth, chips = WALK_CLASSES[name]
    nodes = 2 ** (depth + 1) - 1
    assert E._walk_path(depth, KDD_COLS, sum(k for _, k in cats)) == "dense"
    assert WP.set_rows(cats) == 128
    # the dispatcher asks the default backend, which is the CPU here
    monkeypatch.setattr(WP, "use_pallas", lambda: True)
    mesh, rows = None, KDD_ROWS + 1
    if chips == 4:
        mesh = Mesh(np.array(topo.devices).reshape(-1, 1),
                    (MESH.ROWS, MESH.MODEL))
        rows = MESH.Cloud(mesh).padded_rows(KDD_ROWS)

    def of(shape, dt, spec):
        return sds(shape, dt) if mesh is None else jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, spec))
    tbl = [of((ntrees, nodes), d, P()) for d in
           (jnp.int32, jnp.float32, jnp.bool_, jnp.float32)]
    before = HP.kernel_traces()
    compiled = E._ensemble_walk.__wrapped__.lower(
        of((rows, KDD_COLS), jnp.float32, P(MESH.ROWS)), *tbl,
        of((ntrees,), jnp.float32, P()),
        of((ntrees, nodes, 3), jnp.uint32, P()),
        of((KDD_COLS,), jnp.bool_, P()), of((ntrees,), jnp.int32, P()),
        depth=depth, has_cat=True, cats=cats, mesh=mesh,
        classes=KDD_CLASSES).compile()
    picked = {k for k, v in HP.kernel_traces().items()
              if v > before.get(k, 0)}
    assert picked == {("walk_dense_tile_sets_classes", 1 << depth)}
    text = compiled.as_text()
    assert text.startswith("HloModule jit__ensemble_walk")
    assert "tpu_custom_call" in text
    assert not re.search(r" gather\(", text)
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op       # each chip walks its own rows
    assert compiled.out_info.shape == (rows, KDD_CLASSES)
    # the kernel's (24, rows) output and its transpose: 2 x 235 MB a chip
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


@pytest.mark.parametrize("ntrees,depth", [(10, 20)])
def test_deep_walk_compiles_for_v5e_at_frame_size(ntrees, depth, topo, sds,
                                                  no_persistent_cache,
                                                  monkeypatch):
    """drf_higgs_defaults (10 trees x depth 20, past the dense bound): the
    shape picks the deep body — the tops of the trees through the kernel's
    class variant under its own name (`walk_dense_tile_top`, which Mosaic
    must accept at this shape), the levels under them by gathers — in the
    one module `jit__ensemble_walk`, within the chip's memory."""
    assert E._walk_path(depth, C_REAL, False) == "deep"
    monkeypatch.setattr(WP, "use_pallas", lambda: True)
    args = _walk_args(lambda shape, dt, _: sds(shape, dt), SCORE_ROWS,
                      ntrees, depth)
    before = HP.kernel_traces()
    compiled = E._ensemble_walk.__wrapped__.lower(
        *args, depth=depth, has_cat=False).compile()
    picked = {k for k, v in HP.kernel_traces().items()
              if v > before.get(k, 0)}
    assert picked == {("walk_dense_tile_top", 1 << E._DEEP_TOP)}
    text = compiled.as_text()
    assert text.startswith("HloModule jit__ensemble_walk")
    assert "tpu_custom_call" in text and re.search(r" gather\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
    # the tail's level step reads X once: no (1, rows) slice of X's
    # transpose a column (28 of them in 62 fusions a step when a select a
    # column picked the feature), ONE gather (the node's packed row)
    comps, step = _level_step(text)
    assert _deep_ops(comps, step, rf" = \w+\[1,{SCORE_ROWS}\]\S* slice\(") \
        == 0
    assert 0 < sum(" fusion(" in line for line in step) <= 12
    assert _deep_ops(comps, step, r" gather\(") == 1


def _computations(text):
    """{name: instruction lines} of each computation of an HLO module."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            body = comps[head.group(1)] = []
        elif body is not None and line.startswith("  "):
            body.append(line)
    return comps


def _level_step(text):
    """(computations, the instruction lines of the deep walk's level
    step): the body of the `walk.tail` while loop that holds no loop."""
    comps = _computations(text)
    bodies = [comps[m] for m in re.findall(
        r" while\(.*body=%([\w.\-]+).*walk\.tail", text)]
    inner = [b for b in bodies if not any(" while(" in ln for ln in b)]
    assert len(inner) == 1, len(inner)
    return comps, inner[0]


def _deep_ops(comps, lines, pattern):
    """How many instructions match `pattern` in `lines` and in the
    computations they call (a fusion's ops live in its computation)."""
    return sum(bool(re.search(pattern, line)) + sum(
        _deep_ops(comps, comps[c], pattern)
        for c in re.findall(r"calls=%([\w.\-]+)", line)) for line in lines)
