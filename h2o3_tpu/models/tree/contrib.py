"""SHAP predict_contributions for tree ensembles.

Reference surface: hex/Model.PredictContributions + the genmodel per-algo
contribution scorers (GBM/DRF/XGBoost MOJOs); h2o-py
`model.predict_contributions(frame)` returns one column per feature plus
`BiasTerm`, summing to the margin prediction per row.

Implementation: exact path-dependent TreeSHAP (Lundberg & Lee) over the dense
heap trees, in native C++ (native/treeshap.cpp, ctypes ABI like the CSV
parser) — scoring artifacts are host-side in the reference too; the TPU chips
stay on the training path. Node covers are recorded on device during
training (engine.node_covers)."""

from __future__ import annotations

import ctypes

import numpy as np

_LIB = None


def _lib():
    global _LIB
    if _LIB is None:
        from h2o3_tpu.io.fastcsv import load_native
        try:
            lib = load_native("treeshap")
        except OSError as e:
            raise RuntimeError(
                "native TreeSHAP library (native/treeshap.cpp) could be "
                f"neither loaded nor built: {e}") from e
        lib.treeshap_ensemble.restype = None
        lib.treeshap_ensemble.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double)]
        lib.treeshap_ensemble_cat.restype = None
        lib.treeshap_ensemble_cat.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
        _LIB = lib
    return _LIB


def ensemble_shap(trees, X: np.ndarray) -> np.ndarray:
    """phi (n, C+1) for one TreeArrays ensemble; raw (unscaled) tree values.
    X: (n, C) float64, NaN = NA."""
    col = np.ascontiguousarray(np.asarray(trees.col), np.int32)
    thr = np.ascontiguousarray(np.asarray(trees.thr), np.float32)
    nal = np.ascontiguousarray(np.asarray(trees.na_left), np.uint8)
    val = np.ascontiguousarray(np.asarray(trees.value), np.float32)
    assert trees.cover is not None, \
        "model was trained before covers were recorded; retrain to get SHAP"
    cov = np.ascontiguousarray(np.asarray(trees.cover), np.float32)
    X = np.ascontiguousarray(X, np.float64)
    n, C = X.shape
    T, nodes = col.shape
    phi = np.zeros((n, C + 1), np.float64)
    has_cat = (trees.catbits is not None and trees.col_is_cat is not None
               and bool(np.any(np.asarray(trees.col_is_cat))))
    if has_cat:
        catb = np.ascontiguousarray(np.asarray(trees.catbits), np.uint32)
        iscat = np.zeros(C, np.uint8)
        flags = np.asarray(trees.col_is_cat, bool)
        iscat[: min(C, flags.size)] = flags[:C]
        _lib().treeshap_ensemble_cat(
            T, nodes, trees.depth, C, n,
            col.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            thr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            nal.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            val.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            cov.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            catb.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            iscat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            int(catb.shape[-1]),
            X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            phi.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        return phi
    _lib().treeshap_ensemble(
        T, nodes, trees.depth, C, n,
        col.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        thr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        nal.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        val.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        cov.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        phi.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return phi
