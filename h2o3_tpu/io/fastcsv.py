"""ctypes bridge to the native CSV parser (native/fastcsv.cpp).

The reference's ingest hot loop is a JVM per-byte tokenizer
(water/parser/CsvParser.java); here it's a C++ pass with an in-place
numeric fast path (exact Clinger fast-float + SWAR digit extraction, see
fastcsv.cpp) exporting column-major doubles + a string side table over a
C ABI (no pybind11 in the image). Two entry points feed the distributed
ingest pipeline (io/dparse.py): `parse_columns` for byte ranges of local
files (the native code does its own read, so pool threads overlap read
with tokenize) and `parse_bytes_columns` for caller-staged buffers
(streaming-decompressed gzip/zip windows, HTTP/object-store range reads).
Build: `load_native` runs `make -C native` on first use when the library
is missing or older than its committed source (the binaries are not
tracked); the Python parser falls back to the csv module when the
library can be neither found nor built.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess

import numpy as np

from h2o3_tpu.obs import metrics as _om
from h2o3_tpu.utils.env import env_str
from h2o3_tpu.obs.timeline import span as _span

# bytes handed to the native tokenizer (per byte-range call — the sum over
# ranges equals the file bytes, so this tracks true tokenizer throughput)
FASTCSV_BYTES = _om.counter("h2o3_fastcsv_bytes_total",
                            "bytes tokenized by the native CSV parser")

_LIB = None


def native_dir() -> str:
    """Directory holding the native .so builds (H2O3_NATIVE_DIR override;
    default <repo>/native). Declaration site for the variable — the
    TreeSHAP loader (models/tree/contrib) imports this helper."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return env_str("H2O3_NATIVE_DIR", "") or os.path.join(here, "native")


def _stale(so: str, src: str) -> bool:
    try:
        return os.path.getmtime(so) < os.path.getmtime(src)
    except OSError:
        return True             # no library yet


def load_native(name: str) -> ctypes.CDLL:
    """dlopen native/lib<name>.so, first building it from <name>.cpp when
    that source is present and the library is missing or older. Safe
    under concurrent first use (xdist workers, pool processes): builders
    serialize on a lock file and re-check under it, and the Makefile
    renames the finished library into place, so a process that skips the
    lock because the library looks fresh never maps a half-written file.
    A build that cannot run raises OSError, like a missing library."""
    d = native_dir()
    so = os.path.join(d, f"lib{name}.so")
    src = os.path.join(d, f"{name}.cpp")
    if os.path.exists(src) and _stale(so, src):
        with open(os.path.join(d, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if _stale(so, src):
                try:
                    subprocess.run(["make", "-C", d, f"lib{name}.so"],
                                   check=True, capture_output=True,
                                   text=True)
                except subprocess.CalledProcessError as e:
                    raise OSError(f"building lib{name}.so failed:\n"
                                  f"{e.stderr[-2000:]}") from e
    return ctypes.CDLL(so)


def _lib():
    global _LIB
    if _LIB is None:
        lib = load_native("fastcsv")
        lib.fastcsv_parse.restype = ctypes.c_void_p
        lib.fastcsv_parse.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                      ctypes.c_int]
        lib.fastcsv_parse_range.restype = ctypes.c_void_p
        lib.fastcsv_parse_range.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                            ctypes.c_long, ctypes.c_long,
                                            ctypes.c_int]
        lib.fastcsv_parse_bytes.restype = ctypes.c_void_p
        lib.fastcsv_parse_bytes.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                            ctypes.c_char, ctypes.c_int,
                                            ctypes.c_int]
        lib.fastcsv_nrows.restype = ctypes.c_int64
        lib.fastcsv_nrows.argtypes = [ctypes.c_void_p]
        lib.fastcsv_ncols.restype = ctypes.c_int64
        lib.fastcsv_ncols.argtypes = [ctypes.c_void_p]
        lib.fastcsv_col_data.restype = ctypes.POINTER(ctypes.c_double)
        lib.fastcsv_col_data.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.fastcsv_col_nstr.restype = ctypes.c_int64
        lib.fastcsv_col_nstr.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.fastcsv_col_na.restype = ctypes.c_int64
        lib.fastcsv_col_na.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.fastcsv_str_row.restype = ctypes.c_int64
        lib.fastcsv_str_row.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.fastcsv_str_val.restype = ctypes.c_char_p
        lib.fastcsv_str_val.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.fastcsv_str_rows_ptr.restype = ctypes.POINTER(ctypes.c_int64)
        lib.fastcsv_str_rows_ptr.argtypes = [ctypes.c_void_p,
                                             ctypes.c_int64]
        lib.fastcsv_str_lens_ptr.restype = ctypes.POINTER(ctypes.c_int32)
        lib.fastcsv_str_lens_ptr.argtypes = [ctypes.c_void_p,
                                             ctypes.c_int64]
        lib.fastcsv_str_bytes_ptr.restype = ctypes.POINTER(ctypes.c_char)
        lib.fastcsv_str_bytes_ptr.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int64]
        lib.fastcsv_str_bytes_len.restype = ctypes.c_int64
        lib.fastcsv_str_bytes_len.argtypes = [ctypes.c_void_p,
                                              ctypes.c_int64]
        lib.fastcsv_free.argtypes = [ctypes.c_void_p]
        _LIB = lib
    return _LIB


def available() -> bool:
    try:
        _lib()
        return True
    except OSError:
        return False


def _extract_columns(lib, h):
    """(numeric ndarray, {row: str}) per column from a parse handle.
    The string side table ships through the BULK export (three planes:
    rows / lens / concatenated bytes) — the old per-cell
    fastcsv_str_row/fastcsv_str_val pair cost two ctypes round trips per
    string cell, which dominated categorical-heavy ingest."""
    nrows = lib.fastcsv_nrows(h)
    ncols = lib.fastcsv_ncols(h)
    out = []
    for j in range(ncols):
        ptr = lib.fastcsv_col_data(h, j)
        arr = np.ctypeslib.as_array(ptr, shape=(nrows,)).copy() \
            if nrows else np.empty(0, np.float64)
        nstr = lib.fastcsv_col_nstr(h, j)
        smap = {}
        if nstr:
            rows = np.ctypeslib.as_array(
                lib.fastcsv_str_rows_ptr(h, j), shape=(nstr,))
            lens = np.ctypeslib.as_array(
                lib.fastcsv_str_lens_ptr(h, j), shape=(nstr,))
            blen = lib.fastcsv_str_bytes_len(h, j)
            raw = ctypes.string_at(lib.fastcsv_str_bytes_ptr(h, j), blen)
            offs = np.concatenate([[0], np.cumsum(lens)])
            for i in range(nstr):
                smap[int(rows[i])] = raw[offs[i]:offs[i + 1]].decode(
                    "utf-8", "replace")
        out.append((arr, smap))
    return out


def parse_columns(path: str, sep: str, header: bool,
                  start: int = 0, end: int = -1):
    """Returns list of (numeric ndarray, {row: str}) per column, for the
    byte range [start, end) (chunk-boundary semantics: a range at
    start > 0 begins after the first newline and runs through the line
    straddling `end` — the MultiFileParseTask chunk contract). The
    ctypes call releases the GIL, so ThreadPoolExecutor over ranges
    tokenizes in true parallel."""
    lib = _lib()
    try:
        span_bytes = (end if end >= 0 else os.path.getsize(path)) - start
    except OSError:
        span_bytes = 0
    with _span("parse.tokenize", engine="fastcsv", start=start, end=end):
        h = lib.fastcsv_parse_range(path.encode(), sep.encode(),
                                    start, end, 1 if header else 0)
    if not h:
        raise IOError(f"fastcsv failed on {path}")
    FASTCSV_BYTES.inc(max(span_bytes, 0))
    try:
        return _extract_columns(lib, h)
    finally:
        lib.fastcsv_free(h)


def parse_bytes_columns(buf: bytes, sep: str, header: bool,
                        skip_partial_first: bool = False):
    """Tokenize caller-staged bytes (a streaming-decompressed gzip/zip
    window, an HTTP range read) with the same chunk contract as
    `parse_columns`: `skip_partial_first` applies the start>0 half (the
    head up to the first newline belongs to the previous chunk);
    otherwise the buffer must hold whole lines. Same return shape."""
    lib = _lib()
    # h2o3-ok: R011 same tokenize stage as the range entry above — one engine, two native entry points
    with _span("parse.tokenize", engine="fastcsv_bytes", nbytes=len(buf)):
        h = lib.fastcsv_parse_bytes(buf, len(buf), sep.encode(),
                                    1 if header else 0,
                                    1 if skip_partial_first else 0)
    if not h:
        raise IOError("fastcsv failed on byte buffer")
    FASTCSV_BYTES.inc(len(buf))
    try:
        return _extract_columns(lib, h)
    finally:
        lib.fastcsv_free(h)
