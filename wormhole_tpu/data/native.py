"""ctypes binding to the native C++ chunk parsers (``native/`` at repo root).

The Python parsers in parsers.py are the reference implementations; the C++
library is the hot path for streaming throughput (SURVEY.md §7 hard part (d):
matching GB/s-scale parsing from hosts). ``get_parser`` returns None when the
shared library is absent so everything degrades gracefully.
"""

from __future__ import annotations

import ctypes
import os
from typing import Callable, Optional

import numpy as np

from wormhole_tpu.data.rowblock import RowBlock

_LIB = None
_TRIED = False
_BUILD_ERROR = ""   # why the one-shot `make` failed, for build_error()

_LIB_NAMES = ("libwormhole_data.so",)


def _find_lib() -> Optional[str]:
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    candidates = [os.path.join(here, "native", "build", n) for n in _LIB_NAMES]
    candidates += [os.path.join(here, "native", n) for n in _LIB_NAMES]
    env = os.environ.get("WORMHOLE_NATIVE_LIB")
    if env:
        candidates.insert(0, env)
    for c in candidates:
        if os.path.exists(c):
            return c
    return None


def _try_build() -> Optional[str]:
    """Best-effort one-shot `make` of the native library (a fresh checkout
    has no build/ — the hot path should not silently fall back to Python
    parsing on machines that have a toolchain). A file lock serializes
    concurrent builders (multi-process launches on a fresh checkout would
    otherwise clobber each other's half-written .so)."""
    import subprocess
    global _BUILD_ERROR
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ndir = os.path.join(here, "native")
    if not os.path.exists(os.path.join(ndir, "Makefile")):
        return None
    try:
        import fcntl
        with open(os.path.join(ndir, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)   # waits for a peer's build
            found = _find_lib()
            if found:                          # a peer built it first
                return found
            subprocess.run(["make", "-C", ndir], capture_output=True,
                           text=True, timeout=120, check=True)
    except (OSError, subprocess.SubprocessError) as e:
        # the compiler's own words, not just the exit status
        _BUILD_ERROR = f"{e}: {(getattr(e, 'stderr', '') or '')[-2000:]}"
        from wormhole_tpu.utils.logging import get_logger
        get_logger("native").warning(
            "native build FAILED (%s); the Python parsers are live",
            _BUILD_ERROR)
        return None
    return _find_lib()


def _load():
    global _LIB, _TRIED, _BUILD_ERROR
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("WORMHOLE_DISABLE_NATIVE"):
        return None
    path = _find_lib() or _try_build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as e:
        _BUILD_ERROR = f"cannot load {path}: {e}"
        return None
    # int wh_parse(const char* fmt, const char* buf, int64 len,
    #              ParseOut* out);  see native/parse.cc for the ABI
    lib.wh_parse_count.restype = ctypes.c_int64
    lib.wh_parse_count.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]  # out: rows, nnz
    lib.wh_parse_fill.restype = ctypes.c_int
    lib.wh_parse_fill.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),   # offsets (rows+1)
        ctypes.POINTER(ctypes.c_float),   # labels  (rows)
        ctypes.POINTER(ctypes.c_uint64),  # index   (nnz)
        ctypes.POINTER(ctypes.c_float),   # values  (nnz)
        ctypes.POINTER(ctypes.c_int)]     # has_value flag out
    if hasattr(lib, "wh_parse_to_crec"):
        lib.wh_parse_to_crec.restype = ctypes.c_int64
        lib.wh_parse_to_crec.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint32),  # keys (rows*nnz)
            ctypes.POINTER(ctypes.c_uint8)]   # labels (rows)
    _LIB = lib
    return _LIB


def get_crec_assembler(fmt: str, nnz: int):
    """C-side text chunk -> crec row assembly: parse + key64->u32 fold +
    fixed-nnz sentinel padding + label binarization in one native pass
    (the per-row Python glue the round-3 verdict measured as the text
    ingest bottleneck). Returns fn(chunk) -> (keys (n, nnz) u32,
    labels (n,) u8), or None when the library (or symbol) is absent."""
    lib = _load()
    if lib is None or not hasattr(lib, "wh_parse_to_crec"):
        return None
    if fmt not in ("libsvm", "criteo", "adfea"):
        return None
    cfmt = fmt.encode()

    def assemble(chunk: bytes):
        counts = (ctypes.c_int64 * 2)()
        rc = lib.wh_parse_count(cfmt, chunk, len(chunk), counts)
        if rc < 0:
            raise ValueError(f"native parse_count failed for {fmt}")
        rows = counts[0]
        keys = np.empty((max(rows, 1), nnz), np.uint32)
        labels = np.empty(max(rows, 1), np.uint8)
        got = lib.wh_parse_to_crec(
            cfmt, chunk, len(chunk), nnz,
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if got != rows:
            raise ValueError(f"native crec assembly failed for {fmt}")
        return keys[:rows], labels[:rows]

    return assemble


def get_parser(fmt: str) -> Optional[Callable[[bytes], RowBlock]]:
    lib = _load()
    if lib is None:
        return None
    if fmt not in ("libsvm", "criteo", "adfea"):
        return None
    cfmt = fmt.encode()

    def parse(chunk: bytes) -> RowBlock:
        counts = (ctypes.c_int64 * 2)()
        rc = lib.wh_parse_count(cfmt, chunk, len(chunk), counts)
        if rc < 0:
            raise ValueError(f"native parse_count failed for {fmt}")
        rows, nnz = counts[0], counts[1]
        offsets = np.empty(rows + 1, np.int64)
        labels = np.empty(rows, np.float32)
        index = np.empty(max(nnz, 1), np.uint64)
        values = np.empty(max(nnz, 1), np.float32)
        has_val = ctypes.c_int(0)
        rc = lib.wh_parse_fill(
            cfmt, chunk, len(chunk),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            index.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.byref(has_val))
        if rc != 0:
            raise ValueError(f"native parse_fill failed for {fmt}")
        return RowBlock(
            offset=offsets,
            label=labels,
            index=index[:nnz],
            value=values[:nnz] if has_val.value else None,
        )

    return parse


def available() -> bool:
    return _load() is not None


def build_error() -> str:
    """Why the one-shot ``make`` failed (empty when it did not run or
    succeeded) — chip_smoke.py prints it next to the live parser."""
    _load()
    return _BUILD_ERROR
