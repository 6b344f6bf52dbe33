"""ctypes binding to the native C++ data plane (``native/`` at repo root):
the chunk parsers and the tile encoder.

The Python parsers in parsers.py and the numpy encoder in ops/tilemm.py are
the reference implementations; the C++ library is the hot path for streaming
throughput (SURVEY.md §7 hard part (d): matching GB/s-scale parsing from
hosts). Every getter returns None when the shared library (or its symbol) is
absent so everything degrades gracefully.
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
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")


def _find_lib() -> Optional[str]:
    candidates = [os.path.join(_NATIVE_DIR, "build", n) for n in _LIB_NAMES]
    candidates += [os.path.join(_NATIVE_DIR, n) for n in _LIB_NAMES]
    env = os.environ.get("WORMHOLE_NATIVE_LIB")
    if env:
        candidates.insert(0, env)
    for c in candidates:
        if os.path.exists(c):
            return c
    return None


def _try_build(stale: bool = False) -> Optional[str]:
    """Best-effort one-shot `make` of the native library (a fresh checkout
    has no build/ — the hot path should not silently fall back to Python
    parsing on machines that have a toolchain). A file lock serializes
    concurrent builders (multi-process launches on a fresh checkout would
    otherwise clobber each other's half-written .so). ``stale``: the
    library that is there lacks a symbol, so `make` runs even though one
    is found (a no-op if a peer has rebuilt it meanwhile)."""
    import subprocess
    global _BUILD_ERROR
    ndir = _NATIVE_DIR
    if not os.path.exists(os.path.join(ndir, "Makefile")):
        return None
    try:
        import fcntl
        with open(os.path.join(ndir, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)   # waits for a peer's build
            found = _find_lib()
            if found and not stale:            # a peer built it first
                return found
            subprocess.run(["make", "-C", ndir], capture_output=True,
                           text=True, timeout=120, check=True)
    except (OSError, subprocess.SubprocessError) as e:
        # the compiler's own words, not just the exit status
        _BUILD_ERROR = f"{e}: {(getattr(e, 'stderr', '') or '')[-2000:]}"
        from wormhole_tpu.utils.logging import get_logger
        get_logger("native").warning(
            "native build FAILED (%s); the Python parsers and the numpy "
            "tile encoder are live", _BUILD_ERROR)
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
        if not hasattr(lib, "wh_hot_cut"):
            # found, but built before the tile encoder (or the overflow
            # list's hot form, or its cut by key range) existed
            # (native/build/ is git-ignored and outlives a checkout's
            # update): without this the numpy encoder would run in
            # silence. Unload, `make` once, load what it left.
            import _ctypes
            _ctypes.dlclose(lib._handle)
            lib = ctypes.CDLL(_try_build(stale=True) or path)
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
    u32p = ctypes.POINTER(ctypes.c_uint32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    if hasattr(lib, "wh_tile_count"):
        # see native/tile_encode.cc for the ABI
        lib.wh_tile_count.restype = ctypes.c_int64
        lib.wh_tile_count.argtypes = [
            u32p, ctypes.c_int64, ctypes.c_int64,     # keys, rows, nnz
            ctypes.c_uint32, ctypes.c_int64,          # nb, subblocks
            ctypes.c_int64, ctypes.c_uint32,          # tiles, cap
            u32p, u32p, i64p]                         # buckets, counts, offs
        lib.wh_tile_place.restype = None
        lib.wh_tile_place.argtypes = [
            u32p, ctypes.c_int64, ctypes.c_int64,     # buckets, rows, nnz
            ctypes.c_int64, ctypes.c_int64,           # subblocks, tiles
            ctypes.c_uint32, i64p, u32p,              # cap, offs, counts
            u32p, u32p, u32p]                         # pw, ovf_b, ovf_r
    if hasattr(lib, "wh_hot_rank"):
        lib.wh_hot_rank.restype = ctypes.c_int64
        lib.wh_hot_rank.argtypes = [
            u32p, u32p, ctypes.c_int64, ctypes.c_int64,  # ovf_b, ovf_r, n, S
            u32p, u32p, i64p]                         # uniq, rank, cell_max
        lib.wh_hot_place.restype = ctypes.c_int64
        lib.wh_hot_place.argtypes = [
            u32p, u32p, ctypes.c_int64, ctypes.c_int64,  # rank, ovf_r, n, S
            ctypes.c_int64, ctypes.c_int64,           # tiles, vtiles
            ctypes.c_uint32, i64p, u32p]              # cap, counts, pw
        if hasattr(lib, "wh_hot_cut"):
            lib.wh_hot_cut.restype = ctypes.c_int64
            lib.wh_hot_cut.argtypes = [
                u32p, u32p, ctypes.c_int64,           # ovf_b, ovf_r, n
                ctypes.c_int64, ctypes.c_uint32,      # parts, nb_local
                u32p, u32p, i64p]                     # out_b, out_r, counts
    else:
        from wormhole_tpu.utils.logging import get_logger
        what = ("hot-form encoder" if hasattr(lib, "wh_tile_count")
                else "tile encoder")
        get_logger("native").warning(
            "%s has no %s and could not be rebuilt; the numpy %s is live",
            path, what, what)
    _LIB = lib
    return _LIB


def _u32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


class _SlabPool:
    """Recycled memory for the tile encoder's pair words. A block's ``pw``
    is 201 MB at the criteo geometry, past malloc's largest mmap
    threshold, so ``np.empty`` maps it fresh and every page of it faults
    on first touch: on the chip's host that alone is 260 of a block's 300
    ms (PERF.md section 6, PR 40), six times the encoder's two passes.
    ``empty`` hands out an array over an anonymous mapping this pool owns;
    when the array and every view of it are gone (numpy keeps a view's
    ``base`` at the array, because the mapping under it is no ndarray;
    ``jax.device_put`` holds the array until its transfer is done) the
    mapping comes back, pages in place, for the next block. At most
    ``IDLE_BYTES`` wait idle; beyond that a mapping is let go: room for
    every slab a pass has alive at once, since a pass's end hands them
    all back together (the four-chip group feed has seven to nine alive;
    at 1 GB the pool kept five and every pass mapped two to five afresh,
    a sixth to a third of its blocks at 240 ms each: PERF.md section 6,
    PR 49). No lock:
    the finalizer can run on any thread, also inside ``empty`` (a
    collection), and list ``append``/``pop`` are atomic as they are."""

    IDLE_BYTES = 4 << 30

    def __init__(self) -> None:
        self._idle: list = []     # mmap objects nobody views

    def empty(self, shape, dtype) -> np.ndarray:
        import mmap
        import weakref
        nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
        if not nbytes:
            return np.empty(shape, dtype)
        mm = None
        while mm is None and self._idle:
            try:
                cand = self._idle.pop()
            except IndexError:
                break
            if len(cand) == nbytes:   # another geometry's is let go
                mm = cand
        if mm is None:
            mm = mmap.mmap(-1, nbytes,
                           flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
        arr = np.ndarray(shape, dtype, buffer=mm)
        weakref.finalize(arr, self._give_back, mm).atexit = False
        return arr

    def _give_back(self, mm) -> None:
        if (len(self._idle) + 1) * len(mm) <= self.IDLE_BYTES:
            self._idle.append(mm)


_PW_POOL = _SlabPool()


def _tile_encode(keys: np.ndarray, nb: int, spec):
    """``(pw, ovf_b, ovf_r)`` of a ``(rows, nnz)`` u32 keys grid, the bits
    of ``ops/tilemm.encode_block`` over its folded real keys, in two native
    passes (native/tile_encode.cc): count, then place into arrays sized
    here from the count. Scratch is this call's own, ``pw`` is recycled
    memory no one else views (:class:`_SlabPool`), and ctypes releases
    the GIL for both passes: concurrent callers share nothing."""
    lib = _LIB
    keys = np.ascontiguousarray(keys, np.uint32)
    if keys.ndim != 2:
        raise ValueError(f"keys must be (rows, nnz), got {keys.shape}")
    if not 0 < nb < 1 << 32:
        raise ValueError(f"nb={nb} does not fit the u32 bucket space")
    # rows past the block are no pair (as in encode_block)
    rows, nnz = min(keys.shape[0], spec.block_rows), keys.shape[1]
    cells = spec.subblocks * spec.tiles
    buckets = np.empty(rows * nnz, np.uint32)
    counts = np.empty(cells, np.uint32)
    offs = np.empty(cells, np.int64)
    offs_p = offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    n_ovf = lib.wh_tile_count(_u32p(keys), rows, nnz, nb, spec.subblocks,
                              spec.tiles, spec.cap, _u32p(buckets),
                              _u32p(counts), offs_p)
    pw = _PW_POOL.empty(spec.pairs_shape, np.uint32)
    ovf_b = np.empty(n_ovf, np.uint32)
    ovf_r = np.empty(n_ovf, np.uint32)
    lib.wh_tile_place(_u32p(buckets), rows, nnz, spec.subblocks, spec.tiles,
                      spec.cap, offs_p, _u32p(counts), _u32p(pw),
                      _u32p(ovf_b), _u32p(ovf_r))
    return pw, ovf_b, ovf_r


def get_tile_encoder():
    """The native tile encoder ``fn(keys, nb, spec) -> (pw, ovf_b,
    ovf_r)``, or None when the library (or the symbol) is absent: the
    numpy encoder is then live (data/crec.encode_tile_pairs)."""
    lib = _load()
    if lib is None or not hasattr(lib, "wh_tile_count"):
        return None
    return _tile_encode


def _hot_ranks(ovf_b: np.ndarray, ovf_r: np.ndarray, subblocks: int):
    """``ops/tilemm.hot_ranks`` in one native pass (wh_hot_rank)."""
    ovf_b = np.ascontiguousarray(ovf_b, np.uint32)
    ovf_r = np.ascontiguousarray(ovf_r, np.uint32)
    n = len(ovf_b)
    if len(ovf_r) != n:
        raise ValueError(f"{n} buckets, {len(ovf_r)} rows")
    uniq = np.empty(n, np.uint32)
    rank = np.empty(n, np.uint32)
    cell_max = ctypes.c_int64(0)
    d = _LIB.wh_hot_rank(_u32p(ovf_b), _u32p(ovf_r), n, subblocks,
                         _u32p(uniq), _u32p(rank), ctypes.byref(cell_max))
    return uniq[:d].copy(), rank, int(cell_max.value)


def _hot_place(uniq: np.ndarray, rank: np.ndarray, ovf_r: np.ndarray,
               subblocks: int, tiles: int, vtiles: int):
    """``ops/tilemm.encode_hot`` with the placement native
    (wh_hot_place)."""
    from wormhole_tpu.ops import tilemm
    rank = np.ascontiguousarray(rank, np.uint32)
    ovf_r = np.ascontiguousarray(ovf_r, np.uint32)
    if len(uniq) > tiles * tilemm.TILE or len(rank) != len(ovf_r):
        raise ValueError(f"{len(uniq)} buckets for {tiles} hot tiles, "
                         f"{len(rank)} ranks for {len(ovf_r)} rows")
    if len(rank) and (int(rank.max()) >= max(len(uniq), 1)
                      or int(ovf_r.max()) >= subblocks * tilemm.RSUB):
        raise ValueError("a rank or a row outside the hot form's room")
    pw = np.empty(tilemm.hot_spec(tiles * vtiles, subblocks).pairs_shape,
                  np.uint32)
    counts = np.empty(subblocks * tiles, np.int64)
    lost = _LIB.wh_hot_place(
        _u32p(rank), _u32p(ovf_r), len(rank), subblocks, tiles, vtiles,
        tilemm.HOT_CAP, counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _u32p(pw))
    if lost:
        raise ValueError(f"{lost} pairs past a room of {vtiles} x "
                         f"{tilemm.HOT_CAP} a cell")
    return tilemm.hot_buckets(uniq, tiles), pw


def get_hot_encoder():
    """The native ``(hot_ranks, encode_hot)`` pair of the overflow list's
    hot form (ops/tilemm.py has the numpy pair, the specification), or
    None when the library (or the symbols) is absent."""
    lib = _load()
    if lib is None or not hasattr(lib, "wh_hot_rank"):
        return None
    return _hot_ranks, _hot_place


def _hot_cut(ovf_b: np.ndarray, ovf_r: np.ndarray, parts: int,
             nb_local: int) -> list:
    """``data/crec.cut_overflow`` in one native pass (wh_hot_cut): the
    parts are views of two arrays made here."""
    ovf_b = np.ascontiguousarray(ovf_b, np.uint32)
    ovf_r = np.ascontiguousarray(ovf_r, np.uint32)
    n = len(ovf_b)
    if len(ovf_r) != n:
        raise ValueError(f"{n} buckets, {len(ovf_r)} rows")
    out_b = np.empty(n, np.uint32)
    out_r = np.empty(n, np.uint32)
    counts = np.zeros(parts, np.int64)
    valid = _LIB.wh_hot_cut(
        _u32p(ovf_b), _u32p(ovf_r), n, parts, nb_local, _u32p(out_b),
        _u32p(out_r), counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    if valid < 0:
        raise ValueError(f"an overflow bucket outside {parts} ranges of "
                         f"{nb_local} buckets")
    ends = np.cumsum(counts).tolist()
    return [(out_b[end - c:end], out_r[end - c:end])
            for c, end in zip(counts.tolist(), ends)]


def get_hot_cutter():
    """The native ``cut_overflow`` (data/crec.py has the numpy one, the
    specification), or None when the library (or the symbol) is
    absent."""
    lib = _load()
    if lib is None or not hasattr(lib, "wh_hot_cut"):
        return None
    return _hot_cut


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
