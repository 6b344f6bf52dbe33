"""crec: columnar fixed-nnz record blocks — the TPU device-feed format.

The reference converts hot text formats to binary RecordIO precisely because
text parsing can't feed the cluster (``learn/linear/tool/text2rec.cc``); crec
is that idea taken to its TPU-native conclusion (SURVEY.md §7 hard part (d)):
a block's on-disk bytes ARE the device feed. A block holds ``block_rows``
rows as one contiguous buffer

    keys   u32[block_rows * nnz]   (row-major)
    labels u8 [block_rows]

and the streaming path ships that buffer to the device with a single
``device_put`` — no per-row parse, no host-side localization (key folding
happens on device, see learners/store.py dense-apply). 16 MB-ish blocks are
the measured sweet spot of the host→device interconnect.

File layout (little-endian):

    header (32 B): magic "WCREC\\x01\\0\\0", nnz u32, block_rows u32,
                   total_rows u64, reserved u64
    ceil(total_rows / block_rows) blocks; every block holds exactly
    ``block_rows`` rows except the last, which holds the remainder.

Missing feature slots (criteo rows with empty fields) carry the sentinel key
0xFFFFFFFF — the device step masks them out of the margin and the gradient.
Padded rows (readers pad the tail block to a static shape) carry label 255.

Part semantics: part k of n owns a contiguous range of *blocks* — the crec
analogue of InputSplit's byte-range ownership, exact because blocks are
fixed-size and seekable.
"""

from __future__ import annotations

import functools
import struct
import threading
import time
import queue
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

import numpy as np

from wormhole_tpu.obs import trace

MAGIC = b"WCREC\x01\x00\x00"
_HDR = struct.Struct("<8sIIQQ")  # magic, nnz, block_rows, total_rows, rsvd
HEADER_SIZE = _HDR.size
SENTINEL_KEY = np.uint32(0xFFFFFFFF)
PAD_LABEL = 255


@dataclass(frozen=True)
class CRecInfo:
    nnz: int
    block_rows: int
    total_rows: int

    @property
    def block_bytes(self) -> int:
        return self.block_rows * (self.nnz * 4 + 1)

    @property
    def num_blocks(self) -> int:
        return -(-self.total_rows // self.block_rows) if self.total_rows else 0

    def rows_in_block(self, i: int) -> int:
        if i < self.num_blocks - 1:
            return self.block_rows
        tail = self.total_rows - (self.num_blocks - 1) * self.block_rows
        return int(tail)

    def block_offset(self, i: int) -> int:
        return HEADER_SIZE + i * self.block_bytes

    def block_nbytes(self, i: int) -> int:
        r = self.rows_in_block(i)
        return r * (self.nnz * 4 + 1)


def read_header(path: str) -> CRecInfo:
    from wormhole_tpu.data.stream import open_stream
    with open_stream(path, "rb") as f:
        raw = f.read(HEADER_SIZE)
    magic, nnz, block_rows, total_rows, _ = _HDR.unpack(raw)
    if magic != MAGIC:
        raise ValueError(f"{path}: not a crec file (magic {magic!r})")
    return CRecInfo(nnz=nnz, block_rows=block_rows, total_rows=total_rows)


class CRecWriter:
    """Stream rows into fixed-size blocks; ``close()`` patches total_rows.

    ``append(keys, labels)``: keys u32 (n, nnz) with SENTINEL_KEY padding for
    rows with fewer features; labels 0/1 (u8)."""

    def __init__(self, path: str, nnz: int, block_rows: int = 100_000):
        if block_rows <= 0 or nnz <= 0:
            raise ValueError("nnz and block_rows must be positive")
        self.path = path
        self.nnz = nnz
        self.block_rows = block_rows
        self.total_rows = 0
        self._buf_keys = np.empty((block_rows, nnz), np.uint32)
        self._buf_labels = np.empty(block_rows, np.uint8)
        self._fill = 0
        from wormhole_tpu.data.stream import open_stream
        self._f = open_stream(path, "wb")
        self._f.write(_HDR.pack(MAGIC, nnz, block_rows, 0, 0))

    def append(self, keys: np.ndarray, labels: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint32)
        labels = np.ascontiguousarray(labels, np.uint8)
        if keys.ndim != 2 or keys.shape[1] != self.nnz:
            raise ValueError(f"keys must be (n, {self.nnz}), got {keys.shape}")
        n = keys.shape[0]
        pos = 0
        while pos < n:
            take = min(n - pos, self.block_rows - self._fill)
            self._buf_keys[self._fill:self._fill + take] = keys[pos:pos + take]
            self._buf_labels[self._fill:self._fill + take] = \
                labels[pos:pos + take]
            self._fill += take
            pos += take
            if self._fill == self.block_rows:
                self._flush_block(self.block_rows)

    def _flush_block(self, rows: int) -> None:
        self._f.write(self._buf_keys[:rows].tobytes())
        self._f.write(self._buf_labels[:rows].tobytes())
        self.total_rows += rows
        self._fill = 0

    def close(self) -> None:
        if self._f is None:
            return
        if self._fill:
            self._flush_block(self._fill)
        self._f.seek(0)
        self._f.write(_HDR.pack(MAGIC, self.nnz, self.block_rows,
                                self.total_rows, 0))
        self._f.close()
        self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if exc and exc[0] is not None and self._f is not None:
            # exception mid-write: never publish — remote buffers abort
            # the upload, local files truncate to zero (a header
            # backpatch here would make the partial file look complete)
            from wormhole_tpu.data.stream import discard_output
            discard_output(self._f)
            self._f.close()
            self._f = None
            return
        self.close()


def _part_block_range(info: CRecInfo, part: int, nparts: int) -> range:
    nb = info.num_blocks
    lo = part * nb // nparts
    hi = (part + 1) * nb // nparts
    return range(lo, hi)


def _read_block(f, path: str, info: CRecInfo, i: int,
                pad_tail: bool = True) -> Tuple[np.ndarray, int]:
    """Read one v1 block at its seek offset — safe to call from several
    threads as long as each holds its OWN stream handle (blocks are
    independent fixed-size seekable ranges)."""
    full = info.block_bytes
    rows = info.rows_in_block(i)
    nbytes = info.block_nbytes(i)
    f.seek(info.block_offset(i))
    if rows == info.block_rows:
        buf = np.empty(full, np.uint8)
        got = f.readinto(memoryview(buf))
        if got != full:
            raise IOError(f"{path}: truncated block {i}")
        return buf, rows
    raw = f.read(nbytes)
    if len(raw) != nbytes:
        raise IOError(f"{path}: truncated tail block {i}")
    if not pad_tail:
        return np.frombuffer(raw, np.uint8).copy(), rows
    buf = np.empty(full, np.uint8)
    kb = rows * info.nnz * 4
    kb_full = info.block_rows * info.nnz * 4
    buf[:kb] = np.frombuffer(raw, np.uint8, kb)
    buf[kb:kb_full] = 0xFF          # sentinel keys
    buf[kb_full:kb_full + rows] = np.frombuffer(raw, np.uint8, rows, kb)
    buf[kb_full + rows:] = PAD_LABEL
    return buf, rows


def iter_packed(path: str, part: int = 0, nparts: int = 1,
                pad_tail: bool = True) -> Iterator[Tuple[np.ndarray, int]]:
    """Yield ``(packed_u8, rows)`` per owned block.

    ``packed_u8`` always has the full-block byte length (static shape for
    jit); a short tail block is padded with sentinel keys and PAD_LABEL
    when ``pad_tail`` (rows still reports the real count)."""
    info = read_header(path)
    blocks = _part_block_range(info, part, nparts)
    if not len(blocks):
        return
    from wormhole_tpu.data.stream import open_stream
    with open_stream(path, "rb") as f:
        for i in blocks:
            yield _read_block(f, path, info, i, pad_tail)


def unpack_block(packed: np.ndarray,
                 info: CRecInfo) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side view of a packed block: (keys (R, nnz) u32, labels u8)."""
    kb = info.block_rows * info.nnz * 4
    keys = packed[:kb].view(np.uint32).reshape(info.block_rows, info.nnz)
    labels = packed[kb:kb + info.block_rows]
    return keys, labels


# ---------------------------------------------------------------------------
# crec v2: tile-grouped blocks for the MXU gather/scatter step (ops/tilemm)
# ---------------------------------------------------------------------------
#
# v2 moves the expensive irregular work offline, the way the reference
# pre-converts hot text data to binary recordio (tool/text2rec.cc): the
# writer folds keys to hashed buckets (hashing.fold_keys32 — same model as
# the v1 on-device fold) and groups each block's (bucket, row) pairs by
# 16K-bucket tile (ops/tilemm.encode_block). The on-disk bytes are the
# kernel operands; the device does only dense matmul work.
#
#     header (48 B): magic "WCREC\x04\0\0", nnz u32, block_rows u32,
#                    total_rows u64, nb u32, subblocks u32, cap u32,
#                    ovf_cap u32, reserved u64
#     per block (fixed size, tail padded at write time):
#         pw     u32[T * S/GS * N]      (packed digit words, tilemm layout)
#         labels u8[block_rows]         (255 = padded row)
#         ovf_b  u32[ovf_cap]           (0xFFFFFFFF = unused slot)
#         ovf_r  u32[ovf_cap]

MAGIC2 = b"WCREC\x04\x00\x00"
_HDR2 = struct.Struct("<8sIIQIIIIQ")
HEADER2_SIZE = _HDR2.size


@dataclass(frozen=True)
class CRec2Info:
    nnz: int
    block_rows: int
    total_rows: int
    nb: int
    subblocks: int
    cap: int
    ovf_cap: int

    @property
    def spec(self):
        from wormhole_tpu.ops.tilemm import make_spec
        return make_spec(self.nb, self.subblocks, self.cap)

    @property
    def pairs_bytes(self) -> int:
        t, sg, n = self.spec.pairs_shape
        return t * sg * n * 4

    @property
    def block_bytes(self) -> int:
        return self.pairs_bytes + self.block_rows + 8 * self.ovf_cap

    @property
    def num_blocks(self) -> int:
        return (-(-self.total_rows // self.block_rows)
                if self.total_rows else 0)

    def rows_in_block(self, i: int) -> int:
        if i < self.num_blocks - 1:
            return self.block_rows
        return int(self.total_rows - (self.num_blocks - 1) * self.block_rows)

    def block_offset(self, i: int) -> int:
        return HEADER2_SIZE + i * self.block_bytes


def read_header2(path: str) -> CRec2Info:
    from wormhole_tpu.data.stream import open_stream
    with open_stream(path, "rb") as f:
        raw = f.read(HEADER2_SIZE)
    magic, nnz, block_rows, total, nb, sub, cap, ovf, _ = _HDR2.unpack(raw)
    if magic != MAGIC2:
        if magic in (b"WCREC\x02\x00\x00", b"WCREC\x03\x00\x00"):
            raise ValueError(
                f"{path}: crec2 v{magic[5]} file — the pair encoding "
                "changed in v4 (packed u32 word layout / row digit split); "
                "regenerate with tools/text2rec")
        raise ValueError(f"{path}: not a crec2 file (magic {magic!r})")
    return CRec2Info(nnz=nnz, block_rows=block_rows, total_rows=total,
                     nb=nb, subblocks=sub, cap=cap, ovf_cap=ovf)


def default_cap(nnz: int, nb: int) -> int:
    """Per-(subblock, tile) pair capacity: mean + 3 sigma of the binomial
    tile occupancy for hashed-uniform keys, rounded up to 128. Skew past
    the cap goes to the exact overflow list (expected spill at 3 sigma is
    ~0.01 pairs per cell — negligible; the kernel cost scales linearly
    with cap, so tighter is faster)."""
    from wormhole_tpu.ops.tilemm import RSUB, TILE
    tiles = nb // TILE
    if not tiles:
        # ValueError, not ZeroDivisionError: callers probe tile
        # admissibility by construction (online_info docstring) and a
        # sub-tile bucket table is an inadmissible geometry like any other
        raise ValueError(f"nb={nb} is smaller than one tile "
                         f"({TILE} buckets)")
    mean = RSUB * nnz / tiles
    return max(128, int(-(-(mean + 3 * mean ** 0.5) // 128)) * 128)


def encode_tile_pairs(keys: np.ndarray, nb: int,
                      spec) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One keys grid -> ``(pw, ovf_b, ovf_r)`` with the overflow list as
    long as it is: fold the real keys of a ``(block_rows, nnz)`` u32 grid
    (SENTINEL_KEY empties are no pair) to hashed buckets and tile-group
    them. THE single encoder: the crec2 writer and the online tile-encode
    feed both come through it, which is what makes an online-encoded
    block bit-identical to the same rows pre-converted to a crec2
    file. One native counting pass (native/tile_encode.cc) when the
    process can load it, else the numpy below, which is its written
    specification: the same bits, the list's order included."""
    from wormhole_tpu.data import native
    from wormhole_tpu.data.hashing import fold_keys32
    from wormhole_tpu.ops.tilemm import encode_block
    encode = native.get_tile_encoder()
    if encode is not None:
        return encode(keys, nb, spec)
    rr, cc = np.nonzero(keys != SENTINEL_KEY)
    buckets = fold_keys32(keys[rr, cc], nb)
    return encode_block(buckets, rr.astype(np.int64), spec)


def encode_tile_block(keys: np.ndarray, nb: int, spec,
                      ovf_cap: int) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray, int]:
    """:func:`encode_tile_pairs` with the overflow list at a fixed width:
    ``(pw, ovf_b, ovf_r, n_ovf)`` with ``ovf_cap``-long arrays
    (tilemm.cap_overflow) and the true count, which the caller holds
    against ``ovf_cap`` before trusting them."""
    from wormhole_tpu.ops.tilemm import cap_overflow
    pw, ovb, ovr = encode_tile_pairs(keys, nb, spec)
    return (pw, *cap_overflow(ovb, ovr, ovf_cap), len(ovb))


class CRec2Writer:
    """Stream (keys, labels) rows into tile-grouped crec2 blocks.

    Same append() surface as CRecWriter: keys u32 (n, nnz) with
    SENTINEL_KEY padding, labels 0/1 u8. The writer folds keys to buckets
    (hashing.fold_keys32) and tile-groups each block. Raises if a block's
    overflow exceeds ``ovf_cap`` — raise it or use more buckets."""

    def __init__(self, path: str, nnz: int, nb: int = 1 << 22,
                 subblocks: int = 12, cap: Optional[int] = None,
                 ovf_cap: int = 1024):
        from wormhole_tpu.ops.tilemm import make_spec
        self.path, self.nnz, self.nb = path, nnz, nb
        self.cap = cap or default_cap(nnz, nb)
        self.ovf_cap = ovf_cap
        self.spec = make_spec(nb, subblocks, self.cap)
        self.block_rows = self.spec.block_rows
        self.total_rows = 0
        self._buf_keys = np.full((self.block_rows, nnz), SENTINEL_KEY,
                                 np.uint32)
        self._buf_labels = np.empty(self.block_rows, np.uint8)
        self._fill = 0
        from wormhole_tpu.data.stream import open_stream
        self._f = open_stream(path, "wb")
        self._f.write(_HDR2.pack(MAGIC2, nnz, self.block_rows, 0, nb,
                                 subblocks, self.cap, ovf_cap, 0))

    def append(self, keys: np.ndarray, labels: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, np.uint32)
        labels = np.ascontiguousarray(labels, np.uint8)
        if keys.ndim != 2 or keys.shape[1] != self.nnz:
            raise ValueError(f"keys must be (n, {self.nnz}), got {keys.shape}")
        n, pos = keys.shape[0], 0
        while pos < n:
            take = min(n - pos, self.block_rows - self._fill)
            self._buf_keys[self._fill:self._fill + take] = keys[pos:pos + take]
            self._buf_labels[self._fill:self._fill + take] = \
                labels[pos:pos + take]
            self._fill += take
            pos += take
            if self._fill == self.block_rows:
                self._flush_block(self.block_rows)

    def _flush_block(self, rows: int) -> None:
        keys = self._buf_keys
        keys[rows:] = SENTINEL_KEY
        self._buf_labels[rows:] = PAD_LABEL
        pw, ob, orow, n_ovf = encode_tile_block(keys, self.nb, self.spec,
                                                self.ovf_cap)
        if n_ovf > self.ovf_cap:
            raise ValueError(
                f"{self.path}: block overflow {n_ovf} > ovf_cap "
                f"{self.ovf_cap} — skewed keys; raise ovf_cap or nb")
        self._f.write(pw.tobytes())
        self._f.write(self._buf_labels.tobytes())
        self._f.write(ob.tobytes())
        self._f.write(orow.tobytes())
        self.total_rows += rows
        self._fill = 0
        self._buf_keys[:] = SENTINEL_KEY

    def close(self) -> None:
        if self._f is None:
            return
        if self._fill:
            self._flush_block(self._fill)
        self._f.seek(0)
        self._f.write(_HDR2.pack(MAGIC2, self.nnz, self.block_rows,
                                 self.total_rows, self.nb,
                                 self.spec.subblocks, self.cap,
                                 self.ovf_cap, 0))
        self._f.close()
        self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if exc and exc[0] is not None and self._f is not None:
            # exception mid-write: never publish — remote buffers abort
            # the upload, local files truncate to zero (a header
            # backpatch here would make the partial file look complete)
            from wormhole_tpu.data.stream import discard_output
            discard_output(self._f)
            self._f.close()
            self._f = None
            return
        self.close()


def block2_views(info: CRec2Info, buf: np.ndarray) -> dict:
    """Zero-copy typed views of one v2 block buffer. Typed arrays go to
    the device as-is — a device-side u8->u16 bitcast would force XLA
    relayout copies in front of the tile kernels (measured ~5ms/block)."""
    pb, R, oc = info.pairs_bytes, info.block_rows, info.ovf_cap
    shape = info.spec.pairs_shape
    o0 = pb + R
    return {
        "pw": buf[:pb].view(np.uint32).reshape(shape),
        "labels": buf[pb:pb + R],
        "ovf_b": buf[o0:o0 + 4 * oc].view(np.uint32),
        "ovf_r": buf[o0 + 4 * oc:o0 + 8 * oc].view(np.uint32),
    }


def _read_block2(f, path: str, info: CRec2Info,
                 i: int) -> Tuple[dict, int]:
    """Read one v2 block (same per-thread-handle contract as
    ``_read_block``; all blocks fixed-size, writer already padded the
    tail)."""
    size = info.block_bytes
    f.seek(info.block_offset(i))
    buf = np.empty(size, np.uint8)
    if f.readinto(memoryview(buf)) != size:
        raise IOError(f"{path}: truncated block {i}")
    return block2_views(info, buf), info.rows_in_block(i)


def iter_packed2(path: str, part: int = 0,
                 nparts: int = 1) -> Iterator[Tuple[dict, int]]:
    """Yield ``(views_dict, rows)`` per owned v2 block (all fixed-size;
    the writer already padded the tail)."""
    info = read_header2(path)
    nb_blocks = info.num_blocks
    lo = part * nb_blocks // nparts
    hi = (part + 1) * nb_blocks // nparts
    from wormhole_tpu.data.stream import open_stream
    with open_stream(path, "rb") as f:
        for i in range(lo, hi):
            yield _read_block2(f, path, info, i)


def _map_local(path: str):
    """A read-only mapping of ``path``, or None where the stream behind
    it is not a plain local file (a buffered raw ``FileIO`` with a real
    descriptor): s3, hdfs, a registered filesystem, a compressed or
    in-memory stream."""
    import io
    import mmap
    from wormhole_tpu.data.stream import open_stream
    with open_stream(path, "rb") as f:
        if not isinstance(getattr(f, "raw", None), io.FileIO):
            return None
        try:
            return mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        except (OSError, ValueError):
            return None


class BlockSource:
    """The blocks of one crec/crec2 file for one pass of a feed:
    ``read(i) -> (block, rows)``, from any thread.

    A local crec2 file is mapped read-only, once, and a block is
    :func:`block2_views` of the mapping: no byte is copied on the host,
    and whoever consumes a view (``device_put``) reads the page cache
    itself. Reading into ``np.empty`` instead first-touches a block's
    worth of fresh pages every time, because glibc hands memory of that
    size back to the kernel on free: 0.29 s a 201 MB block on the
    four-chip host (PERF.md). Nothing ever writes a read-only mapping
    and it lives as long as a view of it does, so no buffer is handed
    back or reused: on the CPU backend ``device_put`` may alias its
    source, and on a TPU the source must not change until the transfer
    completes. Every other stream, and crec v1 (whose tail block is
    padded on read), keeps one handle a thread and ``readinto`` fresh
    memory; ``mapped`` tells which, and the feed counts the copies.

    A mapped block's views are read-only (writing one raises). On a
    file the page cache does not hold, ``read`` does no I/O: the disk
    reads are page faults in whoever consumes the views, the one
    transfer thread's ``device_put``. ``MADV_WILLNEED`` on the block
    does not move them to the reader: the kernel cuts the advice to one
    read-ahead window (8 MB of a 201 MB block) and a cold pass measured
    slower with it than without (PERF.md, PR 31). The file's size is
    taken when the source is made, once a pass: a file shortened or
    replaced in place while a pass maps it is a SIGBUS in whoever
    touches the lost pages, where ``readinto`` raised a short read.
    Write a new file and rename it."""

    def __init__(self, path: str, fmt: str = "crec2"):
        self.path = path
        v1 = fmt == "crec"
        self.info = read_header(path) if v1 else read_header2(path)
        self._reader = _read_block if v1 else _read_block2
        self._map = None if v1 else _map_local(path)
        self._tls = threading.local()
        self._handles: list = []
        self._lock = threading.Lock()

    @property
    def mapped(self) -> bool:
        return self._map is not None

    def part_range(self, part: int, nparts: int) -> range:
        return _part_block_range(self.info, part, nparts)

    def read(self, i: int):
        info = self.info
        if self._map is not None:
            try:
                buf = np.frombuffer(self._map, np.uint8, info.block_bytes,
                                    info.block_offset(i))
            except ValueError:
                raise IOError(f"{self.path}: truncated block {i}") from None
            return block2_views(info, buf), info.rows_in_block(i)
        f = getattr(self._tls, "f", None)
        if f is None:
            from wormhole_tpu.data.stream import open_stream
            f = self._tls.f = open_stream(self.path, "rb")
            with self._lock:
                self._handles.append(f)
        return self._reader(f, self.path, info, i)

    def close(self) -> None:
        """Close the handles and let go of the mapping, which is
        unmapped at once where no view of it is left, and else when the
        last view goes."""
        with self._lock:
            handles, self._handles = self._handles, []
        for f in handles:
            try:
                f.close()
            except Exception:
                pass
        m, self._map = self._map, None
        if m is not None:
            try:
                m.close()
            except BufferError:
                pass


@contextmanager
def _timed_put(feed):
    """A feed's own ``device_put``, its seconds added to
    ``feed.put_time`` (one writer: the thread that transfers)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        feed.put_time += time.perf_counter() - t0


class PackedFeed:
    """Prefetching device feed: a producer thread reads blocks and issues
    ``device_put`` so transfer overlaps the consumer's dispatch loop (the
    ThreadedParser of this path, minibatch_iter.h:50). Yields
    ``(device_packed, host_packed, rows)``. ``host_packed`` is read-only
    where the file is mapped (:class:`BlockSource`): copy it to change it.

    ``cache``: keep every block's device buffer and replay from HBM on
    subsequent iterations — multi-pass training then reads the dataset at
    HBM speed instead of host-interconnect speed (the TPU-native answer to
    the reference caching hot data as pre-parsed recordio). Only sensible
    when the dataset fits device memory; the caller opts in.
    """

    def __init__(self, path: str, part: int = 0, nparts: int = 1,
                 depth: int = 3, device_put=None, fmt: str = "crec",
                 cache: bool = False, workers: int = 0, hot=None):
        self.path, self.part, self.nparts = path, part, nparts
        self.fmt = fmt
        # a HotRoom: crec2 blocks whose overflow list it takes also
        # carry the list's hot form (the one-device train step's)
        self.hot = hot if fmt == "crec2" else None
        self.depth = depth
        self.workers = workers
        self.read_time = 0.0
        self.put_time = 0.0
        self.bytes_read = 0
        # bytes this feed copied into host memory on their way to the
        # device (a count; the pass loops add it to their Timer)
        self.host_copy_bytes = 0
        self._mapped = False    # this pass's blocks are views of a mapping
        self._device_put = device_put
        self._iter_blocks = self._source_blocks
        self._cache: Optional[list] = [] if cache else None
        self._cache_full = False
        self._pipe = None  # last DeviceFeed, for stall-counter draining

    def _labels_only(self, packed) -> np.ndarray:
        """Host labels slice of a block — the only host-side bytes any
        later pass needs (eval pooling); cached items drop the rest so the
        device cache doesn't pin a dataset-sized copy in host RAM."""
        if isinstance(packed, dict):
            return packed["labels"].copy()
        info = read_header(self.path)
        kb = info.block_rows * info.nnz * 4
        return packed[kb:kb + info.block_rows].copy()

    def __iter__(self):
        if self._cache_full:
            yield from self._cache
            return
        yield from self._stream()

    def drain_pipe_stats(self, timer, prefix: str = "") -> Optional[dict]:
        """Merge the last pipelined stream's stage/stall counters into
        ``timer`` (no-op for serial streams)."""
        pipe, self._pipe = self._pipe, None
        return pipe.drain_stats(timer, prefix) if pipe is not None else None

    def _stream(self):
        try:
            items = (self._stream_pipelined() if self.workers > 0
                     else self._stream_serial())
            for item in items:
                if self._cache is not None:
                    dev, packed, rows = item
                    self._cache.append((dev, self._labels_only(packed),
                                        rows))
                yield item
            if self._cache is not None:
                self._cache_full = True
        finally:
            if self._cache is not None and not self._cache_full:
                # a partial iteration (error or early consumer exit) must
                # not leave a half-filled cache that a retry would extend
                # into duplicated blocks
                self._cache = []

    def _account(self, packed) -> None:
        n = (sum(v.nbytes for v in packed.values())
             if isinstance(packed, dict) else packed.nbytes)
        self.bytes_read += n
        if not self._mapped:
            self.host_copy_bytes += n

    def _stream_serial(self):
        import jax
        put = self._device_put or jax.device_put
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        SENT = object()

        def _put_or_stop(item) -> bool:
            """Timed put that honors stop — the producer must never block
            forever on a consumer that bailed out mid-iteration."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                for packed, rows in self._iter_blocks(self.path, self.part,
                                                      self.nparts):
                    # the span the pipelined stream's put stage has
                    with trace.span(f"{self.fmt}-feed:put", cat="feed"), \
                            _timed_put(self):
                        dev = put(packed)
                    self._account(packed)
                    if not _put_or_stop((dev, packed, rows)):
                        return
            except BaseException as e:
                _put_or_stop(e)
                return
            _put_or_stop(SENT)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is SENT:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    def _open_source(self) -> BlockSource:
        src = BlockSource(self.path, self.fmt)
        self._mapped = src.mapped
        return src

    def _read(self, src: BlockSource, i: int):
        """Block ``i`` as its reader hands it on: with the hot form of
        its overflow list where this feed makes one (a new dict, the
        file's views as they are beside it)."""
        block, rows = src.read(i)
        if self.hot is not None:
            with trace.span("encode:hot", cat="feed"):
                form = self.hot.form(block["ovf_b"], block["ovf_r"],
                                     src.info.subblocks)
            if form:
                block = dict(block, **form)
        return block, rows

    def _source_blocks(self, path: str, part: int, nparts: int):
        """The serial stream's blocks, in order, on the producer
        thread."""
        src = self._open_source()
        try:
            for i in src.part_range(part, nparts):
                yield self._read(src, i)
        finally:
            src.close()

    def _pipeline_spec(self):
        """(source, prep, collate, on_close) for the parallel read path:
        block indices dispatch to workers that read through one
        :class:`BlockSource` (crec blocks are independent fixed-size
        seekable ranges, so block-index parallelism is exact; a mapped
        source leaves the workers nothing to copy)."""
        src = self._open_source()
        return (iter(src.part_range(self.part, self.nparts)),
                lambda i, _ctx: self._read(src, i), None, src.close)

    def _stream_pipelined(self):
        """DeviceFeed-backed stream: parallel block reads/assembly, one
        in-order transfer thread keeping ``depth`` device-resident blocks
        ahead of the consumer. Yields the same ``(dev, host, rows)``
        triples, in the same order, as the serial stream."""
        import jax
        from wormhole_tpu.data.pipeline import DeviceFeed
        put = self._device_put or jax.device_put

        def transfer(pr):
            # inside the DeviceFeed's <fmt>-feed:put stage and its span
            packed, rows = pr
            with _timed_put(self):
                dev = put(packed)
            self._account(packed)
            return dev, packed, rows

        source, prep, collate, on_close = self._pipeline_spec()
        feed = DeviceFeed(source, prep, workers=self.workers,
                          ring_depth=self.depth, collate=collate,
                          transfer=transfer, on_close=on_close,
                          name=f"{self.fmt}-feed")
        self._pipe = feed
        yield from feed


def _python_crec_assembler(fmt: str, nnz: int):
    """Fallback chunk -> (keys u32 (n,nnz), labels u8) assembler when the
    native library is unavailable (same semantics as wh_parse_to_crec /
    tools/text2rec convert_crec)."""
    from wormhole_tpu.data.hashing import key64_to_key32
    from wormhole_tpu.data.parsers import _TEXT_PARSERS

    parse = _TEXT_PARSERS[fmt]

    def assemble(chunk: bytes):
        blk = parse(chunk)
        n = blk.size
        k32 = key64_to_key32(blk.index)
        per_row = np.diff(blk.offset)
        keys = np.full((n, nnz), SENTINEL_KEY, np.uint32)
        row_ids = np.repeat(np.arange(n, dtype=np.int64), per_row)
        pos = np.arange(len(blk.index), dtype=np.int64) - np.repeat(
            blk.offset[:-1].astype(np.int64), per_row)
        keep = pos < nnz
        keys[row_ids[keep], pos[keep]] = k32[keep]
        return keys, (blk.label > 0.5).astype(np.uint8)

    return assemble


class TextCRecFeed(PackedFeed):
    """Direct text -> device feed: assembles in-memory crec v1 blocks
    from a text part (parse + key fold + fixed-nnz padding run in ONE
    native C pass per chunk, data/native.get_crec_assembler) and ships
    them through the same prefetch/cache pipeline as PackedFeed — the
    text ingest path the round-3 verdict measured at 20K rows/s in
    Python glue becomes a native assembly plus the crec dense-apply
    device step. Binary-feature formats only (criteo/adfea; values are
    dropped like the text2rec crec conversion)."""

    def __init__(self, path: str, part: int = 0, nparts: int = 1, *,
                 text_fmt: str, nnz: int, block_rows: int = 16384,
                 depth: int = 3, device_put=None, cache: bool = False,
                 workers: int = 0):
        super().__init__(path, part, nparts, depth=depth,
                         device_put=device_put, fmt="crec", cache=cache,
                         workers=workers)
        self.text_fmt = text_fmt
        self.nnz = nnz
        self.block_rows = block_rows
        self._iter_blocks = self._text_blocks

    def _labels_only(self, packed) -> np.ndarray:
        kb = self.block_rows * self.nnz * 4
        return packed[kb:kb + self.block_rows].copy()

    def _pack(self, kbuf: np.ndarray, lbuf: np.ndarray) -> np.ndarray:
        kb = self.block_rows * self.nnz * 4
        out = np.empty(kb + self.block_rows, np.uint8)
        out[:kb] = kbuf.reshape(-1).view(np.uint8)
        out[kb:] = lbuf
        return out

    def _assembler(self):
        from wormhole_tpu.data import native
        return (native.get_crec_assembler(self.text_fmt, self.nnz)
                or _python_crec_assembler(self.text_fmt, self.nnz))

    def _block_collator(self):
        """Sequential (keys, labels) → fixed-R-row packed-block folding;
        shared by the serial stream and the pipeline's collate stage
        (which runs it in stream order on the transfer thread).
        ``fold(res)`` returns the finished blocks; ``fold(None)`` flushes
        the padded tail."""
        R = self.block_rows
        kbuf = np.empty((R, self.nnz), np.uint32)
        lbuf = np.empty(R, np.uint8)
        state = {"fill": 0}

        def fold(res):
            out = []
            fill = state["fill"]
            if res is None:
                if fill:
                    kbuf[fill:] = SENTINEL_KEY
                    lbuf[fill:] = PAD_LABEL
                    out.append((self._pack(kbuf, lbuf), fill))
                    state["fill"] = 0
                return out
            keys, labels = res
            pos = 0
            while pos < len(labels):
                take = min(len(labels) - pos, R - fill)
                kbuf[fill:fill + take] = keys[pos:pos + take]
                lbuf[fill:fill + take] = labels[pos:pos + take]
                fill += take
                pos += take
                if fill == R:
                    out.append((self._pack(kbuf, lbuf), R))
                    fill = 0
            state["fill"] = fill
            return out

        return fold

    def _text_blocks(self, path: str, part: int, nparts: int):
        from wormhole_tpu.data.input_split import InputSplit
        asm = self._assembler()
        fold = self._block_collator()
        for chunk in InputSplit(path, part, nparts, "text"):
            yield from fold(asm(bytes(chunk)))
        yield from fold(None)

    def _pipeline_spec(self):
        """Text path: chunks dispatch to workers running the hot native
        parse+fold assembly in parallel (wh_parse_to_crec releases the
        GIL and allocates its own outputs per call); the sequential
        re-blocking into fixed-row packed blocks runs as the collate
        stage on the transfer thread, preserving exact block boundaries
        and order."""
        from wormhole_tpu.data.input_split import InputSplit
        asm = self._assembler()
        fold = self._block_collator()
        split = InputSplit(self.path, self.part, self.nparts, "text")

        def source():
            for chunk in split:
                # bytes() copy here: the split may reuse its chunk buffer
                yield bytes(chunk)

        def prep(chunk, _ctx):
            return asm(chunk)

        return source(), prep, fold, None

    def drain_pipe_stats(self, timer, prefix: str = "") -> Optional[dict]:
        """PackedFeed's snapshot with the reader named: ``text_read`` is
        the dispatcher's busy seconds (``parse``: the split's chunking
        and the ``bytes()`` copy)."""
        snap = super().drain_pipe_stats(timer, prefix)
        if snap is not None:
            snap["text_read"] = snap["parse"]
            if timer is not None:
                timer.add(prefix + "text_read", snap["text_read"],
                          max(snap["batches"], 1))
        return snap


# ---------------------------------------------------------------------------
# online tile encoding: stream ANY v1-block source through the crec2 tile
# step without a pre-converted file (ISSUE 5)
# ---------------------------------------------------------------------------

# The least room an online block's COO overflow list is given: a list of
# up to this many pairs keeps the width it has always had, so a stream
# that hardly overflows compiles the one spill program it always did.
ONLINE_OVF_CAP = 1024


def overflow_room(n: int) -> int:
    """The room given to a list of ``n`` pairs: ``n`` and an eighth more,
    rounded up to a multiple of the power of two that lies between an
    eighth and a quarter of ``n`` (so 1.125 to 1.375 times ``n``), and
    never under ``ONLINE_OVF_CAP``. The coarse step makes the blocks of
    one data set, whose counts differ by a percent, agree on ONE room:
    a room is a shape, and a shape is a compile of the spill step."""
    if n <= ONLINE_OVF_CAP:
        return ONLINE_OVF_CAP
    step = 1 << (int(n).bit_length() - 3)
    return -(-(n + n // 8) // step) * step


class OverflowRoom:
    """The room in force for the overflow lists of online-encoded
    blocks, chosen from what the encoder counted and from nothing else
    (no option): it starts at ``ONLINE_OVF_CAP``, and a block whose list
    passes it sets it to :func:`overflow_room` of that block's count. It
    never shrinks, so a stream settles within its first blocks (a later
    block grows it only by passing the hottest one by an eighth), and a
    settled room compiles nothing. A block with NO overflow pair keeps
    the least width whatever the room: its empty list stays on the host
    (``TableCheckpoint.put_block``) and the block takes the step that
    has no spill. One object outlives the feeds of a job (a pass makes a
    new feed): the app holds it. Encode workers share it under a lock."""

    def __init__(self):
        import threading
        self.room = ONLINE_OVF_CAP
        self.grown = 0            # times a block passed the room
        self._lock = threading.Lock()

    def fit(self, n: int) -> int:
        """The width for a list of ``n`` pairs, the room grown if need
        be."""
        if n == 0:
            return ONLINE_OVF_CAP
        with self._lock:
            if n > self.room:
                self.room = overflow_room(n)
                self.grown += 1
            return self.room


# A list whose room is under this many slots keeps the COO helpers
# whatever it names: two tiles' worth. The hot path's own floor is a
# gather and a scatter of one hot tile (16,384 slots each) and a kernel
# pair over eight virtual tiles, which a COO list pays for at about this
# length (26 ns a gathered slot, PERF.md section 6, PR 42).
HOT_MIN_ROOM = 32768
# ... and one with fewer than this many pairs a distinct bucket: there is
# little to share, the hot tiles would be a quarter of the list or more.
HOT_MIN_SHARE = 4


def hot_vtiles(cell_max: int) -> int:
    """Virtual tiles a hot tile for cells of up to ``cell_max`` pairs:
    :func:`overflow_room` of the count (an eighth more, coarsely
    rounded, so that the blocks of one data set agree on one shape) in
    cells of ``HOT_CAP`` slots, up to a multiple of eight (the kernels'
    tiles a grid step)."""
    from wormhole_tpu.ops.tilemm import HOT_CAP
    return -(-overflow_room(cell_max) // (8 * HOT_CAP)) * 8


def cut_overflow(ovf_b: np.ndarray, ovf_r: np.ndarray, parts: int,
                 nb_local: int) -> list:
    """One overflow list cut by owner for a table in ``parts`` key ranges
    of ``nb_local`` buckets (a MODEL shard each, learners/store
    ``mesh_tile_geometry``): ``[(buckets, rows)] * parts``, part ``m``
    the list's pairs whose bucket lies in ``[m * nb_local, (m + 1) *
    nb_local)`` in the list's order, the bucket made local to the range.
    A stable partition: every pair is in exactly one part and unused
    slots in none."""
    from wormhole_tpu.ops.overflow import UNUSED
    valid = ovf_b != UNUSED
    b, r = ovf_b[valid], ovf_r[valid]
    owner = b // np.uint32(nb_local)
    if len(owner) and int(owner.max()) >= parts:
        raise ValueError(f"overflow bucket {int(b.max())} is outside "
                         f"{parts} ranges of {nb_local} buckets")
    return [(b[owner == m] - np.uint32(m * nb_local), r[owner == m])
            for m in range(parts)]


def _hot_encoder() -> tuple:
    """``(ranks, place)`` of the hot form: native where the process can
    load it (native/tile_encode.cc), else the numpy specification
    (ops/tilemm.py ``hot_ranks`` / ``encode_hot``): the same bits."""
    from wormhole_tpu.data import native
    from wormhole_tpu.ops import tilemm
    return native.get_hot_encoder() or (tilemm.hot_ranks, tilemm.encode_hot)


class HotRoom:
    """Which form the overflow list of a block takes on its way to the
    tile train step, and the room of the hot form (ops/tilemm.py,
    ``encode_hot``), chosen from what the feed's worker can see of the
    list and from nothing else (no option):

    * a list whose room (a static shape of the step program) is under
      ``HOT_MIN_ROOM`` slots stays COO: ``"size"``;
    * one with fewer than ``HOT_MIN_SHARE`` pairs a distinct bucket
      stays COO: ``"distinct"``;
    * every other list also gets the hot form, at a room of ``tiles``
      hot tiles (the distinct buckets in whole tiles of 16,384) and
      ``vtiles`` virtual tiles each (:func:`hot_vtiles` of the fullest
      (subblock, hot tile) cell). Like :class:`OverflowRoom` the room
      grows when a block passes it and never shrinks: a room is a shape,
      and a shape is a compile of the spill step.

    On a mesh the same rule is asked a group at a time, of each member's
    list cut by the MODEL shard that owns the bucket (``form_shards``:
    a hot form A SHARD, one room for the group's step program).

    One object outlives the feeds of a job and is shared by their
    workers under a lock; it counts what it chose (``drain``)."""

    def __init__(self):
        self.tiles = 1
        self.vtiles = 8
        self.slots = 0            # of ovf_pw, at the room in force
        self._lock = threading.Lock()
        self._counts = {"hot_blocks": 0, "coo_blocks": 0, "hot_buckets": 0}
        self._said: set = set()

    def _count(self, **add) -> None:
        with self._lock:
            for k, v in add.items():
                self._counts[k] += v

    def _stays_coo(self, why: str, detail: str, blocks: int = 1) -> None:
        self._count(coo_blocks=blocks)
        with self._lock:
            first = why not in self._said
            self._said.add(why)
        if first:
            from wormhole_tpu.utils.logging import get_logger
            get_logger("crec").info(
                "an overflow list keeps the COO path (%s): %s", why, detail)

    def fit(self, distinct: int, cell_max: int,
            subblocks: int) -> Tuple[int, int]:
        """The room ``(tiles, vtiles)`` for a list of ``distinct``
        buckets whose fullest cell holds ``cell_max`` pairs, grown if
        need be."""
        from wormhole_tpu.ops.tilemm import HOT_CAP, TILE
        with self._lock:
            self.tiles = max(self.tiles, -(-distinct // TILE))
            if cell_max > self.vtiles * HOT_CAP:
                self.vtiles = hot_vtiles(cell_max)
            self.slots = self.tiles * self.vtiles * subblocks * HOT_CAP
            return self.tiles, self.vtiles

    def _take(self, b: np.ndarray, r: np.ndarray, room: int,
              subblocks: int, blocks: int = 1) -> Optional[tuple]:
        """The rule above asked of one list's pairs ``(b, r)`` (no
        unused slot among them) at a room of ``room`` slots: the list
        ranked, ``(uniq, rank, cell_max)`` (``tilemm.hot_ranks``), where
        it takes the hot form; else None, ``blocks`` counted COO."""
        from wormhole_tpu.ops import tilemm
        if room < HOT_MIN_ROOM:
            return self._stays_coo(
                "size", f"a room of {room} slots is under {HOT_MIN_ROOM}",
                blocks)
        if int(r.max()) >= subblocks * tilemm.RSUB:
            raise ValueError(f"overflow row {int(r.max())} is outside "
                             f"a block of {subblocks} subblocks")
        uniq, rank, cell_max = _hot_encoder()[0](b, r, subblocks)
        if len(uniq) * HOT_MIN_SHARE > len(b):
            return self._stays_coo(
                "distinct", f"{len(b)} pairs name {len(uniq)} buckets, "
                f"under {HOT_MIN_SHARE} pairs a bucket", blocks)
        return uniq, rank, cell_max

    def form(self, ovf_b: np.ndarray, ovf_r: np.ndarray,
             subblocks: int) -> Optional[dict]:
        """``{"ovf_u", "ovf_pw"}`` for a block's overflow list as its
        arrays stand (room-long, unused slots ``UNUSED`` from the
        first on), or None where the list is empty or stays COO. One
        native pass where the process can load it
        (native/tile_encode.cc), else the numpy specification: the same
        bits."""
        from wormhole_tpu.ops import overflow
        n = overflow.pairs(ovf_b)
        if not n:
            return None
        if (ovf_b[:n] == overflow.UNUSED).any():
            raise ValueError("an overflow list with a hole in it: unused "
                             "slots must follow the pairs")
        took = self._take(ovf_b[:n], ovf_r[:n], len(ovf_b), subblocks)
        if took is None:
            return None
        uniq, rank, cell_max = took
        tiles, vtiles = self.fit(len(uniq), cell_max, subblocks)
        form = _hot_encoder()[1](uniq, rank, ovf_r[:n], subblocks, tiles,
                                 vtiles)
        self._count(hot_blocks=1, hot_buckets=len(uniq))
        return dict(zip(overflow.HOT, form))

    def form_shards(self, lists: list, parts: int, nb_local: int,
                    subblocks: int) -> Optional[list]:
        """The hot form A SHARD of one mesh group's overflow lists, a
        ``{"ovf_u": (parts, tiles * TILE), "ovf_pw": (parts, ...)}`` a
        member, or None where the group keeps its COO lanes. ``lists``
        holds a member's ``(ovf_b, ovf_r)`` as :meth:`form` takes them.

        Every list is cut by owner (:func:`cut_overflow`; one native
        pass where the process can load it) and every part that holds a
        pair is a list of its own to the rule (:meth:`_take`, at the
        room :func:`overflow_room` gives its count): one part that
        stays COO keeps the group COO, and a group with no listed pair
        is None uncounted, as an empty list is. The room is a static
        shape of the group's ONE step program, so every part is ranked
        first, the room fitted once at the largest, and every part
        placed at it by :meth:`form`'s own encoder; a part without a
        pair is all padding. Counted a member that brought a list, as
        :meth:`form` counts a block."""
        from wormhole_tpu.data import native
        from wormhole_tpu.ops import overflow
        if not any(len(ovf_b) and ovf_b[0] != overflow.UNUSED
                   for ovf_b, _r in lists):
            return None      # unused slots follow the pairs: no pair here
        cut = native.get_hot_cutter() or cut_overflow
        every = [part for ovf_b, ovf_r in lists
                 for part in cut(ovf_b, ovf_r, parts, nb_local)]
        listed = sum(any(len(b) for b, _r in every[i:i + parts])
                     for i in range(0, len(every), parts))
        ranked = []
        for b, r in every:
            took = (self._take(b, r, overflow_room(len(b)), subblocks,
                               blocks=listed) if len(b) else (b, b, 0))
            if took is None:
                return None
            ranked.append(took)
        tiles, vtiles = self.fit(max(len(u) for u, _k, _c in ranked),
                                 max(c for _u, _k, c in ranked), subblocks)
        place = _hot_encoder()[1]
        us, pws = zip(*(place(uniq, rank, r, subblocks, tiles, vtiles)
                        for (uniq, rank, _c), (_b, r) in zip(ranked, every)))
        self._count(hot_blocks=listed,
                    hot_buckets=sum(len(u) for u, _k, _c in ranked))
        return [dict(zip(overflow.HOT, (np.stack(us[i:i + parts]),
                                        np.stack(pws[i:i + parts]))))
                for i in range(0, len(every), parts)]

    def drain(self) -> dict:
        """What was chosen since the last call: blocks that took the hot
        form, blocks with a list that stayed COO, distinct buckets listed
        (summed over the hot blocks), and the slots of ``ovf_pw`` at the
        room in force."""
        with self._lock:
            out = dict(self._counts, hot_room=self.slots)
            self._counts = dict.fromkeys(self._counts, 0)
        return out


def online_info(nnz: int, src_rows: int, nb: int,
                ovf_cap: int = ONLINE_OVF_CAP) -> CRec2Info:
    """Tile geometry for online-encoding a stream of ``src_rows``-row v1
    blocks into ``nb`` buckets: the subblock count rounds the source
    block up to a multiple of RSUB (extra rows ride as padding), cap is
    the same mean+3o default the writer uses. ``ovf_cap`` is the LEAST
    width of a block's overflow list: the width in force is the feed's
    (:class:`OverflowRoom`) and rides on the block's own arrays. Raises
    ValueError (via ``.spec``) exactly where the tilemm limits would
    reject a writer with the same geometry — callers probe admissibility
    by constructing the spec."""
    from wormhole_tpu.ops.tilemm import RSUB
    subblocks = max(-(-src_rows // RSUB), 1)
    return CRec2Info(nnz=nnz, block_rows=subblocks * RSUB, total_rows=0,
                     nb=nb, subblocks=subblocks,
                     cap=default_cap(nnz, nb), ovf_cap=ovf_cap)


class TileOnlineFeed:
    """Online tile-encode stage: chain a v1-block source feed (PackedFeed
    over a crec file, or TextCRecFeed over text) into a DeviceFeed whose
    prep workers run fold+tile-group (``encode_tile_pairs``) per block —
    the CRec2Writer's expensive host work, relocated onto the PR 1
    parallel pad workers so it hides behind device compute. Yields the
    same ``(device_block_dict, host_labels, rows)`` triples the crec2
    PackedFeed path produces, so the consumer runs the MXU tile step on
    a stream that never touched a crec2 file (the worker-side
    pre-encoding move of Li et al.'s parameter server, done in the feed
    instead of a file format).

    Pairs past the per-tile cap ride on the block's COO overflow list,
    as a crec2 file's do, at the width ``room`` has in force
    (:class:`OverflowRoom`: sized to what the encoder counted, grown when
    a block passes it), and where ``hot`` (:class:`HotRoom`) takes the
    list, in its hot form beside it. Every block stays a tile block:
    there is no other step for a skewed one to fall to. ``overflow_pairs``,
    ``overflow_slots`` (the widths of the lists that hold a pair), the
    room's ``grown`` and ``native_blocks`` (blocks the native encoder
    took: all of them or none, by what the process could load) are
    counted for the consumer's timer.

    ``inner`` must yield ``(dev, packed_v1, rows)`` with an identity
    device_put (its packed v1 bytes stay on host for the encode);
    ``workers=0`` runs the encode inline on the consumer thread — the
    determinism oracle, same contract as DeviceFeed."""

    def __init__(self, inner, info: CRec2Info, *, workers: int = 2,
                 depth: int = 2, device_put=None, cache: bool = False,
                 name: str = "tile-encode", room=None, hot=None):
        self.inner = inner
        self.info = info
        self.room = room if room is not None else OverflowRoom()
        self.hot = hot    # a HotRoom: lists it takes also ride hot
        self.workers = workers
        self.depth = depth
        self.name = name
        self._device_put = device_put
        self.put_time = 0.0
        # transfer-thread counters (single writer)
        self.overflow_pairs = 0
        self.overflow_slots = 0
        self.native_blocks = 0
        self._grown_before = self.room.grown
        self._cache: Optional[list] = [] if cache else None
        self._cache_full = False
        self._pipe = None
        # per-feed scratch is NOT shared with prep workers — each encode
        # call allocates its own grid (thread-safe by construction)
        self._src_rows = getattr(inner, "block_rows", None)

    @property
    def bytes_read(self) -> int:
        return self.inner.bytes_read

    @property
    def host_copy_bytes(self) -> int:
        return self.inner.host_copy_bytes

    def __iter__(self):
        if self._cache_full:
            yield from self._cache
            return
        yield from self._stream()

    def _stream(self):
        try:
            for item in self._pipelined():
                if self._cache is not None:
                    self._cache.append(item)
                yield item
            if self._cache is not None:
                self._cache_full = True
        finally:
            if self._cache is not None and not self._cache_full:
                # partial iteration must not leave a half cache that a
                # retry would extend into duplicated blocks (same
                # contract as PackedFeed._stream)
                self._cache = []

    def _encode(self, item, _ctx):
        """Worker-side stage: v1 packed block -> crec2 typed dict, its
        overflow list at the room's width."""
        from wormhole_tpu.data import native
        from wormhole_tpu.ops.tilemm import cap_overflow
        packed, rows = item
        info = self.info
        R, nnz = info.block_rows, info.nnz
        with trace.span("encode:unpack", cat="feed"):
            src = CRecInfo(nnz=nnz, block_rows=self._src(packed),
                           total_rows=0)
            keys, labels = unpack_block(packed, src)
            if src.block_rows == R:
                kgrid = keys
                lab = labels.copy()
            else:
                # source blocks shorter than the tile block: pad rows up —
                # this is what makes ANY source block_rows admissible
                kgrid = np.full((R, nnz), SENTINEL_KEY, np.uint32)
                kgrid[:src.block_rows] = keys
                lab = np.full(R, PAD_LABEL, np.uint8)
                lab[:src.block_rows] = labels
        with trace.span("encode:tile", cat="feed"):
            pw, ovb, ovr = encode_tile_pairs(kgrid, info.nb, info.spec)
        with trace.span("encode:list", cat="feed"):
            ob, orow = cap_overflow(ovb, ovr, self.room.fit(len(ovb)))
        block = {"pw": pw, "labels": lab, "ovf_b": ob, "ovf_r": orow}
        if self.hot is not None:
            with trace.span("encode:hot", cat="feed"):
                block.update(self.hot.form(ob, orow, info.subblocks) or {})
        return (block, lab, rows, len(ovb),
                native.get_tile_encoder() is not None)

    def _src(self, packed) -> int:
        if self._src_rows is None:
            self._src_rows = packed.nbytes // (self.info.nnz * 4 + 1)
        return self._src_rows

    def _transfer(self, res):
        # inside the DeviceFeed's <name>:put stage and its span
        import jax
        payload, lab, rows, n_ovf, native = res
        self.native_blocks += native
        if n_ovf:
            self.overflow_pairs += n_ovf
            self.overflow_slots += len(payload["ovf_b"])
        put = self._device_put or jax.device_put
        with _timed_put(self):
            dev = put(payload)
        return dev, lab, rows

    def _pipelined(self):
        from wormhole_tpu.data.pipeline import DeviceFeed

        def source():
            for _dev, packed, rows in self.inner:
                yield packed, rows

        feed = DeviceFeed(source(), self._encode, workers=self.workers,
                          ring_depth=self.depth, transfer=self._transfer,
                          name=self.name, prep_label="encode")
        self._pipe = feed
        yield from feed

    def drain_pipe_stats(self, timer, prefix: str = "") -> Optional[dict]:
        """Merged two-layer snapshot in PackedFeed's key scheme plus the
        encode stage: ``prep`` stays the inner read/assembly work (the
        consumer's ``read`` timer line), ``encode``/``encode_stall`` are
        the outer pool's busy seconds and the in-order wait on it (the
        time tile encoding actually delayed the stream). ``collate`` is
        the inner feed's (this feed has none), and a text reader's
        ``text_read`` passes through."""
        inner_snap = (self.inner.drain_pipe_stats(None)
                      if hasattr(self.inner, "drain_pipe_stats") else None)
        pipe, self._pipe = self._pipe, None
        snap = pipe.drain_stats(None) if pipe is not None else None
        if snap is None:
            return None
        inner_snap = inner_snap or {}
        out = {
            "parse": inner_snap.get("parse", 0.0),
            "prep": inner_snap.get("prep", 0.0),
            "prep_stall": inner_snap.get("prep_stall", 0.0),
            "put": snap["put"],
            "put_stall": inner_snap.get("put_stall", 0.0),
            "encode": snap["prep"],
            "encode_stall": snap["put_stall"],
            "collate": inner_snap.get("collate", 0.0),
            "consume_stall": snap["consume_stall"],
            "batches": snap["batches"],
            "ring_max": snap["ring_max"],
            # counts, not seconds: what this pass's blocks put on their
            # overflow lists, the slots those lists were shipped at, how
            # often a block passed the room, and the blocks the native
            # encoder took
            "native_blocks": self.native_blocks,
            "overflow_pairs": self.overflow_pairs,
            "overflow_slots": self.overflow_slots,
            "room_grown": self.room.grown - self._grown_before,
            "room": self.room.room,
        }
        if "text_read" in inner_snap:
            out["text_read"] = inner_snap["text_read"]
        self.overflow_pairs = self.overflow_slots = self.native_blocks = 0
        self._grown_before = self.room.grown
        if timer is not None:
            n = max(out["batches"], 1)
            for k in ("parse", "put", "encode"):
                timer.add(prefix + k, out[k], n)
            for k in ("prep_stall", "encode_stall", "consume_stall"):
                timer.add(prefix + k, out[k], n)
        return out


# ---------------------------------------------------------------------------
# sharded multi-device group feed: each chip of the mesh is handed its
# slice of a block as the reader returned it, so the mesh step never
# waits on a host copy
# ---------------------------------------------------------------------------


def mesh_pads(info, is_tile: bool):
    """The shared all-PAD block used to fill a short tail group — built
    once per part, never per dispatch (the pad arrays are megabytes).
    Tile pads are PADWORD pair words + 255 labels + empty overflow; v1
    pads are one all-0xFF buffer (sentinel keys AND pad labels are
    0xFF). Read-only by contract: every padded group shares them."""
    if is_tile:
        from wormhole_tpu.ops.overflow import UNUSED
        from wormhole_tpu.ops.tilemm import PADWORD
        spec = info.spec
        return {
            "pw": np.full(spec.pairs_shape, PADWORD, np.uint32),
            "labels": np.full(info.block_rows, PAD_LABEL, np.uint8),
            "ovf_b": np.full(max(info.ovf_cap, 1), UNUSED, np.uint32),
            "ovf_r": np.zeros(max(info.ovf_cap, 1), np.uint32),
        }
    return np.full(info.block_bytes, 0xFF, np.uint8)


def stack_mesh_group(views: list, D: int, info, pads, is_tile: bool,
                     want_labels: bool = False):
    """Stack one data-axis group of host blocks into the mesh step's
    stacked operands, padding a short group to ``D`` with ``pads``
    (:func:`mesh_pads`): a copy of every byte of the group into fresh
    memory, which the multihost pass makes of a host's members before
    the hosts' groups become one global array (the tests hold
    :func:`place_mesh_group` against it).
    Returns ``(blocks, labels_u8)`` where ``labels_u8`` — only
    materialized when ``want_labels`` (eval pooling) — is a flat view
    of the ALREADY-stacked label lanes, not a per-block concatenate:
    the global (D*R,) row order matches the mesh eval step's margin
    output, PAD rows carried as 255."""
    if len(views) < D:
        views = views + [pads] * (D - len(views))
    if is_tile:
        blocks = {
            "pw": np.stack([v["pw"] for v in views]),
            "labels": np.stack([v["labels"] for v in views]),
            "ovf_b": np.stack([v.get("ovf_b", pads["ovf_b"])
                               for v in views]),
            "ovf_r": np.stack([v.get("ovf_r", pads["ovf_r"])
                               for v in views]),
        }
        labels = blocks["labels"].reshape(-1) if want_labels else None
        return blocks, labels
    blocks = np.stack(views)
    labels = None
    if want_labels:
        lab_off = info.block_rows * info.nnz * 4
        labels = (blocks[:, lab_off:lab_off + info.block_rows]
                  .reshape(-1))
    return blocks, labels


def place_mesh_group(views: list, shardings):
    """One whole data-axis group of host blocks (a short one already
    filled up with the PAD block) as the mesh step's operands, on their
    devices, with no stacked copy: the arrays that
    ``jax.device_put(stack_mesh_group(...)[0], shardings)`` gives (same
    shape, dtype, ``NamedSharding`` and bytes), assembled shard by
    shard. Under ``learners.store.mesh_group_shardings`` every chip
    holds a contiguous slice of ONE block (``pw[d, a:b]``, row ``d`` of
    a lane: a group has as many members as the data axis), so each chip
    is sent that slice of the block's own view."""
    import jax
    D = len(views)

    def place(sharding, *members):
        def shard(index):
            d = index[0].indices(D)[0]
            return members[d][index[1:]][None]
        return jax.make_array_from_callback(
            (D,) + members[0].shape, sharding, shard)

    # tile blocks are dicts of lanes and so are their shardings; a v1
    # block is one array
    return jax.tree.map(place, shardings, *views)


def widen_overflow(views: list) -> list:
    """The tile blocks of one group with their overflow lists at ONE
    width, the widest among them: a shorter list is continued with
    unused slots (tilemm.cap_overflow), in a new dict; a group that
    agrees already is returned as it is."""
    from wormhole_tpu.ops.tilemm import cap_overflow
    width = max(len(v["ovf_b"]) for v in views)
    if all(len(v["ovf_b"]) == width for v in views):
        return views
    out = []
    for v in views:
        if len(v["ovf_b"]) != width:
            ob, orow = cap_overflow(v["ovf_b"], v["ovf_r"], width)
            v = dict(v, ovf_b=ob, ovf_r=orow)
        out.append(v)
    return out


def mesh_group_labels(views: list, info, is_tile: bool) -> np.ndarray:
    """The label lanes of one whole group, concatenated in the global
    ``(D * R,)`` row order of the mesh eval step's margins, PAD rows
    carried as 255 (eval pooling; 98 KB a block)."""
    return np.concatenate([v["labels"] if is_tile
                           else unpack_block(v, info)[1] for v in views])


class MeshGroupFeed:
    """Sharded DeviceFeed for the multi-device crec/crec2 path: the
    mesh counterpart of PackedFeed/TileOnlineFeed.

    The DeviceFeed dispatcher forms data-axis groups in stream order
    (``pipeline.group_blocks``, recording per-group arrival skew — the
    straggler telemetry), and the transfer thread hands every chip its
    slice of the group (:func:`place_mesh_group`): under the (data,
    model) NamedSharding of ``learners.store.mesh_group_shardings`` that
    slice is a contiguous part of ONE block's buffer as the inner feed
    returned it, so no stacked copy of the group is made. The H2D copy
    overlaps the previous group's mesh step, and the step consumes the
    same pre-placed arrays (shape, dtype, sharding) a ``device_put`` of
    the stacked group would give, with zero re-layout. What is left for
    the ``stack`` workers is the label lanes of an eval pass
    (``want_labels``). The feed copies no byte itself:
    ``host_copy_bytes`` is the inner feed's.

    The members of an ONLINE group may bring overflow lists of different
    widths (a block with no overflow pair keeps the least width; the
    room may have grown between two blocks): the stack workers widen the
    shorter lists to the group's widest with unused slots
    (:func:`widen_overflow`), since the group is one array a lane.

    With ``hot`` (a :class:`HotRoom`: a train pass hands the job's) the
    stack workers also ask it for the group's lists in their hot form A
    SHARD (``HotRoom.form_shards``: each member's list cut by owner into
    ``hot_parts`` key ranges, each part ranked and placed at one room).
    Where the rule takes the group, what crosses is ``{pw, labels,
    ovf_u, ovf_pw}`` under ``hot_shardings``: chip ``(d, m)`` receives
    member ``d``'s part ``m`` alone, its distinct listed buckets in whole
    hot tiles and its pairs as rank words, and the COO lanes (two
    room-long lanes a member, to both chips of its MODEL pair) stay on
    the host. Any other group (a part that stays COO, no listed pair, no
    ``hot``) crosses as it always did.

    Yields ``(blocks_dev, labels_u8, rows)`` a group; ``labels_u8`` is
    None unless ``want_labels``. ``workers=0`` runs every stage inline on
    the consumer thread — the bit-determinism oracle, same contract as
    DeviceFeed."""

    def __init__(self, inner, D: int, shardings, info, is_tile: bool, *,
                 workers: int = 2, depth: int = 2, online: bool = False,
                 want_labels: bool = False, name: str = "meshfeed",
                 hot=None, hot_shardings=None, hot_parts: int = 1):
        self.inner = inner
        self.D = D
        self.info = info
        self.is_tile = is_tile
        self.online = online
        self.want_labels = want_labels
        self.hot = hot if is_tile else None
        self.hot_parts = hot_parts
        self._hot_shardings = hot_shardings
        self.workers = workers
        self.depth = depth
        self.name = name
        self._shardings = shardings
        self.put_time = 0.0
        # dispatcher-thread counters (single writer; consumers read via
        # skew_snapshot after iteration)
        self.skew = {"groups": 0, "skew_sum": 0.0, "skew_max": 0.0,
                     "pad_blocks": 0}
        # transfer-thread counters (single writer): the slots of the
        # groups' stacked COO list lanes as they crossed (D x the width
        # after widening; a hot group has none: its room is HotRoom's
        # gauge), and the groups a member of which was widened
        self.overflow_slots = 0
        self.widened_groups = 0
        self._pipe = None

    @property
    def bytes_read(self) -> int:
        return self.inner.bytes_read

    @property
    def host_copy_bytes(self) -> int:
        return self.inner.host_copy_bytes

    @functools.cached_property
    def _pads(self):
        """The shared PAD block, built when a short tail first asks for
        it: a pass makes a new feed, and filling a block's worth of
        fresh memory (201 MB at 2**29 buckets) at every pass start cost
        the four-chip cell 0.2 s of each 1.04 s pass (PERF.md)."""
        return mesh_pads(self.info, self.is_tile)

    def skew_snapshot(self) -> dict:
        return dict(self.skew)

    def _source(self):
        from wormhole_tpu.data.pipeline import group_blocks
        sk = self.skew
        for group, skew_s in group_blocks(self.inner, self.D):
            sk["groups"] += 1
            sk["skew_sum"] += skew_s
            sk["skew_max"] = max(sk["skew_max"], skew_s)
            sk["pad_blocks"] += self.D - len(group)
            yield [p[0] for p in group], sum(p[2] for p in group)

    def _assemble(self, item, _ctx):
        """Worker-side stage, all that is left of group assembly: a
        short tail takes the shared PAD block as its missing members,
        a train group's lists their hot form a shard where ``hot`` takes
        them, any other online group's lists one width, and an eval
        pass its label lanes."""
        from wormhole_tpu.ops import overflow
        views, rows = item
        if len(views) < self.D:
            views = views + [self._pads] * (self.D - len(views))
        if self.hot is not None and "ovf_b" in views[0]:
            with trace.span("meshfeed:hot", cat="feed"):
                views = self._hot_views(views)
        widened = False
        if self.online and not overflow.is_hot(views[0]):
            with trace.span("meshfeed:widen", cat="feed"):
                wide = widen_overflow(views)
            widened, views = wide is not views, wide
        labels = (mesh_group_labels(views, self.info, self.is_tile)
                  if self.want_labels else None)
        return views, labels, rows, widened

    def _hot_views(self, views: list) -> list:
        """The group with its lists in their hot form a shard in the COO
        lanes' place, where the room's rule takes it; else as it is."""
        forms = self.hot.form_shards(
            [(v["ovf_b"], v["ovf_r"]) for v in views], self.hot_parts,
            self.info.nb // self.hot_parts, self.info.subblocks)
        if forms is None:
            return views
        return [{"pw": v["pw"], "labels": v["labels"], **form}
                for v, form in zip(views, forms)]

    def _transfer(self, item):
        # inside the DeviceFeed's <name>:put stage and its span
        from wormhole_tpu.ops import overflow
        views, labels, rows, widened = item
        hot = self.is_tile and overflow.is_hot(views[0])
        if self.is_tile and "ovf_b" in views[0]:
            self.overflow_slots += self.D * len(views[0]["ovf_b"])
            self.widened_groups += widened
        with _timed_put(self):
            dev = place_mesh_group(
                views, self._hot_shardings if hot else self._shardings)
        return dev, labels, rows

    def __iter__(self):
        from wormhole_tpu.data.pipeline import DeviceFeed
        feed = DeviceFeed(self._source(), self._assemble,
                          workers=self.workers, ring_depth=self.depth,
                          transfer=self._transfer, name=self.name,
                          prep_label="stack")
        self._pipe = feed
        yield from feed

    def drain_pipe_stats(self, timer, prefix: str = "") -> Optional[dict]:
        """Merged two-layer snapshot in PackedFeed's key scheme plus the
        stack stage: ``prep``/``parse`` stay the inner feed's read and
        assembly work, ``stack``/``stack_stall`` are the group-assembly
        pool's busy seconds and the in-order transfer wait on it, and
        ``put`` is this feed's sharded device_put seconds (the inner
        feed runs an identity put). An inner ``encode`` stage (online
        tile encoding) passes through."""
        inner_snap = (self.inner.drain_pipe_stats(None)
                      if hasattr(self.inner, "drain_pipe_stats") else None)
        pipe, self._pipe = self._pipe, None
        snap = pipe.drain_stats(None) if pipe is not None else None
        if snap is None:
            return inner_snap
        inner_snap = inner_snap or {}
        out = {
            "parse": inner_snap.get("parse", 0.0),
            "prep": inner_snap.get("prep", 0.0),
            "prep_stall": inner_snap.get("prep_stall", 0.0),
            "put": snap["put"],
            "put_stall": inner_snap.get("put_stall", 0.0),
            "stack": snap["prep"],
            "stack_stall": snap["put_stall"],
            "consume_stall": snap["consume_stall"],
            "batches": snap["batches"],
            "ring_max": snap["ring_max"],
        }
        for k in ("encode", "encode_stall", "collate", "text_read",
                  "native_blocks", "overflow_pairs", "overflow_slots",
                  "room_grown", "room"):
            if k in inner_snap:
                out[k] = inner_snap[k]
        # counts, not seconds: this feed's own (the inner feed's
        # overflow_slots are the blocks' lists before widening)
        out["mesh_overflow_slots"] = self.overflow_slots
        out["mesh_widened_groups"] = self.widened_groups
        self.overflow_slots = self.widened_groups = 0
        if timer is not None:
            n = max(out["batches"], 1)
            for k in ("parse", "put", "stack"):
                timer.add(prefix + k, out[k], n)
            for k in ("prep_stall", "stack_stall", "consume_stall"):
                timer.add(prefix + k, out[k], n)
        return out
