"""The native tile encoder (native/tile_encode.cc behind
``crec.encode_tile_pairs``) against its written specification, the numpy
encoder ``ops/tilemm.encode_block``: the same BITS — ``pw``, and the
overflow list's members AND order, dtypes and shapes — at every geometry
and key shape; and the loader around it: which encoder runs is decided by
what the process can load, a library found without the symbol is rebuilt
once, and where none loads the numpy encoder answers."""

import os
import shutil
import subprocess

import numpy as np
import pytest

import wormhole_tpu.data.crec as crec
from wormhole_tpu.data import native
from wormhole_tpu.data.crec import SENTINEL_KEY
from wormhole_tpu.data.hashing import fold_keys32
from wormhole_tpu.ops import tilemm

needs_native = pytest.mark.skipif(native.get_tile_encoder() is None,
                                  reason="native tile encoder not built")

NNZ = 6


def oracle(keys, nb, spec):
    """The specification, spelled out: fold the real keys, hand the pairs
    to ``encode_block`` in row-major order."""
    rr, cc = np.nonzero(keys != SENTINEL_KEY)
    return tilemm.encode_block(fold_keys32(keys[rr, cc], nb),
                               rr.astype(np.int64), spec)


def assert_same_bits(got, want):
    for name, a, b in zip(("pw", "ovf_b", "ovf_r"), got, want):
        assert a.dtype == b.dtype == np.uint32, name
        assert a.shape == b.shape, name
        assert np.array_equal(a, b), name


def uniform(rng, rows, nb, cap):
    k = rng.integers(0, 1 << 32, size=(rows, NNZ), dtype=np.uint32)
    k[k == SENTINEL_KEY] = 0
    return k


def zipf(rng, rows, nb, cap):
    """Heavy-tailed ids, half the slots on the four hottest: more than a
    third of the pairs pass the cap at every geometry."""
    ids = rng.zipf(1.05, size=(rows, NNZ)) % 100003
    hot = rng.random(ids.shape) < 0.5
    ids[hot] = rng.integers(1, 5, size=int(hot.sum()))
    return (ids * 2654435761 % 0xFFFFFFFF).astype(np.uint32)


def holes(rng, rows, nb, cap):
    """SENTINEL_KEY holes, and whole empty rows at the tail as
    TileOnlineFeed pads a short block."""
    k = zipf(rng, rows, nb, cap)
    k[rng.random(k.shape) < 0.3] = SENTINEL_KEY
    k[rows - rows // 3:] = SENTINEL_KEY
    return k


def all_sentinel(rng, rows, nb, cap):
    return np.full((rows, NNZ), SENTINEL_KEY, np.uint32)


def one_bucket(rng, rows, nb, cap):
    """Every row hits one bucket, NNZ times over."""
    return np.full((rows, NNZ), np.uint32(42), np.uint32)


KEYS = [uniform, zipf, holes, all_sentinel, one_bucket]
GEOMETRIES = [(nb, sub, cap)
              for nb in (1 << 16, 1 << 20, 1 << 24)
              for sub in (1, 4, 12) for cap in (128, 384)]
GEOMETRIES.append((3 * tilemm.TILE, 2, 128))  # nb no power of two: `%`


@needs_native
@pytest.mark.parametrize("make_keys", KEYS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("nb, sub, cap", GEOMETRIES)
def test_native_bits_equal_numpy(rng, nb, sub, cap, make_keys):
    spec = tilemm.make_spec(nb, sub, cap)
    keys = make_keys(rng, spec.block_rows, nb, cap)
    got = crec.encode_tile_pairs(keys, nb, spec)
    want = oracle(keys, nb, spec)
    assert_same_bits(got, want)
    if make_keys in (zipf, one_bucket):
        assert len(got[1]) > np.count_nonzero(keys != SENTINEL_KEY) // 3
    if make_keys is all_sentinel:
        assert len(got[1]) == 0
        assert (got[0] == tilemm.PADWORD).all()


@needs_native
@pytest.mark.parametrize("rows", [1, tilemm.RSUB - 1, tilemm.RSUB + 5,
                                  2 * tilemm.RSUB + 100])
def test_native_grid_shorter_or_longer_than_the_block(rng, rows):
    """A grid with fewer rows than the block leaves the later subblocks
    all pad; rows past the block are no pair, as in ``encode_block``."""
    nb, spec = 1 << 16, tilemm.make_spec(1 << 16, 2, 128)
    keys = zipf(rng, rows, nb, 128)
    assert_same_bits(crec.encode_tile_pairs(keys, nb, spec),
                     oracle(keys, nb, spec))


@needs_native
def test_native_bits_equal_numpy_at_the_text_cells_geometry(rng):
    """The geometry both Criteo-TEXT cells run: 2**29 buckets, 12
    subblocks, cap 128, 39 keys a row; a fifth of the slots on hot keys
    and a tenth empty, so the list is long and the grid has holes."""
    nb = 1 << 29
    spec = tilemm.make_spec(nb, 12, crec.default_cap(39, nb))
    assert spec.cap == 128
    keys = rng.integers(0, 1 << 32, size=(spec.block_rows, 39),
                        dtype=np.uint32)
    hot = rng.random(keys.shape) < 0.2
    keys[hot] = (rng.zipf(1.05, size=int(hot.sum())) % 1009).astype(
        np.uint32)
    keys[rng.random(keys.shape) < 0.1] = SENTINEL_KEY
    got = crec.encode_tile_pairs(keys, nb, spec)
    assert len(got[1]) > 100_000
    assert_same_bits(got, oracle(keys, nb, spec))


def _small(rng):
    nb, spec = 1 << 16, tilemm.make_spec(1 << 16, 2, 128)
    return holes(rng, spec.block_rows, nb, 128), nb, spec


def _unloaded(monkeypatch):
    """native.py as a fresh process finds it: nothing tried yet."""
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_BUILD_ERROR", "")


def test_disabled_native_falls_back_to_numpy(rng, monkeypatch):
    _unloaded(monkeypatch)
    monkeypatch.setenv("WORMHOLE_DISABLE_NATIVE", "1")
    assert native.get_tile_encoder() is None
    keys, nb, spec = _small(rng)
    assert_same_bits(crec.encode_tile_pairs(keys, nb, spec),
                     oracle(keys, nb, spec))


def test_library_without_the_symbol_falls_back_to_numpy(rng, monkeypatch):
    """A loaded library that lacks ``wh_tile_count`` (one built before
    the encoder existed, where it cannot be rebuilt): the getter says
    None and the numpy encoder answers."""
    class OldLib:
        wh_parse_count = wh_parse_fill = wh_parse_to_crec = None
    monkeypatch.setattr(native, "_LIB", OldLib())
    monkeypatch.setattr(native, "_TRIED", True)
    assert native.available()
    assert native.get_tile_encoder() is None
    keys, nb, spec = _small(rng)
    assert_same_bits(crec.encode_tile_pairs(keys, nb, spec),
                     oracle(keys, nb, spec))


def _build_tools():
    return shutil.which("make") and shutil.which(
        os.environ.get("CXX", "g++"))


@pytest.fixture
def stale_native_dir(tmp_path, monkeypatch):
    """A copy of ``native/`` whose ``build/`` holds a library made from
    parse.cc ALONE, older than its sources: what a checkout keeps in its
    git-ignored ``native/build/`` across the update that brought the
    encoder."""
    if not _build_tools():
        pytest.skip("no make / C++ compiler here")
    ndir = tmp_path / "native"
    (ndir / "build").mkdir(parents=True)
    for f in ("Makefile", "parse.cc", "tile_encode.cc"):
        shutil.copy(os.path.join(native._NATIVE_DIR, f), ndir / f)
    lib = ndir / "build" / native._LIB_NAMES[0]
    subprocess.run([os.environ.get("CXX", "g++"), "-O1", "-fPIC",
                    "-std=c++17", "-shared", "-o", str(lib),
                    str(ndir / "parse.cc")], check=True)
    os.utime(lib, (1, 1))
    monkeypatch.setattr(native, "_NATIVE_DIR", str(ndir))
    monkeypatch.delenv("WORMHOLE_NATIVE_LIB", raising=False)
    monkeypatch.delenv("WORMHOLE_DISABLE_NATIVE", raising=False)
    _unloaded(monkeypatch)
    return ndir


def test_stale_library_is_rebuilt_once_and_engages(rng, stale_native_dir):
    """THE trap: a found library that lacks the symbol must not leave the
    numpy encoder running in silence. The loader runs `make` and loads
    what it left; the parsers of the same library still answer."""
    encode = native.get_tile_encoder()
    assert encode is not None, native.build_error()
    keys, nb, spec = _small(rng)
    assert_same_bits(encode(keys, nb, spec), oracle(keys, nb, spec))
    assert native.get_parser("libsvm")(b"1 3:1 9:2\n").label.tolist() == [1]


def test_stale_library_that_cannot_be_rebuilt_falls_back(
        rng, stale_native_dir, caplog):
    """No Makefile beside it (an installed library, no toolchain): the
    old library stays loaded for its parsers, the loader says which
    encoder is live, and numpy answers."""
    os.remove(stale_native_dir / "Makefile")
    with caplog.at_level("WARNING"):
        assert native.get_tile_encoder() is None
    assert "numpy tile encoder is live" in caplog.text
    assert native.get_parser("libsvm") is not None
    keys, nb, spec = _small(rng)
    assert_same_bits(crec.encode_tile_pairs(keys, nb, spec),
                     oracle(keys, nb, spec))


@needs_native
def test_pair_words_are_recycled_only_when_nobody_views_them(rng,
                                                             monkeypatch):
    """The encoder's ``pw`` is memory of a pool (a block's 201 MB would
    otherwise be mapped and faulted afresh): it comes back when the array
    and EVERY view of it are gone, not before, and then the next block of
    the same size is written over the same pages."""
    import jax
    monkeypatch.setattr(native, "_PW_POOL", native._SlabPool())
    keys, nb, spec = _small(rng)
    want = oracle(keys, nb, spec)[0]
    first = native._tile_encode(keys, nb, spec)[0]
    where = first.ctypes.data
    view = first.reshape(-1)[5:]
    on_device = jax.device_put({"pw": first})   # the CPU backend aliases
    del first
    second = native._tile_encode(all_sentinel(rng, spec.block_rows, nb, 0),
                                 nb, spec)[0]
    assert second.ctypes.data != where          # a view and a put hold it
    assert np.array_equal(view, want.reshape(-1)[5:])
    assert np.array_equal(np.asarray(on_device["pw"]), want)
    free = {where, second.ctypes.data}
    del view, on_device, second
    third = native._tile_encode(keys, nb, spec)[0]
    assert third.ctypes.data in free            # recycled, not mapped
    assert np.array_equal(third, want)
    # another geometry's mapping is let go, and the idle room is bounded
    monkeypatch.setattr(native._SlabPool, "IDLE_BYTES", 2 * third.nbytes)
    many = [native._tile_encode(keys, nb, spec)[0] for _ in range(5)]
    del many, third
    assert len(native._PW_POOL._idle) == 2
    other = tilemm.make_spec(1 << 16, 1, 128)
    native._tile_encode(keys[:other.block_rows], 1 << 16, other)
    assert all(len(m) == np.prod(other.pairs_shape) * 4
               for m in native._PW_POOL._idle)


@needs_native
def test_native_encoder_rejects_what_it_cannot_index(rng):
    spec = tilemm.make_spec(1 << 16, 1, 128)
    with pytest.raises(ValueError, match="rows, nnz"):
        native._tile_encode(np.zeros(8, np.uint32), 1 << 16, spec)
    with pytest.raises(ValueError, match="u32 bucket space"):
        native._tile_encode(np.zeros((8, 2), np.uint32), 1 << 32, spec)


@needs_native
def test_concurrent_native_encodes_share_nothing(rng):
    """More encode threads than cores, each on its own grid, a shortened
    switch interval: every result equals the one the same grid gives
    alone (scratch is per call; a shared counter would lose updates)."""
    import sys
    from concurrent.futures import ThreadPoolExecutor
    nb, spec = 1 << 20, tilemm.make_spec(1 << 20, 4, 128)
    grids = [holes(np.random.default_rng(i), spec.block_rows, nb, 128)
             for i in range(4)]
    alone = [crec.encode_tile_pairs(g, nb, spec) for g in grids]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(2 * (os.cpu_count() or 4)) as pool:
            jobs = [(i % 4, pool.submit(crec.encode_tile_pairs,
                                        grids[i % 4], nb, spec))
                    for i in range(64)]
            for i, job in jobs:
                assert_same_bits(job.result(timeout=120), alone[i])
    finally:
        sys.setswitchinterval(old)
