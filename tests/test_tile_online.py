"""Online tile encoding (ISSUE 5): streaming formats (crec v1, criteo
text) route through the crec2 MXU tile step via feed-side encode
(data/crec.TileOnlineFeed) instead of the gather/scatter SparseBatch
path.

The properties pinned here:
  * encoder parity — an online-encoded block is BIT-identical to the
    same rows pre-converted through CRec2Writer (both come through the
    single encoder ``crec.encode_tile_pairs``);
  * model-update parity — tile_online=on over a v1 stream trains the
    same table as the dense-apply v1 path (the oracle), up to the tile
    kernels' bf16 quantization;
  * worker determinism — the encode pool (workers=N) is bit-identical
    to the inline encode (workers=0), per the DeviceFeed contract;
  * overflow room — a block whose pairs pass the per-tile cap keeps
    them on its COO overflow list, at a room sized to what the encoder
    counted (``crec.OverflowRoom``): it stays a tile block whatever its
    skew, the room settles within the first blocks and compiles one
    spill program a width, and an empty list stays on the host;
  * empty columns — the pad key of a short text row is no pair.

Every AsyncSGD here pins a data:1 single-device mesh: the online path's
mesh variant is exercised by the driver's multichip run; these tests
pin semantics, not sharding.
"""

import os

import jax
import numpy as np
import pytest

import wormhole_tpu.data.crec as crec
from wormhole_tpu.data.crec import (CRec2Writer, CRecWriter, PackedFeed,
                                    TileOnlineFeed, iter_packed2,
                                    online_info)
from wormhole_tpu.ops import tilemm

NB = 2 * tilemm.TILE
NNZ = 8


def make_rows(rng, n, planted=True):
    keys = rng.integers(0, 1 << 32, size=(n, NNZ), dtype=np.uint32)
    keys[keys == 0xFFFFFFFF] = 0
    keys[rng.random((n, NNZ)) < 0.1] = 0xFFFFFFFF  # missing slots
    if planted:
        sel = rng.random(n) < 0.5
        keys[sel, 0] = np.uint32(123456)
        keys[~sel, 0] = np.uint32(654321)
        labels = sel.astype(np.uint8)
    else:
        labels = (rng.random(n) < 0.4).astype(np.uint8)
    return keys, labels


def write_v1(path, keys, labels, block_rows):
    with CRecWriter(str(path), nnz=NNZ, block_rows=block_rows) as w:
        w.append(keys, labels)


def single_device_rt():
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:1", jax.devices()[:1])
    return rt


def make_app(path, fmt, **over):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config
    kw = dict(train_data=str(path), data_format=fmt, num_buckets=NB,
              lr_eta=0.5, max_data_pass=3, disp_itv=1e12, max_delay=1,
              pipeline_workers=0)
    kw.update(over)
    return AsyncSGD(Config(**kw), single_device_rt())


def weights(app):
    return np.asarray(app.store.handle.weights(app.store.slots))


def test_online_block_bit_identical_to_writer(tmp_path, rng):
    """The tentpole parity pin: TileOnlineFeed over a v1 file emits the
    SAME pw/labels/ovf bytes the crec2 reader yields for the same rows
    pre-converted with identical geometry."""
    n = tilemm.RSUB                      # one full subblock
    keys, labels = make_rows(rng, n, planted=False)
    v1 = tmp_path / "a.crec"
    write_v1(v1, keys, labels, block_rows=n)
    info = online_info(NNZ, n, NB)
    inner = PackedFeed(str(v1), fmt="crec", device_put=lambda x: x,
                       workers=0)
    feed = TileOnlineFeed(inner, info, workers=0,
                          device_put=lambda x: x)
    got = list(feed)
    assert len(got) == 1
    block, lab, rows = got[0]
    assert rows == n
    assert isinstance(block, dict)       # no fallback on uniform keys

    c2 = tmp_path / "a.crec2"
    with CRec2Writer(str(c2), nnz=NNZ, nb=NB, subblocks=info.subblocks,
                     cap=info.cap, ovf_cap=info.ovf_cap) as w:
        w.append(keys, labels)
    (views, c2rows), = list(iter_packed2(str(c2)))
    assert c2rows == n
    for k in ("pw", "labels", "ovf_b", "ovf_r"):
        a = np.asarray(block[k]).reshape(-1)
        b = np.asarray(views[k]).reshape(-1).view(a.dtype)
        assert np.array_equal(a, b), k
    assert np.array_equal(np.asarray(lab), np.asarray(views["labels"]))


def test_online_v1_matches_dense_oracle(tmp_path, rng):
    """tile_online=on over a crec v1 stream trains the same model as the
    v1 dense-apply path (tile_online=off) on identical rows — same key
    fold, bf16 tile-kernel tolerance — and learns the planted key."""
    n = 4000
    keys, labels = make_rows(rng, n)
    v1 = tmp_path / "b.crec"
    write_v1(v1, keys, labels, block_rows=4 * tilemm.RSUB)
    app_on = make_app(v1, "crec", tile_online="on")
    app_on.run()
    assert app_on.progress.num_ex == 3 * n
    assert app_on.progress.acc / max(app_on.progress.count, 1) > 0.8
    app_off = make_app(v1, "crec", tile_online="off")
    app_off.run()
    w_on, w_off = weights(app_on), weights(app_off)
    live = (np.abs(w_on) > 1e-6) | (np.abs(w_off) > 1e-6)
    assert live.any()
    assert np.allclose(w_on[live], w_off[live], rtol=0.05, atol=5e-3)


def test_online_text_workers_deterministic(tmp_path, rng):
    """criteo text through the online encode: the worker pool
    (pipeline_workers=2) is BIT-identical to the inline oracle
    (pipeline_workers=0) — encode runs on the pool but blocks land in
    stream order either way."""
    n = 3000
    sel = rng.random(n) < 0.5
    path = tmp_path / "t.criteo"
    with open(path, "w") as f:
        for i in range(n):
            ints = "\t".join(str(rng.integers(0, 100)) for _ in range(13))
            cats = "\t".join(f"{rng.integers(0, 1 << 32):x}"
                             for _ in range(26))
            f.write(f"{int(sel[i])}\t{ints}\t{cats}\n")
    apps = []
    for workers in (0, 2):
        app = make_app(path, "criteo", tile_online="on",
                       pipeline_workers=workers, max_data_pass=2,
                       text_block_rows=8192)
        app.run()
        apps.append(app)
    assert apps[0].progress.num_ex == 2 * n
    assert np.array_equal(weights(apps[0]), weights(apps[1]))


@pytest.mark.parametrize("live", ["native", "numpy"])
def test_native_blocks_are_counted(tmp_path, rng, monkeypatch, live):
    """A text pass under tile_online counts the blocks the native encoder
    took, in the registry (``feed/encode_native_blocks``) and on the
    Timer's line (``online_native_blocks``): every block of every pass
    where the process could load it, none where it could not."""
    from wormhole_tpu.data import native
    from wormhole_tpu.obs.metrics import encode_native_counter
    if live == "native" and native.get_tile_encoder() is None:
        pytest.skip("native tile encoder not built")
    if live == "numpy":
        monkeypatch.setattr(native, "get_tile_encoder", lambda: None)
    n = 2000
    path = tmp_path / "t.criteo"
    with open(path, "w") as f:
        for i in range(n):
            ints = "\t".join(str(rng.integers(0, 100)) for _ in range(13))
            cats = "\t".join(f"{rng.integers(0, 1 << 32):x}"
                             for _ in range(26))
            f.write(f"{i % 2}\t{ints}\t{cats}\n")
    app = make_app(path, "criteo", tile_online="on", pipeline_workers=2,
                   max_data_pass=2, text_block_rows=512)
    counter = encode_native_counter(app.obs.registry)
    before = counter.value
    app.run()
    blocks = 2 * -(-n // 512)
    assert app.timer.counts["encode"] == blocks
    want = blocks if live == "native" else 0
    assert app.timer.totals["online_native_blocks"] == want
    assert counter.value - before == want


def hot_rows(rng, n, share):
    """``share`` of the slots on one hot key, the rest uniform: at NB = 2
    tiles the hot key's tile passes the per-tile cap from a share of a
    tenth on."""
    keys, labels = make_rows(rng, n, planted=False)
    keys[rng.random(keys.shape) < share] = np.uint32(42)
    return keys, labels


def overflow_count(keys, info):
    return len(crec.encode_tile_pairs(keys, info.nb, info.spec)[1])


def test_hot_block_stays_on_the_tile_path(tmp_path, rng, monkeypatch):
    """A block with every slot on one hot bucket (32K pairs past the
    per-tile cap, 30 times ONLINE_OVF_CAP) stays a tile block: its pairs
    ride the overflow list at a room sized to them, no sparse step runs,
    and the table is BIT-identical to the one the same rows train from a
    crec2 file written with room for the pairs."""
    from wormhole_tpu.learners.store import ShardedStore
    n = tilemm.RSUB
    keys = np.full((n, NNZ), np.uint32(42), np.uint32)  # one hot bucket
    labels = (rng.random(n) < 0.4).astype(np.uint8)
    v1 = tmp_path / "skew.crec"
    write_v1(v1, keys, labels, block_rows=n)
    info = online_info(NNZ, n, NB)
    n_ovf = overflow_count(keys, info)
    assert n_ovf > 30 * crec.ONLINE_OVF_CAP
    room = crec.overflow_room(n_ovf)

    def no_sparse_step(*_a, **_k):
        raise AssertionError("an online block took the sparse step")
    monkeypatch.setattr(ShardedStore, "train_step", no_sparse_step)
    app = make_app(v1, "crec", tile_online="on", max_data_pass=2)
    app.run()
    assert app.progress.num_ex == 2 * n
    assert app._online_room.room == room and app._online_room.grown == 1
    assert app.timer.totals["online_overflow_pairs"] == 2 * n_ovf
    assert app.timer.totals["online_overflow_slots"] == 2 * room
    assert app.timer.totals["online_room_grown"] == 1

    c2 = tmp_path / "skew.crec2"
    with CRec2Writer(str(c2), nnz=NNZ, nb=NB, subblocks=info.subblocks,
                     cap=info.cap, ovf_cap=room) as w:
        w.append(keys, labels)
    ref = make_app(c2, "crec2", max_data_pass=2)
    ref.run()
    assert np.array_equal(np.asarray(app.store.slots),
                          np.asarray(ref.store.slots))
    assert np.abs(weights(app)).max() > 0


def test_online_block_with_overflow_bit_identical_to_writer(tmp_path, rng):
    """An online block WITH overflow pairs gives the bits a crec2 block
    of the same rows gives: pair words, labels, and the overflow list
    (the file's list is the online one cut or continued to the file's
    width)."""
    n = tilemm.RSUB
    keys, labels = hot_rows(rng, n, 0.3)
    v1 = tmp_path / "a.crec"
    write_v1(v1, keys, labels, block_rows=n)
    info = online_info(NNZ, n, NB)
    n_ovf = overflow_count(keys, info)
    assert n_ovf > crec.ONLINE_OVF_CAP
    inner = PackedFeed(str(v1), fmt="crec", device_put=lambda x: x)
    feed = TileOnlineFeed(inner, info, workers=0,
                          device_put=lambda x: x)
    (block, lab, rows), = list(feed)
    width = len(block["ovf_b"])
    assert width == crec.overflow_room(n_ovf) == feed.room.room
    assert int((block["ovf_b"] != 0xFFFFFFFF).sum()) == n_ovf
    c2 = tmp_path / "a.crec2"
    with CRec2Writer(str(c2), nnz=NNZ, nb=NB, subblocks=info.subblocks,
                     cap=info.cap, ovf_cap=width) as w:
        w.append(keys, labels)
    (views, c2rows), = list(iter_packed2(str(c2)))
    assert c2rows == rows == n
    for k in ("pw", "labels", "ovf_b", "ovf_r"):
        a = np.asarray(block[k]).reshape(-1)
        b = np.asarray(views[k]).reshape(-1).view(a.dtype)
        assert np.array_equal(a, b), k


@pytest.mark.parametrize("n, room", [
    (0, 1024), (1, 1024), (1024, 1024),       # the least room
    (1025, 1280),                             # + an eighth, step 256
    (5913, 7168), (15744, 18432),
    (1_300_000, 1_572_864),                   # the click log's blocks
    (1_293_500, 1_572_864), (1_306_500, 1_572_864),   # half a percent off
    (3_833_856, 4_718_592),                   # every pair of a block
])
def test_overflow_room_of_a_count(n, room):
    """n and an eighth more, up to a multiple of the power of two between
    an eighth and a quarter of n, never under ONLINE_OVF_CAP."""
    assert crec.overflow_room(n) == room
    if n > crec.ONLINE_OVF_CAP:
        assert 1.125 * n <= room <= 1.375 * n + 1
        assert room % (1 << (int(n).bit_length() - 3)) == 0


def test_overflow_room_settles_grows_and_never_shrinks():
    room = crec.OverflowRoom()
    assert room.fit(0) == crec.ONLINE_OVF_CAP and room.grown == 0
    assert room.fit(700) == crec.ONLINE_OVF_CAP and room.grown == 0
    assert room.fit(5913) == 7168 and room.grown == 1
    for n in (5800, 6000, 6500, 7168, 3):   # a settled room takes them
        assert room.fit(n) == 7168
    assert room.grown == 1
    # an empty list keeps the least width whatever the room: it stays on
    # the host, and the block takes the step without a spill
    assert room.fit(0) == crec.ONLINE_OVF_CAP
    assert room.fit(15744) == 18432 and room.grown == 2   # a hotter block
    assert room.fit(5913) == 18432 and room.grown == 2    # never shrinks


def test_room_settles_within_the_first_blocks_and_compiles_once(
        tmp_path, rng):
    """A stream of one cold block (no pair past the cap), three warm
    ones, one hotter by far and one warm again, two passes: the room
    grows at the first warm block and at the hotter one, one spill
    program is compiled a room, the second pass compiles nothing and
    grows nothing, and the cold block's empty list stays on the host (it
    takes the step that has no spill)."""
    n = tilemm.RSUB
    shares = (0.0, 0.2, 0.2, 0.2, 0.5, 0.2)
    parts = [hot_rows(rng, n, s) for s in shares]
    info = online_info(NNZ, n, NB)
    counts = [overflow_count(k, info) for k, _l in parts]
    assert counts[0] == 0 and min(counts[1:]) > crec.ONLINE_OVF_CAP
    warm, hot = crec.overflow_room(counts[1]), crec.overflow_room(counts[4])
    assert max(counts[1:4] + counts[5:]) <= warm < counts[4]
    v1 = tmp_path / "mixed.crec"
    write_v1(v1, np.concatenate([k for k, _l in parts]),
             np.concatenate([l for _k, l in parts]), block_rows=n)
    app = make_app(v1, "crec", tile_online="on")
    shipped = []
    put = app.store.put_block
    app.store.put_block = lambda b: shipped.append(
        len(b["ovf_b"])) or put(b)

    def programs():
        cache = app.store._tile_cache
        return {k[2]: f._cache_size() for k, f in cache.items()
                if k[1] == "train"}

    app.process(str(v1), 0, 1)
    app.flush_metrics()
    assert shipped == [crec.ONLINE_OVF_CAP, warm, warm, warm, hot, hot]
    assert app._online_room.room == hot and app._online_room.grown == 2
    assert programs() == {True: 2, False: 1}
    first = dict(app.timer.totals)
    assert first["online_overflow_pairs"] == sum(counts)
    assert first["online_overflow_slots"] == 3 * warm + 2 * hot
    assert first["online_room_grown"] == 2
    app.process(str(v1), 0, 1)
    app.flush_metrics()
    assert shipped[6:] == [crec.ONLINE_OVF_CAP] + [hot] * 5
    assert programs() == {True: 2, False: 1}          # nothing compiled
    assert app.timer.totals["online_room_grown"] == 2
    assert app.timer.totals["online_overflow_pairs"] == 2 * sum(counts)
    assert app.progress.num_ex == 0 and app._online_room.grown == 2


def test_empty_columns_are_no_feature(tmp_path, rng):
    """A Criteo line with empty columns trains exactly its non-empty
    features: the pad key that fills the short row is no pair, in the
    kernel's pair words and on the overflow list. One step from zero
    state, so FTRL's ``cg`` is |g| to float32 rounding: it equals the
    float64 gradient of the non-empty features alone, and the pad key's
    bucket is untouched."""
    from wormhole_tpu.data.hashing import fold_keys32
    n, nb = tilemm.RSUB, 64 * tilemm.TILE   # per-tile cap 5,248
    empty = rng.random((n, 39)) < 0.35
    # six integer columns hold one hot value wherever present (5,300
    # pairs each): their tiles pass the cap, so the overflow list is
    # exercised too
    ints = rng.integers(0, 50, size=(n, 13))
    ints[:, :6] = 7
    cats = rng.integers(0, 1 << 32, size=(n, 26), dtype=np.uint64)
    cats[:, :8] %= 3
    labels = (rng.random(n) < 0.4).astype(np.uint8)
    path = tmp_path / "holes.criteo"
    with open(path, "w") as f:
        for i in range(n):
            cols = [str(v) for v in ints[i]] + [f"{v:08x}" for v in cats[i]]
            f.write("\t".join([str(labels[i])] + [
                "" if e else c for c, e in zip(cols, empty[i])]) + "\n")
    app = make_app(path, "criteo", tile_online="on", num_buckets=nb,
                   text_block_rows=n, max_data_pass=1, lr_eta=0.1)
    app.run()
    assert app.progress.num_ex == n
    assert app.timer.totals["online_overflow_pairs"] > crec.ONLINE_OVF_CAP
    # the expected gradient, from the program's own text assembler's keys
    # with the pad slots left out
    keys, lab = crec._python_crec_assembler("criteo", 39)(
        open(path, "rb").read())
    real = keys != crec.SENTINEL_KEY
    assert int(real.sum()) == int((~empty).sum())
    rr, cc = np.nonzero(real)
    buckets = fold_keys32(keys[rr, cc], nb).astype(np.int64)
    dual = np.where(lab[rr] > 0, -0.5, 0.5)
    grad = np.bincount(buckets, weights=dual, minlength=nb)
    cg = np.asarray(app.store.slots)[:, 2].astype(np.float64)
    assert np.allclose(cg, np.abs(grad), rtol=1e-6, atol=1e-6)
    pad = int(fold_keys32(np.array([crec.SENTINEL_KEY], np.uint32), nb)[0])
    assert pad not in set(buckets.tolist())
    assert cg[pad] == 0.0 and np.all(np.asarray(app.store.slots)[pad] == 0)
