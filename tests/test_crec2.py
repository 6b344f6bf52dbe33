"""crec2 tile-grouped format + the tile-matmul training path.

Mirrors the v1 crec tests (test_crec.py) plus the key new property: the
crec2/tilemm path must train the SAME model as the v1 crec dense-apply
path (both fold keys with hashing.fold_keys32), up to the tile kernels'
bf16 value quantization.
"""

import os

import jax
import numpy as np
import pytest

from wormhole_tpu.data.crec import (CRec2Writer, CRecWriter, PackedFeed,
                                    block2_views, iter_packed2,
                                    read_header2)
from wormhole_tpu.data.hashing import fold_keys32
from wormhole_tpu.ops import tilemm

NB = 2 * tilemm.TILE
NNZ = 8


def write_file(path, keys, labels, **kw):
    kw.setdefault("subblocks", 4)
    kw.setdefault("cap", 16384)
    with CRec2Writer(str(path), nnz=NNZ, nb=NB, **kw) as w:
        w.append(keys, labels)


def make_rows(rng, n):
    keys = rng.integers(0, 1 << 32, size=(n, NNZ), dtype=np.uint32)
    keys[keys == 0xFFFFFFFF] = 0
    keys[rng.random((n, NNZ)) < 0.1] = 0xFFFFFFFF  # missing slots
    labels = (rng.random(n) < 0.4).astype(np.uint8)
    return keys, labels


def test_roundtrip_pairs(tmp_path, rng):
    n = 3000
    keys, labels = make_rows(rng, n)
    path = tmp_path / "a.crec2"
    write_file(path, keys, labels)
    info = read_header2(str(path))
    assert info.total_rows == n
    assert info.num_blocks == 1
    blocks = list(iter_packed2(str(path)))
    assert len(blocks) == 1
    views, rows = blocks[0]
    assert rows == n
    # decode all pairs back to (bucket, row) and compare multisets
    spec = info.spec
    pw = views["pw"].reshape(spec.tiles, spec.subblocks, spec.cap)
    bt, rt, pad = tilemm.unpack_fields(pw)
    got = []
    for t in range(spec.tiles):
        for s in range(spec.subblocks):
            live = ~pad[t, s]
            b = t * tilemm.TILE + bt[t, s][live].astype(np.int64)
            r = s * tilemm.RSUB + rt[t, s][live].astype(np.int64)
            got += list(zip(b.tolist(), r.tolist()))
    rr, cc = np.nonzero(keys != np.uint32(0xFFFFFFFF))
    want = sorted(zip(fold_keys32(keys[rr, cc], NB).tolist(), rr.tolist()))
    assert sorted(got) == want
    # labels: real rows then PAD_LABEL padding
    lab = views["labels"]
    assert np.array_equal(lab[:n], labels)
    assert np.all(lab[n:] == 255)


def test_part_ownership(tmp_path, rng):
    """Part k of n owns a contiguous block range; parts partition the
    file (InputSplit semantics)."""
    n = 2 * 4 * tilemm.RSUB + 17    # 3 blocks (subblocks=4)
    keys, labels = make_rows(rng, n)
    path = tmp_path / "b.crec2"
    write_file(path, keys, labels, cap=33024)
    info = read_header2(str(path))
    assert info.num_blocks == 3
    seen = []
    for part in range(2):
        for _views, rows in iter_packed2(str(path), part, 2):
            seen.append(rows)
    assert sum(seen) == n and len(seen) == 3


def test_feed_cache_replays(tmp_path, rng):
    keys, labels = make_rows(rng, 1000)
    path = tmp_path / "c.crec2"
    write_file(path, keys, labels)
    feed = PackedFeed(str(path), fmt="crec2", cache=True)
    first = [id(d["pw"]) for d, _h, _r in feed]
    assert feed._cache_full
    second = [id(d["pw"]) for d, _h, _r in feed]
    assert first == second            # same device buffers replayed
    assert feed.bytes_read == read_header2(str(path)).block_bytes


def _train(tmp_path, rng, fmt, keys, labels, passes=3):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config
    path = tmp_path / f"train.{fmt}"
    if fmt == "crec2":
        write_file(path, keys, labels)
    else:
        with CRecWriter(str(path), nnz=NNZ, block_rows=4 * tilemm.RSUB) as w:
            w.append(keys, labels)
    cfg = Config(train_data=str(path), data_format=fmt, num_buckets=NB,
                 lr_eta=0.5, max_data_pass=passes, disp_itv=1e12,
                 max_delay=1)
    app = AsyncSGD(cfg)
    app.run()
    return app


def test_crec2_learns_and_matches_v1(tmp_path, rng):
    """FTRL over crec2 converges, and its weights match the v1 crec
    dense-apply path trained on the same rows (same key fold; bf16
    kernel tolerance)."""
    n = 4000
    keys, labels = make_rows(rng, n)
    # make labels learnable: one planted key decides the label
    planted = np.uint32(123456)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = planted
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    app2 = _train(tmp_path, rng, "crec2", keys, labels, passes=6)
    prog = app2.progress
    assert prog.num_ex == 6 * n
    # mean per-pass accuracy includes the untrained first pass
    assert prog.acc / max(prog.count, 1) > 0.85
    app1 = _train(tmp_path, rng, "crec", keys, labels, passes=6)
    w2 = np.asarray(app2.store.handle.weights(app2.store.slots))
    w1 = np.asarray(app1.store.handle.weights(app1.store.slots))
    live = (np.abs(w1) > 1e-6) | (np.abs(w2) > 1e-6)
    assert live.any()
    assert np.allclose(w1[live], w2[live], rtol=0.05, atol=5e-3)


def test_writer_rejects_skew_overflow(tmp_path, rng):
    """Beyond-ovf_cap skew raises loudly instead of dropping pairs."""
    n = 2000
    keys = np.full((n, NNZ), np.uint32(42), np.uint32)  # one hot bucket
    labels = np.zeros(n, np.uint8)
    with pytest.raises(ValueError, match="overflow"):
        write_file(tmp_path / "d.crec2", keys, labels, cap=128, ovf_cap=128)


@pytest.mark.parametrize("cap", [40960, 128],
                         ids=["no_overflow", "overflow"])
def test_file_written_natively_is_byte_identical(tmp_path, rng, monkeypatch,
                                                 cap):
    """The writer's file does not say which encoder wrote it: the native
    pass and the numpy encoder give the same bytes, the overflow lists
    (two blocks, the second short) included."""
    from wormhole_tpu.data import native
    if native.get_tile_encoder() is None:
        pytest.skip("native tile encoder not built")
    n = 4 * tilemm.RSUB + 1234
    keys, labels = make_rows(rng, n)
    kw = dict(cap=cap, ovf_cap=1 << 18)
    write_file(tmp_path / "native.crec2", keys, labels, **kw)
    monkeypatch.setattr(native, "get_tile_encoder", lambda: None)
    write_file(tmp_path / "numpy.crec2", keys, labels, **kw)
    a = (tmp_path / "native.crec2").read_bytes()
    assert a == (tmp_path / "numpy.crec2").read_bytes()
    n_ovf = sum(int((np.asarray(v["ovf_b"]) != 0xFFFFFFFF).sum())
                for v, _ in iter_packed2(str(tmp_path / "native.crec2")))
    assert (n_ovf > 0) == (cap == 128)


def test_crec2_mesh_training_converges(tmp_path, rng):
    """AsyncSGD over crec2 on a data:2,model:2 mesh (the shard_map tile
    step): learns the planted feature like the single-device path."""
    n = 4000
    keys, labels = make_rows(rng, n)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config
    path = tmp_path / "mesh.crec2"
    write_file(path, keys, labels)
    import jax
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    cfg = Config(train_data=str(path), data_format="crec2", num_buckets=NB,
                 lr_eta=0.5, max_data_pass=6, disp_itv=1e12, max_delay=1)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    app = AsyncSGD(cfg, rt)
    prog = app.run()
    assert prog.num_ex == 6 * n
    assert prog.acc / max(prog.count, 1) > 0.85


def test_crec2_metric_accounting_exact(tmp_path, rng):
    """The on-device metric accumulator + async ticket pipeline credits
    every step exactly once across mid-stream (non-final) drains, cached
    replay windows, and the final flush: num_ex == rows x passes, count
    == steps, and accuracy stays a mean over steps."""
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config

    n = 2 * 4 * tilemm.RSUB + 100          # 3 blocks, padded tail
    keys, labels = make_rows(rng, n)
    keys[rng.random((n, NNZ)) < 0.9] = 0xFFFFFFFF   # sparse rows: small cap
    # keep every row non-empty with a fresh uniform key (a shared
    # constant would be exactly the hot-bucket skew the cap rejects)
    keys[:, 0] = rng.integers(1, 1 << 32, size=n, dtype=np.uint32)
    path = tmp_path / "acct.crec2"
    write_file(path, keys, labels, cap=8192, ovf_cap=4096)
    cfg = Config(train_data=str(path), data_format="crec2", num_buckets=NB,
                 lr_eta=0.5, max_data_pass=1, disp_itv=0.0,  # drain often
                 max_delay=2, cache_device=True)
    app = AsyncSGD(cfg)
    passes = 5
    num_ex = count = 0
    objv_sum = 0.0
    # tiny drain window so replay passes hit the mid-stream ticket path
    # (instance attribute: must not leak into other tests' AsyncSGDs)
    app.CREC_DRAIN_CHUNK = 2
    for _ in range(passes):
        prog = app.process(str(path), 0, 1)
        num_ex += prog.num_ex
        count += prog.count
        objv_sum += prog.objv
    tail = app.flush_metrics()
    num_ex += tail.num_ex
    count += tail.count
    objv_sum += tail.objv
    assert num_ex == passes * n            # padded rows not credited
    # one credit per dispatched step: under a data-parallel mesh the 3
    # blocks ride in ceil(3/D) grouped steps, single-device in 3
    D = max(app.rt.data_axis_size, 1)
    assert count == passes * -(-3 // D)
    assert np.isfinite(objv_sum) and objv_sum > 0
    assert not app._crec_acc.tickets and app._crec_acc.count == 0


def test_crec2_adagrad_l1_learns(tmp_path, rng):
    """The tile path with a non-identity-on-zero-grad handle (AdaGrad +
    L1): the touched-bucket mask keeps untouched buckets frozen, so the
    planted feature is learned instead of being prox-shrunk away every
    sweep."""
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config

    n = 4000
    keys, labels = make_rows(rng, n)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    path = tmp_path / "ada.crec2"
    write_file(path, keys, labels)
    cfg = Config(train_data=str(path), data_format="crec2", num_buckets=NB,
                 lr_eta=0.5, max_data_pass=6, disp_itv=1e12, max_delay=1)
    cfg.algo = type(cfg.algo)("adagrad")
    cfg.lambda_ = [0.1, 0.01]
    app = AsyncSGD(cfg)
    app.run()
    prog = app.progress
    assert prog.num_ex == 6 * n
    assert prog.acc / max(prog.count, 1) > 0.8
    # untouched buckets stayed exactly at init (zero): the L1 prox never
    # swept them, and touched weights are nonzero
    w = np.asarray(app.store.handle.weights(app.store.slots))
    assert app.store.nnz_weight() > 0
    assert np.count_nonzero(w) < NB  # the sweep did not touch everything


def test_crec2_predict_task(tmp_path, rng):
    """test_data + pred_out over crec2 (the tile eval path feeding the
    pooled predict writer): one sigma(margin) per real row, in file
    order, padded tail rows excluded."""
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.ops.metrics import auc_np
    from wormhole_tpu.utils.config import Config

    n = 3000
    keys, labels = make_rows(rng, n)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    path = tmp_path / "p.crec2"
    write_file(path, keys, labels)
    pred = str(tmp_path / "preds.txt")
    cfg = Config(train_data=str(path), test_data=str(path), pred_out=pred,
                 data_format="crec2", num_buckets=NB, lr_eta=0.5,
                 max_data_pass=4, disp_itv=1e12, max_delay=1)
    app = AsyncSGD(cfg)
    app.run()
    probs = np.array([float(x) for x in open(pred).read().split()])
    assert len(probs) == n                 # padded rows not predicted
    assert ((probs >= 0) & (probs <= 1)).all()
    assert auc_np(labels.astype(np.float64), probs) > 0.9


def test_restore_drops_stale_metric_accumulator(tmp_path, rng):
    """Checkpoint restore must not credit pre-restore steps: the
    on-device metric accumulator is dropped with the rest of the
    transient device state."""
    import jax.numpy as jnp
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.data.crec import CRec2Info

    spec_nb = 2 * tilemm.TILE
    spec = tilemm.make_spec(spec_nb, subblocks=4, cap=1024)
    info = CRec2Info(nnz=NNZ, block_rows=spec.block_rows,
                     total_rows=spec.block_rows, nb=spec_nb,
                     subblocks=4, cap=spec.cap, ovf_cap=0)
    store = ShardedStore(StoreConfig(num_buckets=spec_nb, loss="logit"),
                         FTRLHandle(penalty=L1L2(0.1, 0.01),
                                    lr=LearnRate(0.5, 1.0)))
    buckets = rng.integers(0, spec_nb, size=5000, dtype=np.int64)
    rows = rng.integers(0, spec.block_rows, size=5000).astype(np.int64)
    pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
    assert not len(ovb)
    labels = (rng.random(spec.block_rows) < 0.4).astype(np.uint8)
    block = {"pw": jnp.asarray(pw), "labels": jnp.asarray(labels)}
    snap = jax.tree_util.tree_map(np.asarray, store.state_pytree())
    store.tile_train_step(block, info)
    store.restore_pytree(snap)           # rewind: the step never happened
    row = store.fetch_metrics()
    assert row[1] == 0.0                 # no rows credited
    store.tile_train_step(block, info)
    row = store.fetch_metrics()
    assert row[1] == float(spec.block_rows)


def test_cross_format_warm_start_raises(tmp_path, rng):
    """A model saved under the text key fold (splitmix64) must refuse a
    crec2 warm start (mix32): the two schemes bucket every feature
    differently, so a silent load would remap the whole model."""
    from wormhole_tpu.learners.handles import FTRLHandle
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig

    store = ShardedStore(StoreConfig(num_buckets=64), FTRLHandle())
    # plant one nonzero weight (slot 0) so the dump has data lines
    store.slots = store.slots.at[3, 0].set(-1.0)
    path = str(tmp_path / "model.txt")
    store.save_model(path, rank=0, key_fold="splitmix64")
    with pytest.raises(ValueError, match="key_fold"):
        store.load_model(path, expect_key_fold="mix32")
    store.load_model(path, expect_key_fold="splitmix64")  # same fold: OK


def test_crec_v1_mesh_training_converges(tmp_path, rng):
    """AsyncSGD over crec v1 on a data:2,model:2 mesh (the shard_map
    dense-apply step): learns the planted feature like the single-device
    v1 path."""
    n = 4000
    keys, labels = make_rows(rng, n)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config
    path = tmp_path / "mesh.crec"
    with CRecWriter(str(path), nnz=NNZ, block_rows=1024) as w:
        w.append(keys, labels)
    import jax
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    cfg = Config(train_data=str(path), data_format="crec", num_buckets=NB,
                 lr_eta=0.5, max_data_pass=6, disp_itv=1e12, max_delay=1)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    app = AsyncSGD(cfg, rt)
    prog = app.run()
    assert prog.num_ex == 6 * n
    assert prog.acc / max(prog.count, 1) > 0.85


def test_crec_v1_mesh_matches_single_device(tmp_path, rng):
    """v1 mesh dense-apply weights match the single-device v1 step on
    identical rows (exact semantics: same fold, same handle updates —
    only the step grouping differs)."""
    n = 2048
    keys, labels = make_rows(rng, n)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config
    import jax
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    path = tmp_path / "ab.crec"
    with CRecWriter(str(path), nnz=NNZ, block_rows=512) as w:
        w.append(keys, labels)

    def train(mesh_spec):
        cfg = Config(train_data=str(path), data_format="crec",
                     num_buckets=NB, lr_eta=0.5, max_data_pass=2,
                     disp_itv=1e12, max_delay=1)
        rt = MeshRuntime.create()
        if mesh_spec:
            rt.mesh = make_mesh(mesh_spec, jax.devices()[:4])
        else:
            rt.mesh = make_mesh("data:1", jax.devices()[:1])
        app = AsyncSGD(cfg, rt)
        app.run()
        return np.asarray(app.store.handle.weights(
            app.store.slots.astype(np.float32)))

    w_single = train(None)
    # model:4 keeps the per-step geometry identical (one block per step;
    # D=1), so range-sharding the table must be EXACT up to f32 reorder.
    # (data:K instead groups K blocks into one handle update — a batch-
    # size change, covered by the convergence test above.)
    w_mesh = train("data:1,model:4")
    live = (np.abs(w_single) > 1e-6) | (np.abs(w_mesh) > 1e-6)
    assert live.any()
    assert np.allclose(w_single[live], w_mesh[live], rtol=1e-4, atol=1e-5)


# -- the block source: a local crec2 file is mapped, any other stream read --


def _three_blocks(tmp_path, rng):
    n = 2 * 4 * tilemm.RSUB + 17    # 3 blocks (subblocks=4)
    keys, labels = make_rows(rng, n)
    path = tmp_path / "m.crec2"
    write_file(path, keys, labels, cap=33024)
    assert read_header2(str(path)).num_blocks == 3
    return str(path)


def _same_blocks(got, want):
    assert len(got) == len(want)
    for (a, ra), (b, rb) in zip(got, want):
        assert ra == rb and sorted(a) == sorted(b)
        for k in b:
            assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), k


@pytest.mark.parametrize("workers", [0, 2])
def test_local_file_blocks_are_views_of_a_mapping(tmp_path, rng, workers):
    """Over a local file the feed's blocks are read-only views of one
    mapping, equal to ``iter_packed2``'s (which reads into fresh memory)
    for every block over two passes, and the feed copies no byte."""
    path = _three_blocks(tmp_path, rng)
    want = list(iter_packed2(path))
    feed = PackedFeed(path, fmt="crec2", device_put=lambda x: x,
                      workers=workers)
    for _pass in range(2):
        got = [(host, rows) for _dev, host, rows in feed]
        _same_blocks(got, want)
        assert not any(v.flags.writeable for h, _r in got
                       for v in h.values())
    assert feed.host_copy_bytes == 0
    assert feed.bytes_read == 2 * 3 * read_header2(path).block_bytes


@pytest.mark.parametrize("workers", [0, 2])
def test_stream_without_fileno_is_read_into_memory(tmp_path, rng, workers):
    """A stream that is no local file (here an in-memory one behind a
    registered scheme, with no descriptor) keeps ``readinto``: the same
    blocks, and every byte of them counted as copied on the host."""
    import io
    from wormhole_tpu.data import stream
    path = _three_blocks(tmp_path, rng)
    raw = open(path, "rb").read()

    class MemFS(stream.FileSystem):
        def open(self, uri, mode="rb"):
            return io.BytesIO(raw)

    stream.register_filesystem("mem", MemFS())
    try:
        feed = PackedFeed("mem://m.crec2", fmt="crec2",
                          device_put=lambda x: x, workers=workers)
        got = [(host, rows) for _dev, host, rows in feed]
    finally:
        del stream._REGISTRY["mem"]
    _same_blocks(got, list(iter_packed2(path)))
    assert all(v.flags.writeable for h, _r in got for v in h.values())
    assert feed.host_copy_bytes == feed.bytes_read \
        == 3 * read_header2(path).block_bytes


def test_mapping_is_closed_with_the_source(tmp_path, rng):
    """``close`` (the feed's ``on_close``) closes the mapping at once
    when no view of it is left; a view still held keeps it mapped, and
    readable, until the view goes."""
    import gc
    import weakref
    from wormhole_tpu.data.crec import BlockSource
    path = _three_blocks(tmp_path, rng)
    src = BlockSource(path)
    assert src.mapped
    m = src._map
    src.read(0)
    src.close()
    assert m.closed and not src.mapped

    src = BlockSource(path)
    views, _rows = src.read(2)
    alive = weakref.ref(src._map)
    src.close()
    assert not src.mapped and not alive().closed
    _same_blocks([(views, _rows)], list(iter_packed2(path))[2:])
    del views
    gc.collect()
    assert alive() is None


@pytest.mark.parametrize("workers", [0, 2])
def test_feed_closes_its_mapping_every_pass(tmp_path, rng, monkeypatch,
                                            workers):
    """A pass maps the file once and its end (``on_close``, or the
    serial stream's ``finally``) lets go of that mapping: with the
    blocks dropped, nothing stays mapped behind the feed."""
    import gc
    import weakref
    from wormhole_tpu.data import crec
    path = _three_blocks(tmp_path, rng)
    maps = []
    real = crec._map_local

    def recording(p):
        m = real(p)
        maps.append(weakref.ref(m))
        return m

    monkeypatch.setattr(crec, "_map_local", recording)
    feed = PackedFeed(path, fmt="crec2", device_put=lambda x: x,
                      workers=workers)
    for _pass in range(2):
        for item in feed:
            assert maps[-1]() is not None and not maps[-1]().closed
        del item
    gc.collect()
    assert len(maps) == 2 and all(m() is None for m in maps)


def test_truncated_mapped_block_raises(tmp_path, rng):
    from wormhole_tpu.data.crec import BlockSource
    path = _three_blocks(tmp_path, rng)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 64)
    src = BlockSource(path)
    src.read(1)
    with pytest.raises(IOError, match="truncated block 2"):
        src.read(2)
    src.close()
