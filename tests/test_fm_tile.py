"""FM / wide&deep crec2 tile fast path vs the sparse gather/scatter path.

The stretch models previously trained only through the sparse step;
these tests pin the new multi-channel tile path (pooled
pulls + split pushes) to the sparse path's math on identical rows — same
buckets, same update rule — and prove end-to-end learning through the
AsyncSGD driver over a real crec2 file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from wormhole_tpu.data.hashing import fold_keys32
from wormhole_tpu.data.feed import SparseBatch
from wormhole_tpu.models.fm import FMConfig, FMStore
from wormhole_tpu.ops import tilemm

NB = 2 * tilemm.TILE      # 2 tiles
NNZ = 4


@pytest.fixture()
def rng():
    return np.random.default_rng(5)


def _make_rows(rng, n):
    """Distinct keys per row (bucket collisions across rows are fine)."""
    keys = np.empty((n, NNZ), np.uint32)
    for i in range(n):
        keys[i] = rng.choice(1 << 20, size=NNZ, replace=False).astype(
            np.uint32) + 1
    labels = (rng.random(n) < 0.5).astype(np.uint8)
    return keys, labels


def _tile_block(keys, labels, spec, oc=1024):
    """Encode rows exactly as the crec2 writer would (same fold)."""
    n = len(labels)
    buckets = fold_keys32(keys.reshape(-1), spec.nb).astype(np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), keys.shape[1])
    pw, ovb, ovr = tilemm.encode_block(buckets, rows, spec)
    ovb_p = np.full(oc, 0xFFFFFFFF, np.uint32)
    ovr_p = np.zeros(oc, np.uint32)
    ovb_p[:len(ovb)] = ovb
    ovr_p[:len(ovr)] = ovr
    lab = np.full(spec.block_rows, 255, np.uint8)
    lab[:n] = labels
    return {"pw": jnp.asarray(pw), "labels": jnp.asarray(lab),
            "ovf_b": jnp.asarray(ovb_p), "ovf_r": jnp.asarray(ovr_p)}


def _sparse_batch(keys, labels, nb):
    n, nnz = keys.shape
    buckets = fold_keys32(keys.reshape(-1), nb).reshape(n, nnz)
    uniq = np.unique(buckets)
    cols = np.searchsorted(uniq, buckets).astype(np.int32)
    return SparseBatch(
        cols=jnp.asarray(cols),
        vals=jnp.ones((n, nnz), jnp.float32),
        labels=jnp.asarray(labels.astype(np.float32)),
        row_mask=jnp.ones(n, jnp.float32),
        uniq_keys=jnp.asarray(uniq.astype(np.int32)),
        key_mask=jnp.ones(len(uniq), jnp.float32))


class _Info:
    """Minimal stand-in for CRec2Info (spec + ovf_cap is all the tile
    step reads)."""

    def __init__(self, spec, ovf_cap):
        self.spec = spec
        self.ovf_cap = ovf_cap

    def __hash__(self):
        return hash((self.spec, self.ovf_cap))

    def __eq__(self, other):
        return (self.spec, self.ovf_cap) == (other.spec, other.ovf_cap)


def test_fm_tile_step_matches_sparse_step(rng):
    """One FM training step through the tile kernels reproduces the
    sparse gather/scatter step on identical rows: same margins (bf16
    kernel-value tolerance), same touched set, same updated table."""
    n = tilemm.RSUB            # one subblock
    keys, labels = _make_rows(rng, n)
    from wormhole_tpu.data.crec import default_cap
    spec = tilemm.make_spec(NB, 1, default_cap(NNZ, NB))
    info = _Info(spec, 1024)
    cfg = FMConfig(num_buckets=NB, dim=4, seed=3)
    a = FMStore(cfg)           # sparse path
    b = FMStore(cfg)           # tile path (identical init)
    np.testing.assert_array_equal(np.asarray(a.slots), np.asarray(b.slots))
    a.train_step(_sparse_batch(keys, labels, NB))
    b.tile_train_step(_tile_block(keys, labels, spec), info)
    sa, sb = np.asarray(a.slots), np.asarray(b.slots)
    touched_a = np.any(sa != np.asarray(FMStore(cfg).slots), axis=1)
    touched_b = np.any(sb != np.asarray(FMStore(cfg).slots), axis=1)
    np.testing.assert_array_equal(touched_a, touched_b)
    # updated rows agree to bf16-value tolerance (the tile kernels round
    # table values through bf16 — rel ~2^-8 on an init_scale=0.01 table
    # gives ~1e-3 absolute wiggle; the sparse path is all-f32)
    np.testing.assert_allclose(sb[touched_b], sa[touched_a],
                               rtol=0.02, atol=2e-3)
    # eval margins agree too
    ma = np.asarray(a.eval_step(_sparse_batch(keys, labels, NB))[4])
    mb = np.asarray(b.tile_eval_step(_tile_block(keys, labels, spec),
                                     info)[5])[:n]
    np.testing.assert_allclose(mb, ma, rtol=0.02, atol=2e-3)


def test_fm_crec2_end_to_end_learns(tmp_path, rng):
    """AsyncSGD + FMStore over a real crec2 file: the interaction term
    learns an XOR of two planted keys (linearly inseparable — only a
    working FM second-order path can separate it)."""
    from wormhole_tpu.data.crec import CRec2Writer
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    import jax
    n = 6000
    keys, _ = _make_rows(rng, n)
    a = rng.random(n) < 0.5
    b = rng.random(n) < 0.5
    keys[:, 0] = np.where(a, 1111, 2222)
    keys[:, 1] = np.where(b, 3333, 4444)
    labels = (a ^ b).astype(np.uint8)
    path = tmp_path / "fm.crec2"
    with CRec2Writer(str(path), nnz=NNZ, nb=NB, subblocks=1) as w:
        w.append(keys, labels)
    cfg = Config(train_data=str(path), data_format="crec2",
                 num_buckets=NB, max_data_pass=15, disp_itv=1e12,
                 max_delay=1)
    store = FMStore(FMConfig(num_buckets=NB, dim=8, lr_alpha=0.3,
                             seed=1))
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:1", jax.devices()[:1])
    app = AsyncSGD(cfg, rt, store=store)
    prog = app.run()
    assert prog.num_ex == 15 * n
    # late-pass accuracy: average over the last third of passes
    assert prog.acc / max(prog.count, 1) > 0.7


def test_wide_deep_tile_step_matches_sparse_step(rng):
    """One wide&deep training step through the tile kernels reproduces
    the sparse gather/scatter step: same touched set, same table, same
    MLP update (bf16 kernel-value tolerance)."""
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    n = tilemm.RSUB
    keys, labels = _make_rows(rng, n)
    from wormhole_tpu.data.crec import default_cap
    spec = tilemm.make_spec(NB, 1, default_cap(NNZ, NB))
    info = _Info(spec, 1024)
    cfg = WideDeepConfig(num_buckets=NB, dim=4, hidden=(16,), seed=3)
    a = WideDeepStore(cfg)
    b = WideDeepStore(cfg)
    a.train_step(_sparse_batch(keys, labels, NB))
    b.tile_train_step(_tile_block(keys, labels, spec), info)
    sa, sb = np.asarray(a.slots), np.asarray(b.slots)
    fresh = np.asarray(WideDeepStore(cfg).slots)
    touched_a = np.any(sa != fresh, axis=1)
    touched_b = np.any(sb != fresh, axis=1)
    np.testing.assert_array_equal(touched_a, touched_b)
    # bf16-rounded pooled inputs can flip a ReLU near its threshold,
    # discretely changing a handful of bucket gradients — so the table
    # comparison is quantile-based: the bulk must match to bf16
    # tolerance, and even the flipped tail must stay bounded
    diff = np.abs(sb[touched_b] - sa[touched_a])
    assert np.quantile(diff, 0.99) < 5e-3, np.quantile(diff, 0.99)
    assert diff.max() < 0.5, diff.max()
    for kname in a.mlp:
        np.testing.assert_allclose(np.asarray(b.mlp[kname]),
                                   np.asarray(a.mlp[kname]),
                                   rtol=0.05, atol=5e-3)


def test_wide_deep_crec2_end_to_end_learns(tmp_path, rng):
    """AsyncSGD + WideDeepStore over a real crec2 file: the MLP over
    pooled embeddings learns an XOR of two planted keys."""
    from wormhole_tpu.data.crec import CRec2Writer
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    import jax
    n = 6000
    keys, _ = _make_rows(rng, n)
    a = rng.random(n) < 0.5
    b = rng.random(n) < 0.5
    keys[:, 0] = np.where(a, 1111, 2222)
    keys[:, 1] = np.where(b, 3333, 4444)
    labels = (a ^ b).astype(np.uint8)
    path = tmp_path / "wd.crec2"
    with CRec2Writer(str(path), nnz=NNZ, nb=NB, subblocks=1) as w:
        w.append(keys, labels)
    cfg = Config(train_data=str(path), data_format="crec2",
                 num_buckets=NB, max_data_pass=20, disp_itv=1e12,
                 max_delay=1)
    store = WideDeepStore(WideDeepConfig(
        num_buckets=NB, dim=8, hidden=(32,), lr_alpha=0.3,
        lr_alpha_dense=0.1, init_scale=0.1, seed=1))
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:1", jax.devices()[:1])
    app = AsyncSGD(cfg, rt, store=store)
    prog = app.run()
    assert prog.num_ex == 20 * n
    assert prog.acc / max(prog.count, 1) > 0.7


def test_fm_crec2_mesh_training_converges(tmp_path, rng):
    """FM over crec2 on a data:2,model:2 mesh (the shard_map FM tile
    step: model axis shards the embedding-table tiles, data axis shards
    blocks): learns the planted XOR like the single-device path."""
    from wormhole_tpu.data.crec import CRec2Writer
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    import jax
    n = 6000
    keys, _ = _make_rows(rng, n)
    a = rng.random(n) < 0.5
    b = rng.random(n) < 0.5
    keys[:, 0] = np.where(a, 1111, 2222)
    keys[:, 1] = np.where(b, 3333, 4444)
    labels = (a ^ b).astype(np.uint8)
    path = tmp_path / "fm_mesh.crec2"
    with CRec2Writer(str(path), nnz=NNZ, nb=NB, subblocks=1) as w:
        w.append(keys, labels)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    cfg = Config(train_data=str(path), data_format="crec2",
                 num_buckets=NB, max_data_pass=15, disp_itv=1e12,
                 max_delay=1)
    store = FMStore(FMConfig(num_buckets=NB, dim=8, lr_alpha=0.3,
                             seed=1), rt)
    app = AsyncSGD(cfg, rt, store=store)
    prog = app.run()
    assert prog.num_ex == 15 * n
    assert prog.acc / max(prog.count, 1) > 0.7


def test_wide_deep_crec2_mesh_training_converges(tmp_path, rng):
    """Wide&deep over crec2 on a data:2,model:2 mesh: sharded embedding
    table, replicated MLP with data-psum'd gradients."""
    from wormhole_tpu.data.crec import CRec2Writer
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    import jax
    n = 6000
    keys, _ = _make_rows(rng, n)
    a = rng.random(n) < 0.5
    b = rng.random(n) < 0.5
    keys[:, 0] = np.where(a, 1111, 2222)
    keys[:, 1] = np.where(b, 3333, 4444)
    labels = (a ^ b).astype(np.uint8)
    path = tmp_path / "wd_mesh.crec2"
    with CRec2Writer(str(path), nnz=NNZ, nb=NB, subblocks=1) as w:
        w.append(keys, labels)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    cfg = Config(train_data=str(path), data_format="crec2",
                 num_buckets=NB, max_data_pass=20, disp_itv=1e12,
                 max_delay=1)
    store = WideDeepStore(WideDeepConfig(
        num_buckets=NB, dim=8, hidden=(32,), lr_alpha=0.3,
        lr_alpha_dense=0.1, init_scale=0.1, seed=1), rt)
    app = AsyncSGD(cfg, rt, store=store)
    prog = app.run()
    assert prog.num_ex == 20 * n
    assert prog.acc / max(prog.count, 1) > 0.7


def test_fm_put_block_ships_a_coo_list_as_it_is_counts_it_and_drops_an_empty_one(rng):  # noqa: E501
    from wormhole_tpu.data.crec import default_cap
    n = tilemm.RSUB
    keys, labels = _make_rows(rng, n)
    spec = tilemm.make_spec(NB, 1, default_cap(NNZ, NB))
    block = {k: np.array(v) for k, v in
             _tile_block(keys, labels, spec, oc=2048).items()}
    store = FMStore(FMConfig(num_buckets=NB, dim=4, seed=3))
    dev = store.put_block(block)                    # no pair: stays behind
    assert set(dev) == {"pw", "labels"}
    # a list with pairs crosses as the encoder laid it out, a slot a pair
    # (the linear store's COO form: no array beside the two), and its
    # pairs are counted
    block["ovf_b"][:300] = 5 * np.repeat(np.arange(3, dtype=np.uint32), 100)
    block["ovf_r"][:300] = np.arange(300, dtype=np.uint32)
    dev = store.put_block(block)
    assert set(dev) == {"pw", "labels", "ovf_b", "ovf_r"}
    np.testing.assert_array_equal(np.asarray(dev["ovf_b"]), block["ovf_b"])
    np.testing.assert_array_equal(np.asarray(dev["ovf_r"]), block["ovf_r"])
    assert store._listed == {id(dev["ovf_b"]): 300}
    store.tile_train_step(dev, _Info(spec, 2048))
    assert store.timer.totals["fm_spill_blocks"] == 1
    assert store.timer.totals["fm_listed_pairs"] == 300
    del dev
    import gc
    gc.collect()
    assert store._listed == {}                      # gone with the device copy


def test_fm_put_block_ships_one_form_and_counts_the_pairs_in_both(rng):
    """A list that ``HotRoom`` took crosses as its hot form alone, one it
    left COO as its two COO arrays alone; the pairs are counted from the COO
    arrays on the host either way, keyed on whichever list array crossed,
    and each form steps through its own program of the one spill step to
    the same table but for the order of float32 sums."""
    import gc
    from wormhole_tpu.data.crec import HOT_MIN_ROOM, HotRoom, default_cap
    n = tilemm.RSUB
    keys, labels = _make_rows(rng, n)
    spec = tilemm.make_spec(NB, 1, default_cap(NNZ, NB))
    info = _Info(spec, HOT_MIN_ROOM)
    block = {k: np.array(v) for k, v in
             _tile_block(keys, labels, spec, oc=HOT_MIN_ROOM).items()}
    block["ovf_b"][:300] = 5 * np.repeat(np.arange(3, dtype=np.uint32), 100)
    block["ovf_r"][:300] = np.arange(300, dtype=np.uint32)
    form = HotRoom().form(block["ovf_b"], block["ovf_r"], 1)
    assert set(form) == {"ovf_u", "ovf_pw"}
    cfg = FMConfig(num_buckets=NB, dim=4, seed=3)
    a, b = FMStore(cfg), FMStore(cfg)
    assert a.hot_overflow
    hot = a.put_block(dict(block, **form))
    coo = b.put_block(block)
    assert set(hot) == {"pw", "labels", "ovf_u", "ovf_pw"}
    assert set(coo) == {"pw", "labels", "ovf_b", "ovf_r"}
    assert a._listed == {id(hot["ovf_pw"]): 300}
    assert b._listed == {id(coo["ovf_b"]): 300}
    for store, dev in ((a, hot), (b, coo)):
        for _ in range(2):
            store.tile_train_step(dev, info)
        assert store.step_kernel[1] != "in place"
        assert store.timer.totals["fm_spill_blocks"] == 2
        assert store.timer.totals["fm_listed_pairs"] == 600
        assert "fm_in_place_blocks" not in store.timer.totals
    np.testing.assert_allclose(np.asarray(a.slots), np.asarray(b.slots),
                               rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(a.tile_eval_step(hot, info)[5]),
        np.asarray(b.tile_eval_step(coo, info)[5]), rtol=1e-5, atol=1e-6)
    del hot, coo, dev
    gc.collect()
    assert a._listed == b._listed == {}
