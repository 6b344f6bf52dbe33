"""Tile-blocked MXU gather/scatter vs the exact numpy reference.

Mirrors the reference's kernel-test style (spmv_test.cc:16-89 checks the
parallel SpMV against the single-thread result); here the tiled matmul
formulation is checked against a scatter/gather oracle, including padding,
masked pairs, and the overflow spill path.
"""

import numpy as np
import pytest

from wormhole_tpu.learners import table as tbl
from wormhole_tpu.ops import tilemm

SPEC = tilemm.TileSpec(nb=2 * tilemm.TILE, subblocks=2, cap=1280,
                       group=2, tiles_step=2)


def make_pairs(rng, n_pairs, spec=SPEC, rows_limit=None):
    buckets = rng.integers(0, spec.nb, size=n_pairs).astype(np.int64)
    rows = rng.integers(0, rows_limit or spec.block_rows,
                        size=n_pairs).astype(np.int64)
    return buckets, rows


def test_encode_roundtrip():
    rng = np.random.default_rng(0)
    buckets, rows = make_pairs(rng, 2000)
    pw, ovb, ovr = tilemm.encode_block(buckets, rows, SPEC)
    assert pw.shape == SPEC.pairs_shape
    assert len(ovb) == 0
    # decode every non-pad pair and compare multisets
    pw_f = pw.reshape(SPEC.tiles, SPEC.subblocks, SPEC.cap)
    bt, rt, pad = tilemm.unpack_fields(pw_f)
    got = []
    for t in range(SPEC.tiles):
        for s in range(SPEC.subblocks):
            for c in range(SPEC.cap):
                if not pad[t, s, c]:
                    b = t * tilemm.TILE + int(bt[t, s, c])
                    r = s * tilemm.RSUB + int(rt[t, s, c])
                    got.append((b, r))
    want = sorted(zip(buckets.tolist(), rows.tolist()))
    assert sorted(got) == want


def test_forward_backward_match_oracle():
    rng = np.random.default_rng(1)
    buckets, rows = make_pairs(rng, 4000)
    pw, _, _ = tilemm.encode_block(buckets, rows, SPEC)
    w = (rng.standard_normal(SPEC.nb) * 0.1).astype(np.float32)
    dual = rng.standard_normal(SPEC.block_rows).astype(np.float32)
    mg = np.asarray(tilemm.forward_margins(pw, w, SPEC))
    g = np.asarray(tilemm.backward_grad(pw, dual, SPEC))
    om = tilemm.forward_margins_ref(buckets, rows, w, SPEC.block_rows)
    og = tilemm.backward_grad_ref(buckets, rows, dual, SPEC.nb)
    # bf16 one-hot matmuls quantize the VALUES (w, dual) to bf16; the
    # reductions accumulate in f32
    assert np.max(np.abs(mg - om)) <= 2e-2 * max(1, np.abs(om).max())
    assert np.max(np.abs(g - og)) <= 2e-2 * max(1, np.abs(og).max())


def test_overflow_spill_exact():
    """A hot bucket past `cap` spills to the COO path and stays exact."""
    rng = np.random.default_rng(2)
    buckets, rows = make_pairs(rng, 3000)
    hot = 7 * tilemm.TILE // 4          # some bucket in tile 1
    buckets = np.concatenate([buckets, np.full(1400, hot, np.int64)])
    rows = np.concatenate(
        [rows, rng.integers(0, tilemm.RSUB, size=1400).astype(np.int64)])
    pw, ovb, ovr = tilemm.encode_block(buckets, rows, SPEC)
    assert len(ovb) > 0                  # hot bucket exceeds cap
    cap_o = 1536
    pad_b = np.full(cap_o, 0xFFFFFFFF, np.uint32)
    pad_r = np.zeros(cap_o, np.uint32)
    pad_b[:len(ovb)], pad_r[:len(ovr)] = ovb, ovr
    w = (rng.standard_normal(SPEC.nb) * 0.1).astype(np.float32)
    dual = rng.standard_normal(SPEC.block_rows).astype(np.float32)
    mg = np.asarray(tilemm.forward_margins(pw, w, SPEC, pad_b, pad_r))
    g = np.asarray(tilemm.backward_grad(pw, dual, SPEC, pad_b, pad_r))
    om = tilemm.forward_margins_ref(buckets, rows, w, SPEC.block_rows)
    og = tilemm.backward_grad_ref(buckets, rows, dual, SPEC.nb)
    assert np.max(np.abs(mg - om)) <= 2e-2 * max(1, np.abs(om).max())
    assert np.max(np.abs(g - og)) <= 2e-2 * max(1, np.abs(og).max())


def test_pad_pairs_are_noops():
    """All-pad encoding produces zero margins and zero gradient."""
    pw = np.full(SPEC.pairs_shape, tilemm.PADWORD, np.uint32)
    rng = np.random.default_rng(3)
    w = rng.standard_normal(SPEC.nb).astype(np.float32)
    dual = rng.standard_normal(SPEC.block_rows).astype(np.float32)
    assert np.all(np.asarray(tilemm.forward_margins(pw, w, SPEC)) == 0)
    assert np.all(np.asarray(tilemm.backward_grad(pw, dual, SPEC)) == 0)


def _listed_block(rng, spec, listed, nb_local):
    """One block's pairs for the mesh oracle test, split into what the
    tile kernels take and what rides the COO overflow list. ``listed``:
    "empty" (no list), "short" (a few dozen pairs) or "third" (a third of
    the block's pairs). A list always holds pairs on both sides of the
    MODEL shard boundary and on its first and last bucket: for each of
    the four edge buckets one reserved row whose ONLY pair is that listed
    pair (its margin is the bucket's weight, unrounded), and five more
    listed pairs from rows of one label, so that the bucket's weight
    leaves zero at the first step. Returns (pw, ovf_b, ovf_r, buckets,
    rows, labels, edges) with ``edges`` the (bucket, reserved row)s."""
    R = spec.block_rows
    buckets, rows = make_pairs(rng, 3000, spec)
    labels = (rng.random(R) < 0.4).astype(np.uint8)
    if listed == "empty":
        pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
        assert not len(ovb)
        return pw, None, None, buckets, rows, labels, []
    edge_b = np.array([0, nb_local - 1, nb_local, spec.nb - 1], np.int64)
    reserved = np.arange(4, dtype=np.int64) * 17 + 5
    keep = ~np.isin(buckets, edge_b) & ~np.isin(rows, reserved)
    buckets, rows = buckets[keep], rows[keep]
    on_list = (rng.random(len(buckets)) < 1 / 3 if listed == "third"
               else np.arange(len(buckets)) < 16)
    lb, lr = [buckets[on_list]], [rows[on_list]]
    lb.append(edge_b)
    lr.append(reserved)
    ones = np.setdiff1d(np.flatnonzero(labels == 1), reserved)
    for b in edge_b:                     # five rows of one label a bucket
        lb.append(np.full(5, b, np.int64))
        lr.append(rng.choice(ones, size=5, replace=False).astype(np.int64))
    lb, lr = np.concatenate(lb), np.concatenate(lr)
    order = rng.permutation(len(lb))     # the edges anywhere in the list
    lb, lr = lb[order], lr[order]
    pw, ovb, _ = tilemm.encode_block(buckets[~on_list], rows[~on_list],
                                     spec)
    assert not len(ovb)
    return (pw, lb, lr, np.concatenate([buckets[~on_list], lb]),
            np.concatenate([rows[~on_list], lr]), labels,
            list(zip(edge_b.tolist(), reserved.tolist())))


@pytest.mark.parametrize("algo,listed,dtype", [
    *[pytest.param(algo, listed, "float32", id=f"{algo}-{listed}")
      for listed in ("empty", "short", "third")
      for algo in ("ftrl", "adagrad_l1")],
    # a bfloat16 table stays (nb, val_len): the same step slices the
    # shard's planes out of it and stacks them again
    pytest.param("ftrl", "short", "bfloat16", id="ftrl-short-bf16-stacked"),
])
def test_mesh_tile_step_matches_oracle(algo, listed, dtype):
    """The shard_map tile step on a data:2,model:2 mesh computes the same
    margins/gradient/update as the exact scatter oracle: model shards own
    tile ranges, data shards own blocks, gradients sum across data. A
    float32 table is one plane a slot there, each split over MODEL on its
    tile axis, before the step and after it, and no step crosses.
    The adagrad_l1 case compiles and checks the masked (touched-bucket)
    mesh branch: zero-psum'd-grad buckets must keep their exact slots.
    With a COO overflow list (``short``, ``third``) every chip is handed
    its DATA member's whole list and takes the pairs whose bucket its
    MODEL shard owns: two steps, so that the listed pairs' weights are
    gathered from a table that has left zero; after the first the
    gradient is exact (every dual is +-0.5), and after it a row whose one
    pair is a listed pair on the shard boundary reads that bucket's
    weight to the last bit: taken by one shard, once, unrounded."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners.handles import (AdaGradHandle, FTRLHandle,
                                               LearnRate)
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.loss import logit_dual
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh

    rng = np.random.default_rng(5)
    nb = 2 * tilemm.TILE            # one tile per model shard
    spec = tilemm.make_spec(nb, subblocks=2, cap=1280)
    raw = [_listed_block(rng, spec, listed, nb // 2) for _ in range(2)]
    width = 0 if listed == "empty" else 64 * -(-max(
        len(b[1]) for b in raw) // 64)
    info = CRec2Info(nnz=8, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb,
                     subblocks=2, cap=spec.cap, ovf_cap=width)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    if algo == "ftrl":
        handle = FTRLHandle(penalty=L1L2(0.1, 0.01), lr=LearnRate(0.5, 1.0))
    else:
        handle = AdaGradHandle(penalty=L1L2(0.1, 0.01),
                               lr=LearnRate(0.5, 1.0))
    store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit",
                                     param_dtype=dtype), handle, rt)
    planar = dtype == "float32"
    assert isinstance(store.slots, tbl.PlaneTable) == planar

    blocks = {"pw": np.stack([b[0] for b in raw]),
              "labels": np.stack([b[5] for b in raw])}
    if width:
        lists = [tilemm.cap_overflow(b[1].astype(np.uint32),
                                     b[2].astype(np.uint32), width)
                 for b in raw]
        blocks["ovf_b"] = np.stack([ob for ob, _ in lists])
        blocks["ovf_r"] = np.stack([orow for _, orow in lists])

    want = np.asarray(store.slots).astype(np.float32)
    mask = np.ones(spec.block_rows, np.float32)
    for step in range(2 if width else 1):
        slots0 = want
        store.tile_train_step_mesh(blocks, info)
        assert isinstance(store.slots, tbl.PlaneTable) == planar
        assert store.slots.sharding.spec[0] == "model"
        got = np.asarray(jax.device_get(store.slots)).astype(np.float32)

        # oracle: per-block margins/duals on pre-step weights; gradient
        # sums
        w0 = np.asarray(handle.weights(jnp.asarray(slots0)))
        g_tot = np.zeros(nb, np.float64)
        for _pw, _lb, _lr, buckets, rows, labels, _edges in raw:
            mg = tilemm.forward_margins_ref(buckets, rows, w0,
                                            spec.block_rows)
            dual = np.asarray(logit_dual(
                jnp.asarray(mg), jnp.asarray(labels.astype(np.float32)),
                jnp.asarray(mask)))
            g_tot += tilemm.backward_grad_ref(buckets, rows, dual, nb)
        want = np.asarray(handle.push(jnp.asarray(slots0),
                                      jnp.asarray(g_tot.astype(np.float32)),
                                      jnp.float32(step + 1), jnp.float32(0)))
        if algo != "ftrl":
            want = np.where((g_tot != 0.0)[:, None], want, slots0)
            # the masked branch really froze the buckets no pair names
            # (a touched bucket's float32 gradient may cancel to zero in
            # float64 and not on the chip, or the other way round)
            untouched = np.bincount(np.concatenate([b[3] for b in raw]),
                                    minlength=nb) == 0
            assert untouched.any()
            np.testing.assert_array_equal(got[untouched], slots0[untouched])
        err = np.max(np.abs(got - want)) / (np.abs(want).max() + 1e-9)
        assert err < 2e-2, (step, err)
        if not planar:
            want = got              # the table's own rounding is no error
        if step == 0 and algo == "ftrl":
            # from zero weights every dual is +-0.5: FTRL's z is the
            # gradient, exact in float32 on both sides, listed pairs and
            # all; a pair dropped or taken twice is off by 0.5
            np.testing.assert_array_equal(got[:, 1],
                                          g_tot.astype(np.float32))
    if not width:
        return
    # the listed pairs at the shard boundary, through the eval step: the
    # reserved rows' margins are their one bucket's weight, bit for bit
    margin = np.asarray(store.tile_eval_step_mesh(blocks, info)[5])
    assert store.timer.counts.get("table_cross", 0) == 0
    w_now = np.asarray(handle.weights(jnp.asarray(got)))
    for d, (_pw, _lb, _lr, _b, _r, _labels, edges) in enumerate(raw):
        for bucket, row in edges:
            assert w_now[bucket] != 0.0, bucket
            assert margin[d * spec.block_rows + row] == w_now[bucket], (
                d, bucket, row)


def test_a_mesh_table_without_whole_tiles_a_shard_stays_stacked():
    """Three tiles over ``model:2`` split into rows but not into whole
    tiles: the store keeps ``(nb, val_len)`` rows over MODEL, the mesh
    tile step refuses the geometry as it always did, and the v1 dense
    mesh step runs on the stacked shards with no crossing."""
    import jax
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners.handles import FTRLHandle
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh

    nb = 3 * tilemm.TILE
    rt = MeshRuntime(mesh=make_mesh("data:2,model:2", jax.devices()[:4]))
    store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                         FTRLHandle(), rt)
    assert not store._planar
    assert store.slots.shape == (nb, 3)
    assert tuple(store.slots.sharding.spec) == ("model", None)
    spec = tilemm.make_spec(nb, subblocks=1, cap=128)
    info = CRec2Info(nnz=1, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb, subblocks=1,
                     cap=spec.cap, ovf_cap=0)
    with pytest.raises(ValueError, match="not shardable over model axis"):
        store.tile_train_step_mesh({}, info)
    rows, nnz = 64, 3
    rng = np.random.default_rng(3)
    packed = np.concatenate([
        rng.integers(0, 1 << 32, (2, rows * nnz), dtype=np.uint32)
        .view(np.uint8).reshape(2, -1),
        rng.integers(0, 2, (2, rows), dtype=np.uint8)], axis=1)
    store.dense_train_step_mesh(packed, rows, nnz)
    assert store.slots.shape == (nb, 3)
    assert tuple(store.slots.sharding.spec) == ("model", None)
    assert np.abs(np.asarray(store.slots)[:, 1]).sum() > 0   # z moved
    assert store.timer.counts.get("table_cross", 0) == 0


def test_mesh_tile_step_large_nb_cap_floor():
    """Model-axis sharding in the HIGH-nb pad-floor regime: 128 tiles (nb=2^21) with ~64 pairs per (subblock, tile)
    — cap floors at 128, so the pairs array is ~50% padding — sharded
    model:4 across a data:2,model:4 CPU mesh. The mesh step must still
    match the exact scatter oracle: pad words contribute nothing, tile
    ranges partition cleanly at any tiles/shard, and gradients sum
    across data shards."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.loss import logit_dual
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh

    rng = np.random.default_rng(9)
    nb = 128 * tilemm.TILE          # 2^21 buckets, 32 tiles per shard
    spec = tilemm.make_spec(nb, subblocks=1, cap=128)
    n_pairs = 8192                  # ~64 per tile: deep in the pad floor
    info = CRec2Info(nnz=1, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb,
                     subblocks=1, cap=spec.cap, ovf_cap=0)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:4", jax.devices()[:8])
    handle = FTRLHandle(penalty=L1L2(0.1, 0.01), lr=LearnRate(0.5, 1.0))
    store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                         handle, rt)

    blocks = {"pw": [], "labels": []}
    raw = []
    for _ in range(2):
        buckets, rows = make_pairs(rng, n_pairs, spec)
        pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
        assert not len(ovb)
        # the point of the regime: most slots are pad
        pad_frac = 1.0 - n_pairs / (spec.tiles * spec.cap)
        assert pad_frac > 0.4, pad_frac
        labels = (rng.random(spec.block_rows) < 0.4).astype(np.uint8)
        blocks["pw"].append(pw)
        blocks["labels"].append(labels)
        raw.append((buckets, rows, labels))
    blocks = {k: np.stack(v) for k, v in blocks.items()}

    slots0 = np.asarray(store.slots)
    store.tile_train_step_mesh(blocks, info)
    got = np.asarray(jax.device_get(store.slots))

    w0 = np.asarray(handle.weights(jnp.asarray(slots0)))
    g_tot = np.zeros(nb, np.float64)
    for buckets, rows, labels in raw:
        mg = tilemm.forward_margins_ref(buckets, rows, w0,
                                        spec.block_rows)
        mask = np.ones(spec.block_rows, np.float32)
        dual = np.asarray(logit_dual(
            jnp.asarray(mg), jnp.asarray(labels.astype(np.float32)),
            jnp.asarray(mask)))
        g_tot += tilemm.backward_grad_ref(buckets, rows, dual, nb)
    want = np.asarray(handle.push(jnp.asarray(slots0),
                                  jnp.asarray(g_tot.astype(np.float32)),
                                  jnp.float32(1), jnp.float32(0)))
    err = np.max(np.abs(got - want)) / (np.abs(want).max() + 1e-9)
    assert err < 2e-2, err


def test_mesh_model_sharding_bitwise_vs_replicated():
    """Bucket-space sharding over the model axis must be a pure layout
    change: the same two blocks through a ``data:2,model:4`` mesh and
    through a replicated ``data:2`` mesh (model axis absent) produce a
    BITWISE-identical slot table at tau=0. nnz=1 makes the margin psum
    over the model axis exact — each row's single pair lives on exactly
    one model shard, so the reduction adds one finite term to zeros —
    and per-bucket gradients never cross tile (hence shard) boundaries,
    so no float reassociation is possible anywhere in the step."""
    import jax
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh

    rng = np.random.default_rng(23)
    nb = 128 * tilemm.TILE
    spec = tilemm.make_spec(nb, subblocks=1, cap=128)
    info = CRec2Info(nnz=1, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb,
                     subblocks=1, cap=spec.cap, ovf_cap=0)

    blocks = {"pw": [], "labels": []}
    for _ in range(2):
        buckets, rows = make_pairs(rng, 8192, spec)
        pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
        assert not len(ovb)
        blocks["pw"].append(pw)
        blocks["labels"].append(
            (rng.random(spec.block_rows) < 0.4).astype(np.uint8))
    blocks = {k: np.stack(v) for k, v in blocks.items()}

    def run(mesh_spec, ndev):
        rt = MeshRuntime.create()
        rt.mesh = make_mesh(mesh_spec, jax.devices()[:ndev])
        handle = FTRLHandle(penalty=L1L2(0.1, 0.01),
                            lr=LearnRate(0.5, 1.0))
        store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                             handle, rt)
        store.tile_train_step_mesh(blocks, info)
        return np.asarray(jax.device_get(store.slots))

    sharded = run("data:2,model:4", 8)
    replicated = run("data:2", 2)
    assert np.array_equal(sharded, replicated)


def test_fused_tiles_match_unfused_and_oracle():
    """The K-tile fused bwd kernel (high-nb regime) must match the
    unfused kernels bit-for-bit (same bf16 arithmetic, same pairs — only
    the chain view changes) and the exact oracle to bf16 rounding; pad
    words must stay inert through the joint-digit dual gather (their
    rhi field gathers a dual row, but the hi one-hot zeroes the
    histogram column)."""
    import dataclasses
    import jax
    rng = np.random.default_rng(17)
    nb = 32 * tilemm.TILE
    spec = tilemm.make_spec(nb, subblocks=4, cap=128)
    assert spec.fuse > 1, spec       # the regime this test exists for
    unfused = dataclasses.replace(spec, fuse=1)
    n_pairs = 12_000                 # ~94 per (subblock, tile): pad-heavy
    buckets, rows = make_pairs(rng, n_pairs, spec)
    pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
    assert not len(ovb)
    w = rng.standard_normal(nb).astype(np.float32)
    dual = rng.standard_normal(spec.block_rows).astype(np.float32)

    mg_f = np.asarray(tilemm._build_fwd(spec)(pw, w))
    mg_u = np.asarray(tilemm._build_fwd(unfused)(pw, w))
    np.testing.assert_array_equal(mg_f, mg_u)
    g_f = np.asarray(tilemm._build_bwd(spec)(pw, dual))
    g_u = np.asarray(tilemm._build_bwd(unfused)(pw, dual))
    np.testing.assert_array_equal(g_f, g_u)

    om = tilemm.forward_margins_ref(buckets, rows, w, spec.block_rows)
    og = tilemm.backward_grad_ref(buckets, rows, dual, nb)
    assert np.max(np.abs(mg_f - om)) < 5e-2   # bf16-value rounding
    assert np.max(np.abs(g_f - og)) < 5e-2


def test_spec_validation():
    with pytest.raises(ValueError):
        tilemm.TileSpec(nb=1000, subblocks=2, cap=128)
    with pytest.raises(ValueError):
        tilemm.TileSpec(nb=tilemm.TILE, subblocks=3, cap=128, group=2)
    with pytest.raises(ValueError):
        tilemm.TileSpec(nb=tilemm.TILE, subblocks=2, cap=100)


def test_multi_channel_pulls_match_oracle():
    """forward_pulls/backward_pushes (the FM / wide&deep embedding
    kernels) against per-channel scatter/gather oracles, including the
    overflow spill path."""
    rng = np.random.default_rng(7)
    ch = 3
    buckets, rows = make_pairs(rng, 9000)
    # force some overflow: one hot bucket beyond cap
    hot = np.full(1400, 17, np.int64)
    buckets = np.concatenate([buckets, hot])
    rows = np.concatenate([rows, rng.integers(
        0, SPEC.block_rows, size=1400).astype(np.int64)])
    pw, ovb, ovr = tilemm.encode_block(buckets, rows, SPEC)
    assert len(ovb) > 0          # spill path exercised
    oc = 8192
    ovb_p = np.full(oc, 0xFFFFFFFF, np.uint32)
    ovr_p = np.zeros(oc, np.uint32)
    ovb_p[:len(ovb)] = ovb
    ovr_p[:len(ovr)] = ovr
    w = rng.normal(0, 0.5, (SPEC.nb, ch)).astype(np.float32)
    import jax.numpy as jnp
    pulls = np.asarray(tilemm.forward_pulls(
        jnp.asarray(pw), jnp.asarray(w), SPEC,
        jnp.asarray(ovb_p), jnp.asarray(ovr_p)))
    w16 = w.astype(np.float32)
    for jc in range(ch):
        want = tilemm.forward_margins_ref(buckets, rows, w16[:, jc],
                                          SPEC.block_rows)
        np.testing.assert_allclose(pulls[:, jc], want, rtol=0, atol=0.15)
    dual = rng.normal(0, 1.0, (SPEC.block_rows, ch)).astype(np.float32)
    g = np.asarray(tilemm.backward_pushes(
        jnp.asarray(pw), jnp.asarray(dual), SPEC,
        jnp.asarray(ovb_p), jnp.asarray(ovr_p)))
    for jc in range(ch):
        want = tilemm.backward_grad_ref(buckets, rows, dual[:, jc],
                                        SPEC.nb)
        np.testing.assert_allclose(g[:, jc], want, rtol=0, atol=0.15)
