"""Sharded multichip feed (data/crec.MeshGroupFeed).

The mesh feed forms data-axis groups off the dispatch thread and hands
every chip its slice of a group from the transfer ring, on the (data,
model) NamedSharding the step takes, with no stacked copy of the group.
The contracts pinned here:

  * worker/mode determinism — the pipelined ring (workers=N) is
    bit-identical to the serial inline feed (workers=0), and the ring
    path trains the same table as a synchronous stack-in-the-loop
    dispatch written in the test (``stack_mesh_group`` + the store's
    mesh step): same groups, same padding, same step order — only
    WHERE the bytes are gathered moves;
  * short-tail PAD parity — an eval pass whose tail group is mostly
    PAD filler blocks pools exactly the same (margin, label) rows as
    the single-device path over the same file: PAD lanes (label 255)
    are invisible;
  * overflow in a group — an online-encoded block whose pairs pass
    the per-tile cap stays a member of its group with its overflow
    list, the lists of one group widened to one width: every row
    credited once, the crec2 path's table;
  * direct placement — every shard of a group assembled chip by chip
    holds the bytes of the same index of the stacked group, under the
    step's shape, dtype and sharding (tile and v1; full and padded;
    workers 0 and 2), and the pass books the host bytes it copied.
"""

import os

import jax
import numpy as np
import pytest

from wormhole_tpu.data.crec import CRec2Writer, CRecWriter
from wormhole_tpu.ops import tilemm
from wormhole_tpu.sched.workload_pool import VAL

NB = 2 * tilemm.TILE
NNZ = 8
BR = tilemm.RSUB          # subblocks=1: one RSUB-row block per group slot


def make_rows(rng, n, planted=True):
    keys = rng.integers(0, 1 << 32, size=(n, NNZ), dtype=np.uint32)
    keys[keys == 0xFFFFFFFF] = 0
    keys[rng.random((n, NNZ)) < 0.1] = 0xFFFFFFFF
    if planted:
        sel = rng.random(n) < 0.5
        keys[sel, 0] = np.uint32(123456)
        keys[~sel, 0] = np.uint32(654321)
        labels = sel.astype(np.uint8)
    else:
        labels = (rng.random(n) < 0.4).astype(np.uint8)
    return keys, labels


def write_file(path, keys, labels):
    with CRec2Writer(str(path), nnz=NNZ, nb=NB, subblocks=1,
                     ovf_cap=4096) as w:
        w.append(keys, labels)


def make_app(path, mesh_spec, fmt="crec2", **over):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    kw = dict(train_data=str(path), data_format=fmt, num_buckets=NB,
              lr_eta=0.5, max_data_pass=1, disp_itv=1e12, max_delay=1)
    kw.update(over)
    rt = MeshRuntime.create()
    n_dev = int(np.prod([int(p.split(":")[1])
                         for p in mesh_spec.split(",")]))
    rt.mesh = make_mesh(mesh_spec, jax.devices()[:n_dev])
    return AsyncSGD(Config(**kw), rt)


def test_ring_workers_and_sync_mode_bit_identical(tmp_path, rng):
    """data:8 over 11 blocks (one full group + a 3-block padded tail):
    the pipelined ring, the serial ring (workers=0, the inline oracle)
    and the synchronous stack-in-the-loop dispatch (written out here:
    ``stack_mesh_group`` on the dispatch thread, jit-time transfer) all
    produce the SAME slots, bit for bit, and credit every row."""
    from wormhole_tpu.data.crec import (iter_packed2, mesh_pads,
                                        read_header2, stack_mesh_group)
    n = 10 * BR + 4000
    keys, labels = make_rows(rng, n)
    path = tmp_path / "det.crec2"
    write_file(path, keys, labels)

    def train(workers):
        app = make_app(path, "data:8", pipeline_workers=workers)
        prog = app.run()
        assert prog.num_ex == n, workers
        return np.asarray(app.store.slots)

    def train_sync():
        app = make_app(path, "data:8")
        info = read_header2(str(path))
        pads = mesh_pads(info, True)
        blocks = [v for v, _rows in iter_packed2(str(path))]
        for k in range(0, len(blocks), 8):
            stacked, _lab = stack_mesh_group(blocks[k:k + 8], 8, info,
                                             pads, True)
            app.store.tile_train_step_mesh(stacked, info)
        assert int(app.store.fetch_metrics()[1]) == n
        return np.asarray(app.store.slots)

    ring2 = train(2)
    ring0 = train(0)
    sync = train_sync()
    assert np.array_equal(ring2, ring0)
    assert np.array_equal(ring2, sync)


def test_padded_tail_eval_pooled_matches_single_device(tmp_path, rng):
    """Eval pooled output across a data:2 mesh whose last group is one
    real block + one all-PAD filler equals the single-device pass over
    the same file and weights: same margins, same labels, no phantom
    rows from the PAD lanes."""
    n = 2 * BR + 1000                       # 3 blocks -> tail group pads
    keys, labels = make_rows(rng, n)
    path = tmp_path / "tail.crec2"
    write_file(path, keys, labels)

    ref = make_app(path, "data:1")
    ref.run()                               # train once for nonzero margins
    host_slots = np.asarray(ref.store.slots)

    def eval_pooled(app):
        app.store.slots = jax.numpy.asarray(host_slots)
        pooled = []
        prog = app.process(str(path), 0, 1, kind=VAL, pooled=pooled)
        m = np.concatenate([p[0] for p in pooled])
        y = np.concatenate([p[1] for p in pooled])
        return prog, m, y

    prog1, m1, y1 = eval_pooled(make_app(path, "data:1"))
    prog2, m2, y2 = eval_pooled(make_app(path, "data:2"))
    assert prog1.num_ex == n and prog2.num_ex == n
    assert y1.shape == (n,) and y2.shape == (n,)
    assert np.array_equal(y1, y2)
    assert np.array_equal(y1, np.minimum(labels, 1).astype(np.float32))
    assert np.allclose(m1, m2, rtol=1e-4, atol=1e-5)
    assert np.isclose(prog1.objv, prog2.objv, rtol=1e-4)


def test_online_hot_block_rides_its_group(tmp_path, rng):
    """tile_online over a v1 stream on a data:2 mesh: a hot-bucket block
    (32K pairs past the per-tile cap) stays a member of its group, its
    pairs on its overflow list; the cold member's list is widened to the
    hot one's width; every row is credited once; the table is the one
    the same rows train from a crec2 file written with room for the
    pairs; and the pipelined ring matches the workers=0 oracle bit for
    bit."""
    from wormhole_tpu.data import crec
    blocks = []
    lab = []
    for i in range(4):
        k, l = make_rows(rng, BR)
        if i == 2:                          # the hot block: one bucket
            k = np.full((BR, NNZ), np.uint32(42), np.uint32)
        blocks.append(k)
        lab.append(l)
    keys = np.concatenate(blocks)
    labels = np.concatenate(lab)
    n = len(labels)
    path = tmp_path / "hot.crec"
    with CRecWriter(str(path), nnz=NNZ, block_rows=BR) as w:
        w.append(keys, labels)
    info = crec.online_info(NNZ, BR, NB)
    counts = [len(crec.encode_tile_pairs(k, NB, info.spec)[1])
              for k in blocks]
    n_ovf = counts[2]
    assert n_ovf > 30 * crec.ONLINE_OVF_CAP > 30 * max(counts[:2])

    def train(workers):
        app = make_app(path, "data:2", fmt="crec", tile_online="on",
                       pipeline_workers=workers)
        prog = app.run()
        assert prog.num_ex == n, workers
        assert app._online_room.room == crec.overflow_room(n_ovf)
        assert app.timer.totals["online_overflow_pairs"] == sum(counts)
        return np.asarray(app.store.slots)

    w2 = train(2)
    w0 = train(0)
    assert np.array_equal(w2, w0)
    c2 = tmp_path / "hot.crec2"
    with CRec2Writer(str(c2), nnz=NNZ, nb=NB, subblocks=1, cap=info.cap,
                     ovf_cap=crec.overflow_room(n_ovf)) as w:
        w.append(keys, labels)
    ref = make_app(c2, "data:2")
    assert ref.run().num_ex == n
    assert np.array_equal(np.asarray(ref.store.slots), w0)


def test_widen_overflow_makes_a_group_one_width():
    from wormhole_tpu.data.crec import widen_overflow
    a = {"pw": 1, "ovf_b": np.array([7, 0xFFFFFFFF], np.uint32),
         "ovf_r": np.array([3, 0], np.uint32)}
    b = {"pw": 2, "ovf_b": np.array([5, 6, 8, 0xFFFFFFFF], np.uint32),
         "ovf_r": np.array([1, 2, 4, 0], np.uint32)}
    same = [a, dict(a)]
    assert widen_overflow(same) is same
    wa, wb = widen_overflow([a, b])
    assert wb is b and wa["pw"] == 1 and len(a["ovf_b"]) == 2
    assert wa["ovf_b"].tolist() == [7, 0xFFFFFFFF, 0xFFFFFFFF, 0xFFFFFFFF]
    assert wa["ovf_r"].tolist() == [3, 0, 0, 0]


def _group_feed(path, fmt, workers, want_labels=False):
    """A MeshGroupFeed on a data:2,model:2 mesh over ``path``, with the
    oracle's ingredients: (feed, info, pads, shardings, is_tile)."""
    from wormhole_tpu.data.crec import (MeshGroupFeed, PackedFeed,
                                        mesh_pads, read_header,
                                        read_header2)
    from wormhole_tpu.learners.store import mesh_group_shardings
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    is_tile = fmt == "crec2"
    info = read_header2(str(path)) if is_tile else read_header(str(path))
    shardings = mesh_group_shardings(rt, is_tile)
    inner = PackedFeed(str(path), fmt=fmt, device_put=lambda x: x,
                       workers=workers)
    feed = MeshGroupFeed(inner, 2, shardings, info, is_tile,
                         workers=workers, want_labels=want_labels)
    return feed, info, mesh_pads(info, is_tile), shardings, is_tile


@pytest.mark.parametrize("workers", [0, 2])
@pytest.mark.parametrize("member", ["full", "tail"])
@pytest.mark.parametrize("fmt", ["crec2", "crec"])
def test_placed_group_equals_stacked_group(tmp_path, rng, fmt, member,
                                           workers):
    """Three blocks on data:2,model:2: a full group, then a tail of one
    block and the shared PAD block. Every addressable shard of the
    group the feed assembles chip by chip holds the bytes of the same
    index of ``stack_mesh_group``'s array, and the global array has the
    shape, dtype and sharding that a ``device_put`` of the stacked group
    gives the step; the pooled label lane is the stacked one."""
    from wormhole_tpu.data.crec import (iter_packed, iter_packed2,
                                        stack_mesh_group)
    n = 2 * BR + 1000
    keys, labels = make_rows(rng, n)
    path = tmp_path / f"g.{fmt}"
    if fmt == "crec2":
        write_file(path, keys, labels)
        blocks = [v for v, _r in iter_packed2(str(path))]
    else:
        with CRecWriter(str(path), nnz=NNZ, block_rows=BR) as w:
            w.append(keys, labels)
        blocks = [b for b, _r in iter_packed(str(path))]
    assert len(blocks) == 3
    feed, info, pads, shardings, is_tile = _group_feed(
        path, fmt, workers, want_labels=True)
    groups = list(feed)
    assert [g[2] for g in groups] == [2 * BR, 1000]
    k = 0 if member == "full" else 1
    placed, lab, _rows = groups[k]
    want, want_lab = stack_mesh_group(blocks[2 * k:2 * k + 2], 2, info,
                                      pads, is_tile, want_labels=True)
    assert np.array_equal(lab, want_lab)
    oracle = jax.device_put(want, shardings)
    flat = (lambda t: sorted(t.items())) if is_tile \
        else (lambda t: [("blocks", t)])
    for (name, got), (_, exp), (_, host) in zip(flat(placed), flat(oracle),
                                                flat(want)):
        assert got.shape == exp.shape == host.shape, name
        assert got.dtype == exp.dtype == host.dtype, name
        assert got.sharding == exp.sharding, name
        assert len(got.addressable_shards) == 4
        for shard, other in zip(got.addressable_shards,
                                exp.addressable_shards):
            assert shard.device == other.device
            assert shard.index == other.index
            assert np.array_equal(np.asarray(shard.data),
                                  host[shard.index]), (name, shard.index)
    if member == "tail":            # the pad member is the PAD block
        pw_or_bytes = np.asarray(placed["pw"] if is_tile else placed)[1]
        assert np.array_equal(pw_or_bytes, pads["pw"] if is_tile else pads)


def test_mesh_pass_books_host_copy_bytes(tmp_path, rng):
    """The mesh pass adds the bytes its feed copied on the host to the
    Timer, a count beside mesh_steps: nothing over a local crec2 file
    (blocks are views of a mapping and no group is stacked), every
    block's bytes over a stream the reader cannot map (crec v1, which
    PackedFeed reads into memory)."""
    from wormhole_tpu.data.crec import HEADER_SIZE
    n = 4 * BR
    keys, labels = make_rows(rng, n)

    def copied(path, fmt):
        app = make_app(path, "data:2", fmt=fmt)
        assert app.run().num_ex == n
        assert app.timer.totals["mesh_steps"] == 2
        return app.timer.totals["host_copy_bytes"]

    mapped = tmp_path / "c.crec2"
    write_file(mapped, keys, labels)
    assert copied(mapped, "crec2") == 0
    read = tmp_path / "c.crec"
    with CRecWriter(str(read), nnz=NNZ, block_rows=BR) as w:
        w.append(keys, labels)
    assert copied(read, "crec") == os.path.getsize(read) - HEADER_SIZE


@pytest.mark.parametrize("blocks", [2, 3])
def test_pad_block_built_only_for_a_short_tail(tmp_path, rng, blocks):
    """A pass makes a new feed; the shared PAD block (a block's worth of
    memory to fill) is built when a short tail asks for it, not with
    the feed."""
    n = (blocks - 1) * BR + 500
    keys, labels = make_rows(rng, n)
    path = tmp_path / "p.crec2"
    write_file(path, keys, labels)
    feed = _group_feed(path, "crec2", 2)[0]
    assert sum(g[2] for g in feed) == n
    assert ("_pads" in vars(feed)) == (blocks % 2 == 1)
    assert feed.skew_snapshot()["pad_blocks"] == blocks % 2


def test_text_groups_with_lists_ring_matches_inline(tmp_path, rng,
                                                    monkeypatch):
    """MeshGroupFeed over TileOnlineFeed over TextCRecFeed on a
    data:2,model:2 mesh, from Criteo text with hot values (every block
    brings an overflow list) and empty columns, two passes over five
    groups: the pipelined ring (pipeline_workers=2) trains the table of
    the inline oracle (workers=0), bit for bit; the encoder's recycled
    slabs come round again while earlier groups' per-chip slices are
    still in flight; the inner feeds' counters reach the part's Timer
    from the mesh pass beside the mesh feed's own; and the stack workers'
    making of the hot form a shard is a span."""
    from wormhole_tpu.data import crec, native
    from wormhole_tpu.obs import trace
    n, rows = 10 * BR, BR
    path = tmp_path / "log.criteo"
    hot = rng.random((n, 26)) < 0.5
    with open(path, "w") as f:
        for i in range(n):
            ints = [str(rng.integers(0, 100)) for _ in range(13)]
            ints[3] = "" if i % 3 == 0 else ints[3]      # an empty column
            cats = ["7f" if hot[i, j] else f"{rng.integers(0, 1 << 32):x}"
                    for j in range(26)]
            f.write("\t".join([str(i % 2)] + ints + cats) + "\n")
    seen = []
    real = native._PW_POOL.empty

    def recording(shape, dtype):
        arr = real(shape, dtype)
        seen.append(arr.ctypes.data)
        return arr
    monkeypatch.setattr(native._PW_POOL, "empty", recording)

    def train(workers):
        app = make_app(path, "data:2,model:2", fmt="criteo",
                       tile_online="on", pipeline_workers=workers,
                       text_block_rows=rows, max_data_pass=2)
        assert app.run().num_ex == 2 * n, workers
        return app

    trace.configure(enabled=True)
    try:
        ring = train(2)
        spans = {e["name"] for e in trace.events() if e["ph"] == "X"}
    finally:
        trace.configure(enabled=False)
    inline = train(0)
    assert np.array_equal(np.asarray(ring.store.slots),
                          np.asarray(inline.store.slots))
    # every group goes hot, and a hot group's COO lanes are not widened
    assert {"meshfeed:hot", "mesh:dispatch"} <= spans
    assert "meshfeed:widen" not in spans
    if native.get_tile_encoder() is not None:
        # twenty blocks a run came out of fewer mappings than blocks
        assert len(seen) == 40 and len(set(seen)) < 20
    t = ring.timer.totals
    width = ring._online_room.room
    assert width > crec.ONLINE_OVF_CAP          # every block has a list
    for key in ("read", "encode", "text_read", "collate", "stack", "put"):
        assert t.get(key, 0.0) > 0.0, key
    assert "encode_stall" in t and t["mesh_steps"] == 10
    assert t["online_overflow_pairs"] > 20 * crec.ONLINE_OVF_CAP
    # ten groups of two lists each, and every one goes hot (ISSUE 49:
    # half a block's pairs name one bucket): what crosses is a hot form
    # a chip, and no COO lane at all
    assert t["overflow_hot_blocks"] == 20 and t["overflow_coo_blocks"] == 0
    assert ring._hot_room.slots > 0 and t["mesh_overflow_slots"] == 0
    assert t["mesh_widened_groups"] == inline.timer.totals[
        "mesh_widened_groups"] == 0
