"""The overflow list's hot form on a shard (ISSUE 49): a mesh group whose
members' lists pass ``HotRoom``'s rule crosses to the chips as a hot form
A MODEL SHARD, each list cut by owner on the host, and the mesh step runs
every chip's own listed pairs through the hot kernel pair the one-device
step uses.

The properties pinned here:
  * the cut by key range is a partition: every listed pair is in exactly
    one shard's part, its bucket local to the shard's range, unused slots
    in none; every part's hot form decodes to the part; the native and the
    numpy encoder (cut, forms) give the same bits;
  * the rule (``HotRoom.form_shards``): every part that holds a pair is a
    list of its own to ``HOT_MIN_ROOM`` / ``HOT_MIN_SHARE``; one part that
    stays COO keeps the whole group COO; a group without a listed pair is
    nobody's; one room a group, fitted at its largest part, grow-only;
  * a hot group and the same group kept COO leave the same table (to the
    order of float32 sums) and the same metrics, on ``data:2,model:2``,
    ``data:1,model:2`` and ``data:2``, and land on the float64 oracle;
  * a shard's listed pairs handed to the wrong owner, dropped or handed
    to both shards by the cut leave the float64 oracle;
  * a group with a COO member, a group with no listed pair and an eval
    pass take the COO program and no other;
  * the mesh pass exports what the room chose (``overflow_hot_blocks``,
    ``overflow_coo_blocks``, ``overflow_hot_buckets``, the room gauge) and
    the stack workers' ``meshfeed:hot`` span;
  * the COO mesh step (what a uniform stream runs) lowers to the text it
    lowered to before this form existed.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import wormhole_tpu.data.crec as crec
from wormhole_tpu.data import native
from wormhole_tpu.data.crec import HotRoom, cut_overflow
from wormhole_tpu.ops import tilemm

from test_mesh_feed import make_app
from test_overflow_hot import (NB, S, _ftrl64, _list, _multiset,
                               _one_tile_keys, _zipf_keys)
from test_tile_online import NNZ, weights, write_v1

ROOM = 4 * crec.HOT_MIN_ROOM          # a width every list here fits


def _padded(b, r, width=ROOM):
    return tilemm.cap_overflow(b, r, width)


@pytest.fixture(params=["native", "numpy"])
def encoder(request, monkeypatch):
    if request.param == "numpy":
        monkeypatch.setattr(native, "get_hot_cutter", lambda: None)
        monkeypatch.setattr(native, "get_hot_encoder", lambda: None)
    elif native.get_hot_cutter() is None:
        pytest.skip(f"no native library: {native.build_error()}")
    return request.param


@pytest.mark.parametrize("parts", [1, 2, 4])
@pytest.mark.parametrize("kind", ["skewed", "uniform", "empty", "one_pair",
                                  "two_hot_tiles"])
def test_native_and_numpy_cut_and_forms_give_the_same_bits(kind, parts,
                                                           monkeypatch):
    cut = native.get_hot_cutter()
    if cut is None:
        pytest.skip(f"no native library: {native.build_error()}")
    monkeypatch.setattr(crec, "HOT_MIN_ROOM", 64)
    rng = np.random.default_rng(9)
    ob, orow = _padded(*_list(kind, rng))
    nb_local = NB // parts
    got, want = (f(ob, orow, parts, nb_local) for f in (cut, cut_overflow))
    assert len(got) == len(want) == parts
    for a, b in zip(got, want):
        for x, y in zip(a, b):
            assert x.dtype == np.uint32 and np.array_equal(x, y)
    forms = [HotRoom().form_shards([(ob, orow)], parts, nb_local, S)]
    monkeypatch.setattr(native, "get_hot_cutter", lambda: None)
    monkeypatch.setattr(native, "get_hot_encoder", lambda: None)
    forms.append(HotRoom().form_shards([(ob, orow)], parts, nb_local, S))
    assert (forms[0] is None) == (forms[1] is None)
    for a, b in zip(*(f or [] for f in forms)):
        for k in ("ovf_u", "ovf_pw"):
            assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
            assert a[k].tobytes() == b[k].tobytes()
    for f in (cut, cut_overflow):                     # a foreign bucket
        with pytest.raises(ValueError):
            f(np.array([NB], np.uint32), np.zeros(1, np.uint32), parts,
              nb_local)


# -- the cut ------------------------------------------------------------------

@pytest.mark.parametrize("parts", [1, 2, 4])
@pytest.mark.parametrize("kind", ["skewed", "uniform", "empty", "one_pair",
                                  "two_hot_tiles"])
def test_the_cut_by_owner_is_a_partition(kind, parts):
    rng = np.random.default_rng(3)
    b, r = _list(kind, rng)
    ob, orow = _padded(b, r)
    nb_local = NB // parts
    cut = cut_overflow(ob, orow, parts, nb_local)
    assert len(cut) == parts
    back = []
    for m, (pb, pr) in enumerate(cut):
        assert pb.dtype == np.uint32 and pr.dtype == np.uint32
        assert len(pb) == len(pr)
        assert (pb < nb_local).all()                  # local, in range
        back += _multiset(pb.astype(np.int64) + m * nb_local, pr)
        # stable: a part keeps the list's order
        mine = (b // nb_local) == m
        assert np.array_equal(pb + np.uint32(m * nb_local), b[mine])
        assert np.array_equal(pr, r[mine])
    assert sorted(back) == _multiset(b, r)            # each pair once
    with pytest.raises(ValueError):                   # a foreign bucket
        cut_overflow(np.array([NB], np.uint32), np.zeros(1, np.uint32),
                     parts, nb_local)


@pytest.mark.parametrize("parts", [1, 2, 4])
def test_every_parts_hot_form_decodes_to_the_part(parts, encoder,
                                                  monkeypatch):
    """Two members of one group: a two-hot-tile list and a skewed one. One
    room for all their parts; each part's form gives the part back."""
    monkeypatch.setattr(crec, "HOT_MIN_ROOM", 64)
    rng = np.random.default_rng(5)
    lists = [_list("two_hot_tiles", rng), _list("skewed", rng)]
    room = HotRoom()
    nb_local = NB // parts
    forms = room.form_shards([_padded(b, r) for b, r in lists], parts,
                             nb_local, S)
    assert len(forms) == 2
    shapes = {(f["ovf_u"].shape, f["ovf_pw"].shape) for f in forms}
    assert len(shapes) == 1                           # one room a group
    (u_shape, pw_shape), = shapes
    assert u_shape == (parts, room.tiles * tilemm.TILE)
    assert pw_shape == (parts, *tilemm.hot_spec(
        room.tiles * room.vtiles, S).pairs_shape)
    distinct = 0
    for (b, r), form in zip(lists, forms):
        for m, (pb, pr) in enumerate(cut_overflow(*_padded(b, r), parts,
                                                  nb_local)):
            got = tilemm.decode_hot(form["ovf_u"][m], form["ovf_pw"][m], S)
            assert _multiset(*got) == _multiset(pb, pr)
            used = form["ovf_u"][m] != tilemm.UNUSED
            assert (form["ovf_u"][m][used] < nb_local).all()
            distinct += int(used.sum())
    assert room.drain() == {"hot_blocks": 2, "coo_blocks": 0,
                            "hot_buckets": distinct,
                            "hot_room": forms[0]["ovf_pw"][0].size}


# -- the rule -----------------------------------------------------------------

def test_form_shards_rule_counters_and_room(monkeypatch):
    rng = np.random.default_rng(7)
    room = HotRoom()
    b, r = _list("skewed", rng)
    ub, ur = _list("uniform", rng)
    nb_local = NB // 2
    # a part that would be given a room under HOT_MIN_ROOM: the group is COO
    assert crec.overflow_room(len(b)) < crec.HOT_MIN_ROOM
    assert room.form_shards([_padded(b, r)] * 2, 2, nb_local, S) is None
    assert room.drain()["coo_blocks"] == 2
    monkeypatch.setattr(crec, "HOT_MIN_ROOM", 64)
    # no listed pair in the group: nobody's, and nothing is counted
    empty = _padded(b[:0], r[:0])
    assert room.form_shards([empty, empty], 2, nb_local, S) is None
    assert room.drain() == {"hot_blocks": 0, "coo_blocks": 0,
                            "hot_buckets": 0, "hot_room": 0}
    # a member of mostly distinct buckets keeps the whole group COO
    assert len(np.unique(ub)) * crec.HOT_MIN_SHARE > len(ub)
    assert room.form_shards([_padded(b, r), _padded(ub, ur)], 2, nb_local,
                            S) is None
    assert room.drain()["coo_blocks"] == 2
    # ... and so does one PART that is: the skewed list with its upper
    # shard's pairs spread over distinct buckets
    upper = np.arange(nb_local, nb_local + 500, dtype=np.uint32)
    mixed = (np.concatenate([b[b < nb_local], upper]),
             np.concatenate([r[b < nb_local], np.arange(500, dtype=np.uint32)]))
    assert room.form_shards([_padded(*mixed)], 2, nb_local, S) is None
    assert room.drain()["coo_blocks"] == 1
    # a member without a list rides a hot group as padding, uncounted
    forms = room.form_shards([_padded(b, r), empty], 2, nb_local, S)
    assert (forms[1]["ovf_u"] == tilemm.UNUSED).all()
    assert (forms[1]["ovf_pw"] == tilemm.PADWORD).all()
    assert forms[0]["ovf_pw"].shape == forms[1]["ovf_pw"].shape
    assert room.drain()["hot_blocks"] == 1
    # the room is fitted at the group's largest part and never shrinks
    small = (room.tiles, room.vtiles)
    big = _list("two_hot_tiles", rng)
    grown = room.form_shards([_padded(*big), _padded(b, r)], 1, NB, S)
    assert room.tiles == 2 and room.vtiles >= small[1]
    again = room.form_shards([_padded(b, r)], 2, nb_local, S)
    assert again[0]["ovf_pw"].shape[1:] == grown[0]["ovf_pw"].shape[1:]
    # a row outside the block is nobody's writer's
    with pytest.raises(ValueError):
        room.form_shards([_padded(b, r + np.uint32(S * tilemm.RSUB))], 2,
                         nb_local, S)


# -- the step -----------------------------------------------------------------

def _sparse_keys(rng, n):
    """One feature a row: no tile passes its cap, every list is empty."""
    keys = np.full((n, NNZ), crec.SENTINEL_KEY, np.uint32)
    keys[:, 0] = rng.integers(0, 1 << 31, n, dtype=np.uint32)
    return keys, (rng.random(n) < 0.4).astype(np.uint8)


def _run(tmp_path, name, blocks, mesh, monkeypatch, min_room=64, **over):
    """One pass of ``blocks`` (a v1 file, online tile path) on ``mesh``:
    the app, the groups' lane names as the steps were handed them, and
    the merged Progress."""
    n = tilemm.RSUB
    path = tmp_path / f"{name}.crec"
    write_v1(path, np.concatenate([k for k, _l in blocks]),
             np.concatenate([l for _k, l in blocks]), block_rows=n)
    monkeypatch.setattr(crec, "HOT_MIN_ROOM", min_room)
    kw = dict(fmt="crec", tile_online="on", num_buckets=NB, lr_eta=0.1,
              pipeline_workers=0)
    kw.update(over)
    app = make_app(path, mesh, **kw)
    handed = []
    for step in ("tile_train_step_mesh", "tile_eval_step_mesh"):
        def spy(blocks, info, *a, _real=getattr(app.store, step), **k):
            handed.append((_real.__name__, tuple(sorted(blocks))))
            return _real(blocks, info, *a, **k)
        setattr(app.store, step, spy)
    return app, handed, app.run()


def _programs(app):
    """The mesh programs the store built: ('train'|'eval', hot?)."""
    return {(k[1], "hot" in k) for k in app.store._tile_cache
            if "mesh" in k}


HOT_LANES = ("labels", "ovf_pw", "ovf_u", "pw")
COO_LANES = ("labels", "ovf_b", "ovf_r", "pw")


@pytest.mark.parametrize("mesh", ["data:2,model:2", "data:1,model:2",
                                  "data:2"])
def test_a_hot_group_leaves_the_coo_groups_table_and_metrics(tmp_path, mesh,
                                                             monkeypatch):
    """Four blocks of Zipf keys at 2**16 buckets, an eighth of the pairs
    listed: hot, the table is the COO path's but for the order of float32
    sums, the metrics are the COO path's, and both land on float64 FTRL
    within the tile path's limits (test_crec2: rtol 0.05, atol 5e-3)."""
    from wormhole_tpu import obs
    rng = np.random.default_rng(23)
    blocks = [_zipf_keys(rng, tilemm.RSUB) for _ in range(4)]
    D = 1 if mesh.startswith("data:1") else 2
    before = [m.value for m in obs.metrics.overflow_hot_metrics()[:3]]
    hot, handed, p_hot = _run(tmp_path, "hot", blocks, mesh, monkeypatch)
    assert handed == [("tile_train_step_mesh", HOT_LANES)] * (4 // D)
    assert _programs(hot) == {("train", True)}
    t = hot.timer.totals
    assert t["overflow_hot_blocks"] == 4 and t["overflow_coo_blocks"] == 0
    info = crec.online_info(NNZ, tilemm.RSUB, NB)
    parts = 2 if "model:2" in mesh else 1
    distinct = sum(
        len(np.unique(pb)) for k, _l in blocks for pb, _pr in cut_overflow(
            *crec.encode_tile_pairs(k, NB, info.spec)[1:], parts,
            NB // parts))
    assert t["overflow_hot_buckets"] == distinct
    hot_c, coo_c, buckets_c, room_g = obs.metrics.overflow_hot_metrics(
        hot.obs.registry)
    assert (hot_c.value - before[0], coo_c.value - before[1],
            buckets_c.value - before[2]) == (4, 0, distinct)
    assert room_g.value == hot._hot_room.slots > 0
    # what crossed: a hot form a chip and no COO lane
    assert t["mesh_overflow_slots"] == 0

    coo, handed, p_coo = _run(tmp_path, "coo", blocks, mesh, monkeypatch,
                              min_room=1 << 30)
    assert handed == [("tile_train_step_mesh", COO_LANES)] * (4 // D)
    assert _programs(coo) == {("train", False)}
    assert coo.timer.totals["overflow_coo_blocks"] == 4
    assert coo.timer.totals["overflow_hot_blocks"] == 0
    assert p_hot.num_ex == p_coo.num_ex == 4 * tilemm.RSUB
    assert p_hot.count == p_coo.count
    for name in ("objv", "acc", "auc", "wdelta2"):
        assert getattr(p_hot, name) == pytest.approx(getattr(p_coo, name),
                                                     rel=1e-5), name
    w_hot, w_coo = weights(hot), weights(coo)
    np.testing.assert_allclose(w_hot, w_coo, rtol=2e-5, atol=1e-7)
    # the oracle steps a block at a time; a group of two is one step of
    # both blocks' rows
    grouped = [(np.concatenate([k for k, _l in blocks[i:i + D]]),
                np.concatenate([l for _k, l in blocks[i:i + D]]))
               for i in range(0, 4, D)]
    w64 = _ftrl64(grouped, NB, alpha=0.1, beta=1.0, l1=0.0, l2=0.0)
    live = (np.abs(w64) > 1e-6) | (np.abs(w_hot) > 1e-6)
    assert live.sum() > 100
    for w in (w_hot, w_coo):
        assert np.allclose(w[live], w64[live], rtol=0.05, atol=5e-3)


@pytest.mark.parametrize("fault", [None, "wrong_owner", "dropped",
                                   "doubled"])
def test_a_fault_planted_in_the_cut_is_off_the_oracle(tmp_path, monkeypatch,
                                                      encoder, fault):
    """The cut decides which shard applies a listed pair, and nothing on
    the device checks it (a hot group's step masks nothing). So a fault in
    it must show in the table: the fuller shard's listed pairs handed to
    the other shard instead, dropped, or handed to both, under the native
    cut and the numpy one, each leave the float64 oracle by more than the
    tile path's limits; the sound cut stays inside them."""
    sound = native.get_hot_cutter() or cut_overflow

    def planted(ovf_b, ovf_r, parts, nb_local):
        cut = sound(ovf_b, ovf_r, parts, nb_local)
        m = int(np.argmax([len(b) for b, _r in cut]))  # the fuller shard's
        both = tuple(np.concatenate([x, y]) for x, y in zip(cut[1 - m],
                                                            cut[m]))
        none = tuple(x[:0] for x in cut[m])
        cut[1 - m], cut[m] = {"wrong_owner": (both, none),
                              "dropped": (cut[1 - m], none),
                              "doubled": (both, cut[m])}[fault]
        return cut

    if fault:
        monkeypatch.setattr(native, "get_hot_cutter", lambda: planted)
    rng = np.random.default_rng(31)
    blocks = [_zipf_keys(rng, tilemm.RSUB) for _ in range(4)]
    app, handed, _prog = _run(tmp_path, "f", blocks, "data:2,model:2",
                              monkeypatch)
    assert handed == [("tile_train_step_mesh", HOT_LANES)] * 2
    grouped = [(np.concatenate([k for k, _l in blocks[i:i + 2]]),
                np.concatenate([l for _k, l in blocks[i:i + 2]]))
               for i in (0, 2)]
    w64 = _ftrl64(grouped, NB, alpha=0.1, beta=1.0, l1=0.0, l2=0.0)
    w = weights(app)
    live = (np.abs(w64) > 1e-6) | (np.abs(w) > 1e-6)
    assert np.allclose(w[live], w64[live], rtol=0.05, atol=5e-3) \
        == (fault is None)


@pytest.mark.parametrize("case", ["a_coo_member", "no_listed_pair", "eval"])
def test_every_other_group_takes_the_coo_program(tmp_path, monkeypatch,
                                                 case):
    rng = np.random.default_rng(29)
    n = tilemm.RSUB
    if case == "a_coo_member":
        # the second member's list names mostly distinct buckets
        blocks = [_zipf_keys(rng, n), _one_tile_keys(rng, n)]
    elif case == "no_listed_pair":
        blocks = [_sparse_keys(rng, n) for _ in range(2)]
    else:
        blocks = [_zipf_keys(rng, n) for _ in range(2)]
    over = {}
    if case == "eval":
        over["val_data"] = str(tmp_path / "g.crec")
    app, handed, _prog = _run(tmp_path, "g", blocks, "data:2,model:2",
                              monkeypatch, **over)
    t = app.timer.totals
    if case == "eval":
        # the train group goes hot; the eval pass over the same blocks
        # makes no hot form and runs the COO eval program
        assert handed == [("tile_train_step_mesh", HOT_LANES),
                          ("tile_eval_step_mesh", COO_LANES)]
        assert _programs(app) == {("train", True), ("eval", False)}
        assert t["overflow_hot_blocks"] == 2
        return
    assert handed == [("tile_train_step_mesh", COO_LANES)]
    assert _programs(app) == {("train", False)}
    assert t["overflow_hot_blocks"] == 0
    assert t["overflow_coo_blocks"] == (2 if case == "a_coo_member" else 0)
    if case == "no_listed_pair":
        assert t["online_overflow_pairs"] == 0


def test_the_multihost_stack_keeps_the_coo_lanes():
    """``stack_mesh_group`` (the multihost pass's assembly) knows no hot
    form: what it stacks is the COO lanes."""
    from wormhole_tpu.data.crec import mesh_pads, stack_mesh_group
    info = crec.online_info(NNZ, tilemm.RSUB, NB)
    pads = mesh_pads(info, True)
    blocks, _ = stack_mesh_group([pads], 2, info, pads, True)
    assert tuple(sorted(blocks)) == COO_LANES


def test_the_stack_workers_hot_form_is_a_span(tmp_path, monkeypatch):
    from wormhole_tpu.obs import trace
    rng = np.random.default_rng(31)
    blocks = [_zipf_keys(rng, tilemm.RSUB) for _ in range(2)]
    trace.configure(enabled=True)
    try:
        app, _handed, _prog = _run(tmp_path, "s", blocks, "data:2,model:2",
                                   monkeypatch, pipeline_workers=2)
        spans = [e["name"] for e in trace.events() if e["ph"] == "X"]
    finally:
        trace.configure(enabled=False)
    assert spans.count("meshfeed:hot") == 1
    assert app.timer.totals["overflow_hot_blocks"] == 2


# -- the COO program is the parent's ------------------------------------------

# sha256 of ``_tile_step_mesh(info, kind).lower(...).as_text()`` at the
# geometry below, taken from the tree BEFORE the hot form reached the mesh
# (commit c91ad08: ``git archive`` it, put it first on the path, print the
# same digest). A uniform stream (no listed pair) runs this program and no
# other, so its text must not move when the hot form's code does. A change
# that means to touch the COO mesh step re-pins these from its own tree.
COO_TEXT = {
    ("train", 1024):
        "17d7cb422e34bca161ac8254d93b385d189d2908def0aaf9d0240a52e53bd075",
    ("eval", 1024):
        "a268db7312ce4ee4bd3b6623448c482080fc1b9cb873b479930dc97d777caca2",
    ("train", 49152):
        "d651e1789cea0ee1e10c357e852b70cff546f3f2734c242531eed2ea5b8d9200",
    ("eval", 49152):
        "d2573052098a7f6be0fa07d6c36afd1f241b51a326b01dfc7bbf4e59040f92f8",
}


@pytest.mark.parametrize("kind, oc", list(COO_TEXT))
def test_the_coo_mesh_step_lowers_to_the_parents_text(kind, oc):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from wormhole_tpu.data.crec import CRec2Info, default_cap
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import (ShardedStore, StoreConfig,
                                             TableCheckpoint,
                                             mesh_step_specs)
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    nb, sub = 1 << 18, 2
    store = ShardedStore(
        StoreConfig(num_buckets=nb),
        FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0)),
        MeshRuntime(mesh=make_mesh("data:2,model:2", jax.devices()[:4])))
    spec = tilemm.make_spec(nb, sub, default_cap(39, nb))
    info = CRec2Info(nnz=39, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb, ovf_cap=oc,
                     subblocks=sub, cap=spec.cap)
    mesh = store.rt.mesh
    Pm, Pblk, _ = mesh_step_specs(True, planes=True)
    lane = P("data", None)

    def on(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    args = [tbl.PlaneTable([on(tbl.plane_shape(nb), jnp.float32, Pm)] * 3),
            on((2, *spec.pairs_shape), jnp.uint32, Pblk),
            on((2, spec.block_rows), jnp.uint8, lane),
            on((2, oc), jnp.uint32, lane), on((2, oc), jnp.uint32, lane)]
    if kind == "train":
        args += [on((), jnp.int32, P()), on((), jnp.float32, P()),
                 on((TableCheckpoint.MACC_LEN,), jnp.float32, P())]
    text = store._tile_step_mesh(info, kind).lower(*args).as_text()
    assert "mesh_ovf_gather" in text
    assert hashlib.sha256(text.encode()).hexdigest() == COO_TEXT[kind, oc], \
        "the COO mesh step's lowered text moved (see COO_TEXT's comment)"
