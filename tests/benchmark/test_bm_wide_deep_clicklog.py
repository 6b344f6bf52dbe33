"""``criteo_wide_deep_clicklog`` on the CPU at small sizes: ``WideDeepStore``'s
spill step with a list of tens of thousands of pairs (the 33 float32 plane
gathers of ``wd_ovf_pull``, the 34 dual channels of ``wd_ovf_scatter``, the
kernel pair, the tower and the one update pass, through ``put_block`` and
``tile_train_step``) held to the configuration's own plain reference on seeded
weights, with every listed pair taken unrounded; the faults the oracle must
refuse; planes against a stacked table; the store's counts; the stated
geometry and byte counts; the catalogue's entries for the new cell.

The widths are cut for the interpreter (dim 8, hidden 64-32: the kernels run
in Pallas interpret mode here, correctness only); the cell's own run on the
CPU is ``test_bm_cell_wide_deep_clicklog_cpu.py``.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

import bm_helpers

from benchmark import check
from benchmark.configs.criteo_wide_deep_clicklog import (reference, roofline,
                                                         system as hooks)
from benchmark.generators import fields

BENCH = bm_helpers.load("BENCHMARK.json")
CELL = "criteo_wide_deep_clicklog.replay_fields"
CONFIG = bm_helpers.load(
    "benchmark/configs/criteo_wide_deep_clicklog/config.json")
TRAFFIC = bm_helpers.load("benchmark/traffic/replay_fields.json")
NEW = ("wd_overflow_ms_per_step.replay", "wd_overflow_hbm_roofline.replay",
       "wd_update_ms_per_step.replay", "wd_update_hbm_roofline.replay",
       "wd_listed_pairs_per_block.replay")
ROOM = 1 << 19
# The widths the interpreter walks fast. No hidden layer (pooled -> 1): with
# a ReLU between, a unit whose pre-activation lies within a rounding of zero
# flips on one side and not the other, a handful a step at these sizes or
# none (configs/criteo_wide_deep/README.md), and the readings of a sound step
# jump from 1e-7 to 1e-3 by the seed; without one they show what this file is
# about, the precision of the list's path. RELU has the hidden layers, held
# to the cell's own limits.
SMALL = dict(dim=8, hidden=[])
RELU = dict(dim=8, hidden=[64, 32])
# Limits of these tests, not the cell's (those are set from chip readings at
# 2**24 buckets and the published widths, config.json): over the three
# geometries and seeds 6 to 8 the sound step reads at most loss_rel 1.6e-7,
# grad_norm_rel 4.5e-7, change_norm_rel 1.5e-7, state_rel_rms 6.5e-6 on this
# CPU; against a reference that rounds the listed pairs too at least 7.7e-7,
# 4.7e-5, 3.9e-6 and 7.1e-4; with the list's pulled values rounded in the
# program at least 1.35e-6, 9.0e-6, 5.0e-6 and 7.9e-5. The ``m_list`` leaf
# (the margins of the listed singles' rows, 1,300 to 6,400 of them here)
# reads 1e-6 to 2.9e-5, and 1.2e-4 where ONE row's pooled value rounds to the
# other bfloat16 neighbour on one side (a margin off by 4e-4: seed 6 at the
# first geometry); with the list's duals rounded in the program 4.3e-2, with
# its pulled values rounded 2.6e-3: ``state_rel_rms``'s limit has room for
# the one and refuses the others by 8 times and more
LIMITS = {"loss_rel": 6e-7, "grad_norm_rel": 2e-6, "change_norm_rel": 1e-6,
          "state_rel_rms": 3e-4}
# (tiles, subblocks, cap): a quarter to two fifths of the pairs listed
GEOMETRIES = [(4, 1, 49152), (16, 2, 14336), (8, 4, 28672)]


def _patched(tiles: int, subblocks: int, cap: int, **over) -> dict:
    from wormhole_tpu.ops import tilemm
    return dict(CONFIG, num_buckets=tiles * tilemm.TILE, subblocks=subblocks,
                block_rows=subblocks * tilemm.RSUB,
                tile=dict(CONFIG["tile"], cap=cap), **dict(SMALL, **over))


def _info(config: dict):
    from wormhole_tpu.data.crec import CRec2Info
    rows = config["block_rows"]
    return CRec2Info(nnz=39, block_rows=rows, total_rows=rows,
                     nb=config["num_buckets"], ovf_cap=ROOM,
                     subblocks=config["subblocks"], cap=config["tile"]["cap"])


def _store(config: dict, seed: int, stacked: bool = False):
    """A ``WideDeepStore`` at the configuration's hyper-parameters with the
    benchmark's seeded weights, as ``system.make_app`` leaves it; ``stacked``:
    the same weights as one (nb, 66) array assigned to ``slots``."""
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    h = config["hyper"]
    store = WideDeepStore(WideDeepConfig(
        num_buckets=config["num_buckets"], dim=config["dim"],
        hidden=tuple(config["hidden"]), lr_alpha=h["lr_alpha"],
        lr_alpha_dense=h["lr_alpha_dense"], lr_beta=h["lr_beta"],
        l2_v=h["l2_v"], init_scale=h["init_scale"]))
    hooks.seed_table(store, config, seed)
    if stacked:
        store.slots = jnp.asarray(np.asarray(store.slots))
    return store


def _encoded(config: dict, keys, labels) -> tuple:
    """A block as the crec2 writer makes it, and its list's valid pairs."""
    from wormhole_tpu.data.crec import encode_tile_block
    pw, ob, orow, n = encode_tile_block(keys, config["num_buckets"],
                                        _info(config).spec, ROOM)
    assert 0 < n <= ROOM
    valid = ob != np.uint32(0xFFFFFFFF)
    return ({"pw": pw, "labels": labels, "ovf_b": ob, "ovf_r": orow},
            (ob[valid].astype(np.int64), orow[valid].astype(np.int64)))


def _blocks(config: dict, seed: int, steps: int = 3) -> list:
    return [fields.make_block(TRAFFIC, seed, i, config["block_rows"])
            for i in range(steps)]


def _program(config: dict, seed: int, steps: int = 3) -> tuple:
    """The store's first steps on the seed's blocks, as the harness reads
    them: (observed, the blocks, their lists, the store)."""
    blocks = _blocks(config, seed, steps)
    store, info = _store(config, seed), _info(config)
    app = types.SimpleNamespace(store=store)
    observed, lists = {"losses": []}, []
    for i, (keys, labels) in enumerate(blocks):
        block, listed = _encoded(config, keys, labels)
        lists.append(listed)
        store.tile_train_step(store.put_block(block), info)
        m = store.fetch_metrics()
        observed["losses"].append(float(m[0] / m[1]))
        if i == 0:
            observed["grad_norms"] = hooks.grad_norms(app, config, seed)
    observed["change_norms"] = hooks.change_norms(app, config, seed)
    return observed, blocks, lists, store


def _numbers(config, seed, observed, blocks, store, **precision) -> dict:
    expected, ref = check.run_reference(reference, config, blocks, seed,
                                        **precision)
    assert ref.list_fault is None
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    got = dict(observed, state=hooks.state(
        types.SimpleNamespace(store=store), config, seed, buckets))
    return check.numbers(got, expected)


@pytest.mark.parametrize("tiles,subblocks,cap", GEOMETRIES)
def test_the_spill_step_is_the_references_with_every_listed_pair_unrounded(
        tiles, subblocks, cap):
    config, seed = _patched(tiles, subblocks, cap), 6
    observed, blocks, lists, store = _program(config, seed)
    pairs = config["block_rows"] * 39
    # tens of thousands of pairs a list, not tiny_patches' 1,024 slots
    assert all(0.25 * pairs < len(b) < 0.45 * pairs for b, _r in lists)
    stated = check.stated_precision(config, lists)
    assert stated == {"operands": "bfloat16", "exact_pairs": lists}
    nums = _numbers(config, seed, observed, blocks, store, **stated)
    ok, lines = check.verdict(nums, LIMITS)
    assert ok, lines
    # every block took the spill step with its list, the table was never
    # stacked, and the store counted the pairs its lists held
    from wormhole_tpu.learners import table as tbl
    totals = store.timer.totals
    assert totals["wd_spill_blocks"] == 3
    assert totals["wd_listed_pairs"] == sum(len(b) for b, _r in lists)
    assert "wd_listless_blocks" not in totals
    assert "table_cross" not in store.timer.counts
    assert isinstance(store.slots, tbl.PlaneTable)
    assert store.step_kernel[0] == "split"
    # ... and the reference of another program is refused: every listed
    # pair rounded to bfloat16 too (what the spill step must not do)
    nums = _numbers(config, seed, observed, blocks, store,
                    **dict(stated, exact_pairs=None))
    for name in ("grad_norm_rel", "change_norm_rel", "state_rel_rms"):
        assert nums[name] > 2 * LIMITS[name], nums


def test_the_spill_step_with_the_relu_tower_is_inside_the_cells_limits():
    """The same three steps with hidden layers between the pooled values and
    the output, every phase of the cell's step at once, under the limits
    ``config.json`` states for the cell (set for a ReLU tower's roughness)."""
    config, seed = _patched(4, 1, 49152, **RELU), 6
    observed, blocks, lists, store = _program(config, seed)
    nums = _numbers(config, seed, observed, blocks, store,
                    **check.stated_precision(config, lists))
    ok, lines = check.verdict(nums, config["check"]["limits"])
    assert ok, lines
    assert store.timer.totals["wd_spill_blocks"] == 3


def test_the_cells_limits_refuse_a_rounded_list_under_the_relu_tower(
        monkeypatch):
    """What the whole-model numbers cannot see past a ReLU tower's roughness
    the ``m_list`` leaf does: with the hidden layers in, at the limits
    ``config.json`` states for the cell, the reference that rounds every
    listed pair too is refused through ``check.verdict`` by twice
    ``state_rel_rms``'s limit and more, and so is a program that rounds the
    list's duals as the kernels round the others; the sound program reads
    the leaf under half the limit (4e-4 here: 1,528 margins of 0.05 and a
    few rows in which a hidden unit rounds the other way on one side; the
    cell's 17,000 margins of 0.1 read less, ``PERF.md`` section 2)."""
    config, seed = _patched(4, 1, 49152, **RELU), 6
    limits = config["check"]["limits"]
    observed, blocks, lists, store = _program(config, seed)
    stated = check.stated_precision(config, lists)
    expected, ref = check.run_reference(reference, config, blocks, seed,
                                        **stated)
    assert len(ref.singles) > 1000
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    got = dict(observed, state=hooks.state(
        types.SimpleNamespace(store=store), config, seed, buckets))
    m, m_ref = got["state"]["m_list"], expected["state"]["m_list"]
    assert m.shape == m_ref.shape == ref.singles.shape
    assert np.linalg.norm(m - m_ref) < 0.5 * limits["state_rel_rms"] \
        * np.linalg.norm(m_ref)
    nums = _numbers(config, seed, observed, blocks, store,
                    **dict(stated, exact_pairs=None))
    assert nums["state_rel_rms"] > 2 * limits["state_rel_rms"], nums
    assert not check.verdict(nums, limits)[0]
    _round_the_lists_duals(monkeypatch)
    observed, blocks, lists, store = _program(config, seed)
    nums = _numbers(config, seed, observed, blocks, store, **stated)
    assert nums["state_rel_rms"] > 2 * limits["state_rel_rms"], nums
    assert not check.verdict(nums, limits)[0]


def test_the_listed_singles_by_hand_count():
    """``m_list``'s buckets: one pair in all the checked blocks together,
    that pair in the first block and on its list."""
    pairs = [(np.array([5, 7, 7, 9, 11, 13]), np.arange(6)),
             (np.array([5, 8, 11]), np.arange(3))]
    listed = [np.array([0, 1, 0, 1, 1, 1], bool), np.array([0, 1, 0], bool)]
    # 5: not listed and twice; 7: twice; 8: listed in the second block;
    # 11: listed in the first but met again; 9 and 13 are the singles
    assert reference.listed_singles(pairs, listed).tolist() == [9, 13]
    assert np.allclose(reference.logit(np.array([0.5, 0.25])),
                       [0.0, -np.log(3.0)])
    # a dropped pair (cg_w 0) or a doubled one (cg_w 1 and more) reads no
    # finite margin: ``check.verdict`` refuses what is not finite
    assert not np.isfinite(reference.logit(np.array([0.0, 1.0, 1.2]))).any()


# -- planted faults ---------------------------------------------------------

def _swapped(config: dict, keys, listed: tuple) -> tuple:
    """The list with one tile's first listed pair replaced by a pair the
    tile keeps, of an earlier row: every count stays right."""
    nb, tile = config["num_buckets"], config["tile"]
    b, r = check.block_pairs([(keys, None)], nb)[0][0]
    mask = check.exact_mask(b, r, listed, nb)
    tiles = nb // tile["buckets"]
    cell = (r // tile["rows"]) * tiles + b // tile["buckets"]
    lb, lr = (np.array(x) for x in listed)
    c = (lr[0] // tile["rows"]) * tiles + lb[0] // tile["buckets"]
    kept = np.flatnonzero((cell == c) & ~mask & (r < lr[0]))[0]
    lb[0], lr[0] = b[kept], r[kept]
    return lb, lr


LIST_FAULTS = ["dropped", "doubled", "neighbouring_bucket", "out_of_order"]


@pytest.mark.parametrize("fault", LIST_FAULTS)
def test_a_planted_list_fault_is_refused(fault, capsys):
    """The reference checks the list it is handed against its own count: a
    pair dropped, doubled, sent to the neighbouring bucket, or a kept pair
    listed in a later one's place fails the check, every loss is NaN and
    ``correct`` is false; the sound list passes."""
    config, seed = _patched(4, 1, 49152), 6
    blocks = _blocks(config, seed, 2)
    lists = [_encoded(config, k, l)[1] for k, l in blocks]
    sound, ref = check.run_reference(
        reference, config, blocks, seed,
        **check.stated_precision(config, lists))
    assert ref.list_fault is None and np.isfinite(sound["losses"]).all()
    b, r = lists[1]
    bad = {"dropped": lambda: (b[1:], r[1:]),
           "doubled": lambda: (np.r_[b, b[:1]], np.r_[r, r[:1]]),
           "neighbouring_bucket": lambda: (
               np.r_[b[1:], (b[0] + 1) % config["num_buckets"]],
               np.r_[r[1:], r[0]]),
           "out_of_order": lambda: _swapped(config, blocks[1][0], lists[1]),
           }[fault]()
    got, ref = check.run_reference(
        reference, config, blocks, seed,
        **check.stated_precision(config, [lists[0], bad]))
    assert ref.list_fault and ref.list_fault.startswith("step 1")
    assert np.isnan(got["losses"]).all()
    nums = {"loss_rel": max(abs(a - b) / abs(b) for a, b in zip(
        sound["losses"], got["losses"]))}
    assert not check.verdict(nums, {"loss_rel": LIMITS["loss_rel"]})[0]
    assert "[reference] the overflow list handed for step 1" in \
        capsys.readouterr().err


def _round_the_lists_pull(monkeypatch):
    from wormhole_tpu.ops import tilemm
    real = tilemm.plane_spill_pull_rows

    def rounded(planes, *rest):
        return real([p.astype(jnp.bfloat16).astype(jnp.float32)
                     for p in planes], *rest)
    monkeypatch.setattr(tilemm, "plane_spill_pull_rows", rounded)


def _round_the_lists_duals(monkeypatch):
    from wormhole_tpu.ops import tilemm
    real = tilemm.spill_push_scatter_lanes

    def rounded(g, dual_rows, ovf_b, ovf_r, spec):
        return real(g, dual_rows.astype(jnp.bfloat16).astype(jnp.float32),
                    ovf_b, ovf_r, spec)
    monkeypatch.setattr(tilemm, "spill_push_scatter_lanes", rounded)


def _scatter_33_of_34_channels(monkeypatch):
    from wormhole_tpu.ops import tilemm
    real = tilemm.spill_push_scatter_lanes

    def short(g, dual_rows, ovf_b, ovf_r, spec):
        k = dual_rows.shape[1] - 2
        return real(g, dual_rows.at[:, k].set(0.0), ovf_b, ovf_r, spec)
    monkeypatch.setattr(tilemm, "spill_push_scatter_lanes", short)


def _drop_the_list(monkeypatch):
    from wormhole_tpu.ops import tilemm
    real = tilemm.plane_spill_pull_rows
    monkeypatch.setattr(
        tilemm, "plane_spill_pull_rows",
        lambda *args: 0.0 * real(*args))


PROGRAM_FAULTS = {
    "pulled_values_rounded_to_bfloat16": _round_the_lists_pull,
    "duals_rounded_to_bfloat16": _round_the_lists_duals,
    "duals_scattered_into_33_of_34_channels": _scatter_33_of_34_channels,
    "listed_pairs_not_pulled": _drop_the_list,
}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_a_fault_planted_in_the_programs_list_path_leaves_the_oracle(
        fault, monkeypatch):
    """The list halves of the step broken in the program itself: the listed
    pairs' 33 values or their 34 duals rounded as the kernels round the
    others, the last embedding channel's duals left out of the scatter, the
    list's pull left out. Each fails a limit; the sound program (the first
    test) passes them."""
    PROGRAM_FAULTS[fault](monkeypatch)
    config, seed = _patched(4, 1, 49152), 7
    observed, blocks, lists, store = _program(config, seed)
    nums = _numbers(config, seed, observed, blocks, store,
                    **check.stated_precision(config, lists))
    ok, lines = check.verdict(nums, LIMITS)
    assert not ok, lines


# -- planes and a stacked table ---------------------------------------------

def test_planes_and_a_stacked_table_step_a_long_list_alike():
    """The same block with its list of a hundred thousand pairs through the
    planar store (the list's values gathered plane by plane, its duals
    scattered a plane at a time: ``spill_push_scatter_lanes`` past its
    short-list rule) and through a store handed the same weights stacked
    (the (nb, ch) helpers): margins, loss and tower to the bit; the table but
    for the last bit of the update's fusion (``tests/test_table_planes.py``,
    ``_assert_same_table``: XLA contracts a fusion over planes and one over
    slices of (nb, 66) differently), and the stacked start comes back as
    planes with no crossing counted."""
    from wormhole_tpu.learners import table as tbl
    config, seed = _patched(4, 1, 49152), 8
    keys, labels = _blocks(config, seed, 1)[0]
    block, listed = _encoded(config, keys, labels)
    assert 8 * len(listed[0]) > 4 * 128      # the plane-by-plane scatter
    info = _info(config)
    planar, stacked = _store(config, seed), _store(config, seed, True)
    assert not isinstance(stacked.slots, tbl.PlaneTable)
    rows = []
    for st in (planar, stacked):
        dev = st.put_block(block)
        margin = np.asarray(st.tile_eval_step(dev, info)[5])
        st.tile_train_step(dev, info)
        rows.append((margin, st.fetch_metrics()))
    np.testing.assert_array_equal(rows[0][0], rows[1][0])
    np.testing.assert_array_equal(np.delete(rows[0][1], 3),
                                  np.delete(rows[1][1], 3))
    for name, leaf in planar.mlp.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(stacked.mlp[name]))
    assert isinstance(stacked.slots, tbl.PlaneTable)
    assert "table_cross" not in stacked.timer.counts
    got, want = np.asarray(planar.slots), np.asarray(stacked.slots)
    assert np.any(got != np.asarray(_store(config, seed).slots))
    np.testing.assert_allclose(got, want, rtol=4e-5, atol=1e-7)
    assert np.mean(got != want) < 1e-3


# -- the list's distinct buckets --------------------------------------------

def test_a_long_lists_distinct_buckets_are_the_list_again():
    """``overflow.distinct``: every pair's bucket read back through its
    index; the distinct buckets ascending in whole tiles, never fewer than
    the caller has met; the unused slots' indices dealt round the room, no
    one address for all of them; a list too short to pay for the room
    crosses without."""
    from wormhole_tpu.ops import overflow, tilemm
    config, seed = _patched(4, 1, 49152), 10
    block, listed = _encoded(config, *_blocks(config, seed, 1)[0])
    ovf_b, n = block["ovf_b"], len(listed[0])
    ovf_d, ovf_k = overflow.distinct(ovf_b, 1, tilemm.TILE)
    uniq = np.unique(listed[0])
    assert ovf_d.dtype == ovf_k.dtype == np.uint32
    assert len(ovf_d) == -(-len(uniq) // tilemm.TILE) * tilemm.TILE
    np.testing.assert_array_equal(ovf_d[:len(uniq)], uniq)
    assert (ovf_d[len(uniq):] == overflow.UNUSED).all()
    np.testing.assert_array_equal(ovf_d[ovf_k[:n]], ovf_b[:n])
    unused = ovf_k[n:]
    assert unused.max() < len(ovf_d)
    assert np.bincount(unused).max() <= -(-len(unused) // len(ovf_d))
    wider = overflow.distinct(ovf_b, 3, tilemm.TILE)
    assert len(wider[0]) == 3 * tilemm.TILE
    np.testing.assert_array_equal(wider[0][wider[1][:n]], ovf_b[:n])
    short = overflow.DISTINCT_MIN_SLOTS * tilemm.TILE
    assert overflow.distinct(ovf_b[:short - 1], 1, tilemm.TILE) is None
    assert overflow.distinct(ovf_b[:short], 1, tilemm.TILE) is not None


@pytest.mark.parametrize("case", ["long_list_on_planes", "no_planes_kept",
                                  "room_of_1024_slots", "hot_form",
                                  "another_store"])
def test_which_lists_cross_with_their_distinct_buckets(case):
    """``put_block``: wide&deep's long COO list on planes crosses with
    ``ovf_d`` and ``ovf_k`` and the step takes them (``overflow.of``); that
    of a store that keeps no planes, a list in a room of 1,024 slots (the uniform cells':
    their step program is what it was), a hot list and another store's
    cross as they did."""
    from wormhole_tpu.ops import overflow, tilemm
    config, seed = _patched(4, 1, 49152), 10
    block, listed = _encoded(config, *_blocks(config, seed, 1)[0])
    store = _store(config, seed)
    if case == "no_planes_kept":
        store._planar = False
    elif case == "room_of_1024_slots":
        block = dict(block, ovf_b=block["ovf_b"][:1024],
                     ovf_r=block["ovf_r"][:1024])
    elif case == "hot_form":
        block = dict(block, ovf_u=np.zeros(tilemm.TILE, np.uint32),
                     ovf_pw=np.zeros((1, 1, 8), np.uint32))
    elif case == "another_store":
        store._distinct_tiles = None
    dev = store.put_block(block)
    if case == "long_list_on_planes":
        assert set(overflow.of(dev)) == set(overflow.COO + overflow.DISTINCT)
        assert store._distinct_tiles == len(dev["ovf_d"]) // tilemm.TILE
        np.testing.assert_array_equal(
            np.asarray(dev["ovf_d"])[np.asarray(dev["ovf_k"])[
                :len(listed[0])]], listed[0])
    else:
        assert not set(overflow.DISTINCT) & set(dev)
        assert set(overflow.of(dev)) == set(
            overflow.HOT if case == "hot_form" else overflow.COO)


def test_a_plane_read_once_a_listed_bucket_gives_the_slot_a_pair_bits():
    """The spill step with the list's distinct buckets beside it (a plane
    read once a listed bucket, the slots from those) against the same step
    a slot a pair (a store that ships no ``ovf_d``): the same values, so
    margins, metrics, tower and table to the bit, eval and three train
    steps; and the helper alone on a list whose unused slots are most of
    it."""
    from wormhole_tpu.ops import overflow, tilemm
    config, seed = _patched(4, 1, 49152, **RELU), 11
    info = _info(config)
    once, slot = _store(config, seed), _store(config, seed)
    slot._distinct_tiles = None
    for keys, labels in _blocks(config, seed):
        block, _ = _encoded(config, keys, labels)
        rows = []
        for st in (once, slot):
            dev = st.put_block(block)
            assert (overflow.DISTINCT[0] in dev) == (st is once)
            margin = np.asarray(st.tile_eval_step(dev, info)[5])
            st.tile_train_step(dev, info)
            rows.append((margin, st.fetch_metrics()))
        np.testing.assert_array_equal(rows[0][0], rows[1][0])
        np.testing.assert_array_equal(rows[0][1], rows[1][1])
    np.testing.assert_array_equal(np.asarray(once.slots),
                                  np.asarray(slot.slots))
    for name, leaf in once.mlp.items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(slot.mlp[name]))
    ovf_b = np.full(ROOM, overflow.UNUSED, np.uint32)
    ovf_b[:1000] = block["ovf_b"][:1000]
    planes = tuple(p for p in once.slots.planes[:3])
    args = (planes, jnp.asarray(ovf_b), jnp.asarray(block["ovf_r"]),
            info.spec)
    np.testing.assert_array_equal(
        np.asarray(tilemm.plane_spill_pull_rows(*args)),
        np.asarray(tilemm.plane_spill_pull_rows(*args, tuple(
            jnp.asarray(a) for a in overflow.distinct(ovf_b, 1,
                                                      tilemm.TILE)))))


# -- the store's counts -----------------------------------------------------

def test_the_store_counts_spill_blocks_listed_pairs_and_listless_blocks():
    """``wd_spill_blocks`` and ``wd_listed_pairs`` from blocks that bring
    their lists (counted once where the list crosses, added at every step
    the resident block takes); ``wd_listless_blocks`` from one whose list
    stayed behind; the registry's counters move with the timer's."""
    from wormhole_tpu.obs import metrics
    config, seed = _patched(4, 1, 49152), 9
    keys, labels = _blocks(config, seed, 1)[0]
    block, listed = _encoded(config, keys, labels)
    store, info = _store(config, seed), _info(config)
    before = [c.value for c in metrics.wd_step_metrics()]
    dev = store.put_block(block)
    for _ in range(2):
        store.tile_train_step(dev, info)
    t = store.timer.totals
    assert (t["wd_spill_blocks"], t["wd_listed_pairs"]) == (
        2, 2 * len(listed[0]))
    assert "wd_listless_blocks" not in t
    lost = {k: v for k, v in dev.items() if not k.startswith("ovf_")}
    store.tile_train_step(lost, info)
    assert t["wd_listless_blocks"] == 1 and t["wd_spill_blocks"] == 2
    moved = [c.value - b for c, b in zip(metrics.wd_step_metrics(), before)]
    assert moved == [2, 2 * len(listed[0]), 1]
    counted = hooks.counters(types.SimpleNamespace(timer=store.timer))
    assert counted == {"table_cross": 0, "wd_listless_blocks": 1,
                       "wd_spill_blocks": 2,
                       "wd_listed_pairs": 2 * len(listed[0])}
    assert set(CONFIG["program"]["zero_counters"]) == {
        "table_cross", "wd_listless_blocks"} <= set(counted)


# -- what the configuration states ------------------------------------------

def test_the_stated_tile_geometry_and_widths_are_the_programs():
    from wormhole_tpu.data import crec
    from wormhole_tpu.models.wide_deep import WideDeepConfig
    from wormhole_tpu.ops import tilemm
    assert (tilemm.TILE, tilemm.RSUB) == (CONFIG["tile"]["buckets"],
                                          CONFIG["tile"]["rows"])
    assert CONFIG["num_buckets"] == 2 ** 24
    assert crec.default_cap(39, 2 ** 24) == CONFIG["tile"]["cap"] == 384
    assert CONFIG["block_rows"] == CONFIG["subblocks"] * tilemm.RSUB
    assert (CONFIG["dim"], CONFIG["hidden"]) == (32, [1024, 512, 256])
    assert CONFIG["reduced"] == ["rows", "num_buckets"]
    assert CONFIG["precision"] == {
        "table": "float32", "kernel_operands": "bfloat16",
        "overflow_operands": "float32", "tower_operands": "bfloat16",
        "accumulate": "float32"}
    assert CONFIG["state_bytes_per_bucket"] == 4 * 2 * (1 + CONFIG["dim"])
    # the same model, geometry and limits' controls as the uniform cell's:
    # the two differ in keys alone
    uniform = bm_helpers.load("benchmark/configs/criteo_wide_deep/config.json")
    for key in ("nnz", "subblocks", "block_rows", "num_buckets", "dim",
                "hidden", "hyper", "precision", "tower_parameters"):
        assert CONFIG[key] == uniform[key], key
    assert CONFIG["program"]["model_conf"] \
        == uniform["program"]["model_conf"]
    assert CONFIG["check"]["controls"] == uniform["check"]["controls"]
    for key in ("lr_beta", "l2_v", "init_scale"):
        assert CONFIG["hyper"][key] == getattr(WideDeepConfig(), key)
    # the mix is criteo_fm_clicklog's to the letter, and stands unedited
    assert TRAFFIC["ovf_cap"] == 1638400 and TRAFFIC["blocks"] == 12
    assert TRAFFIC["program"]["cache_device"] == 1
    for text in CONFIG["guarantees"]:
        assert text and "\n" not in text
    assert len(CONFIG["source"]) <= 200 and CONFIG["deployment"]


def test_update_pass_and_list_bytes_by_hand_count():
    # 34 push planes and 66 state planes in, 66 out, 4 B a bucket a plane
    assert roofline.update_pass_bytes(CONFIG) == 166 * 4 * 2 ** 24 \
        == 11_140_071_424
    # a listed pair: 33 values read, 34 dual values written, 4 B each
    assert roofline.list_bytes(CONFIG, 1) == 4 * (33 + 34) == 268
    assert roofline.list_bytes(CONFIG, 1_104_036) == 268 * 1_104_036
    uniform = bm_helpers.load("benchmark/configs/criteo_wide_deep/config.json")
    from benchmark.configs.criteo_wide_deep import roofline as wd
    assert roofline.block_work(CONFIG, 3833856, 98304, 40000) \
        == wd.block_work(uniform, 3833856, 98304, 40000)
    assert roofline.tower_flops(CONFIG, 98304) == 406_025_404_416


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    for module in (reference, roofline):
        for node in ast.walk(ast.parse(inspect.getsource(module))):
            names = [a.name for a in node.names] if isinstance(
                node, ast.Import) else [node.module] if isinstance(
                node, ast.ImportFrom) else []
            assert not any(n.startswith("wormhole_tpu") for n in names)


def test_the_hook_seeds_in_place_and_refuses_a_stacked_table():
    """v0 is ``criteo_fm``'s hash (the float64 twin to float32's last bit),
    w and the accumulators zero, the tower ``init_tower``'s with zero
    accumulators; the planes stay planes; a stacked table is refused."""
    from wormhole_tpu.learners import table as tbl
    config, seed = _patched(4, 1, 49152), 11
    store = _store(config, seed)
    assert isinstance(store.slots, tbl.PlaneTable)
    k, nb = config["dim"], config["num_buckets"]
    got = np.asarray(store.slots)
    want = reference.init_factors(np.arange(nb), k, seed, 0.01)
    assert np.allclose(got[:, 1:1 + k], want, rtol=3e-7, atol=0.0)
    assert not got[:, 0].any() and not got[:, 1 + k:].any()
    tower = reference.init_tower(reference.tower_sizes(config), seed)
    for l, (w, b) in enumerate(tower):
        np.testing.assert_array_equal(np.asarray(store.mlp[f"W{l}"]), w)
        assert not np.asarray(store.mlp_accum[f"W{l}"]).any()
        assert not b.any()
    store.slots = jnp.asarray(got)
    with pytest.raises(RuntimeError, match="never stacked"):
        hooks.seed_table(store, config, seed)


# -- the phases' names ------------------------------------------------------

def test_the_list_phases_keep_their_names_in_the_compiled_step():
    """``wd_ovf_pull`` and ``wd_ovf_scatter`` are jits of their own inside
    ``wd_pull`` and ``wd_push``: the optimized program's op metadata (what
    the profiler files an op's event under, and what the readers'
    ``scoped_ops`` reads back from a trace's ``tf_op``) names every gather
    and scatter of the step under one of them, 1 + k plane gathers and k + 2
    plane scatter-adds; a block without a list has neither."""
    import re
    config, seed = _patched(4, 1, 49152), 6
    store, info = _store(config, seed), _info(config)
    keys, labels = _blocks(config, seed, 1)[0]
    dev = store.put_block(_encoded(config, keys, labels)[0])
    k = config["dim"]

    def text_of(block, spill):
        return store._tile_step(info, "train", spill).lower(
            store.slots, store.mlp, store.mlp_accum, block,
            store._t_device(), store._tau_const(0.0),
            store._macc_buf()).compile().as_text()

    text = text_of(dev, True)
    for op, phase, n in (("gather", "wd_ovf_pull", 1 + k),
                         ("scatter", "wd_ovf_scatter", k + 2)):
        lines = [ln for ln in text.splitlines()
                 if re.search(r" = \S+ %s\(" % op, ln)]
        assert sum("jit(%s)" % phase in ln for ln in lines) >= n, op
    for phase in ("wd_pull", "wd_push", "wd_table_update", "wd_tower"):
        assert re.search(r"jit\(%s\)" % phase, text), phase
    assert "wd_tower_forward" in text and "wd_tower_backward" in text
    bare = text_of({k_: v for k_, v in dev.items()
                    if not k_.startswith("ovf_")}, False)
    assert "wd_ovf_" not in bare and "jit(wd_table_update)" in bare


def test_the_new_readers_find_their_scopes_by_name_in_a_trace():
    """The readers' own decoder on the recorded trace the other reader tests
    read: FTRL's step files no op under wide&deep's scopes, so each new
    trace reader reads nothing from it, and the scopes each asks for are the
    names the program gives its jits."""
    import inspect
    import os
    from benchmark.readers import (tower_ms_per_step,
                                   wd_overflow_ms_per_step,
                                   wd_update_ms_per_step)
    from wormhole_tpu.models import wide_deep
    scopes = tower_ms_per_step.scoped_ops(
        os.path.join(bm_helpers.DATA, "ftrl_replay.xplane.pb"))
    assert scopes
    source = inspect.getsource(wide_deep)
    for reader in (wd_overflow_ms_per_step, wd_update_ms_per_step):
        for scope in reader.SCOPES:
            assert f"def {scope}(" in source, scope
            assert not any(scope in path for path in scopes.values())
    assert wd_overflow_ms_per_step.SCOPES == ("wd_ovf_pull",
                                              "wd_ovf_scatter")


# -- the catalogue's entries for the cell -----------------------------------

def _entry(metric: str) -> dict:
    return next(m for m in BENCH["end_to_end"] + BENCH["per_layer"]
                if m["name"] == metric)


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_lists_this_cell_alone_and_says_what_it_reads(metric):
    entry = _entry(metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_ex_per_s" and entry["layer"] == "step"
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    assert spec["regime"] == "replay" and spec["what"]
    assert spec["reader"] == f"benchmark.readers.{metric.split('.')[0]}:read"
    # after every metric that was there before this cell's
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(metric) > names.index(
        "fm_listed_pairs_per_block.replay")


def test_the_cell_joins_the_replay_metrics_it_reports_after_the_cells_before_it():  # noqa: E501
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    assert cells.index(CELL) > cells.index("criteo_fm_clicklog.replay_fields")
    assert configs.index("criteo_wide_deep_clicklog") \
        > configs.index("criteo_fm_clicklog")
    cell = BENCH["workloads"][cells.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "criteo_wide_deep_clicklog", "replay_fields", 1)
    joined = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", ())]
    assert {m["name"] for m in joined} == {
        "train_ex_per_s", "xla_ms_per_step.replay",
        "nonkernel_ms_per_step.replay", "kernel_ms_per_step.replay",
        "tile_kernel_roofline.replay", "device_idle_share.replay",
        "hbm_peak_gb.replay", "tower_ms_per_step.replay",
        "tower_mxu_roofline.replay", *NEW}
    for m in joined:
        # appended: every cell that was listed before it still is, in order
        at = m["workloads"].index(CELL)
        assert all(cells.index(w) < cells.index(CELL)
                   for w in m["workloads"][:at])


# what the program's Timer held over a window before this PR, and a reduced
# trace of a program whose ops carry none of its scopes
OLD_TIMERS = {"dispatch": 0.02, "wait": 0.001, "tower_flops": 2.0e14,
              "dense_param_bytes": 5.7e9}
OLD_TRACE = {"window_s": 51.6, "busy_s": 51.5, "step_s": 38.7,
             "kernel_s": 38.5, "steps": 516, "device_ops": [],
             "idle_gaps": []}


def _reading(timers: dict, trace) -> dict:
    return {"window": {"window_s": 51.6, "rows": 516 * 98304, "steps": 516,
                       "blocks": 516, "timers": timers},
            "setup_s": 46.5, "config": CONFIG, "traffic": TRAFFIC,
            "memory_peak_bytes": 5660000000, "trace": trace,
            "least_s_per_step": 1.5e-4}


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_reads_nothing_from_the_parents_program(metric):
    from benchmark import run
    read = run.reader_of(bm_helpers.REPO, metric)
    for trace in (None, OLD_TRACE):
        assert read(_reading(dict(OLD_TIMERS), trace)) is None
    assert read({"trace": None}) is None


def test_the_counter_reader_reads_pairs_a_spill_block():
    from benchmark.readers import (wd_listed_pairs_per_block,
                                   wd_overflow_hbm_roofline)
    r = _reading({"wd_listed_pairs": 516 * 1.1e6, "wd_spill_blocks": 516.0},
                 None)
    assert wd_listed_pairs_per_block.read(r) == pytest.approx(1.1e6)
    # the share needs the list's device time too: none without a trace
    assert wd_overflow_hbm_roofline.read(r) is None
