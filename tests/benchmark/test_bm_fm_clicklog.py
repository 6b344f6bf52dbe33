"""``criteo_fm_clicklog`` on the CPU at small sizes: ``FMStore``'s spill step
(the gradient-writing kernel, the float32 COO pull and scatter, the one update
pass, through ``put_block`` and ``tile_train_step``) held to the
configuration's own plain reference on seeded weights, with every listed pair
taken unrounded; the faults the limits must refuse; and the rule by which a
block takes the spill step or the in-place one.

The kernels run in Pallas interpret mode here: correctness only.
"""

import types

import jax
import numpy as np
import pytest

import bm_helpers

from benchmark import check
from benchmark.configs.criteo_fm_clicklog import reference, system as hooks
from benchmark.generators import fields

CONFIG = bm_helpers.load("benchmark/configs/criteo_fm_clicklog/config.json")
TRAFFIC = bm_helpers.load("benchmark/traffic/replay_fields.json")
ROOM = 1 << 19
# Limits of these tests, not the cell's (those are set from chip readings at
# 2**26 buckets, config.json): over the three geometries and three seeds each
# the sound step reads at most loss_rel 1.3e-5, grad_norm_rel 1.1e-6,
# change_norm_rel 5.6e-6, state_rel_rms 1.44e-4 on this CPU, and against a
# reference that rounds the listed pairs too at least 6.4e-5, 8.2e-6, 5.1e-5
# and 9.9e-4 (fewer buckets than the cell: a hot bucket weighs more)
LIMITS = {"loss_rel": 5e-5, "grad_norm_rel": 5e-6, "change_norm_rel": 2e-5,
          "state_rel_rms": 4e-4}
# (tiles, subblocks, cap): a quarter to two fifths of the pairs listed
GEOMETRIES = [(4, 1, 49152), (16, 2, 14336), (8, 4, 28672)]


def _patched(tiles: int, subblocks: int, cap: int) -> dict:
    from wormhole_tpu.ops import tilemm
    return dict(CONFIG, num_buckets=tiles * tilemm.TILE, subblocks=subblocks,
                block_rows=subblocks * tilemm.RSUB,
                tile=dict(CONFIG["tile"], cap=cap))


def _info(config: dict):
    from wormhole_tpu.data.crec import CRec2Info
    rows = config["block_rows"]
    return CRec2Info(nnz=39, block_rows=rows, total_rows=rows,
                     nb=config["num_buckets"], ovf_cap=ROOM,
                     subblocks=config["subblocks"], cap=config["tile"]["cap"])


def _store(config: dict, seed: int):
    from wormhole_tpu.models.fm import FMConfig, FMStore
    store = FMStore(FMConfig(num_buckets=config["num_buckets"],
                             dim=config["dim"], tile_step_kernel="fused"))
    hooks.seed_table(store, config, seed)
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


def _program(config: dict, seed: int, steps: int = 3) -> tuple:
    """The store's first steps on the seed's blocks, as the harness reads
    them: (observed, the blocks, their lists, the store)."""
    rows = config["block_rows"]
    blocks = [fields.make_block(TRAFFIC, seed, i, rows)
              for i in range(steps)]
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


@pytest.mark.parametrize("tiles,subblocks,cap", GEOMETRIES)
def test_the_spill_step_is_the_references_with_every_listed_pair_unrounded(
        tiles, subblocks, cap):
    config, seed = _patched(tiles, subblocks, cap), 6
    observed, blocks, lists, store = _program(config, seed)
    pairs = config["block_rows"] * 39
    assert all(0.25 * pairs < len(b) < 0.45 * pairs for b, _r in lists)
    stated = check.stated_precision(config, lists)
    assert stated == {"operands": "bfloat16", "exact_pairs": lists}
    expected, ref = check.run_reference(reference, config, blocks, seed,
                                        **stated)
    assert ref.list_fault is None
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    observed["state"] = hooks.state(types.SimpleNamespace(store=store),
                                    config, seed, buckets)
    ok, lines = check.verdict(check.numbers(observed, expected), LIMITS)
    assert ok, lines
    # every block took the spill step, the table was never stacked, and
    # the store counted the pairs its lists held
    from wormhole_tpu.learners import table as tbl
    totals = store.timer.totals
    assert totals["fm_spill_blocks"] == 3
    assert totals["fm_listed_pairs"] == sum(len(b) for b, _r in lists)
    assert "fm_in_place_blocks" not in totals
    assert "table_cross" not in store.timer.counts
    assert isinstance(store.slots, tbl.PlaneTable)
    assert store.step_kernel[0] == "fused"
    # ... and the reference of another program is refused: every listed
    # pair rounded to bfloat16 too (what the spill step must not do)
    rounded, _ = check.run_reference(reference, config, blocks, seed,
                                     buckets=buckets,
                                     **dict(stated, exact_pairs=None))
    nums = check.numbers(observed, rounded)
    assert nums["state_rel_rms"] > 2 * LIMITS["state_rel_rms"], nums
    assert nums["change_norm_rel"] > 2 * LIMITS["change_norm_rel"], nums


def _blocks_and_lists(config: dict, seed: int, steps: int = 2) -> tuple:
    blocks = [fields.make_block(TRAFFIC, seed, i, config["block_rows"])
              for i in range(steps)]
    return blocks, [_encoded(config, k, l)[1] for k, l in blocks]


@pytest.mark.parametrize("fault", ["dropped", "doubled", "foreign"])
def test_a_planted_list_fault_is_refused(fault, capsys):
    """The reference checks the list it is handed against its own count: a
    pair dropped, doubled or not the block's fails the check, every loss is
    NaN and ``correct`` is false; the sound list passes."""
    config, seed = _patched(4, 1, 49152), 6
    blocks, lists = _blocks_and_lists(config, seed)
    sound, ref = check.run_reference(
        reference, config, blocks, seed,
        **check.stated_precision(config, lists))
    assert ref.list_fault is None and np.isfinite(sound["losses"]).all()
    b, r = lists[1]
    bad = {"dropped": (b[1:], r[1:]),
           "doubled": (np.r_[b, b[:1]], np.r_[r, r[:1]]),
           "foreign": (np.r_[b[1:], (b[0] + 1) % config["num_buckets"]],
                       r)}[fault]
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


def test_the_stated_tile_geometry_is_the_programs():
    from wormhole_tpu.data import crec
    from wormhole_tpu.ops import tilemm
    assert (tilemm.TILE, tilemm.RSUB) == (CONFIG["tile"]["buckets"],
                                          CONFIG["tile"]["rows"])
    assert crec.default_cap(39, CONFIG["num_buckets"]) \
        == CONFIG["tile"]["cap"]
    assert CONFIG["block_rows"] == CONFIG["subblocks"] * tilemm.RSUB
    assert TRAFFIC["fields"] == bm_helpers.load(
        "benchmark/traffic/stream_fields.json")["fields"]
    assert TRAFFIC["program"]["cache_device"] == 1
    from wormhole_tpu.models.fm import FMConfig
    for key in ("lr_alpha", "lr_beta", "l1", "l2", "l2_v", "init_scale"):
        assert CONFIG["hyper"][key] == getattr(FMConfig(), key)
    assert CONFIG["state_bytes_per_bucket"] == 4 * 2 * (1 + CONFIG["dim"])


def _planes(store) -> list:
    return [np.asarray(p) for p in store.slots.planes]


def test_a_block_without_a_list_takes_the_in_place_step_and_an_empty_list_changes_no_bit():  # noqa: E501
    """``in_place`` is decided from what the store sees, a list or none: a
    block whose list holds no pair leaves it on the host (``put_block``) and
    takes the in-place kernel, counted; its program holds none of the spill
    step's phases. The spill step on the same block with its empty list
    present writes the same table to the last bit."""
    from wormhole_tpu.models.fm import IN_PLACE
    from wormhole_tpu.ops import tilemm
    config, seed = _patched(4, 1, 128 * 1024), 6    # no tile passes the cap
    keys, labels = fields.make_block(TRAFFIC, seed, 0, config["block_rows"])
    from wormhole_tpu.data.crec import encode_tile_block
    info = _info(config)
    pw, ob, orow, n = encode_tile_block(keys, config["num_buckets"],
                                        info.spec, ROOM)
    assert n == 0
    block = {"pw": pw, "labels": labels, "ovf_b": ob, "ovf_r": orow}

    a = _store(config, seed)
    dev = a.put_block(block)
    assert "ovf_b" not in dev and "ovf_r" not in dev
    a.tile_train_step(dev, info)
    assert a.step_kernel[:2] == ("fused", IN_PLACE)
    assert a.timer.totals == {"fm_in_place_blocks": 1.0}
    text = a._tile_step(info, "train", False).lower(
        a.slots, dev, a._t_device(), a._tau_const(0.0),
        a._macc_buf()).as_text()
    for phase in ("fm_ovf_pull", "fm_ovf_scatter", "fm_table_update"):
        assert phase not in text

    b = _store(config, seed)
    b.tile_train_step(jax.device_put(block), info)   # the list stays
    assert b.step_kernel[0] == "fused" and b.step_kernel[1] != IN_PLACE
    assert b.timer.totals["fm_spill_blocks"] == 1.0
    assert "fm_in_place_blocks" not in b.timer.totals
    for pa, pb in zip(_planes(a), _planes(b)):
        np.testing.assert_array_equal(pa, pb)
    # the metric rows too, but for the progress number (index 3, the sum of
    # the squared change of w), which the in-place kernel sums tile by tile
    # and the update pass in one reduction: the same terms in another order
    ma, mb = a.fetch_metrics(), b.fetch_metrics()
    np.testing.assert_array_equal(np.delete(ma, 3), np.delete(mb, 3))
    np.testing.assert_allclose(ma[3], mb[3], rtol=1e-5)
    assert tilemm._interpret()      # no device number comes from here
