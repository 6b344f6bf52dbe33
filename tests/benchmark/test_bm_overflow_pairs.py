"""Which pairs of a checked block the COO overflow path takes is a fact of
the crec2 file: ``TrainSystem.end_data`` keeps the file's own lists for the
reference, and they and the tile pairs together are the block's pairs."""

import numpy as np
import pytest

import bm_helpers
from benchmark import check, system


def _system(tmp_path, traffic_name, seed, group=1):
    config_patch, traffic_patch = bm_helpers.tiny_patches("criteo_ftrl",
                                                          traffic_name)
    from benchmark import run
    config = run.merge(bm_helpers.load(
        "benchmark/configs/criteo_ftrl/config.json"), config_patch)
    traffic = run.merge(bm_helpers.load(
        f"benchmark/traffic/{traffic_name}.json"), traffic_patch)
    if group > 1:
        traffic["program"] = dict(traffic["program"],
                                  mesh_shape=f"data:{group}")
        traffic["blocks"] = 3 * group
    return system.TrainSystem(config, traffic, None, str(tmp_path), seed)


def _tile_pairs(views, info):
    """The (bucket, row) pairs that a block's packed tile words hold."""
    from wormhole_tpu.ops.tilemm import RSUB, unpack_fields
    spec = info.spec
    tiles, slices, n = spec.pairs_shape
    b, r, pad = unpack_fields(views["pw"])
    tile = np.arange(tiles)[:, None, None]
    sub = (np.arange(slices)[None, :, None] * spec.group
           + np.arange(n)[None, None, :] // spec.cap)
    keep = ~pad
    return ((tile * 16384 + b)[keep].astype(np.int64),
            (sub * RSUB + r)[keep].astype(np.int64))


def _sorted_keys(buckets, rows, nb):
    return np.sort(np.asarray(rows, np.int64) * nb
                   + np.asarray(buckets, np.int64))


@pytest.mark.parametrize("seed", [11, 2**31 + 12])
def test_the_kept_lists_are_the_files_own(tmp_path, seed):
    from wormhole_tpu.data.crec import iter_packed2, read_header2
    sut = _system(tmp_path, "stream_fields", seed)
    try:
        work = sut.end_data(sut.begin_data())
        nb = int(sut.config["num_buckets"])
        info = read_header2(sut.files[0])
        assert len(sut.check_blocks) == len(sut.check_overflow) \
            == sut.check_steps
        lo, hi = work["overflow_pairs_per_block"]
        for (views, rows), (keys, labels), (ovf_b, ovf_r) in zip(
                iter_packed2(sut.files[0]), sut.check_blocks,
                sut.check_overflow):
            valid = views["ovf_b"] != np.uint32(0xFFFFFFFF)
            assert np.array_equal(ovf_b, views["ovf_b"][valid])
            assert np.array_equal(ovf_r, views["ovf_r"][valid])
            assert 0 < lo <= len(ovf_b) <= hi
            # the blocks kept for the reference are the file's blocks
            assert rows == sut.block_rows == len(labels)
            assert np.array_equal(views["labels"], labels)
            # tile pairs and overflow pairs together are the block's pairs
            (buckets, prow), = check.block_pairs([(keys, labels)], nb)[0]
            tb, tr = _tile_pairs(views, info)
            assert len(tb) + len(ovf_b) == work["pairs_per_block"] \
                == len(buckets)
            assert np.array_equal(
                _sorted_keys(np.concatenate([tb, ovf_b]),
                             np.concatenate([tr, ovf_r]), nb),
                _sorted_keys(buckets, prow, nb))
            # and the reference finds each listed pair among its own, the
            # rest being the tile kernels'
            mask = check.exact_mask(buckets, prow, (ovf_b, ovf_r), nb)
            assert np.array_equal(_sorted_keys(buckets[~mask], prow[~mask],
                                               nb), _sorted_keys(tb, tr, nb))
    finally:
        sut.close()


def test_a_replay_cells_lists_are_empty(tmp_path):
    sut = _system(tmp_path, "replay_uniform", 13)
    try:
        work = sut.end_data(sut.begin_data())
        assert work["overflow_pairs_per_block"] == [0, 0]
        assert len(sut.check_overflow) == sut.check_steps
        assert all(len(b) == 0 and len(r) == 0
                   for b, r in sut.check_overflow)
        stated = check.stated_precision(sut.config, check.merge_exact_pairs(
            sut.check_overflow, sut.check_blocks, sut.group))
        assert [len(b) for b, _r in stated["exact_pairs"]] == [0, 0, 0]
    finally:
        sut.close()


def test_a_groups_lists_follow_its_blocks(tmp_path):
    """With two blocks a step the checked blocks are the first six, and
    their lists merge into three steps' worth that the reference accepts
    (every listed pair is found at its shifted row)."""
    sut = _system(tmp_path, "stream_fields", 14, group=2)
    try:
        sut.end_data(sut.begin_data())
        assert sut.group == 2 and len(sut.check_overflow) == 6
        steps = check.merge_groups(sut.check_blocks, 2)
        exact = check.merge_exact_pairs(sut.check_overflow,
                                        sut.check_blocks, 2)
        nb = int(sut.config["num_buckets"])
        pairs, _ids = check.block_pairs(steps, nb)
        masks = check.exact_masks(pairs, exact, nb)
        for i, mask in enumerate(masks):
            assert mask.sum() == len(sut.check_overflow[2 * i][0]) \
                + len(sut.check_overflow[2 * i + 1][0])
            assert exact[i][1].max() >= sut.block_rows
    finally:
        sut.close()
