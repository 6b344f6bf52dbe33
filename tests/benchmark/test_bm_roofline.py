"""The roofline functions against hand counts, and the table of peaks."""

import pytest

import bm_helpers
from benchmark import peaks
from benchmark.configs.criteo_fm import roofline as fm_roof
from benchmark.configs.criteo_ftrl import roofline as ftrl_roof

FTRL = bm_helpers.load("benchmark/configs/criteo_ftrl/config.json")
FM = bm_helpers.load("benchmark/configs/criteo_fm/config.json")


def test_ftrl_block_work_by_hand():
    # 98,304 rows x 39 pairs, 773,203 distinct buckets (a chip run's count)
    pairs, rows, distinct = 3833856, 98304, 773203
    work = ftrl_roof.block_work(FTRL, pairs, rows, distinct)
    assert work["bytes"] == 4 * 3833856 + 98304 + 2 * 12 * 773203
    assert work["bytes"] == 33990600
    assert work["flops"] == 4 * 3833856


def test_fm_block_work_by_hand():
    pairs, rows, distinct = 3833856, 98304, 765428
    work = fm_roof.block_work(FM, pairs, rows, distinct)
    # 2 x (1 + 8) f32 a bucket = 72 B, read once and written once
    assert work["bytes"] == 4 * 3833856 + 98304 + 2 * 72 * 765428
    assert work["bytes"] == 125655360
    # 10 channels (w, 8 factors, sum v^2), 2 FLOPs forward and 2 backward
    assert work["flops"] == 4 * 3833856 * 10 == 153354240


def test_state_bytes_match_the_state_lists():
    assert FTRL["state_bytes_per_bucket"] == 4 * len(FTRL["state_per_bucket"])
    assert FM["state_bytes_per_bucket"] == 4 * 2 * (1 + FM["dim"])


def test_least_seconds_takes_the_larger_bound():
    pk = peaks.peaks_of("TPU v5 lite")
    secs, bound = peaks.least_seconds({"bytes": 33990600, "flops": 15335424},
                                      pk)
    assert bound == "bytes"
    assert secs == pytest.approx(33990600 / 819e9)
    secs, bound = peaks.least_seconds({"bytes": 8, "flops": 197e12}, pk)
    assert bound == "flops" and secs == pytest.approx(1.0)


def test_v5e_peaks_and_their_source():
    pk = peaks.peaks_of("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["flops_per_s"] == 197e12
    assert "TPU v5e" in pk["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="peaks.json"):
        peaks.peaks_of("cpu")


def test_roofline_share_cannot_pass_100_for_a_perfect_kernel():
    from benchmark.readers import tile_kernel_roofline as reader
    least = 41.5e-6
    reading = {"least_s_per_step": least,
               "trace": {"steps": 10, "kernel_s": 10 * least}}
    assert reader.read(reading) == pytest.approx(100.0)
    reading["trace"]["kernel_s"] = 10 * 44.3e-3
    assert reader.read(reading) == pytest.approx(0.0937, rel=1e-2)
    assert reader.read({"least_s_per_step": least, "trace": None}) is None
