"""The click-log wide&deep cell's code path end to end at a small size on the
CPU, through the unchanged harness (a file of its own, as
``test_bm_cell_fm_clicklog_cpu.py``: the interpreter walks the multi-channel
kernels slowly, and the driver spreads test files over its workers). At
2**18 buckets the program's cap is 20,480 and a 16,384-row block of the mix
lists 16,000 to 26,000 of its 638,976 pairs, so every block brings a list, as
at the cell's size; the tower keeps its ReLU layers at widths the interpreter
walks (dim 8, hidden 64-32)."""

import json

import pytest

import bm_helpers

CELL = "criteo_wide_deep_clicklog.replay_fields"
NB = 1 << 18

# the list's duals scattered into 9 of the 10 channels the tiny model has
# (33 of 34 at the cell's widths): the last embedding value's are left out
NINE_OF_TEN_CHANNELS = """
from wormhole_tpu.ops import tilemm as _tilemm
_real = _tilemm.spill_push_scatter_lanes
def _short(g, dual_rows, ovf_b, ovf_r, spec):
    k = dual_rows.shape[1] - 2
    return _real(g, dual_rows.at[:, k].set(0.0), ovf_b, ovf_r, spec)
_tilemm.spill_push_scatter_lanes = _short
"""

# every block's list stays behind where it crosses to the device
LISTS_LOST = """
from wormhole_tpu.ops import overflow as _overflow
_real = _overflow.crossing
def _crossing(block, drop_empty):
    return {k: v for k, v in _real(block, drop_empty).items()
            if k not in _overflow.COO}
_overflow.crossing = _crossing
"""


def _patches():
    from wormhole_tpu.data.crec import default_cap
    config, traffic = bm_helpers.tiny_patches(*CELL.split("."))
    config.update(
        num_buckets=NB, dim=8, hidden=[64, 32],
        tile={"cap": default_cap(39, NB)},
        program={"conf": [f"num_buckets = {NB}" if c.startswith("num_buckets")
                          else c for c in config["program"]["conf"]],
                 "model_conf": ["dim=8", "hidden=64,32", "lr_alpha=0.001",
                                "lr_alpha_dense=0.001"]})
    traffic.update(ovf_cap=262144, blocks=3)
    return config, traffic


def _counters(stdout: str) -> dict:
    line = next(x for x in stdout.splitlines()
                if x.startswith("[bench] program counters: "))
    return json.loads(line.split(": ", 1)[1])


@pytest.mark.parametrize("trace", [False, True])
def test_wide_deep_clicklog_cell_end_to_end(tmp_path, trace):
    r, result = bm_helpers.run_tiny(CELL, tmp_path, patches=_patches(),
                                    seconds=0.2, trace=trace)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    # a block with a list: the split pair with XLA's tower between, as on
    # the chip
    assert '"step_kernel": "split"' in r.stdout
    assert "wide&deep spill" in r.stdout
    # every block took the spill step with its list, and the table was never
    # stacked (the configuration states both counters stay 0)
    counted = _counters(r.stdout)
    assert counted["wd_listless_blocks"] == 0 == counted["table_cross"]
    assert counted["wd_spill_blocks"] >= 3 + 3 + result["attempted"]
    assert counted["wd_listed_pairs"] > 16000 * counted["wd_spill_blocks"]
    # every leaf is compared, against a reference handed the lists
    for leaf in ("'w':", "'v':", "'t0':", "'t2':"):
        assert leaf in r.stdout
    assert "check state_rel_rms" in r.stdout
    assert "pairs taken unrounded a step (the file's overflow lists): [" \
        in r.stdout
    # the three counts and the tower's reach the window's timers
    for name in ("wd_spill_blocks", "wd_listed_pairs", "tower_flops"):
        assert f'"{name}"' in r.stdout.split("timers in the window")[1]
    if not trace:
        assert set(result["metrics"]) == {"train_ex_per_s", "setup_s"}
        return
    # a CPU traced run reports the listed metrics that are no device's
    # (the process's memory peak reads 0 here: left out) and no other
    assert "device metrics: not measured" in r.stdout
    assert set(result["metrics"]) == {"wd_listed_pairs_per_block.replay"}
    pairs = result["metrics"]["wd_listed_pairs_per_block.replay"]
    assert pairs["unit"] == "pairs" and 16000 < pairs["value"] < 26000


def test_a_list_path_that_leaves_a_channel_out_is_not_correct(tmp_path):
    r, result = bm_helpers.run_tiny(CELL, tmp_path, patches=_patches(),
                                    seconds=0.2,
                                    prelude=NINE_OF_TEN_CHANNELS)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is False
    assert "NOT OK" in r.stdout


def test_blocks_that_lost_their_lists_fail_the_run(tmp_path):
    """``program.zero_counters``: a block of this cell that steps without
    its list is counted, and a run that counts one prints no result."""
    r, result = bm_helpers.run_tiny(CELL, tmp_path, patches=_patches(),
                                    seconds=0.2, prelude=LISTS_LOST)
    assert r.returncode != 0 and result is None
    assert "wd_listless_blocks" in r.stderr
    assert "the run took another path" in r.stderr
    counted = _counters(r.stdout)
    assert counted["wd_listless_blocks"] > 0 == counted["wd_spill_blocks"]
