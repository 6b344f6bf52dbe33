"""The click-log FM cell's code path end to end at a small size on the CPU (a
file of its own, as ``test_bm_cell_fm_cpu.py``: the interpreter walks the
10-channel kernel slowly, and the driver spreads test files over its
workers). At 2**18 buckets the program's cap is 20,480 and a 16,384-row block
of the mix lists 16,000 to 26,000 of its 638,976 pairs, so every block brings
a list, as at the cell's size."""

import json

import pytest

import bm_helpers

CELL = "criteo_fm_clicklog.replay_fields"
NB = 1 << 18


def _patches():
    from wormhole_tpu.data.crec import default_cap
    config, traffic = bm_helpers.tiny_patches(*CELL.split("."))
    config.update(
        num_buckets=NB, tile={"cap": default_cap(39, NB)},
        program={"conf": [f"num_buckets = {NB}" if c.startswith("num_buckets")
                          else c for c in config["program"]["conf"]]})
    traffic.update(ovf_cap=262144)
    return config, traffic


def _counters(stdout: str) -> dict:
    line = next(x for x in stdout.splitlines()
                if x.startswith("[bench] program counters: "))
    return json.loads(line.split(": ", 1)[1])


@pytest.mark.parametrize("trace", [False, True])
def test_fm_clicklog_cell_end_to_end(tmp_path, trace):
    r, result = bm_helpers.run_tiny(CELL, tmp_path, patches=_patches(),
                                    seconds=0.5, trace=trace)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert '"step_kernel": "fused"' in r.stdout
    # every block took the spill step, none the in-place one, and the table
    # was never stacked (the configuration states both stay 0)
    counted = _counters(r.stdout)
    assert counted["fm_in_place_blocks"] == 0 == counted["table_cross"]
    assert counted["fm_spill_blocks"] >= 3 + 4 + result["attempted"]
    assert counted["fm_listed_pairs"] > 16000 * counted["fm_spill_blocks"]
    # both leaves are compared, against a reference handed the lists
    assert "'v':" in r.stdout and "check state_rel_rms" in r.stdout
    assert "pairs taken unrounded a step (the file's overflow lists): [" \
        in r.stdout
    if not trace:
        assert set(result["metrics"]) == {"train_ex_per_s", "setup_s"}
        return
    # a CPU traced run reports the listed metrics that are no device's
    # (the process's memory peak reads 0 here: left out) and no other
    assert "device metrics: not measured" in r.stdout
    assert set(result["metrics"]) == {"fm_listed_pairs_per_block.replay"}
    pairs = result["metrics"]["fm_listed_pairs_per_block.replay"]
    assert pairs["unit"] == "pairs" and 16000 < pairs["value"] < 26000
