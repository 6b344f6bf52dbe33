"""The FM cell's code path end to end at a tiny size on the CPU (a file of
its own: the interpreter walks the 10-channel kernel slowly, and the driver
spreads test files over its workers)."""

import bm_helpers

BENCH = bm_helpers.load("BENCHMARK.json")


def test_fm_cell_end_to_end(tmp_path):
    cell = "criteo_fm.replay_uniform"
    r, result = bm_helpers.run_tiny(cell, tmp_path, seconds=0.5)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_ex_per_s", "setup_s"}
    assert result["metrics"]["train_ex_per_s"]["unit"] == "ex/s"
    assert '"step_kernel": "fused"' in r.stdout
    # both leaves are compared, and the seeded factors reached the table
    assert "'v':" in r.stdout and "check state_rel_rms" in r.stdout
