"""The wide&deep cell's code path end to end at a tiny size on the CPU,
through the unchanged harness (a file of its own: the interpreter walks the
34-channel kernels slowly, and the driver spreads test files over its
workers). Widths stay (39 fields a row, 32-value embeddings, the
1024-512-256 tower); depth goes (two 16,384-row blocks a pass, 65,536
buckets)."""

import bm_helpers


def test_wide_deep_cell_end_to_end(tmp_path):
    cell = "criteo_wide_deep.replay_uniform"
    config_patch, traffic_patch = bm_helpers.tiny_patches(*cell.split("."))
    traffic_patch["blocks"] = 3          # a pass is the three checked blocks
    r, result = bm_helpers.run_tiny(cell, tmp_path, seconds=0.1,
                                    patches=(config_patch, traffic_patch))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_ex_per_s", "setup_s"}
    # a file from the normal writer carries an overflow list, so the step
    # is the split pair with XLA's tower between, as on the chip
    assert '"step_kernel": "split"' in r.stdout
    assert "wide&deep spill" in r.stdout
    # every leaf is compared: the table's two and the tower's four
    for leaf in ("'w':", "'v':", "'t0':", "'t3':"):
        assert leaf in r.stdout
    assert "check state_rel_rms" in r.stdout
    # the program's own counters of the tower's work reach the window
    assert '"tower_flops"' in r.stdout and '"dense_param_bytes"' in r.stdout
