"""A four-chip cell is a data file and an entry away: the harness drives
groups of blocks (one update from the data axis's blocks read at the same
weights) and the reference follows them. ``traffic/mesh4_stream_uniform.json``
is in the tree; its cell is not in ``BENCHMARK.json`` yet (PERF.md, Open
questions). Here it runs tiny on four host devices, in a throw-away copy."""

import json
import os

import numpy as np

import bm_helpers
from benchmark import check

CELL = "criteo_ftrl.mesh4_stream_uniform"


def test_groups_merge_into_one_update():
    blocks = [(np.full((2, 3), i, np.uint32), np.full(2, i % 2, np.uint8))
              for i in range(4)]
    assert check.merge_groups(blocks, 1) is blocks
    merged = check.merge_groups(blocks, 2)
    assert len(merged) == 2
    assert merged[1][0].shape == (4, 3) and list(merged[1][1]) == [0, 0, 1, 1]
    assert (merged[1][0][:2] == 2).all() and (merged[1][0][2:] == 3).all()


def test_a_groups_overflow_rows_shift_with_its_blocks():
    """A group's later blocks sit below the earlier ones in the merged
    step, so their overflow pairs' rows move down by the rows before."""
    blocks = [(np.zeros((5, 3), np.uint32), np.zeros(5, np.uint8))
              for _ in range(4)]
    overflow = [(np.array([10 + i, 20 + i], np.uint32),
                 np.array([0, 4], np.uint32)) for i in range(4)]
    same = check.merge_exact_pairs(overflow, blocks, 1)
    assert [(list(b), list(r)) for b, r in same] == [
        ([10 + i, 20 + i], [0, 4]) for i in range(4)]
    merged = check.merge_exact_pairs(overflow, blocks, 2)
    assert len(merged) == len(check.merge_groups(blocks, 2)) == 2
    assert list(merged[1][0]) == [12, 22, 13, 23]
    assert list(merged[1][1]) == [0, 4, 5, 9]
    assert merged[1][1].dtype == np.int64


def test_the_mesh_cell_runs_tiny_on_four_host_devices(tmp_path):
    root = bm_helpers.copy_benchmark(str(tmp_path))
    bench = bm_helpers.load("BENCHMARK.json")
    bench["workloads"].append({
        "name": CELL, "config": "criteo_ftrl",
        "traffic": "mesh4_stream_uniform", "chips": 4, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "stream_ex_per_s" or m["name"].endswith(".stream"):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    config_patch, traffic_patch = bm_helpers.tiny_patches(
        "criteo_ftrl", "mesh4_stream_uniform")
    traffic_patch["blocks"] = 8
    r, result = bm_helpers.run_tiny(
        CELL, os.path.join(str(tmp_path), "work"), root=root,
        patches=(config_patch, traffic_patch), devices=4)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    # a step is a group of two blocks: four steps a pass of eight blocks
    assert result["attempted"] % 4 == 0 and result["attempted"] >= 4
    assert '"step_kernel": "split"' in r.stdout
    assert set(result["metrics"]) == {"stream_ex_per_s", "setup_s"}
