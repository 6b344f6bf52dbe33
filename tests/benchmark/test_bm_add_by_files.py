"""A later PR adds a configuration, a traffic mix, a per-layer metric and a
cell by adding files and one entry each, and edits no file that is there.

This test does so in a throw-away copy: the copy's harness files are
byte-for-byte the repo's, the new cell runs through them (tiny, on the CPU),
and its new metric appears in the result."""

import filecmp
import json
import os
import shutil

import bm_helpers

NEW_READER = '''"""Steps a second of wall clock: a throw-away per-layer metric."""


def read(r):
    return r["window"]["steps"] / r["window"]["window_s"]
'''


def _add(root):
    """Only new files, and new entries in BENCHMARK.json."""
    b = os.path.join(root, "benchmark")
    # a configuration: its directory, with sizes, reference, roofline, hooks
    src, dst = (os.path.join(b, "configs", n)
                for n in ("criteo_ftrl", "toy_ftrl"))
    shutil.copytree(src, dst)
    cfg = bm_helpers.load("benchmark/configs/criteo_ftrl/config.json")
    cfg.update(name="toy_ftrl", num_buckets=bm_helpers.TINY_NB, subblocks=2,
               block_rows=16384)
    cfg["check"]["sample"] = 4096
    cfg["program"]["conf"] = [
        c if not c.startswith("num_buckets")
        else f"num_buckets = {bm_helpers.TINY_NB}"
        for c in cfg["program"]["conf"]]
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    # a traffic mix: one data file, the general generator reads it
    mix = bm_helpers.load("benchmark/traffic/stream_fields.json")
    mix.update(name="stream_toy", blocks=4, ovf_cap=262144, fields=[
        {"count": 30, "dist": "uniform", "cardinality": 500},
        {"count": 9, "dist": "zipf", "cardinality": 100000,
         "exponent": 1.2}])
    with open(os.path.join(b, "traffic", "stream_toy.json"), "w") as f:
        json.dump(mix, f)
    # a per-layer metric: its file and its reader
    with open(os.path.join(b, "readers", "steps_per_s.py"), "w") as f:
        f.write(NEW_READER)
    with open(os.path.join(b, "metrics", "steps_per_s.toy.json"), "w") as f:
        json.dump({"name": "steps_per_s.toy", "kind": "per_layer",
                   "unit": "1/s", "better": "higher",
                   "source": "host_clock", "layer": "pass loop",
                   "moves": "stream_ex_per_s", "regime": "stream",
                   "reader": "benchmark.readers.steps_per_s:read"}, f)
    # the entries
    bench = bm_helpers.load("BENCHMARK.json")
    cell = "toy_ftrl.stream_toy"
    bench["configs"].append({
        "name": "toy_ftrl", "source": "a test",
        "file": "benchmark/configs/toy_ftrl/config.json",
        "reduced": ["rows", "num_buckets"], "why": "a test"})
    bench["workloads"].append({"name": cell, "config": "toy_ftrl",
                               "traffic": "stream_toy", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "stream_ex_per_s":
            m["workloads"].append(cell)
    for m in bench["per_layer"]:
        if m["name"].endswith(".stream"):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "steps_per_s.toy", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "pass loop",
        "moves": "stream_ex_per_s", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cell


def _unchanged(root):
    """Every file the repo's benchmark has is in the copy, unchanged."""
    cmp = filecmp.dircmp(os.path.join(bm_helpers.REPO, "benchmark"),
                         os.path.join(root, "benchmark"),
                         ignore=[".cache", "__pycache__"])
    stack, changed, missing = [cmp], [], []
    while stack:
        c = stack.pop()
        changed += c.diff_files
        missing += c.left_only
        stack += list(c.subdirs.values())
    return changed, missing


def test_a_cell_added_by_files_runs_without_touching_the_harness(tmp_path):
    root = bm_helpers.copy_benchmark(str(tmp_path))
    cell = _add(root)
    assert _unchanged(root) == ([], [])
    work = os.path.join(str(tmp_path), "work")
    r, result = bm_helpers.run_tiny(cell, work, trace=True, root=root,
                                    patches=({}, {}))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True
    assert result["metrics"]["steps_per_s.toy"]["unit"] == "1/s"
    assert result["metrics"]["steps_per_s.toy"]["value"] > 0
    assert "loop_wait_share.stream" in result["metrics"]
    assert "config toy_ftrl (num_buckets=2**16), traffic stream_toy" \
        in r.stdout
    r, result = bm_helpers.run_tiny(cell, work, trace=False, root=root,
                                    patches=({}, {}))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert set(result["metrics"]) == {"stream_ex_per_s", "setup_s"}
