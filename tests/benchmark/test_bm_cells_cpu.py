"""Each cell's code path end to end at a tiny size on the CPU: the last
line's keys, `correct`, and that a device-metric name never carries a CPU
number (it is left out and the run says "not measured")."""

import json
import os
import subprocess
import sys

import pytest

import bm_helpers

BENCH = bm_helpers.load("BENCHMARK.json")
DEVICE_METRICS = {m["name"] for m in BENCH["per_layer"]
                  if m["source"] == "device_trace"} | {
    m["name"] for m in BENCH["per_layer"] if m["name"].startswith("hbm_")}
FTRL_CELLS = [w["name"] for w in BENCH["workloads"]
              if w["config"] == "criteo_ftrl" and w["chips"] == 1]


def _check_line(result, cell, kind):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 4
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    wanted = {m["name"]: m for m in BENCH[kind]
              if "workloads" not in m or cell in m["workloads"]}
    assert set(result["metrics"]) <= set(wanted)
    for name, m in result["metrics"].items():
        assert m["unit"] == wanted[name]["unit"]
        assert isinstance(m["value"], float) and m["value"] > 0.0
    return wanted


@pytest.mark.parametrize("cell", FTRL_CELLS)
def test_ftrl_cell_end_to_end(cell, tmp_path):
    r, result = bm_helpers.run_tiny(cell, tmp_path)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    wanted = _check_line(result, cell, "end_to_end")
    assert set(result["metrics"]) == set(wanted)          # both are host's
    out = r.stdout
    for needle in ("device: {", "step kernel: {", '"pallas_interpret": true',
                   "compile cache:", "work per block:", "set-up parts (s):",
                   "window rate", "pass-median rate",
                   "check loss_rel", "check grad_norm_rel",
                   "check change_norm_rel", "check state_rel_rms"):
        assert needle in out, needle
    last = out.strip().splitlines()[-1]
    for needle in ("step_kernel", "hits", "pairs_per_block"):
        assert needle not in last
    assert not os.listdir(tmp_path) or not any(
        n.endswith(".crec2") for n in os.listdir(tmp_path))


@pytest.mark.parametrize("cell", FTRL_CELLS)
def test_traced_run_on_the_cpu_reports_no_device_metric(cell, tmp_path):
    r, result = bm_helpers.run_tiny(cell, tmp_path, trace=True)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    _check_line(result, cell, "per_layer")
    assert not set(result["metrics"]) & DEVICE_METRICS
    assert "device metrics: not measured" in r.stdout
    assert "breakdown" not in result
    assert "busy_s" not in result["device"]
    if "stream" in cell:
        assert set(result["metrics"]) == {
            "loop_wait_share.stream", "feed_stall_share.stream",
            "feed_put_ms_per_block.stream"}


def _printed(stdout, start):
    """The JSON object that ends the one line starting with ``start``."""
    line, = [ln for ln in stdout.splitlines() if ln.startswith(start)]
    return json.loads(line[line.index("{", len(start)):])


@pytest.mark.parametrize("seed", [21, 22, 2**31 + 23])
def test_the_stream_cells_mismatch_was_the_references(seed, tmp_path):
    """The tiny stream cell (a sixtieth of its pairs on the overflow list)
    against the reference that takes the file's overflow pairs unrounded,
    as the configuration states for that path: the interpreted program
    agrees as closely as in a replay cell. Against the reference of PRs
    25-27, which rounds every pair, the same program reads a decade or more
    worse. The controls, overflow pairs exact in them too, fail a limit."""
    cell = "criteo_ftrl.stream_fields"
    r, result = bm_helpers.run_tiny(cell, tmp_path, seed=seed, control=True)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True
    out = r.stdout
    now = {ln.split()[2]: float(ln.split()[4]) for ln in out.splitlines()
           if ln.startswith("[bench] check ") and " = " in ln}
    config = bm_helpers.load("benchmark/configs/criteo_ftrl/config.json")
    from benchmark import check
    limits = check.limits_of(config, "stream_fields")
    assert limits["state_rel_rms"] == config["check"]["limits"][
        "state_rel_rms"]           # the configuration's own, in the stream too
    assert set(now) == set(limits)
    assert now["state_rel_rms"] < 1e-5
    assert "pairs taken unrounded a step (the file's overflow lists): [" \
        in out
    was = _printed(out, "[bench] program against every pair rounded")
    assert was["state_rel_rms"] > 10 * now["state_rel_rms"]
    assert was["state_rel_rms"] > 1e-5
    for control in config["check"]["controls"]:
        nums = _printed(out, f"[bench] control {control} {{")
        assert any(nums[k] > limits[k] for k in limits), (control, nums)
        assert nums["state_rel_rms"] > 2 * limits["state_rel_rms"]


def test_without_a_tpu_the_command_fails_and_prints_no_result(tmp_path):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "criteo_ftrl.replay_uniform", "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=bm_helpers.REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
             "BENCH_RUN": "7"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    lines = r.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{")
    leftovers = os.path.join(bm_helpers.REPO, "benchmark", ".cache",
                             "criteo_ftrl.replay_uniform")
    assert not os.path.isdir(leftovers) or not any(
        n.endswith(".crec2") for n in os.listdir(leftovers))


def test_an_unknown_workload_fails(tmp_path):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no.such_cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=bm_helpers.REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert r.returncode != 0 and "no workload" in r.stderr


def test_alone_with_its_paths_the_benchmark_fails(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no system to test: non-zero, no result."""
    import shutil
    shutil.copy(os.path.join(bm_helpers.REPO, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(bm_helpers.REPO, path),
                        os.path.join(tmp_path, path),
                        ignore=shutil.ignore_patterns(".cache",
                                                      "__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "criteo_ftrl.replay_uniform", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        timeout=600, env={k: v for k, v in os.environ.items()
                          if k != "PYTHONPATH"})
    assert r.returncode != 0
    assert "wormhole_tpu" in r.stderr
    lines = r.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{")
