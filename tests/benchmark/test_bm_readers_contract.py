"""The rule that refused PR 46 (``benchmark_breaks_parent``), held for every
reader the benchmark names: a traced run is made with the PR's benchmark files
over the PARENT's program, so a reader meets readings whose Timer lacks the
counters a PR adds and whose trace lacks its scopes. It then returns ``None``
("nothing to read, left out") or a number, and never raises: a reader that
raises ends the run with no result line (``run.py``: the loop over
``metrics_of``). And the per-layer metrics this PR adds list the new cell and
no accepted one, so no accepted cell's line depends on them at all.
"""

import glob
import os

import pytest

import bm_helpers

from benchmark import run

BENCH = bm_helpers.load("BENCHMARK.json")
CELL = "criteo_fm_clicklog.replay_fields"
NEW = ("fm_overflow_ms_per_step.replay", "fm_update_ms_per_step.replay",
       "fm_update_hbm_roofline.replay", "fm_listed_pairs_per_block.replay")
METRICS = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(bm_helpers.REPO, "benchmark", "metrics", "*.json")))

# what the program's Timer held over a window BEFORE this PR: the standing
# keys of a replay cell, of a stream cell from text and of a mesh cell, and
# none of fm_spill_blocks, fm_in_place_blocks, fm_listed_pairs
TIMERS = {
    "replay": {"dispatch": 0.02, "wait": 0.001},
    "stream": {"dispatch": 0.9, "wait": 30.1, "put": 1.1, "read": 44.0,
               "feed_stall": 13.2, "read_stall": 0.2, "put_stall": 20.0,
               "encode": 28.0, "encode_stall": 22.1, "text_read": 6.1,
               "collate": 5.0, "online_native_blocks": 384.0,
               "online_overflow_pairs": 4.2e8, "online_overflow_slots": 5e8,
               "host_copy_bytes": 1e9},
    "mesh": {"mesh:dispatch": 0.5, "wait": 20.0, "put": 2.5, "stack": 3.0,
             "stack_stall": 30.0, "mesh_steps": 240.0, "ici_bytes": 2.5e11,
             "feed_stall": 30.0, "mesh_overflow_slots": 6e8,
             "mesh_widened_groups": 0.0},
}
# a reduced trace as trace_reduce.reduce_trace returns it, of a program whose
# ops carry none of this PR's scopes; the run's .xplane.pb is gone
TRACE = {"window_s": 51.6, "busy_s": 51.5, "step_s": 38.7, "kernel_s": 38.5,
         "steps": 516, "device_ops": [
             ["%custom-call.2 tpu_custom_call f32[98304]{0}", 38.5],
             ["%fusion f32[2,512]{1,0}", 0.13]],
         "idle_gaps": [["inside_a_pass", 0.05], ["between_passes", 0.05]]}


def _reading(cell: dict, timers: dict, trace) -> dict:
    config = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    blocks = 516
    return {"window": {"window_s": 51.6, "rows": blocks * 98304,
                       "steps": blocks, "blocks": blocks, "objv": 1.0,
                       "passes": [(1.2, 12 * 98304)] * 43,
                       "compiles": 0, "compile_s": 0.0, "timers": timers},
            "setup_s": 46.5, "config": bm_helpers.load(config["file"]),
            "traffic": bm_helpers.load(
                f"benchmark/traffic/{cell['traffic']}.json"),
            "work": {"blocks": 12, "files": 1, "rows_per_block": 98304,
                     "pairs_per_block": 3833856, "file_bytes": 369000000,
                     "overflow_pairs_per_block": [0, 0]},
            "memory_peak_bytes": 5660000000,
            "trace": trace, "least_s_per_step": 1.5e-4}


@pytest.mark.parametrize("metric", METRICS)
def test_a_reader_reads_a_number_or_nothing_from_a_program_without_this_prs_counters_and_scopes(metric):  # noqa: E501
    read = run.reader_of(bm_helpers.REPO, metric)
    for cell in BENCH["workloads"]:
        for timers in TIMERS.values():
            for trace in (None, TRACE):
                got = read(_reading(cell, dict(timers), trace))
                assert got is None or float(got) == float(got), (
                    metric, cell["name"])


@pytest.mark.parametrize("metric", NEW)
def test_a_new_reader_reads_nothing_where_its_scope_or_counter_is_absent(
        metric):
    read = run.reader_of(bm_helpers.REPO, metric)
    for cell in BENCH["workloads"]:
        for timers in TIMERS.values():
            for trace in (None, TRACE):
                assert read(_reading(cell, dict(timers), trace)) is None
    # not even a window: still nothing, and no raise
    assert read({"trace": None}) is None


def test_the_counter_reader_reads_its_counter():
    from benchmark.readers import fm_listed_pairs_per_block
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    r = _reading(cell, {"fm_listed_pairs": 516 * 1.5e6,
                        "fm_spill_blocks": 516.0}, None)
    assert fm_listed_pairs_per_block.read(r) == pytest.approx(1.5e6)


def test_the_update_pass_moves_46_planes():
    from benchmark.configs.criteo_fm_clicklog import roofline
    config = bm_helpers.load("benchmark/configs/criteo_fm_clicklog/config.json")
    assert roofline.update_pass_bytes(config) == 46 * 4 * 2 ** 26


@pytest.mark.parametrize("metric", NEW)
def test_a_new_metric_lists_the_new_cell_and_no_accepted_one(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_ex_per_s" and entry["layer"] == "step"
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    assert spec["regime"] == "replay" and spec["what"]
    # the entries this PR adds are the last of their list
    assert [m["name"] for m in BENCH["per_layer"][-len(NEW):]] == list(NEW)


def test_the_new_cell_is_the_last_of_every_list_it_joins():
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "criteo_fm_clicklog"
    joined = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
              if CELL in m.get("workloads", ())]
    assert {m["name"] for m in joined} == {
        "train_ex_per_s", "xla_ms_per_step.replay",
        "nonkernel_ms_per_step.replay", "kernel_ms_per_step.replay",
        "tile_kernel_roofline.replay", "device_idle_share.replay",
        "hbm_peak_gb.replay", *NEW}
    for m in joined:
        assert m["workloads"][-1] == CELL
