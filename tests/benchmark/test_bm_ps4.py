"""``criteo_ftrl_ps4`` and its four-chip cell, tiny, on four host devices.

The cell through the unchanged harness by files alone; the share test (the
two server shards together are the one-device table, and the reference's);
controls that break the mesh underneath and must come out not ``correct``;
the catalog of what the configuration and its metrics have to state."""

import types

import numpy as np
import pytest

import bm_helpers
from benchmark import check, run

CONFIG = "criteo_ftrl_ps4"
CELL = "criteo_ftrl_ps4.mesh4_stream_uniform"
BENCH = bm_helpers.load("BENCHMARK.json")
NEW_METRICS = {m["name"]: m for m in BENCH["per_layer"]
               if m["name"].endswith(".mesh")}
PROGRAM_SIDE = {"ici_gb_per_step.mesh", "group_put_ms_per_step.mesh"}

# a psum that returns its own part: what a lost push or pull looks like
LOSSY_PSUM = """
import jax
_psum = jax.lax.psum
def _lossy(x, axis_name, **kw):
    if axis_name == {axis!r} and getattr(x, "ndim", 0) == 1 \\
            and x.shape[0] > 4096:
        return x
    return _psum(x, axis_name, **kw)
jax.lax.psum = _lossy
"""


def _patches():
    config_patch, traffic_patch = bm_helpers.tiny_patches(
        CONFIG, "mesh4_stream_uniform")
    traffic_patch["blocks"] = 8
    return config_patch, traffic_patch


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "plain"])
def test_the_cell_runs_by_files_alone(trace, tmp_path):
    r, result = bm_helpers.run_tiny(CELL, tmp_path, trace=trace,
                                    patches=_patches(), devices=4)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    # a step is a group of two blocks: four steps a pass of eight blocks
    assert result["attempted"] % 4 == 0 and result["attempted"] >= 4
    assert '"step_kernel": "split"' in r.stdout
    assert "num_buckets=2**16" in r.stdout
    got = set(result["metrics"])
    if not trace:
        assert got == {"stream_ex_per_s", "setup_s"}
        return
    # the program-side metrics need no device; the trace's are left out
    assert got == PROGRAM_SIDE | {
        "loop_wait_share.stream", "feed_stall_share.stream",
        "feed_put_ms_per_block.stream"}
    assert "device metrics: not measured" in r.stdout
    # the ring model of the tiny mesh step, by hand: the margins' psum over
    # MODEL (16,384 floats), the gradient's over DATA (a shard's 32,768),
    # the metric row's over DATA (1,027) and one scalar over MODEL;
    # 2(k-1)/k = 1 on an axis of two
    booked = 4 * (16384 + 32768 + 1027 + 1)
    assert result["metrics"]["ici_gb_per_step.mesh"]["value"] \
        == pytest.approx(booked / 1e9, rel=1e-9)
    ms = result["metrics"]["group_put_ms_per_step.mesh"]["value"]
    assert ms == pytest.approx(
        2 * result["metrics"]["feed_put_ms_per_block.stream"]["value"])


@pytest.mark.parametrize("axis,lost", [
    ("data", "one worker's gradient"), ("model", "one shard's margin")])
def test_a_lost_psum_is_not_correct(axis, lost, tmp_path):
    r, result = bm_helpers.run_tiny(
        CELL, tmp_path, patches=_patches(), devices=4,
        prelude=LOSSY_PSUM.format(axis=axis))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is False, lost
    assert "NOT OK" in r.stdout


def test_the_shards_add_up_to_the_one_device_table():
    """Guide section 4's share test in this system's terms. After K grouped
    steps the MODEL shards, put end to end, are the table of the one-device
    store stepped on the same rows (each group as one block of twice the
    subblocks), the DATA pair's copies are equal bit for bit, and both
    tables are the reference's inside the configuration's limits."""
    import jax
    from wormhole_tpu.data.crec import (CRec2Info, default_cap,
                                        encode_tile_block)
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops import tilemm
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from benchmark.generators import fields

    config = run.merge(
        bm_helpers.load(f"benchmark/configs/{CONFIG}/config.json"),
        {"num_buckets": bm_helpers.TINY_NB})
    traffic = bm_helpers.load("benchmark/traffic/mesh4_stream_uniform.json")
    hooks = run.config_module(CONFIG, "system")
    nb, nnz, rows, steps, seed = bm_helpers.TINY_NB, 39, tilemm.RSUB, 3, 11
    h = config["hyper"]

    def store_on(runtime):
        return ShardedStore(
            StoreConfig(num_buckets=nb, tile_step_kernel="split"),
            FTRLHandle(penalty=L1L2(h["lambda1"], h["lambda2"]),
                       lr=LearnRate(h["lr_eta"], h["lr_beta"])), runtime)

    def info_of(subblocks):
        spec = tilemm.make_spec(nb, subblocks, default_cap(nnz, nb))
        return CRec2Info(nnz=nnz, block_rows=spec.block_rows,
                         total_rows=spec.block_rows, nb=nb,
                         subblocks=subblocks, cap=spec.cap, ovf_cap=0)

    def encoded(keys, labels, info):
        pw, _b, _r, spilled = encode_tile_block(keys, nb, info.spec, 1024)
        assert spilled == 0
        return {"pw": pw, "labels": labels.astype(np.uint8)}

    blocks = [fields.make_block(traffic, seed, i, rows)
              for i in range(2 * steps)]
    groups = check.merge_groups(blocks, 2)

    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    mesh, one = store_on(rt), store_on(None)
    app = types.SimpleNamespace(store=mesh)
    observed = {"losses": []}
    for i in range(steps):
        pair = [encoded(k, l, info_of(1)) for k, l in blocks[2 * i:2 * i + 2]]
        mesh.tile_train_step_mesh(
            {k: np.stack([b[k] for b in pair]) for k in pair[0]},
            info_of(1))
        one.tile_train_step(encoded(*groups[i], info_of(2)), info_of(2))
        objv, num_ex = mesh.fetch_metrics()[:2]
        assert num_ex == 2 * rows
        assert one.fetch_metrics()[0] == pytest.approx(objv, rel=1e-5)
        observed["losses"].append(float(objv) / float(num_ex))
        if i == 0:
            observed["grad_norms"] = hooks.grad_norms(app, config, seed)
    observed["change_norms"] = hooks.change_norms(app, config, seed)

    table = mesh.slots
    by_place = {}
    for shard in table.addressable_shards:
        by_place.setdefault(shard.index[0].start or 0, []).append(
            np.asarray(shard.data))
    assert sorted(by_place) == [0, nb // 2]          # two server shards
    for copies in by_place.values():                 # a worker pair each
        assert len(copies) == 2
        np.testing.assert_array_equal(copies[0], copies[1])
    whole = np.concatenate([by_place[0][0], by_place[nb // 2][0]])
    np.testing.assert_array_equal(whole, np.asarray(table))
    np.testing.assert_allclose(whole, np.asarray(one.slots), rtol=2e-5,
                               atol=1e-6)
    assert np.count_nonzero(whole[:, 0]) > 1000

    reference = run.config_module(CONFIG, "reference")
    stated = check.stated_precision(
        config, [(np.zeros(0, np.int64), np.zeros(0, np.int64))] * steps)
    expected, ref = check.run_reference(reference, config, groups, seed,
                                        **stated)
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    observed["state"] = hooks.state(app, config, seed, buckets)
    np.testing.assert_array_equal(observed["state"]["w"],
                                  whole[buckets, 0].astype(np.float64))
    ok, lines = check.verdict(check.numbers(observed, expected),
                              check.limits_of(config,
                                              "mesh4_stream_uniform"))
    assert ok, lines


def test_the_configuration_states_its_deployment():
    config = bm_helpers.load(f"benchmark/configs/{CONFIG}/config.json")
    entry, = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert config["source"] == entry["source"]
    assert config["reduced"] == ["rows", "num_buckets", "servers", "workers"]
    assert config["num_buckets"] == 2 ** 29
    assert (config["servers"], config["workers"]) == (2, 2)
    assert (config["published"]["servers"],
            config["published"]["workers"]) == (100, 100)
    assert "2 servers x 2 workers" in config["deployment"]
    assert len(config["assumed"]) >= 2 and len(config["guarantees"]) >= 4
    assert any("exactly once" in g for g in config["guarantees"])
    assert config["precision"] == {
        "table": "float32", "kernel_operands": "bfloat16",
        "overflow_operands": "float32", "accumulate": "float32",
        "psum": "float32"}
    assert f"num_buckets = {2 ** 29}" in config["program"]["conf"]
    assert set(config["check"]["limits"]) == {
        "loss_rel", "grad_norm_rel", "change_norm_rel", "state_rel_rms"}
    assert set(config["check"]["controls"]) == {"fp8_operands", "bf16_table"}
    # the shapes are the one-chip configuration's, to the letter
    ftrl = bm_helpers.load("benchmark/configs/criteo_ftrl/config.json")
    for key in ("nnz", "subblocks", "block_rows", "hyper",
                "state_per_bucket", "state_bytes_per_bucket"):
        assert config[key] == ftrl[key], key
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "mesh4_stream_uniform"
    traffic = bm_helpers.load("benchmark/traffic/mesh4_stream_uniform.json")
    assert traffic["program"]["mesh_shape"] == "data:2,model:2"
    assert traffic["step_kernel"] == config["program"]["step_kernel"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_mesh_metric_resolves_and_reads_nothing_from_nothing(metric):
    entry = NEW_METRICS[metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "stream_ex_per_s"
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    assert spec["regime"] == "mesh" and spec["what"]
    read = run.reader_of(bm_helpers.REPO, metric)
    # a program without the counters and a run without a trace: left out
    empty = {"window": {"timers": {"put": 1.0, "wait": 2.0}, "steps": 6,
                        "window_s": 3.0}, "trace": None}
    assert read(empty) is None


def test_the_collective_reader_sums_the_collectives_among_the_top_ops():
    from benchmark.readers import (collective_ms_per_step, ici_gb_per_step,
                                   ici_roofline)
    reading = {
        "window": {"timers": {"mesh_steps": 20.0, "ici_bytes": 20 * 1.0e9,
                              "put": 4.0}},
        "trace": {"steps": 20, "device_ops": [
            ["%fusion.3 fusion f32[268435456,3]{1,0}", 1.2],
            ["%psum.57 all-reduce f32[268435456]{0:T(1024)}", 0.8],
            ["%all-reduce-start.1 all-reduce-start f32[98304]{0}", 0.15],
            ["%all-reduce-done.1 all-reduce-done f32[98304]{0}", 0.05],
            ["%custom-call.2 tpu_custom_call f32[98304]{0}", 0.9]]}}
    assert collective_ms_per_step.read(reading) == pytest.approx(50.0)
    assert ici_gb_per_step.read(reading) == pytest.approx(1.0)
    assert ici_roofline.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(KeyError, match="ici_peaks.json"):
        ici_roofline.ici_bytes_per_s("cpu")
    reading["trace"]["device_ops"] = reading["trace"]["device_ops"][:1]
    assert collective_ms_per_step.read(reading) is None
    assert ici_roofline.read(reading) is None
