"""``criteo_ftrl_clicklog_ps4``: the click log as it is, read from TEXT through
``tile_online`` into a table sharded over a ``data:2,model:2`` mesh, on the
CPU at small sizes and four host devices.

- the cell through the unchanged harness by files alone, traced and plain;
- the share test with lists: over the two MODEL shards every real listed pair
  is valid in exactly one range mask, and the shards' tables put end to end
  are the one-device table stepped on the same rows with the same lists;
- the mesh step against the plain reference, which parses a group's text and
  checks the lists it is handed, and five faults planted underneath, each of
  which must come out not ``correct``;
- the format's groups, the reference's group semantics, the new reader, and
  the catalog of what the configuration has to state.
"""

import json
import os

import numpy as np
import pytest

import bm_helpers
from benchmark import check, run
from benchmark.configs.criteo_ftrl_clicklog_ps4 import reference
from benchmark.generators import criteo_clicklog

CONFIG_NAME = "criteo_ftrl_clicklog_ps4"
MIX_NAME = "mesh4_stream_text_fields"
CELL = f"{CONFIG_NAME}.{MIX_NAME}"
BENCH = bm_helpers.load("BENCHMARK.json")
CONFIG = bm_helpers.load(f"benchmark/configs/{CONFIG_NAME}/config.json")
MIX = bm_helpers.load(f"benchmark/traffic/{MIX_NAME}.json")
ROWS = 16384                    # a block: two tile row ranges
NB = 1 << 20                    # 64 tiles, 32 a shard: a fourteenth listed
NEW_METRIC = "overflow_ms_per_step.mesh"


def _patched(nb=NB):
    """The configuration cut to ``nb`` buckets and 16,384-row blocks, its
    stated tile cap the program's at that size."""
    from wormhole_tpu.data.crec import default_cap
    swap = {"num_buckets": nb, "text_block_rows": ROWS}
    lines = [f"{k} = {swap[k]}" if (k := c.split(" = ")[0]) in swap else c
             for c in CONFIG["program"]["conf"]]
    return run.merge(CONFIG, {
        "num_buckets": nb, "subblocks": 2, "block_rows": ROWS,
        "tile": {"cap": default_cap(39, nb)},
        "check": {"sample": 4096}, "program": {"conf": lines}})


def _texts(seed, blocks):
    out = []
    for i in range(blocks):
        ints, cats, labels, empty = criteo_clicklog.make_block(
            MIX, seed, i, ROWS)
        out.append((criteo_clicklog.render(ints, cats, labels, empty),
                    labels))
    return out


def _keys(text):
    from wormhole_tpu.data import crec, native
    asm = native.get_crec_assembler("criteo", 39) \
        or crec._python_crec_assembler("criteo", 39)
    return asm(text)


def _mesh_runtime():
    import jax
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    return rt


def _app(tmp_path, config, train_data):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import load_config
    conf = os.path.join(tmp_path, "cell.conf")
    with open(conf, "w") as f:
        f.write(f"train_data = {train_data}\n")
        f.write("\n".join(config["program"]["conf"]) + "\n")
    return AsyncSGD(load_config(conf, ["pipeline_workers=0"]),
                    _mesh_runtime())


# -- the cell, by files alone -------------------------------------------------

def _tiny(tmp_path, **kw):
    config_patch, traffic_patch = bm_helpers.tiny_patches(*CELL.split("."))
    tmp_path = os.path.join(str(tmp_path), "work")
    from wormhole_tpu.data.crec import default_cap
    config_patch["tile"] = {"cap": default_cap(39, bm_helpers.TINY_NB)}
    traffic_patch.pop("ovf_cap")      # the mix states none: the program's
    traffic_patch["blocks"] = 8
    return bm_helpers.run_tiny(CELL, tmp_path, devices=4,
                               patches=(config_patch, traffic_patch), **kw)


@pytest.mark.parametrize("trace", [True, False], ids=["traced", "plain"])
def test_the_cell_runs_by_files_alone(trace, tmp_path):
    r, result = _tiny(tmp_path, trace=trace, seed=2**31 + 43)
    tmp_path = os.path.join(str(tmp_path), "work")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    # a step is a group of two blocks: four steps a pass of eight blocks
    assert result["attempted"] % 4 == 0 and result["attempted"] >= 4
    out = r.stdout
    assert '"step_kernel": "split"' in out and "num_buckets=2**16" in out
    window = next(ln for ln in out.splitlines()
                  if ln.startswith("[bench] window:"))
    passes = int(window.split(" passes")[0].split()[-1])
    assert f"{passes * 4} steps, {passes * 8 * 16384} rows" in window
    counted = json.loads(out[out.index("program counters: ") + 18:]
                         .splitlines()[0])
    work = json.loads(out[out.index("work per block: ") + 16:]
                      .splitlines()[0])
    assert counted["online_overflow_pairs"] > 0
    assert counted["online_room_grown"] in (1, 2)
    assert max(work["overflow_pairs_per_block"]) \
        <= counted["online_overflow_room"] <= work["ovf_cap"]
    # every group's two lists crossed at the room's width (or, before it
    # settled, under it)
    assert 0 < counted["mesh_overflow_slots"] <= 2 * counted[
        "online_overflow_room"] * (3 + (1 + passes) * 4)
    assert 34.5 < work["features_per_row"][0] <= work[
        "features_per_row"][1] < 35.1
    # three steps' lists, each a group's two
    assert "pairs taken unrounded a step (the file's overflow lists): [" \
        in out
    if not trace:
        assert set(result["metrics"]) == {"stream_ex_per_s", "setup_s"}
        assert not any(n.endswith(".txt") for n in os.listdir(tmp_path))
        return
    device = {m["name"] for m in BENCH["per_layer"]
              if m["source"] == "device_trace"} | {"hbm_peak_gb.stream"}
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert NEW_METRIC in listed
    # every listed metric that is no device's fills on the CPU, none other
    assert set(result["metrics"]) == listed - device
    assert all(m["value"] > 0 for m in result["metrics"].values())
    pairs = result["metrics"]["online_overflow_pairs_per_block.stream"]
    assert work["overflow_pairs_per_block"][0] * 0.8 \
        < pairs["value"] < work["overflow_pairs_per_block"][1] * 1.2
    assert "device metrics: not measured" in out


def test_the_format_writes_groups_and_hands_text_that_merges(tmp_path):
    from benchmark import system
    config = _patched(bm_helpers.TINY_NB)
    traffic = run.merge(MIX, {"blocks": 4})
    sut = system.TrainSystem(config, traffic, None, str(tmp_path), 17)
    try:
        assert sut.group == 2
        work = sut.end_data(sut.begin_data())
        src = sut.source
        assert len(src.check_files) == 3 and len(src.check_blocks) == 6
        assert len(src.check_overflow) == 6 and sut.nblocks == 4
        gen = criteo_clicklog
        for i, path in enumerate(src.check_files):
            data = open(path, "rb").read()
            assert data.count(b"\n") == 2 * ROWS
            assert data == b"".join(
                gen.render(*gen.make_block(traffic, 17, j, ROWS))
                for j in (2 * i, 2 * i + 1))
            assert src.check_part(i) == (path, 0, 1)
        assert open(sut.files[0], "rb").read().startswith(
            gen.render(*gen.make_block(traffic, 17, 6, ROWS)))
        handed = src.reference_blocks()
        assert all(t.dtype == np.uint8 and t.ndim == 1 for t, _l in handed)
        steps = check.merge_groups(handed, 2)
        assert len(steps) == 3
        assert steps[1][0].tobytes() == open(src.check_files[1],
                                             "rb").read()
        assert len(steps[1][1]) == 2 * ROWS
        assert work["blocks"] == 4 and work["parser"] in ("native", "python")
    finally:
        sut.close()
    assert not os.listdir(tmp_path)
    from benchmark.formats import criteo_text_clicklog_mesh as fmt
    with pytest.raises(ValueError, match="whole number of groups"):
        fmt.Source(config, run.merge(MIX, {"blocks": 3}), str(tmp_path),
                   1, 2)


# -- the shares add up --------------------------------------------------------

def test_the_shards_with_lists_add_up_to_the_one_device_table():
    """Guide section 4's share test on blocks WITH lists. Over the two MODEL
    shards every real listed pair is valid in exactly one range mask and an
    unused slot in none; after three grouped steps the shards put end to end
    are the table of the one-device store stepped on the same rows (each
    group as one block of twice the subblocks, its list the group's two,
    the second's rows shifted), and the DATA pair's copies are equal bit for
    bit."""
    import jax
    from wormhole_tpu.data import crec
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import (ShardedStore, StoreConfig,
                                             shard_range_mask)
    from wormhole_tpu.ops import tilemm
    from wormhole_tpu.ops.penalty import L1L2
    nb, rows, steps, seed = NB, tilemm.RSUB, 3, 11
    h = CONFIG["hyper"]

    def store_on(runtime):
        return ShardedStore(
            StoreConfig(num_buckets=nb, tile_step_kernel="split"),
            FTRLHandle(penalty=L1L2(h["lambda1"], h["lambda2"]),
                       lr=LearnRate(h["lr_eta"], h["lr_beta"])), runtime)

    def block_of(keys, labels, info, width):
        pw, ob, orow = crec.encode_tile_pairs(keys, nb, info.spec)
        assert 0 < len(ob) <= width
        ob, orow = tilemm.cap_overflow(ob, orow, width)
        return {"pw": pw, "labels": labels.astype(np.uint8),
                "ovf_b": ob, "ovf_r": orow}

    keyed = []
    for i in range(2 * steps):
        ints, cats, labels, empty = criteo_clicklog.make_block(
            MIX, seed, i, rows)
        keyed.append(_keys(criteo_clicklog.render(ints, cats, labels,
                                                  empty)))
    info1 = crec.online_info(39, rows, nb)
    info2 = crec.online_info(39, 2 * rows, nb)
    width = crec.overflow_room(max(
        len(crec.encode_tile_pairs(k, nb, info1.spec)[1]) for k, _l in keyed))
    assert width > 8 * crec.ONLINE_OVF_CAP

    mesh, one = store_on(_mesh_runtime()), store_on(None)
    nb_local = nb // 2
    for i in range(steps):
        pair = [block_of(k, l, info1, width)
                for k, l in keyed[2 * i:2 * i + 2]]
        for member in pair:
            valid = [np.asarray(shard_range_mask(
                jax.numpy.asarray(member["ovf_b"]), m * nb_local,
                nb_local)[0]) for m in range(2)]
            real = member["ovf_b"] != np.uint32(0xFFFFFFFF)
            assert real.sum() > 1000
            assert np.array_equal(valid[0] ^ valid[1], real)
            assert not (valid[0] & valid[1]).any()
            assert valid[0].sum() > 100 and valid[1].sum() > 100
        mesh.tile_train_step_mesh(
            {k: np.stack([b[k] for b in pair]) for k in pair[0]}, info1)
        whole = block_of(np.concatenate([k for k, _l in keyed[2 * i:
                                                               2 * i + 2]]),
                         np.concatenate([l for _k, l in keyed[2 * i:
                                                               2 * i + 2]]),
                         info2, 2 * width)
        # the group's merged list is its members' lists, the second
        # member's rows shifted
        merged = np.concatenate(
            [pair[0]["ovf_r"][pair[0]["ovf_b"] != 0xFFFFFFFF],
             pair[1]["ovf_r"][pair[1]["ovf_b"] != 0xFFFFFFFF] + rows])
        assert np.array_equal(
            np.sort(whole["ovf_r"][whole["ovf_b"] != 0xFFFFFFFF]),
            np.sort(merged))
        one.tile_train_step(whole, info2)
        objv, num_ex = mesh.fetch_metrics()[:2]
        assert num_ex == 2 * rows
        assert one.fetch_metrics()[0] == pytest.approx(objv, rel=1e-5)

    table = mesh.slots
    by_place = {}
    for shard in table.addressable_shards:
        by_place.setdefault(shard.index[0].start or 0, []).append(
            np.asarray(shard.data))
    assert sorted(by_place) == [0, nb_local]         # two server shards
    for copies in by_place.values():                 # a worker pair each
        assert len(copies) == 2
        np.testing.assert_array_equal(copies[0], copies[1])
    whole = np.concatenate([by_place[0][0], by_place[nb_local][0]])
    # float32 sums in another order (a worker's rows, then the psum), three
    # steps deep: parts in 10**5 on two of three million values
    np.testing.assert_allclose(whole, np.asarray(one.slots), rtol=1e-4,
                               atol=1e-6)
    assert np.count_nonzero(whole[:, 0]) > 1000


# -- against the reference, sound and broken ----------------------------------

@pytest.fixture(scope="module")
def groups(tmp_path_factory):
    """Three check groups at 2**20 buckets, seed 7, as files; the lists the
    program's encoder gives their blocks; the reference's numbers; and the
    planted boundary faults' band."""
    from wormhole_tpu.data import crec
    tmp = str(tmp_path_factory.mktemp("groups"))
    seed, config = 7, _patched()
    blocks = _texts(seed, 6)
    paths = []
    for i in range(3):
        paths.append(os.path.join(tmp, f"check{i}.txt"))
        with open(paths[-1], "wb") as f:
            f.write(blocks[2 * i][0] + blocks[2 * i + 1][0])
    info = crec.online_info(39, ROWS, NB)
    lists = []
    for text, _labels in blocks:
        _pw, ob, orow = crec.encode_tile_pairs(_keys(text)[0], NB, info.spec)
        lists.append((ob.astype(np.int64), orow.astype(np.int64)))
    assert min(len(b) for b, _r in lists) > 20 * crec.ONLINE_OVF_CAP
    handed = [(np.frombuffer(t, np.uint8), l) for t, l in blocks]
    steps = check.merge_groups(handed, 2)
    stated = check.stated_precision(
        config, check.merge_exact_pairs(lists, blocks, 2))
    expected, ref = check.run_reference(reference, config, steps, seed,
                                        **stated)
    assert ref.list_fault is None
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    # the planted boundary faults' band: the upper MODEL shard's first
    # tile of buckets, which holds listed pairs of the first group
    band_end = NB // 2 + 16384 - 1
    first = np.concatenate([b for b, _r in lists[:2]])
    assert ((first >= NB // 2) & (first <= band_end)).sum() > 100
    return {"tmp": tmp, "seed": seed, "config": config, "paths": paths,
            "expected": expected, "buckets": buckets, "steps": steps,
            "stated": stated, "band_end": band_end,
            "listed_pairs": sum(len(b) for b, _r in lists)}


def _observe(groups, tmp_path):
    from benchmark.configs.criteo_ftrl_clicklog_ps4 import system as hooks
    config, seed = groups["config"], groups["seed"]
    app = _app(str(tmp_path), config, groups["paths"][0])
    out = {"losses": []}
    for i, path in enumerate(groups["paths"]):
        prog = app.process(path, 0, 1)
        prog.merge(app.flush_metrics())
        assert prog.count == 1
        out["losses"].append(prog.objv / prog.num_ex)
        if i == 0:
            out["grad_norms"] = hooks.grad_norms(app, config, seed)
    out["change_norms"] = hooks.change_norms(app, config, seed)
    out["state"] = hooks.state(app, config, seed, groups["buckets"])
    nums = check.numbers(out, groups["expected"])
    ok, lines = check.verdict(nums, check.limits_of(config, MIX_NAME))
    return app, nums, ok, lines


def test_the_mesh_step_from_text_reads_the_references_numbers(groups,
                                                              tmp_path):
    app, nums, ok, lines = _observe(groups, tmp_path)
    assert ok, lines
    t = app.timer.totals
    assert t["online_overflow_pairs"] == groups["listed_pairs"]
    assert t["mesh_steps"] == 3 and t["mesh_overflow_slots"] > 0
    counted = run.config_module(CONFIG_NAME, "system").counters(app)
    # the registry is the process's (other apps of this worker count into
    # it too); the Timer is the app's
    assert counted["online_overflow_pairs"] >= groups["listed_pairs"]
    assert counted["mesh_overflow_slots"] == t["mesh_overflow_slots"]
    # a reference that rounds the listed pairs too is further off
    rounded, _ = check.run_reference(
        reference, groups["config"], groups["steps"], groups["seed"],
        buckets=groups["buckets"],
        **dict(groups["stated"], exact_pairs=None))
    assert check.numbers(rounded, groups["expected"])["state_rel_rms"] \
        > 3 * nums["state_rel_rms"]


def _plant(fault, monkeypatch, groups):
    """One fault underneath the mesh pass; each is what its name says and
    nothing else."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.data import crec
    from wormhole_tpu.learners import store
    from wormhole_tpu.parallel.mesh import DATA_AXIS
    nb_local, end = NB // 2, groups["band_end"]
    real_mask = store.shard_range_mask
    real_gather, real_scatter = store.mesh_ovf_gather, store.mesh_ovf_scatter

    def in_band(ovb):
        bi = ovb.astype(jnp.int32)
        return (ovb != jnp.uint32(0xFFFFFFFF)) & (bi >= nb_local) \
            & (bi <= end)

    if fault == "dropped_at_the_shard_boundary":
        # the upper shard disowns the listed pairs of its first tile
        def mask(ovb, off, nb_l):
            valid, idx = real_mask(ovb, off, nb_l)
            valid = valid & ~in_band(ovb)
            return valid, jnp.where(valid, idx, 0)
        monkeypatch.setattr(store, "shard_range_mask", mask)
    elif fault == "applied_by_both_shards":
        # the lower shard takes them too (at the same place in its range)
        def mask(ovb, off, nb_l):
            valid, idx = real_mask(ovb, off, nb_l)
            also = in_band(ovb) & (off == 0)
            return valid | also, jnp.where(
                also, ovb.astype(jnp.int32) - nb_local, idx)
        monkeypatch.setattr(store, "shard_range_mask", mask)
    elif fault == "overflow_gradient_left_out_of_the_data_psum":
        # the second worker's listed pairs never reach the gradient's sum
        def scatter(g, dual, ovb, ovr, off, *, nb_local):
            first = jax.lax.axis_index(DATA_AXIS) == 0
            return jnp.where(first, real_scatter(
                g, dual, ovb, ovr, off, nb_local=nb_local), g)
        monkeypatch.setattr(store, "mesh_ovf_scatter", scatter)
    elif fault == "every_listed_pair_rounded_to_bfloat16":
        def rounded(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        monkeypatch.setattr(
            store, "mesh_ovf_gather",
            lambda mg, w, *a, **k: real_gather(mg, rounded(w), *a, **k))
        monkeypatch.setattr(
            store, "mesh_ovf_scatter",
            lambda g, dual, *a, **k: real_scatter(g, rounded(dual), *a, **k))
    elif fault == "a_line_dropped_from_one_member_of_a_group":
        # every group's second block loses its first line: no features,
        # and the row is padding
        real_encode = crec.TileOnlineFeed._encode
        seen = []

        def encode(self, item, ctx):
            packed, rows = item
            seen.append(1)
            if len(seen) % 2 == 0:
                packed = np.array(packed)
                packed[:39 * 4] = 0xFF
                packed[self.info.block_rows * 39 * 4] = 255
            return real_encode(self, (packed, rows), ctx)
        monkeypatch.setattr(crec.TileOnlineFeed, "_encode", encode)
    else:
        raise AssertionError(fault)


@pytest.fixture
def fresh_list_jits(monkeypatch):
    """The mesh step's two list jits are module-level and keep what they
    traced: emptied before a fault is planted under them (so that the
    next trace reads the planted ``shard_range_mask``) and after (torn
    down before ``monkeypatch`` undoes the fault, and nothing traces in
    between)."""
    from wormhole_tpu.learners import store
    jits = (store.mesh_ovf_gather, store.mesh_ovf_scatter)

    def clear():
        for fn in jits:
            fn.clear_cache()
    clear()
    yield
    clear()


@pytest.mark.parametrize("fault, moved", [
    ("dropped_at_the_shard_boundary", "grad_norm_rel"),
    ("applied_by_both_shards", "grad_norm_rel"),
    ("overflow_gradient_left_out_of_the_data_psum", "grad_norm_rel"),
    ("every_listed_pair_rounded_to_bfloat16", "state_rel_rms"),
    ("a_line_dropped_from_one_member_of_a_group", "state_rel_rms"),
])
def test_a_fault_planted_under_the_mesh_pass_is_not_correct(
        fault, moved, groups, tmp_path, monkeypatch, fresh_list_jits):
    _plant(fault, monkeypatch, groups)
    _app_, nums, ok, lines = _observe(groups, tmp_path)
    limits = check.limits_of(groups["config"], MIX_NAME)
    assert not ok, (fault, lines)
    assert nums[moved] > 2 * limits[moved], (fault, moved, nums)


# -- the many-seeds driver ------------------------------------------------------

@pytest.mark.parametrize("plant", [0, 2], ids=["sound", "planted"])
def test_the_seeds_driver_reads_correct_through_the_harness_comparison(
        plant, tmp_path):
    """``seeds.py`` (where the limits' tails and the rounded-list control's
    readings come from) at its rehearsal size: sound seeds are ``correct``
    and the control that rounds every listed pair is refused by the
    configuration's own limits; with a listed pair dropped at the shard
    boundary every seed is refused, by ``grad_norm_rel`` among others."""
    import subprocess
    import sys
    out = os.path.join(str(tmp_path), "seeds.jsonl")
    script = os.path.join(bm_helpers.REPO, "benchmark", "configs",
                          CONFIG_NAME, "seeds.py")
    r = subprocess.run(
        [sys.executable, script, "--first", str(2**31 + 4300), "--count",
         "1", "--controls", "1", "--workers", "1", "--cpu", "1", "--plant",
         str(plant), "--out", out, "--tmp", os.path.join(str(tmp_path), "w")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    rec, = [json.loads(line) for line in open(out)]
    assert rec["seed"] == 2**31 + 4300 and rec["planted_tiles"] == plant
    assert rec["list_fault"] is None
    assert rec["overflow_pairs_per_block"][0] > 20000
    assert rec["counters"]["mesh_overflow_slots"] > 0
    if plant:
        assert rec["correct"] is False
        assert "grad_norm_rel" in rec["refused_by"]
    else:
        assert rec["correct"] is True and rec["refused_by"] == []
        assert "state_rel_rms" in rec["rounded_list"]["refused_by"]
    assert set(rec["controls"]) == {"fp8_operands", "bf16_table",
                                    "exact_operands"}
    assert all(c["refused_by"] for c in rec["controls"].values())


# -- the reference's own checks, under group semantics -------------------------

def test_the_reference_checks_a_groups_list_and_takes_bytes_or_arrays():
    config = _patched(1 << 16)
    blocks = _texts(3, 2)
    text = blocks[0][0] + blocks[1][0]
    ids, rows, labels = reference.parse(text)
    assert len(labels) == 2 * ROWS and rows.max() == 2 * ROWS - 1
    assert np.array_equal(labels, np.concatenate([l for _t, l in blocks]))
    buckets = reference.buckets_of(ids, 1 << 16)
    # a cap under the fullest tile's count, so that the list is short; the
    # sound list is each tile's pairs past it in line order, a tile being
    # 8,192 rows OF A BLOCK: the group's second block starts a new range
    tile = dict(config["tile"], cap=int(np.bincount(
        (rows // 8192) * 4 + buckets // 16384).max()) - 300)
    cell = (rows // 8192) * 4 + buckets // 16384
    order = np.argsort(cell, kind="stable")
    first = np.searchsorted(cell[order], cell[order], side="left")
    past = order[np.arange(len(order)) - first >= tile["cap"]]
    listed = (buckets[past], rows[past])
    assert 300 <= len(past) <= 4000 and listed[1].max() >= ROWS
    assert reference.check_overflow_list(buckets, rows, listed, 1 << 16,
                                         tile) is None
    config = dict(config, tile=tile)
    as_array = (np.frombuffer(text, np.uint8), labels)
    for handed in (as_array, text):
        ref = reference.Reference(config, [handed], 3, operands="bfloat16",
                                  exact_pairs=[listed])
        assert np.isfinite(ref.step()) and ref.list_fault is None
        assert int(ref.exact[0].sum()) == len(past)
    # a pair of the second block listed at the first block's row: foreign
    # or miscounted, either way refused
    wrong = (listed[0], np.where(listed[1] >= ROWS, listed[1] - ROWS,
                                 listed[1]))
    ref = reference.Reference(config, [as_array], 3, exact_pairs=[wrong])
    assert ref.list_fault is not None and np.isnan(ref.step())
    with pytest.raises(ValueError, match="tile row ranges"):
        reference.Reference(dict(config, block_rows=ROWS + 1), [as_array], 3)


def _imports_of(config_name):
    import ast
    path = os.path.join(bm_helpers.REPO, "benchmark", "configs", config_name,
                        "reference.py")
    tree = ast.parse(open(path).read())
    mods = {n.module for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom)} | {
        a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
        for a in n.names}
    return mods


def test_the_reference_imports_nothing_of_the_program():
    """It is the one-chip click-log configuration's plain reference under
    group semantics: that module and numpy, and that module in turn nothing
    of the program."""
    assert _imports_of(CONFIG_NAME) == {
        "__future__", "numpy", "benchmark.configs.criteo_ftrl_clicklog"}
    assert _imports_of("criteo_ftrl_clicklog") == {
        "__future__", "functools", "sys", "numpy", "benchmark.check"}
    assert issubclass(reference.Reference,
                      run.config_module("criteo_ftrl_clicklog",
                                        "reference").Reference)


# -- the new reader and the catalog -------------------------------------------

def test_the_mesh_overflow_reader():
    from benchmark.readers import overflow_ms_per_step_mesh as reader
    from benchmark.readers import tower_ms_per_step
    entry, = [m for m in BENCH["per_layer"] if m["name"] == NEW_METRIC]
    assert entry["workloads"] == [CELL] and entry["layer"] == "step"
    assert entry["moves"] == "stream_ex_per_s"
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended: the accepted entries keep their places
    assert names.index(NEW_METRIC) > names.index("idle_in_flight_share.stream")
    assert run.reader_of(bm_helpers.REPO, NEW_METRIC) is reader.read
    # no trace, no steps, or a trace that is gone: nothing, and no error
    r = {"trace": None, "config": {"name": "c"}, "traffic": {"name": "t"}}
    assert reader.read(r) is None
    assert reader.read(dict(r, trace={"steps": 0})) is None
    assert reader.read(dict(r, trace={"steps": 5})) is None
    # a recorded trace of a program without the jits (any parent): nothing
    recorded = os.path.join(bm_helpers.DATA, "ftrl_replay.xplane.pb")
    assert reader.seconds_a_chip(recorded) is None
    # the scopes are the program's own jits' names
    from wormhole_tpu.learners import store
    assert reader.SCOPES == (store.mesh_ovf_gather.__name__,
                             store.mesh_ovf_scatter.__name__)
    assert not any(s in scope for scope in
                   tower_ms_per_step.scoped_ops(recorded).values()
                   for s in reader.SCOPES)


# the standing metrics whose readers find something in the cell (ISSUE 43,
# item 6); ``overflow_ms_per_step.stream`` reads scopes the mesh step lacks,
# ``parse_ms_per_block.stream`` is pinned to the one-chip text format
LISTS = {
    "loop_wait_share.stream", "feed_stall_share.stream",
    "feed_put_ms_per_block.stream", "xla_ms_per_step.stream",
    "nonkernel_ms_per_step.stream", "kernel_ms_per_step.stream",
    "device_idle_share.stream", "hbm_peak_gb.stream",
    "collective_ms_per_step.mesh", "ici_gb_per_step.mesh",
    "ici_roofline.mesh", "group_put_ms_per_step.mesh",
    "encode_ms_per_block.stream", "encode_stall_share.stream",
    "text_mb_per_s.stream", "online_overflow_pairs_per_block.stream",
    "idle_head_share.stream", "idle_starved_share.stream",
    "idle_tail_share.stream", "idle_unnamed_share.stream",
    "pass_head_ms.stream", "text_read_ms_per_block.stream",
    "collate_ms_per_block.stream", "idle_in_flight_share.stream"}


def test_the_cells_entries_are_appended_and_change_nothing_that_stands():
    """The cell's ``BENCHMARK.json`` entries: one configuration, one cell,
    one metric, each after what stood, and the cell's name at the end of the
    list of every standing metric it reports; inside the contract's limits."""
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    config, = [c for c in BENCH["configs"] if c["name"] == CONFIG_NAME]
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    metric, = [m for m in BENCH["per_layer"] if m["name"] == NEW_METRIC]
    for entry in (config, cell, metric):
        assert name.match(entry["name"])
        assert all(len(entry[k]) <= 200 and "\n" not in entry[k]
                   for k in ("why", "source", "layer") if k in entry)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    for kind, entry, before in (
            ("configs", config, "criteo_ftrl_clicklog"),
            ("workloads", cell, "criteo_ftrl_clicklog.stream_text_fields")):
        names = [e["name"] for e in BENCH[kind]]
        assert names.index(entry["name"]) > names.index(before)
    # every metric the cell is appended to reports the rate it reports, and
    # lists the cell after the cells it had
    listed = {m["name"]: m for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(listed) == LISTS | {NEW_METRIC}
    assert all(m["moves"] == "stream_ex_per_s" for m in listed.values())
    standing = {w["name"] for w in BENCH["workloads"]
                if w["config"] in ("criteo_ftrl", "criteo_ftrl_ps4",
                                   "criteo_ftrl_text", "criteo_ftrl_clicklog")}
    for m in listed.values():
        at = m["workloads"].index(CELL)
        assert all(w in standing for w in m["workloads"][:at])
    rates = [m["name"] for m in BENCH["end_to_end"]
             if CELL in m.get("workloads", ())]
    assert rates == ["stream_ex_per_s"]
    assert os.path.getsize(os.path.join(bm_helpers.REPO, "BENCHMARK.json")) \
        < 64 * 1024


# Two standing catalog tests pin the catalog at seven cells and are red with
# an eighth (``len(stream) == 4`` in test_bm_host_spans.py; ``== [CELL]`` for
# every ``.mesh`` metric in test_bm_ps4.py), so what they held BELOW that
# first assert no longer runs there. It is held here, with the cell lists
# read from ``BENCHMARK.json``, until a ``benchmark`` PR mends the pins.

def _stream_cells():
    return [w["name"] for w in BENCH["workloads"]
            if bm_helpers.load(f"benchmark/traffic/{w['traffic']}.json")
            ["regime"] == "stream"]


SPAN_METRICS = ("idle_head_share.stream", "idle_in_flight_share.stream",
                "idle_starved_share.stream", "idle_tail_share.stream",
                "idle_unnamed_share.stream", "pass_head_ms.stream")
COUNTER_METRICS = ("text_read_ms_per_block.stream",
                   "collate_ms_per_block.stream")


@pytest.mark.parametrize("metric", SPAN_METRICS + COUNTER_METRICS)
def test_the_host_span_metrics_list_every_cell_they_read(metric):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    stream = _stream_cells()
    assert CELL in stream and len(stream) == len(set(stream))
    # the spans are every stream cell's; the text feed's counters the cells'
    # that read text with columns left empty (both click logs)
    want = [c for c in stream if "clicklog" in c] \
        if metric in COUNTER_METRICS else stream
    assert sorted(entry["workloads"]) == sorted(want)
    assert entry["better"] == "lower" and entry["moves"] == "stream_ex_per_s"
    assert spec["source"] == ("program_counter" if metric in COUNTER_METRICS
                              else "device_trace")
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(metric) >= names.index("overflow_ms_per_step.stream")


@pytest.mark.parametrize("metric", sorted(
    m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".mesh")))
def test_a_mesh_metric_lists_its_mesh_cells_and_reads_nothing_from_nothing(
        metric):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    mesh = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    # the list's two phases are this cell's alone; the rest every mesh cell's
    assert entry["workloads"] == ([CELL] if metric == NEW_METRIC else mesh)
    assert entry["moves"] == "stream_ex_per_s"
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    assert spec["regime"] == "mesh" and spec["what"]
    read = run.reader_of(bm_helpers.REPO, metric)
    # a program without the counters and a run without a trace: left out
    empty = {"window": {"timers": {"put": 1.0, "wait": 2.0}, "steps": 6,
                        "window_s": 3.0}, "trace": None}
    assert read(empty) is None


def test_the_configuration_states_its_deployment():
    entry, = [c for c in BENCH["configs"] if c["name"] == CONFIG_NAME]
    assert CONFIG["source"] == entry["source"] and len(entry["source"]) <= 200
    assert CONFIG["reduced"] == entry["reduced"] == [
        "rows", "num_buckets", "servers", "workers"]
    assert CONFIG["num_buckets"] == 2 ** 29
    assert (CONFIG["servers"], CONFIG["workers"]) == (2, 2)
    assert (CONFIG["published"]["servers"],
            CONFIG["published"]["workers"]) == (100, 100)
    assert "2 servers x 2 workers" in CONFIG["deployment"]
    assert CONFIG["precision"] == {
        "table": "float32", "kernel_operands": "bfloat16",
        "overflow_operands": "float32", "accumulate": "float32",
        "psum": "float32"}
    # the union of the two parents' guarantees, and the mesh's own
    said = " ".join(CONFIG["guarantees"])
    for needle in ("exactly once a pass", "empty column is no feature",
                   "to the shard that owns its bucket",
                   "by the shard that owns its bucket and by no other",
                   "no block leaves the tile path", "DATA psum",
                   "MODEL psum", "staleness 0", "no shard drops"):
        assert needle in said, needle
    click = bm_helpers.load(
        "benchmark/configs/criteo_ftrl_clicklog/config.json")
    ps4 = bm_helpers.load("benchmark/configs/criteo_ftrl_ps4/config.json")
    # the shapes are the two parents', to the letter
    for key in ("schema", "tile", "nnz", "subblocks", "block_rows", "hyper",
                "num_buckets", "state_per_bucket", "state_bytes_per_bucket"):
        assert CONFIG[key] == click[key], key
    assert CONFIG["program"]["conf"] == click["program"]["conf"]
    for key in ("servers", "workers", "precision"):
        assert CONFIG[key] == ps4[key], key
    assert CONFIG["program"]["step_kernel"] == "split" == MIX["step_kernel"]
    assert set(CONFIG["check"]["limits"]) == {
        "loss_rel", "grad_norm_rel", "change_norm_rel", "state_rel_rms"}
    # the mix is the one-chip click-log cell's, but for layout and format
    one = bm_helpers.load("benchmark/traffic/stream_text_fields.json")
    for key in ("regime", "blocks", "files", "warm_passes", "generator",
                "fields", "empty_fields", "planted_model"):
        assert MIX[key] == one[key], key
    assert MIX["program"] == dict(one["program"],
                                  mesh_shape="data:2,model:2")
    assert MIX["format"] == "criteo_text_clicklog_mesh"
    cell, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 4
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    # the stated geometry is the program's
    from wormhole_tpu.data import crec
    from wormhole_tpu.ops import tilemm
    assert (tilemm.TILE, tilemm.RSUB) == (CONFIG["tile"]["buckets"],
                                          CONFIG["tile"]["rows"])
    assert crec.default_cap(39, CONFIG["num_buckets"]) \
        == CONFIG["tile"]["cap"]
