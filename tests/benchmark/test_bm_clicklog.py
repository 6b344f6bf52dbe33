"""``criteo_ftrl_clicklog``: the click log as it is (heavy-tailed keys, empty
columns) read from TEXT through ``tile_online``, on the CPU at small sizes.

- the witness of PERF.md section 7's first item (PR 37 found that skewed text
  trained ANOTHER model under ``tile_online``): the program now reads the
  plain reference's numbers, and the table is the one the same rows train
  from a crec2 file through the COO path, bit for bit;
- the reference's own check of the overflow list it is handed;
- the generator's empty columns, the stated tile geometry, the two readers,
  and the cell end to end through ``run_cell``.
"""

import json
import os

import numpy as np
import pytest

import bm_helpers
from benchmark import check
from benchmark.configs.criteo_ftrl_clicklog import reference
from benchmark.generators import criteo_clicklog

CELL = "criteo_ftrl_clicklog.stream_text_fields"
CONFIG = bm_helpers.load("benchmark/configs/criteo_ftrl_clicklog/config.json")
MIX = bm_helpers.load("benchmark/traffic/stream_text_fields.json")
ROWS = 16384


def _patched(nb):
    """The configuration cut to ``nb`` buckets and 16,384-row blocks, its
    stated tile cap the program's at that size."""
    from wormhole_tpu.data.crec import default_cap
    from benchmark import run
    swap = {"num_buckets": nb, "text_block_rows": ROWS}
    lines = [f"{k} = {swap[k]}" if (k := c.split(" = ")[0]) in swap else c
             for c in CONFIG["program"]["conf"]]
    return run.merge(CONFIG, {
        "num_buckets": nb, "subblocks": 2, "block_rows": ROWS,
        "tile": {"cap": default_cap(39, nb)},
        "check": {"sample": 4096}, "program": {"conf": lines}})


def _texts(seed, blocks=3):
    out = []
    for i in range(blocks):
        ints, cats, labels, empty = criteo_clicklog.make_block(
            MIX, seed, i, ROWS)
        out.append(criteo_clicklog.render(ints, cats, labels, empty))
    return out


def _keys(text):
    from wormhole_tpu.data import crec, native
    asm = native.get_crec_assembler("criteo", 39) \
        or crec._python_crec_assembler("criteo", 39)
    return asm(text)


def _app(tmp_path, config, train_data, fmt):
    import jax
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import load_config
    conf = os.path.join(tmp_path, f"{fmt}.conf")
    with open(conf, "w") as f:
        f.write(f"train_data = {train_data}\n")
        f.write("\n".join(c for c in config["program"]["conf"]
                          if not c.startswith("data_format")) + "\n")
        f.write(f"data_format = {fmt}\n")
    # the tests' process has eight host devices: one of them, as on the chip
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:1", jax.devices()[:1])
    return AsyncSGD(load_config(conf, ["pipeline_workers=0",
                                       "tile_step_kernel=fused"]), rt)


def _observe(app, hooks, config, parts, seed):
    """Three one-block steps as the harness drives them."""
    out = {"losses": []}
    for i, part in enumerate(parts):
        prog = app.process(*part)
        prog.merge(app.flush_metrics())
        assert prog.count == 1 and prog.num_ex == ROWS
        out["losses"].append(prog.objv / prog.num_ex)
        if i == 0:
            out["grad_norms"] = hooks.grad_norms(app, config, seed)
    out["change_norms"] = hooks.change_norms(app, config, seed)
    return out


@pytest.mark.parametrize("nb", [1 << 16, 1 << 20])
def test_skewed_text_with_empty_columns_trains_the_references_model(
        nb, tmp_path):
    """The witness, seed 7: at 2**16 buckets (PR 37's size: 1,349 pairs of
    570,190 past the cap, already more than ONLINE_OVF_CAP) and at 2**20
    (39,595: a fourteenth of the block). Through ``tile_online`` the losses,
    the first gradient, the change and the sampled state are inside the
    configuration's limits against the plain reference, which checks the
    handed lists; the same rows written through ``CRec2Writer`` with room for
    the pairs and stepped through the COO path give the same table, bit for
    bit; a reference that rounds the overflow pairs too is further off."""
    from benchmark.configs.criteo_ftrl_clicklog import system as hooks
    from wormhole_tpu.data import crec
    seed, config = 7, _patched(nb)
    texts = _texts(seed)
    paths = []
    for i, text in enumerate(texts):
        paths.append(os.path.join(tmp_path, f"check{i}.txt"))
        with open(paths[-1], "wb") as f:
            f.write(text)
    app = _app(tmp_path, config, paths[0], "criteo")
    observed = _observe(app, hooks, config, [(p, 0, 1) for p in paths], seed)
    assert app.timer.counts.get("table_cross", 0) == 0

    info = crec.online_info(39, ROWS, nb)
    assert (info.cap, 16384, 8192) == (
        config["tile"]["cap"], config["tile"]["buckets"],
        config["tile"]["rows"])
    lists = []
    for text in texts:
        _pw, ob, orow = crec.encode_tile_pairs(_keys(text)[0], nb, info.spec)
        lists.append((ob.astype(np.int64), orow.astype(np.int64)))
    assert min(len(b) for b, _r in lists) > crec.ONLINE_OVF_CAP
    assert app.timer.totals["online_overflow_pairs"] == sum(
        len(b) for b, _r in lists)

    stated = check.stated_precision(config, lists)
    expected, ref = check.run_reference(reference, config, texts, seed,
                                        **stated)
    assert ref.list_fault is None
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    observed["state"] = hooks.state(app, config, seed, buckets)
    nums = check.numbers(observed, expected)
    ok, lines = check.verdict(nums, check.limits_of(config,
                                                    "stream_text_fields"))
    assert ok, lines
    rounded, _ = check.run_reference(reference, config, texts, seed,
                                     buckets=buckets,
                                     **dict(stated, exact_pairs=None))
    assert check.numbers(observed, rounded)["state_rel_rms"] \
        > 3 * nums["state_rel_rms"]

    # the same rows from a crec2 file with room for the pairs: the COO path
    c2 = os.path.join(tmp_path, "rows.crec2")
    room = crec.overflow_room(max(len(b) for b, _r in lists))
    with crec.CRec2Writer(c2, nnz=39, nb=nb, subblocks=info.subblocks,
                          cap=info.cap, ovf_cap=room) as w:
        for text in texts:
            w.append(*_keys(text))
    sound = _app(tmp_path, config, c2, "crec2")
    from_file = _observe(sound, hooks, config,
                         [(c2, i, 3) for i in range(3)], seed)
    assert from_file["losses"] == observed["losses"]
    assert np.array_equal(np.asarray(sound.store.slots),
                          np.asarray(app.store.slots))


def _block_pairs(nb=1 << 16, seed=3):
    ids, rows, _labels = reference.parse(_texts(seed, 1)[0])
    return reference.buckets_of(ids, nb), rows


def _sound_list(buckets, rows, nb, tile):
    """A block's pairs past each tile's cap, in line order: what the
    program's encoder lists, computed the plain way."""
    tiles = nb // tile["buckets"]
    cell = (rows // tile["rows"]) * tiles + buckets // tile["buckets"]
    order = np.argsort(cell, kind="stable")
    first = np.searchsorted(cell[order], cell[order], side="left")
    past = order[np.arange(len(order)) - first >= tile["cap"]]
    return buckets[past], rows[past]


def _faulty(kind, lb, lr, buckets, rows, nb):
    if kind == "dropped":
        return lb[1:], lr[1:]
    if kind == "doubled":
        return np.r_[lb, lb[:1]], np.r_[lr, lr[:1]]
    if kind == "foreign":
        b = int(lb[0])
        while np.any((buckets == b) & (rows == lr[0])):
            b = (b + 1) % nb
        return np.r_[b, lb[1:]], lr
    if kind == "another_tiles":
        # a pair of a tile under its cap in place of one past it
        tiles = nb // 16384
        cell = (rows // 8192) * tiles + buckets // 16384
        quiet = int(np.argmin(np.bincount(cell, minlength=2 * tiles)))
        k = int(np.flatnonzero(cell == quiet)[0])
        return np.r_[buckets[k], lb[1:]], np.r_[rows[k], lr[1:]]
    assert kind == "sound"
    return lb, lr


@pytest.mark.parametrize("kind, says", [
    ("sound", None),
    ("dropped", "tiles list another number of pairs"),
    ("doubled", "listed more often than the block has them"),
    ("foreign", "no pair of the block"),
    ("another_tiles", "tiles list another number of pairs"),
])
def test_the_reference_checks_the_list_it_is_handed(kind, says):
    """A list with a pair dropped, doubled, foreign or taken from another
    tile fails the check and every loss is NaN, which fails ``correct``; the
    sound list passes and the losses are finite. (The cap is set 500 under
    the fullest tile's count, so that the list is a few hundred pairs.)"""
    nb = 1 << 16
    buckets, rows = _block_pairs(nb)
    tile = {"buckets": 16384, "rows": 8192, "cap": int(np.bincount(
        (rows // 8192) * 4 + buckets // 16384).max()) - 500}
    lb, lr = _sound_list(buckets, rows, nb, tile)
    assert 500 <= len(lb) <= 1000
    listed = _faulty(kind, lb, lr, buckets, rows, nb)
    fault = reference.check_overflow_list(buckets, rows, listed, nb, tile)
    if says is None:
        assert fault is None
    else:
        assert says in fault
    config = dict(_patched(nb), tile=tile)
    text = _texts(3, 1)[0]
    ref = reference.Reference(config, [text], 3, operands="bfloat16",
                              exact_pairs=[listed])
    loss = ref.step()
    assert np.isfinite(loss) == (says is None)
    assert (ref.list_fault is None) == (says is None)
    nums = {"loss_rel": abs(0.6931 - loss) / loss}
    ok, _lines = check.verdict(nums, {"loss_rel": 1e-3})
    assert ok == (says is None)
    if says is None:
        assert int(ref.exact[0].sum()) == len(lb)


def test_the_programs_encoder_lists_what_the_reference_expects():
    """The stated geometry is the program's: the list ``encode_tile_pairs``
    gives for a block is, pair for pair and in order, the plain reading of
    'a tile keeps its first ``cap`` pairs in line order' over the
    reference's own parse."""
    from wormhole_tpu.data import crec
    from wormhole_tpu.ops import tilemm
    nb = 1 << 20
    config = _patched(nb)
    assert (tilemm.TILE, tilemm.RSUB) == (CONFIG["tile"]["buckets"],
                                          CONFIG["tile"]["rows"])
    assert crec.default_cap(39, CONFIG["num_buckets"]) \
        == CONFIG["tile"]["cap"]
    text = _texts(5, 1)[0]
    info = crec.online_info(39, ROWS, nb)
    _pw, ob, orow = crec.encode_tile_pairs(_keys(text)[0], nb, info.spec)
    buckets, rows = _block_pairs(nb, seed=5)
    assert reference.check_overflow_list(
        buckets, rows, (ob, orow), nb, config["tile"]) is None
    lb, lr = _sound_list(buckets, rows, nb, config["tile"])
    assert len(lb) > 20000
    key = lambda b, r: np.sort(np.asarray(r, np.int64) * nb
                               + np.asarray(b, np.int64))
    assert np.array_equal(key(ob, orow), key(lb, lr))


def test_empty_columns_are_drawn_rendered_and_left_out_of_the_margin():
    shares = criteo_clicklog.empty_shares(MIX)
    assert shares.sum() == pytest.approx(4.2) and (shares > 0).sum() == 8
    assert [i for i, s in enumerate(shares) if s == 0.75] == [11, 34]
    ints, cats, labels, empty = criteo_clicklog.make_block(MIX, 11, 2, ROWS)
    again = criteo_clicklog.make_block(MIX, 11, 2, ROWS)
    assert all(np.array_equal(a, b) for a, b in zip(
        (ints, cats, labels, empty), again))
    got = empty.mean(axis=0)
    assert np.allclose(got, shares, atol=0.02)
    assert not empty[:, shares == 0].any()
    # the values are criteo_text's at the same seed: the empties have a
    # generator stream of their own
    from benchmark.generators import criteo_text
    ints0, cats0, _labels0 = criteo_text.make_block(MIX, 11, 2, ROWS)
    assert np.array_equal(ints, ints0) and np.array_equal(cats, cats0)
    text = criteo_clicklog.render(ints, cats, labels, empty)
    lines = text.split(b"\n")[:-1]
    assert len(lines) == ROWS
    cols = [ln.split(b"\t") for ln in lines[:2000]]
    assert all(len(c) == 40 for c in cols)
    is_empty = np.array([[c == b"" for c in row[1:]] for row in cols])
    assert np.array_equal(is_empty, empty[:2000])
    full = criteo_text.render(ints, cats, labels).split(b"\n")
    for row, ref_row, e in zip(cols[:50], full[:50], empty[:50]):
        want = ref_row.split(b"\t")
        assert [c for c, x in zip(row[1:], e) if not x] \
            == [c for c, x in zip(want[1:], e) if not x]
    # the reference parses exactly the non-empty columns as features
    ids, rows, lab = reference.parse(text)
    assert len(ids) == int((~empty).sum())
    assert np.array_equal(np.bincount(rows, minlength=ROWS),
                          (~empty).sum(axis=1))
    assert np.array_equal(lab, labels)
    with pytest.raises(ValueError):
        criteo_clicklog.empty_shares({"empty_fields": {"I14": 0.1}})


def test_the_two_readers():
    from benchmark.readers import (online_overflow_pairs_per_block,
                                   overflow_ms_per_step)
    window = {"timers": {"online_overflow_pairs": 2400.0, "encode": 1.0},
              "blocks": 12}
    assert online_overflow_pairs_per_block.read({"window": window}) == 200.0
    # a program without the counter (the parent), or no block: nothing
    assert online_overflow_pairs_per_block.read(
        {"window": {"timers": {"encode": 1.0}, "blocks": 12}}) is None
    assert online_overflow_pairs_per_block.read(
        {"window": dict(window, blocks=0)}) is None
    # no trace, no steps, or a trace that is gone: nothing, and no error
    r = {"trace": None, "config": {"name": "c"}, "traffic": {"name": "t"}}
    assert overflow_ms_per_step.read(r) is None
    assert overflow_ms_per_step.read(dict(r, trace={"steps": 0})) is None
    assert overflow_ms_per_step.read(dict(r, trace={"steps": 5})) is None
    # a recorded trace of a program without the scopes: nothing
    recorded = os.path.join(bm_helpers.DATA, "ftrl_replay.xplane.pb")
    from benchmark.readers import tower_ms_per_step
    assert not any(s in scope for scope in
                   tower_ms_per_step.scoped_ops(recorded).values()
                   for s in overflow_ms_per_step.SCOPES)


def _tiny(tmp_path, **kw):
    config_patch, traffic_patch = bm_helpers.tiny_patches(*CELL.split("."))
    from wormhole_tpu.data.crec import default_cap
    config_patch["tile"] = {"cap": default_cap(39, bm_helpers.TINY_NB)}
    traffic_patch.pop("ovf_cap")      # the mix states none: the program's
    return bm_helpers.run_tiny(CELL, tmp_path,
                               patches=(config_patch, traffic_patch), **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_end_to_end(trace, tmp_path):
    r, result = _tiny(tmp_path, trace=trace, seed=2**31 + 39,
                      control=not trace)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    out = r.stdout
    counted = json.loads(out[out.index("program counters: ") + 18:]
                         .splitlines()[0])
    assert counted["table_cross"] == 0
    assert counted["online_overflow_pairs"] > 0
    work = json.loads(out[out.index("work per block: ") + 16:]
                      .splitlines()[0])
    # at 16,384 rows a block's count swings by a quarter, where the cell's
    # blocks differ by half a percent: the room is the first block's, or
    # grew once more, and holds every list
    assert counted["online_room_grown"] in (1, 2)
    assert max(work["overflow_pairs_per_block"]) \
        <= counted["online_overflow_room"] <= work["ovf_cap"]
    assert 34.5 < work["features_per_row"][0] <= work[
        "features_per_row"][1] < 35.1
    assert "pairs taken unrounded a step (the file's overflow lists): [" \
        in out
    if trace:
        bench = bm_helpers.load("BENCHMARK.json")
        device = {m["name"] for m in bench["per_layer"]
                  if m["source"] == "device_trace"} | {"hbm_peak_gb.stream"}
        listed = {m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ())}
        assert {"online_overflow_pairs_per_block.stream",
                "overflow_ms_per_step.stream"} <= listed
        assert set(result["metrics"]) == listed - device
        pairs = result["metrics"]["online_overflow_pairs_per_block.stream"]
        assert work["overflow_pairs_per_block"][0] * 0.8 \
            < pairs["value"] < work["overflow_pairs_per_block"][1] * 1.2
        assert "device metrics: not measured" in out
    else:
        assert set(result["metrics"]) == {"stream_ex_per_s", "setup_s"}
        limits = check.limits_of(CONFIG, "stream_text_fields")
        for control in CONFIG["check"]["controls"]:
            line, = [ln for ln in out.splitlines()
                     if ln.startswith(f"[bench] control {control} {{")]
            nums = json.loads(line[line.index("}: {") + 3:])
            assert nums["state_rel_rms"] > 2 * limits["state_rel_rms"]
    assert not any(n.endswith(".txt") for n in os.listdir(tmp_path))


def test_a_program_without_the_room_cannot_run_the_format(tmp_path,
                                                          monkeypatch):
    """The parent commit with this PR's benchmark files laid over it: the
    format refuses at once, before a file is written."""
    from benchmark.formats import criteo_text_clicklog
    from wormhole_tpu.data import crec
    monkeypatch.delattr(crec, "OverflowRoom")
    source = criteo_text_clicklog.Source(
        _patched(1 << 16), MIX, str(tmp_path), 1, 1)
    with pytest.raises(RuntimeError, match="cannot run a mix"):
        source.begin()
    assert not os.listdir(tmp_path)
