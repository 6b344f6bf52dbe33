"""``FMStore``'s whole one-device tile train steps compiled for a DESCRIBED
TPU v5e, without a chip (see ``test_tpu_compile.py``)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from wormhole_tpu.ops import tilemm

from tpu_compile_helpers import (_hot_form,  # noqa: F401
                                 compiled_not_interpreted, v5e)


def _compile_fm_train_step(v5e, step, spec, nb: int, k: int, room: int = 0,
                           hot: tuple = ()):
    """An ``FMStore`` tile train step compiled for one described chip on
    a planar table of ``nb`` buckets; ``room``: the slots of the block's
    COO overflow list (0: the block brings none); ``hot``: the ``(tiles,
    vtiles)`` of the list's hot form, which then crosses in place of the
    COO arrays. Returns (compiled, the plane's shape struct)."""
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.learners.store import TableCheckpoint
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plane = on(tbl.plane_shape(nb), jnp.float32)
    block = {"pw": on(spec.pairs_shape, jnp.uint32),
             "labels": on((spec.block_rows,), jnp.uint8)}
    if hot:
        # the list as HotRoom made it and FMStore.put_block ships it
        u, pw = _hot_form(spec, *hot)
        block.update(ovf_u=on(*u), ovf_pw=on(*pw))
    elif room:
        # the list as FMStore.put_block ships it: a slot a pair
        block.update(ovf_b=on((room,), jnp.uint32),
                     ovf_r=on((room,), jnp.uint32))
    compiled = step.lower(
        tbl.PlaneTable([plane] * (2 * (1 + k))), block,
        on((), jnp.int32), on((), jnp.float32),
        on((TableCheckpoint.MACC_LEN,), jnp.float32)).compile()
    return compiled, plane


def test_fm_train_step_on_planes_compiles_for_v5e(v5e):
    """The whole one-device FM train step of a planar ``FMStore`` at the
    widths of ``criteo_fm`` (cap 256), two tiles a grid step: the fused
    10-channel kernel with the AdaGrad update inside, all 18 planes
    aliased onto its outputs. Around the Mosaic call the v5e compiler
    leaves nothing that touches a plane: no fusion, no copy, no
    concatenate, pad, slice or transpose. What
    ``criteo_fm.replay_uniform`` steps."""
    import re
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.models.fm import IN_PLACE, FMConfig, FMStore
    # two tiles a grid step (tiles_step divides the tile count) keep the
    # unrolled kernel short; 1018 tiles keep a plane out of VMEM, as at
    # the cell's 2048
    k, nb = 8, 2 * 509 * tilemm.TILE
    store = FMStore(FMConfig(num_buckets=2 * tilemm.TILE, dim=k,
                             tile_step_kernel="fused"))
    info = CRec2Info(nnz=39, block_rows=12 * tilemm.RSUB,
                     total_rows=12 * tilemm.RSUB, nb=nb, ovf_cap=1024,
                     subblocks=12, cap=256)      # the cell's, at 2**25
    spec = info.spec
    step = store._tile_step(info, "train", False)
    assert store.step_kernel[:2] == ("fused", IN_PLACE)
    compiled, plane = _compile_fm_train_step(v5e, step, spec, nb, k)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1
    plane_txt = "f32[%d,%d,%d]" % plane.shape
    entry = text[text.index("ENTRY"):]
    makers = set()
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
        if m and plane_txt in m.group(1):
            makers.add(m.group(2))
    assert makers == {"parameter", "custom-call", "get-tuple-element",
                      "tuple"}, makers
    # the 18 planes are donated onto the 18 results; the pushes have no
    # buffer at all
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (1 + k) * 4 * nb
    assert mem.temp_size_in_bytes < 4 * nb


@pytest.mark.parametrize("form", ["coo", "hot"])
def test_fm_spill_train_step_compiles_for_v5e_at_the_click_log_cells_size(
        v5e, form):
    """The one-device FM train step of a planar ``FMStore`` for a block
    that brings an overflow list, at the size of
    ``criteo_fm_clicklog.replay_fields``: 2**26 buckets (cap 128, sixteen
    tiles a grid step), a list room of 1,638,400 slots; ``coo``: a slot a
    pair, as ``put_block`` ships a list that ``HotRoom`` leaves; ``hot``:
    the same list as ``HotRoom`` makes it there (two hot tiles of 192
    virtual tiles each, ten channels as thirty parts). The v5e compiler
    accepts it inside the chip's memory, the three XLA phases keep their
    names in the optimized HLO (each is a jit of its own, so the device
    trace can tell them apart), and nothing in it, operand or temporary,
    is the table stacked as ``(nb, 18)``; the hot program gathers and
    scatters two hot tiles' slots a plane and nothing as long as the
    list's room. A minute, and two for the hot one."""
    import json
    import re
    from wormhole_tpu.data.crec import CRec2Info, default_cap
    from wormhole_tpu.models.fm import IN_PLACE, FMConfig, FMStore
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "criteo_fm_clicklog", "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "replay_fields.json")) as f:
        room = int(json.load(f)["ovf_cap"])
    k, nb = int(config["dim"]), int(config["num_buckets"])
    assert (k, nb, room) == (8, 1 << 26, 1638400)
    store = FMStore(FMConfig(num_buckets=2 * tilemm.TILE, dim=k,
                             tile_step_kernel="fused"))
    info = CRec2Info(nnz=39, block_rows=12 * tilemm.RSUB,
                     total_rows=12 * tilemm.RSUB, nb=nb, ovf_cap=room,
                     subblocks=12, cap=default_cap(39, nb))
    assert info.cap == config["tile"]["cap"]
    spec = info.spec
    step = store._tile_step(info, "train", True)
    assert store.step_kernel[0] == "fused"
    assert store.step_kernel[1] != IN_PLACE
    compiled, _plane = _compile_fm_train_step(
        v5e, step, spec, nb, k, room, (2, 192) if form == "hot" else ())
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (3 if form == "hot" else 1)
    for phase in ("fm_ovf_pull", "fm_ovf_scatter", "fm_table_update"):
        assert re.search(r"jit\(%s\)" % phase, text), phase
    # every gather and scatter of the program sits under one of the two
    # list jits, 1 + k plane gathers and k + 2 plane scatter-adds among
    # them; the hot form's are two hot tiles long (32,768 slots), and
    # nothing in its program, operand or temporary, is as long as the
    # list's room
    for op, phase, n in (("gather", "fm_ovf_pull", 1 + k),
                         ("scatter", "fm_ovf_scatter", k + 2)):
        lines = [ln for ln in text.splitlines()
                 if re.search(r" = \S+ %s\(" % op, ln)]
        assert all("jit(fm_ovf_" in ln for ln in lines), op
        assert sum("jit(%s)" % phase in ln for ln in lines) >= n, op
    gathered = set(re.findall(r" = (f32\[\d+\])\S* gather\(", text))
    if form == "hot":
        assert str(room) not in text
        assert gathered == {"f32[%d]" % (2 * tilemm.TILE)}, gathered
    else:
        assert "f32[%d]" % room in gathered
    # the table is planes throughout: no array of nb rows by some columns
    assert not re.findall(r"f32\[%d,\d+\]" % nb, text)
    # the 18 planes are donated onto the 18 results, and the program (its
    # arguments and its temporaries: the ten push planes among them, and
    # the hot pair's operand and output, 0.4 and 0.75 GB) fits the chip
    # beside nothing else with 6.5 GB to spare
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (1 + k) * 4 * nb
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 9.5e9
