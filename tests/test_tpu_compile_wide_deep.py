"""``WideDeepStore``'s whole one-device tile train steps compiled for a
DESCRIBED TPU v5e, without a chip (see ``test_tpu_compile.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from wormhole_tpu.ops import tilemm

from tpu_compile_helpers import (_hot_form,  # noqa: F401
                                 compiled_not_interpreted, v5e)


def test_wide_deep_train_step_compiles_with_the_stated_tower_precision(v5e):
    """The whole one-device train step of ``WideDeepStore`` at the widths of
    ``criteo_wide_deep`` (32 values pooled into 1024-512-256), two tiles:
    the split kernel pair with the tower between. The tower's precision is
    stated in the program, and the v5e compiler has to keep it: every tower
    matmul it leaves as a convolution takes bfloat16 operands, and the
    one-column last layer, which it turns into float32 multiplies, has its
    operands' rounding as ``reduce-precision`` (which the compiler may not
    drop, as it dropped that layer's ``convert`` pairs: PERF.md, PR 34):
    the activations, the weights and the incoming gradient, once each.
    What ``criteo_wide_deep.replay_uniform`` steps."""
    import re
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners.store import TableCheckpoint
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    k, hidden, nb = 32, (1024, 512, 256), 2 * tilemm.TILE
    store = WideDeepStore(WideDeepConfig(num_buckets=nb, dim=k,
                                         hidden=hidden))
    info = CRec2Info(nnz=39, block_rows=12 * tilemm.RSUB,
                     total_rows=12 * tilemm.RSUB, nb=nb, ovf_cap=1024,
                     subblocks=12, cap=128)
    spec = info.spec
    step = store._tile_step(info, "train")
    assert store.step_kernel[0] == "split" and "spill" in store.step_kernel[1]
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mlp = jax.tree.map(lambda a: on(a.shape, a.dtype), store.mlp)
    text = step.lower(
        on((nb, 2 * (1 + k)), jnp.float32), mlp, mlp,
        {"pw": on(spec.pairs_shape, jnp.uint32),
         "labels": on((spec.block_rows,), jnp.uint8),
         "ovf_b": on((1024,), jnp.uint32), "ovf_r": on((1024,), jnp.uint32)},
        on((), jnp.int32), on((), jnp.float32),
        on((TableCheckpoint.MACC_LEN,), jnp.float32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    tower = [line for line in text.splitlines()
             if " convolution(" in line and "wd_tower" in line]
    assert len(tower) >= 7, len(tower)       # the wide layers' matmuls
    for line in tower:
        operands = re.search(r" convolution\(([^)]*)\)", line).group(1)
        names = [o.strip().split(" ")[-1] for o in operands.split(",")]
        for name in names:
            made = re.search(r"^\s*(?:ROOT )?" + re.escape(name)
                             + r" = (\w+)\[", text, re.M)
            assert made and made.group(1) == "bf16", (name, line[:120])
    rounded = re.findall(r"reduce-precision\([^)]*\), exponent_bits=8, "
                         r"mantissa_bits=7", text)
    assert 3 <= len(rounded) <= 6, len(rounded)


def test_wide_deep_train_step_on_planes_compiles_for_v5e(v5e):
    """The whole one-device train step of a planar ``WideDeepStore`` at the
    widths of ``criteo_wide_deep`` (cap 384, 33 channels pulled, 34 pushed,
    two tiles a grid step, a 1024-pair overflow list), the table as 66
    planes: the split kernel pair with the tower between. The v5e compiler
    forms no ``(nb, 66)``, ``(nb, 34)`` or ``(nb, 33)`` array anywhere, and
    neither transposes nor copies anything of a plane's size or more: the
    operand is rounded into place, the pushes stay where the kernel wrote
    them (the overflow rows scattered in place), the 66 planes are donated
    onto the 66 results. The tower's matmuls still carry its name. What
    ``criteo_wide_deep.replay_uniform`` steps."""
    import re
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.learners.store import TableCheckpoint
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    # 1018 tiles keep a plane out of VMEM, as at the cell's 1024
    k, hidden, nb = 32, (1024, 512, 256), 2 * 509 * tilemm.TILE
    store = WideDeepStore(WideDeepConfig(num_buckets=2 * tilemm.TILE, dim=k,
                                         hidden=hidden))
    info = CRec2Info(nnz=39, block_rows=12 * tilemm.RSUB,
                     total_rows=12 * tilemm.RSUB, nb=nb, ovf_cap=1024,
                     subblocks=12, cap=384)
    spec = info.spec
    step = store._tile_step(info, "train", True)
    assert store.step_kernel[0] == "split" and "spill" in store.step_kernel[1]
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mlp = jax.tree.map(lambda a: on(a.shape, a.dtype), store.mlp)
    plane = on(tbl.plane_shape(nb), jnp.float32)
    compiled = step.lower(
        tbl.PlaneTable([plane] * (2 * (1 + k))), mlp, mlp,
        {"pw": on(spec.pairs_shape, jnp.uint32),
         "labels": on((spec.block_rows,), jnp.uint8),
         "ovf_b": on((1024,), jnp.uint32), "ovf_r": on((1024,), jnp.uint32)},
        on((), jnp.int32), on((), jnp.float32),
        on((TableCheckpoint.MACC_LEN,), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    for width in (2 * (1 + k), k + 2, k + 1):
        assert f"[{nb},{width}]" not in text, width
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* "
                     r"(transpose|copy)\(", line)
        if m and np.prod([int(d) for d in m.group(2).split(",")]) >= nb:
            moved.append(line.strip()[:100])
    assert moved == []
    tower = [line for line in text.splitlines()
             if " convolution(" in line and "wd_tower" in line]
    assert len(tower) >= 7, len(tower)
    # the planes are donated onto the results; beside them the step holds
    # the tiled pushes (the operand's buffer is free by then) and little
    # else: nothing table-sized a second time
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (1 + k) * 4 * nb
    assert mem.temp_size_in_bytes < 1.1 * (k + 2) * 4 * nb


def test_wide_deep_spill_step_compiles_at_the_click_log_cells_list_width(v5e):
    """``criteo_wide_deep_clicklog.replay_fields``'s train step for the v5e
    at the cell's own sizes: 66 planes of ``2**24`` buckets, cap 384, the
    published tower, and the mix's 1,638,400-slot COO list (eleven thousand
    times the longest list any other wide&deep cell brings) with its
    distinct buckets beside it in three tiles, as ``put_block`` sends a
    click-log block's 39-41 thousand. The compiler
    accepts it inside the chip's memory beside the cell's 0.35 GB of resident
    blocks with room to spare; the list's two halves keep their names in the
    optimized HLO (each is a jit of its own inside ``wd_pull`` / ``wd_push``,
    so the device trace can tell them from the kernel pair and the tower),
    every gather and scatter of the program sits under one of them, and
    nothing in it is the table stacked as ``(nb, 66)``. A minute."""
    import json
    import os
    import re
    from wormhole_tpu.data.crec import CRec2Info, default_cap
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.learners.store import TableCheckpoint
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "criteo_wide_deep_clicklog", "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "replay_fields.json")) as f:
        room = int(json.load(f)["ovf_cap"])
    k, hidden = int(config["dim"]), tuple(config["hidden"])
    nb = int(config["num_buckets"])
    assert (k, hidden, nb, room) == (32, (1024, 512, 256), 1 << 24, 1638400)
    store = WideDeepStore(WideDeepConfig(num_buckets=2 * tilemm.TILE, dim=k,
                                         hidden=hidden))
    info = CRec2Info(nnz=39, block_rows=12 * tilemm.RSUB,
                     total_rows=12 * tilemm.RSUB, nb=nb, ovf_cap=room,
                     subblocks=12, cap=default_cap(39, nb))
    assert info.cap == config["tile"]["cap"]
    spec = info.spec
    step = store._tile_step(info, "train", True)
    assert store.step_kernel[0] == "split" and "spill" in store.step_kernel[1]
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    mlp = jax.tree.map(lambda a: on(a.shape, a.dtype), store.mlp)
    plane = on(tbl.plane_shape(nb), jnp.float32)
    compiled = step.lower(
        tbl.PlaneTable([plane] * (2 * (1 + k))), mlp, mlp,
        {"pw": on(spec.pairs_shape, jnp.uint32),
         "labels": on((spec.block_rows,), jnp.uint8),
         "ovf_b": on((room,), jnp.uint32), "ovf_r": on((room,), jnp.uint32),
         "ovf_d": on((3 * tilemm.TILE,), jnp.uint32),
         "ovf_k": on((room,), jnp.uint32)},
        on((), jnp.int32), on((), jnp.float32),
        on((TableCheckpoint.MACC_LEN,), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    for phase in ("wd_ovf_pull", "wd_ovf_scatter", "wd_table_update",
                  "wd_tower"):
        assert re.search(r"jit\(%s\)" % phase, text), phase
    # two gathers a plane under the list's pull (the distinct buckets'
    # values from the plane, the slots' from those), k + 2 plane
    # scatter-adds under its scatter (a list this long goes a plane at a
    # time), and no gather or scatter anywhere else in the step
    for op, phase, n in (("gather", "wd_ovf_pull", 2 * (1 + k)),
                         ("scatter", "wd_ovf_scatter", k + 2)):
        lines = [ln for ln in text.splitlines()
                 if re.search(r" = \S+ %s\(" % op, ln)]
        assert all("jit(wd_ovf_" in ln for ln in lines), op
        assert sum("jit(%s)" % phase in ln for ln in lines) >= n, op
    assert not re.findall(r"f32\[%d,\d+\]" % nb, text)
    # the 66 planes are donated onto the 66 results; arguments and
    # temporaries (the 34 push planes, the list's gathered rows and duals)
    # leave a third of the chip's 15.75 GB free
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (1 + k) * 4 * nb
    assert mem.temp_size_in_bytes < 5.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9


@pytest.mark.parametrize("vtiles", [96, 112])
def test_wide_deep_hot_spill_step_compiles_at_the_click_log_cells_rooms(
        v5e, vtiles):
    """``criteo_wide_deep_clicklog.replay_fields``'s TRAIN step as it runs
    since ISSUE 52, for the v5e at the cell's own sizes: 66 planes of
    ``2**24`` buckets, cap 384, the published tower, and the block's list
    in its hot form at the two rooms the mix's seeds land on (three hot
    tiles of 96 or 112 virtual tiles each: ``HotRoom`` on the host). The
    list's two halves hold the hot kernel pair under their own names, a
    call a part: three Mosaic calls of 33 channels under ``wd_ovf_pull``
    and three of 34 under ``wd_ovf_scatter`` beside the main pair's two,
    which is how ``wd_overflow_ms_per_step.replay`` and
    ``kernel_ms_per_step.replay`` find them. Nothing in the program is as
    long as the COO list's 1,638,400 slots: every gather and scatter sits
    under one of the two names and moves a hot tile's 49,152 slots. Two
    minutes a room."""
    import json
    import os
    import re
    from wormhole_tpu.data.crec import CRec2Info, default_cap
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.learners.store import TableCheckpoint
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "criteo_wide_deep_clicklog", "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "replay_fields.json")) as f:
        room = int(json.load(f)["ovf_cap"])
    k, hidden = int(config["dim"]), tuple(config["hidden"])
    nb = int(config["num_buckets"])
    store = WideDeepStore(WideDeepConfig(num_buckets=2 * tilemm.TILE, dim=k,
                                         hidden=hidden))
    info = CRec2Info(nnz=39, block_rows=12 * tilemm.RSUB,
                     total_rows=12 * tilemm.RSUB, nb=nb, ovf_cap=room,
                     subblocks=12, cap=default_cap(39, nb))
    spec = info.spec
    step = store._tile_step(info, "train", True)
    assert store.step_kernel[0] == "split" and "spill" in store.step_kernel[1]
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tiles = 3
    hs = tilemm.hot_spec(tiles * vtiles, spec.subblocks)
    assert tilemm._hot_calls(hs, 1 + k) == tilemm._hot_calls(hs, k + 2) == 3
    u, pw = _hot_form(spec, tiles, vtiles)
    mlp = jax.tree.map(lambda a: on(a.shape, a.dtype), store.mlp)
    plane = on(tbl.plane_shape(nb), jnp.float32)
    compiled = step.lower(
        tbl.PlaneTable([plane] * (2 * (1 + k))), mlp, mlp,
        {"pw": on(spec.pairs_shape, jnp.uint32),
         "labels": on((spec.block_rows,), jnp.uint8),
         "ovf_u": on(*u), "ovf_pw": on(*pw)},
        on((), jnp.int32), on((), jnp.float32),
        on((TableCheckpoint.MACC_LEN,), jnp.float32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if re.search(r" = \S+ custom-call\(", ln)
             and "tpu_custom_call" in ln]
    assert len(calls) == 2 + 3 + 3, len(calls)
    for phase, ch in (("wd_ovf_pull", 1 + k), ("wd_ovf_scatter", k + 2)):
        mine = [ln for ln in calls if "jit(%s)" % phase in ln]
        assert len(mine) == 3, (phase, len(mine))
        assert all("%d]" % (ch * tilemm.B_LO) in ln.split(" custom-call(")[0]
                   for ln in mine), phase
    for op in ("gather", "scatter"):
        lines = [ln for ln in text.splitlines()
                 if re.search(r" = \S+ %s\(" % op, ln)]
        assert lines and all("jit(wd_ovf_" in ln for ln in lines), op
    assert str(room) not in text
    assert not re.findall(r"f32\[%d,\d+\]" % nb, text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (1 + k) * 4 * nb
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
    print("memory", vtiles, mem.argument_size_in_bytes / 1e9,
          mem.temp_size_in_bytes / 1e9)
