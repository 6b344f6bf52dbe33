"""Compile the tile kernels for a DESCRIBED TPU v5e, without a chip.

Every other test drives the Pallas kernels through interpret mode, which
cannot see what the chip's compiler refuses: VMEM overruns, slices that
do not align to the (8, 128) tiling, reshapes Mosaic has no lowering for.
libtpu is installed here and compiles for a topology that is described
and not attached (``on-chip-measurement`` guide, section 2.3), so these
tests ask it to compile each kernel a chip run can reach. Nothing
executes; a pass says "the compiler accepts it", never "it ran".

Tier-1 holds the flagship's main-path kernels at the real per-tile
widths (``cap=1408, group=4, subblocks=12``) but two tiles, because
compile time grows with the unrolled ``tiles_step``. The full-geometry
compiles (``nb=2**22, tiles_step=16``) and the variants off the main
path are marked ``slow``: minutes each, run by hand before a chip call
(seconds per case are in CHANGES.md, PR 23).

The cases run one after another in one process — two processes
compiling for the TPU at once collide on libtpu's lock file — and with
the persistent compilation cache off: a described-topology executable
is written to the cache but cannot be read back without a chip.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from wormhole_tpu.ops import histmm, tilemm

NB = 1 << 22                     # the criteo bucket table (bench.py)
CRITEO = dict(subblocks=12, cap=1408)   # 98,304-row crec2 blocks
_BUILDERS = (tilemm._build_fwd, tilemm._build_bwd, tilemm._build_step_grad,
             tilemm._build_step_update, tilemm._build_fwd_multi,
             tilemm._build_bwd_multi, tilemm._build_fm_step_fused,
             tilemm._build_fm_step_update, tilemm._build_wd_step_fused)


@pytest.fixture(scope="module")
def v5e():
    """A described (not attached) v5e host of four chips, 2x2."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu / topology unknown to it
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")


@pytest.fixture(autouse=True)
def compiled_not_interpreted(monkeypatch):
    """Steer the kernels to the Mosaic path (the backend here is the
    CPU, so ``_interpret()`` would pick the interpreter), with the
    builder caches emptied on both sides so no interpret-mode build
    leaks in or out, and the persistent cache off around the compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(tilemm, "_interpret", lambda: False)
    for b in _BUILDERS:
        b.cache_clear()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    for b in _BUILDERS:
        b.cache_clear()


def _ftrl():
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.ops.penalty import L1L2
    return FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))


# -- one builder per kernel: spec -> (jitted fn, argument shapes) -----------

def _pw(spec):
    return (spec.pairs_shape, jnp.uint32)


def _rows(spec, *trail):
    return ((spec.block_rows, *trail), jnp.float32)


def fwd(spec):
    return tilemm._build_fwd(spec), [_pw(spec), ((spec.nb,), jnp.float32)]


def bwd(spec):
    return tilemm._build_bwd(spec), [_pw(spec), _rows(spec)]


def step_grad(spec, cache=False, spill=False, exact_dense=True):
    fn = tilemm._build_step_grad(spec, "logit", exact_dense, cache, spill)
    args = [_pw(spec), ((spec.nb,), jnp.float32), _rows(spec), _rows(spec)]
    return fn, args + ([_rows(spec)] if spill else [])


def step_update(spec, cache=False):
    fn = tilemm._build_step_update(spec, "logit", _ftrl(), cache)
    plane = ((spec.tiles, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    return fn, [_pw(spec), [plane, plane, plane], _rows(spec), _rows(spec)]


def fwd_multi(spec, ch):
    return (tilemm._build_fwd_multi(spec, ch),
            [_pw(spec), ((spec.nb, ch), jnp.float32)])


def bwd_multi(spec, ch):
    return tilemm._build_bwd_multi(spec, ch), [_pw(spec), _rows(spec, ch)]


def _hot_form(spec, tiles, vtiles):
    """``(ovf_u, ovf_pw)`` of a hot form of ``tiles`` hot tiles."""
    hs = tilemm.hot_spec(tiles * vtiles, spec.subblocks)
    return [((tiles * tilemm.TILE,), jnp.uint32),
            (hs.pairs_shape, jnp.uint32)]


def hot_gather(spec, vtiles):
    """The overflow list through the hot tile, pull side: the gather of
    one hot tile and the three-channel pull kernel over its hot form."""
    return (lambda w, u, pw: tilemm.hot_margin_rows(w, u, pw, spec),
            [((spec.nb,), jnp.float32), *_hot_form(spec, 1, vtiles)])


def hot_scatter(spec, vtiles):
    return (lambda g, d, u, pw: tilemm.hot_grad_scatter(g, d, u, pw, spec),
            [((spec.nb,), jnp.float32), _rows(spec),
             *_hot_form(spec, 1, vtiles)])


def fm_hot_pull(spec, k, vtiles):
    """FM's list through the hot tile, pull side: 1 + k plane gathers of
    one hot tile and the pull kernel over 3(k + 2) parts."""
    from wormhole_tpu.ops.loss import opaque_one
    plane = ((spec.tiles, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    return (lambda planes, u, pw: tilemm.fm_hot_pull_rows(
        planes, u, pw, spec, opaque_one(planes[0])),
        [[plane] * (1 + k), *_hot_form(spec, 1, vtiles)])


def fm_hot_push(spec, k, vtiles):
    plane = ((spec.tiles, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    return (lambda push, d, u, pw: tilemm.hot_push_scatter_planes(
        push, d, u, pw, spec),
        [[plane] * (k + 2), _rows(spec, k + 2), *_hot_form(spec, 1, vtiles)])


def fm_step(spec, k, spill=False):
    fn = tilemm._build_fm_step_fused(spec, k, "logit", spill)
    plane = ((spec.tiles, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    args = [_pw(spec), [plane] * (1 + k), _rows(spec), _rows(spec)]
    return fn, args + ([_rows(spec, k + 2)] if spill else [])


def fm_step_update(spec, k):
    from wormhole_tpu.models.fm import FMAdaGrad
    from wormhole_tpu.ops.penalty import L1L2
    fn = tilemm._build_fm_step_update(
        spec, k, "logit", FMAdaGrad(0.05, 1.0, 1e-4, L1L2(0.0, 0.0)))
    plane = ((spec.tiles, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    return fn, [_pw(spec), [plane] * (2 * (1 + k)), _rows(spec),
                _rows(spec)]


def wd_step(spec, k, hidden):
    fn = tilemm._build_wd_step_fused(spec, k, tuple(hidden), "logit")
    sizes = [k, *hidden, 1]
    mlp = {}
    for i, (a, b) in enumerate(zip(sizes, sizes[1:])):
        mlp[f"W{i}"] = ((a, b), jnp.float32)
        mlp[f"b{i}"] = ((b,), jnp.float32)
    return fn, [_pw(spec), ((spec.nb, k + 1), jnp.float32), _rows(spec),
                _rows(spec), mlp]


def gbdt_hist(n, feat, nodes, bins):
    """histmm's matmul level histogram (plain XLA, no Pallas): Higgs is
    28 features wide, depth 6 is 64 nodes, 256 bins."""
    from functools import partial
    fn = partial(histmm._dense_matmul, num_nodes=nodes, num_bins=bins)
    return fn, [((n, feat), jnp.uint8), ((n,), jnp.int32),
                ((n,), jnp.float32), ((n,), jnp.float32),
                ((n,), jnp.float32)]


def _criteo(tiles=None):
    """The flagship geometry; ``tiles`` cuts the table (and with it the
    unrolled tiles_step) for tier-1, per-tile widths unchanged."""
    return tilemm.make_spec(tiles * tilemm.TILE if tiles else NB, **CRITEO)


def _wide_deep(tiles):
    """``criteo_wide_deep``'s geometry (cap 384 at 2**24 buckets), cut to
    ``tiles`` tiles."""
    return tilemm.make_spec(tiles * tilemm.TILE, subblocks=12, cap=384)


def _narrow():
    """The geometry bench.py's cached A/B uses — one subblock of nnz=16
    rows — which ``_onehot_cache_decision`` admits under ``auto``."""
    from wormhole_tpu.data.crec import default_cap
    spec = tilemm.make_spec(NB, 1, default_cap(16, NB))
    assert tilemm.resolve_step_kernel("fused", spec=spec).cache
    return spec


def _high_nb():
    """A K>1 spec from make_spec's cap <= 256 regime (nb = 2**26)."""
    spec = tilemm.make_spec(1 << 26, 12, 128)
    assert spec.fuse > 1
    return spec


def _case(build, id, slow=False, pallas=True):
    return pytest.param(build, pallas, id=id,
                        marks=[pytest.mark.slow] if slow else [])


CASES = [
    # tier-1: the path chip_smoke.py takes, at two tiles. A crec2 file
    # from the normal writer carries ovf_cap > 0, so the trainer's step
    # is step_grad(spill); step_update is the ovf_cap == 0 variant.
    _case(lambda: fwd(_criteo(2)), "fwd-2tiles"),
    _case(lambda: bwd(_criteo(2)), "bwd-2tiles"),
    _case(lambda: step_grad(_criteo(2), spill=True),
          "step_grad_spill-2tiles"),
    _case(lambda: step_update(_criteo(2)), "step_update-2tiles"),
    # the wide&deep cell's split pair at its own per-tile widths (cap 384
    # at 2**24, 33 channels pulled, 34 pushed, tiles_step 2), two tiles
    _case(lambda: fwd_multi(_wide_deep(2), 33), "fwd_multi-wd32-2tiles"),
    _case(lambda: bwd_multi(_wide_deep(2), 34), "bwd_multi-wd32-2tiles"),
    # the skewed cells' overflow lists through the hot tile (PR 42): the
    # kernels' per-step widths are the real ones (12 subblocks, 512 slots
    # a cell, 3 channels, two tiles a step); 8 virtual tiles for 224-256
    _case(lambda: hot_gather(_criteo(2), 8), "hot_gather-8vtiles"),
    _case(lambda: hot_scatter(_criteo(2), 8), "hot_scatter-8vtiles"),
    # FM's list through the same pair (PR 48): ten float32 channels as
    # thirty bfloat16 parts, two tiles a step, 12 subblocks of 512 slots
    _case(lambda: fm_hot_pull(_criteo(2), 8, 8), "fm_hot_pull-k8-8vtiles"),
    _case(lambda: fm_hot_push(_criteo(2), 8, 8), "fm_hot_push-k8-8vtiles"),
    # by hand before a chip call: full geometry, and the other variants
    _case(lambda: fwd(_criteo()), "fwd-criteo", slow=True),
    _case(lambda: bwd(_criteo()), "bwd-criteo", slow=True),
    _case(lambda: step_grad(_criteo(), spill=True),
          "step_grad_spill-criteo", slow=True),
    _case(lambda: step_grad(_criteo()), "step_grad-criteo", slow=True),
    _case(lambda: step_update(_criteo()), "step_update-criteo", slow=True),
    _case(lambda: bwd(_high_nb()), "bwd-K8", slow=True),
    _case(lambda: step_update(_high_nb()), "step_update-K8", slow=True),
    _case(lambda: step_grad(_narrow(), cache=True), "step_grad-cached",
          slow=True),
    _case(lambda: step_update(_narrow(), cache=True), "step_update-cached",
          slow=True),
    _case(lambda: fwd_multi(_criteo(), 10), "fwd_multi-fm8", slow=True),
    _case(lambda: bwd_multi(_criteo(), 10), "bwd_multi-fm8", slow=True),
    _case(lambda: fm_step(_criteo(), 8), "fm_step-k8", slow=True),
    _case(lambda: fm_step(_criteo(), 8, spill=True), "fm_step_spill-k8",
          slow=True),
    _case(lambda: fm_step_update(_criteo(), 8), "fm_step_update-k8",
          slow=True),
    _case(lambda: wd_step(_criteo(), 16, (64, 32)), "wd_step-16x64x32",
          slow=True),
    _case(lambda: gbdt_hist(1_000_000, 28, 64, 256), "gbdt_hist-higgs",
          slow=True, pallas=False),
]


@pytest.mark.parametrize("build,pallas", CASES)
def test_compiles_for_v5e(build, pallas, v5e):
    fn, shapes = build()
    one_chip = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], sharding=one_chip),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    compiled = jax.jit(fn).lower(*args).compile()
    if pallas:
        assert "tpu_custom_call" in compiled.as_text(), \
            "no Mosaic kernel in the compiled program"
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def _table_sized_fusions(text: str, nb_local: int) -> list:
    """(results, operands) of every fusion of the entry computation that
    makes or reads an f32 array of one table column's size and shape
    (flat, a plane, or (nb_local, slots)) outside the two list jits:
    how many such arrays it writes and how many it reads."""
    import re
    from wormhole_tpu.learners import table as tbl
    column = {"f32[%d]" % nb_local,
              "f32[%d,%d,%d]" % tbl.plane_shape(nb_local)}
    column |= {"f32[%d,%d]" % (nb_local, k) for k in (1, 3)}
    entry = text[text.index("ENTRY"):]
    made, fusions = {}, []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\((.*)",
                     line)
        if not m:
            continue
        name, result, op, rest = m.groups()
        made[name] = result
        if op == "fusion" and not re.search(
                r"jit\(mesh_ovf_(gather|scatter)\)", line):
            fusions.append((result, rest.split("), kind=")[0]))

    def count(shapes: str) -> int:
        return sum(s in column for s in re.findall(r"f32\[[\d,]*\]", shapes))

    out = []
    for result, operands in fusions:
        reads = sum(count(made.get(o.strip(), ""))
                    for o in operands.split(",") if o.strip() in made)
        if count(result) or reads:
            out.append((count(result), reads))
    return out


@pytest.mark.parametrize("form", ["coo", "hot"])
@pytest.mark.parametrize("nb", [
    pytest.param(4 * tilemm.TILE, id="2tiles-a-shard"),
    pytest.param(NB, id="criteo", marks=pytest.mark.slow)])
def test_mesh_step_compiles_for_v5e_2x2(nb, form, v5e):
    """The whole ``data:2,model:2`` train step of the flagship store —
    shard_map, the split fwd/bwd kernels on each model shard, the psums
    — for the four described chips, with the NamedShardings the mesh
    feed places its groups on and the table as the store keeps it on a
    mesh: one plane a slot, each split over MODEL on its tile axis. What
    ``chip_smoke.py --chips 4`` runs.

    Around the kernels and the list's two jits the compiler leaves ONE
    table-sized fusion: the push, which reads the shard's three planes
    and the summed gradient and writes the three planes. Nothing is
    shaped like a stacked shard or a column sliced out of one (the
    stacked step had three such fusions: the slice of w, the push, the
    concatenate: PERF.md, PR 45).

    ``hot``: the group's lists crossed in their hot form a shard (ISSUE
    49), a chip's own hot tile and 104 virtual tiles of rank words (the
    click log's half lists), and the list's two jits hold the hot kernel
    pair: two more Mosaic calls, filed under the jits' names."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from wormhole_tpu.data.crec import CRec2Info
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.learners.store import (ShardedStore, StoreConfig,
                                             TableCheckpoint,
                                             mesh_step_specs)
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    shape = "data:2,model:2"
    # the store places its table when built, which a described device
    # cannot hold: build it on four host devices, then hand the step
    # builder the described mesh
    store = ShardedStore(
        StoreConfig(num_buckets=nb), _ftrl(),
        MeshRuntime(mesh=make_mesh(shape, jax.devices()[:4])))
    assert isinstance(store.slots, tbl.PlaneTable)
    store.rt = MeshRuntime(mesh=make_mesh(shape, v5e.devices))
    spec = tilemm.make_spec(nb, **CRITEO)
    oc = 1024                                   # CRec2Writer's default
    info = CRec2Info(nnz=39, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb,
                     ovf_cap=oc, **CRITEO)
    hot = form == "hot"
    step = store._tile_step_mesh(info, "train", hot)
    mesh = store.rt.mesh
    Pm, Pblk, specs = mesh_step_specs(True, planes=True, hot=hot)
    lane = P("data", None)

    def on(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    compiled = step.lower(
        tbl.PlaneTable([on(tbl.plane_shape(nb), jnp.float32, Pm)] * 3),
        on((2, *spec.pairs_shape), jnp.uint32, Pblk),
        on((2, spec.block_rows), jnp.uint8, lane),
        *(on((2, 2, *shape), jnp.uint32, sp) for (shape, _), sp in zip(
            _hot_form(spec, 1, 104), specs[3:])) if hot else
        (on((2, oc), jnp.uint32, lane), on((2, oc), jnp.uint32, lane)),
        on((), jnp.int32, P()), on((), jnp.float32, P()),
        on((TableCheckpoint.MACC_LEN,), jnp.float32, P())).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    kernels = [line for line in text.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    in_lists = [line for line in kernels
                if re.search(r"jit\(mesh_ovf_(gather|scatter)\)", line)]
    assert (len(kernels), len(in_lists)) == ((4, 2) if hot else (2, 0))
    # the list's two phases are jits of their own: the compiler keeps
    # their names on the ops it makes of them, which is what the device
    # trace files an op under (overflow_ms_per_step.mesh reads them)
    assert "jit(mesh_ovf_gather)" in text and "jit(mesh_ovf_scatter)" in text
    nb_local = nb // 2
    for gone in ("f32[%d,3]" % nb_local, "f32[%d,1]" % nb_local):
        assert gone not in text, gone
    # the push: three planes out; three planes and the gradient in
    assert _table_sized_fusions(text, nb_local) == [(3, 4)]
    # the planes are donated onto the new planes
    assert compiled.memory_analysis().alias_size_in_bytes >= 3 * 4 * nb_local


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
        # the list as FMStore.put_block ships it: with its distinct
        # buckets (two tiles hold a click-log list's 25,000) and each
        # slot's index in them
        block.update(ovf_b=on((room,), jnp.uint32),
                     ovf_r=on((room,), jnp.uint32),
                     ovf_u=on((2 * tilemm.TILE,), jnp.uint32),
                     ovf_k=on((room,), jnp.uint32))
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
