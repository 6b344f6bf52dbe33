"""The linear store's whole ``data:2,model:2`` mesh train step compiled for
a DESCRIBED TPU v5e 2x2, without a chip (see ``test_tpu_compile.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest

from wormhole_tpu.ops import tilemm

from tpu_compile_helpers import (CRITEO, NB, _ftrl, _hot_form,  # noqa: F401
                                 compiled_not_interpreted, v5e)


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
