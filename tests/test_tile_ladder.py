"""The one-device tile step's ladder (ISSUE 50): ``TableCheckpoint._tile_step``
is written once for the three stores, and a store supplies its model half
(``_tile_body``). Held here for every variant the ladder can build, store x
kind x list x kernel: the ``(kernel, why, one-hot cache)`` record is what
each store's own ladder gave (stated from ``tilemm.resolve_step_kernel``
and the in-place rule, not read back from the store), the step's program
names exactly the variant's phase jits (the device trace files an op under
them, and the benchmark's readers find the ops there), the train step
donates table, tower, clock and accumulator and the eval step nothing, and
a second call with the same key gives the cached function."""

import re

import jax
import jax.numpy as jnp
import pytest

from wormhole_tpu.learners import store as store_mod
from wormhole_tpu.learners import table as tbl
from wormhole_tpu.learners.store import TableCheckpoint
from wormhole_tpu.models import fm as fm_mod
from wormhole_tpu.ops import overflow, tilemm

from test_table_planes import _fm_store, _store, _wd_store
from test_tilemm_fused import SPEC, make_info

OC = 1536
EVAL = ("split", "eval is forward-only",
        "onehot_cache=off:eval is forward-only")
# every jit a one-device tile step nests, by store (the linear store's and
# FM's: the list's pull, the list's scatter, the update pass)
PHASES = {
    "linear": ("tile_ovf_gather", "tile_ovf_scatter", "tile_table_update"),
    "fm": ("fm_ovf_pull", "fm_ovf_scatter", "fm_table_update"),
    "wide_deep": ("wd_pull", "wd_ovf_pull", "wd_tower", "wd_push",
                  "wd_ovf_scatter", "wd_table_update", "wd_dense_update"),
}
MAKE = {"linear": _store, "fm": _fm_store, "wide_deep": _wd_store}
DIM = 4                        # of the two embedding stores' factors


def _sd(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _block(lst: str) -> dict:
    block = {"pw": _sd(SPEC.pairs_shape, jnp.uint32),
             "labels": _sd((SPEC.block_rows,), jnp.uint8)}
    if lst == "coo":
        block.update({k: _sd((OC,), jnp.uint32) for k in overflow.COO})
    elif lst == "hot":
        hs = tilemm.hot_spec(8, SPEC.subblocks)
        block.update(zip(overflow.HOT, (_sd((tilemm.TILE,), jnp.uint32),
                                        _sd(hs.pairs_shape, jnp.uint32))))
    return block


def _expected(name: str, kind: str, lst: str, kernel: str):
    """(record, phase jits) of the variant, from the rules the three
    ladders each spelled."""
    oc = OC if lst != "none" else 0
    args = {"linear": {}, "fm": {"channels": DIM + 2},
            "wide_deep": {"deep": True, "dim": DIM, "hidden": (16, 8),
                          "channels": DIM + 2}}[name]
    res = tilemm.resolve_step_kernel(kernel, ovf_cap=oc, spec=SPEC,
                                     onehot_cache="auto", **args)
    fused = res.kernel == "fused" and kind == "train"
    in_place = fused and oc == 0 and name != "wide_deep"
    why = {"linear": store_mod.IN_PLACE, "fm": fm_mod.IN_PLACE}
    record = EVAL if kind == "eval" else (
        "fused" if fused else "split",
        why[name] if in_place else res.why, res.cache_record)
    if name == "wide_deep":
        # the forward half, the list's pull inside wd_pull
        phases = {"wd_pull", "wd_tower"} | ({"wd_ovf_pull"} if oc else set())
        if kind == "train":
            phases = {"wd_table_update", "wd_dense_update"} | (
                set() if fused else phases | {"wd_push"} | (
                    {"wd_ovf_scatter"} if oc else set()))
    else:
        pull, scatter, update = PHASES[name]
        phases = {pull} if oc else set()
        if kind == "train":
            phases = set() if in_place else phases | {update} | (
                {scatter} if oc else set())
    return record, phases


CASES = [(name, kind, lst, kernel)
         for name in PHASES for kind in ("train", "eval")
         for lst in (("none", "coo") if name == "wide_deep"
                     else ("none", "coo", "hot"))
         for kernel in ("fused", "split")]


@pytest.mark.parametrize("name,kind,lst,kernel", CASES,
                         ids=["-".join(c) for c in CASES])
def test_the_ladder_builds_each_stores_variant(name, kind, lst, kernel):
    store = MAKE[name](SPEC.nb, kernel)
    assert isinstance(store, TableCheckpoint)
    assert type(store)._tile_step is TableCheckpoint._tile_step
    info = make_info(SPEC, ovf_cap=OC if lst != "none" else 0)
    step = store._tile_step(info, kind, lst != "none")
    record, phases = _expected(name, kind, lst, kernel)
    assert store.step_kernel == record
    # the cache is keyed (info, kind, spill): the same function, and the
    # record of THIS variant after another was asked for in between
    store._tile_step(info, "eval" if kind == "train" else "train",
                     lst != "none")
    assert store._tile_step(info, kind, lst != "none") is step
    assert store.step_kernel == record

    train = kind == "train"
    table = tbl.PlaneTable(
        [_sd(tbl.plane_shape(SPEC.nb), jnp.float32)] * store.slots.shape[1])
    extra = jax.tree.map(lambda a: _sd(a.shape, a.dtype),
                         store._tile_extra(train))
    clock = (_sd((), jnp.int32), _sd((), jnp.float32),
             _sd((TableCheckpoint.MACC_LEN,), jnp.float32)) if train else ()
    lowered = step.lower(table, *extra, _block(lst), *clock)
    text = lowered.as_text()
    assert text.startswith("module @jit_step ")
    named = set(re.findall(r"func\.func private @(\w+)\(", text))
    assert named & {p for ps in PHASES.values() for p in ps} == phases
    # donated: the table, the store's own state, the clock and the
    # accumulator, where the step returns them; never the block or tau
    args = lowered.args_info[0]
    donated = [all(leaf.donated for leaf in jax.tree.leaves(a))
               for a in args]
    kept = [not any(leaf.donated for leaf in jax.tree.leaves(a))
            for a in args]
    n = len(extra)
    if train:
        assert donated == [True] * (1 + n) + [False, True, False, True]
        assert kept == [not d for d in donated]
    else:
        assert kept == [True] * (2 + n)
