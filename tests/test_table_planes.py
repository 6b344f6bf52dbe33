"""The table kept as slot planes in the tile kernels' layout
(learners/table.py), and the store's door between that form and the
``(nb, slots)`` array: planar tile steps against the split oracle, the
identity of the crossing, the checkpoint's bytes, the benchmark's probes,
and a structural guard on what the in-place step does around its kernel."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from wormhole_tpu.learners import table as tbl
from wormhole_tpu.learners.handles import LearnRate, create_handle
from wormhole_tpu.learners.store import (IN_PLACE, ShardedStore,
                                         StoreConfig, TableCheckpoint)
from wormhole_tpu.ops import tilemm
from wormhole_tpu.ops.penalty import L1L2

from test_tilemm_fused import (SPEC, SPECK2, make_block, make_info,
                               make_spill_block)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OC = 1536


def _store(nb, kernel="fused", algo="ftrl", **cfg):
    return ShardedStore(
        StoreConfig(num_buckets=nb, loss="logit", tile_step_kernel=kernel,
                    **cfg),
        create_handle(algo, L1L2(0.05, 0.1), LearnRate(0.1, 1.0)))


def _crossings(store) -> int:
    return store.timer.counts.get("table_cross", 0)


def _blocks(rng, spec, n, spill):
    out = []
    for _ in range(n):
        if spill:
            pw, labels, ob, orow = make_spill_block(rng, spec, oc=OC)
            out.append({"pw": pw, "labels": labels, "ovf_b": ob,
                        "ovf_r": orow})
        else:
            # few enough pairs that none passes a narrow spec's cap
            pw, labels = make_block(rng, spec,
                                    n_pairs=3000 if spec.cap > 1000 else 200)
            out.append({"pw": pw, "labels": labels})
    return out


# -- (a) planar steps against the split path ---------------------------------

@pytest.mark.parametrize("spec,spill,why", [
    pytest.param(SPEC, False, IN_PLACE, id="fused_update"),
    pytest.param(SPECK2, False, IN_PLACE, id="fused_update-K2"),
    pytest.param(SPEC, True, "", id="fused_spill"),
])
def test_planar_steps_match_the_split_oracle(spec, spill, why):
    """Five steps from a random table: w, z, cg and the margins bitwise
    what the split kernel pair and the XLA update give; the progress
    number sum((w_new - w_old)**2) to rounding (in the in-place kernel it
    is summed a lane at a time). The fused store's table stays planes."""
    rng = np.random.default_rng(29)
    info = make_info(spec, ovf_cap=OC if spill else 0)
    start = (rng.standard_normal((spec.nb, 3)) * 0.1).astype(np.float32)
    start[:, 2] = np.abs(start[:, 2])
    blocks = _blocks(rng, spec, 5, spill)
    fused, split = _store(spec.nb, "fused"), _store(spec.nb, "split")
    for st in (fused, split):
        st.slots = jnp.asarray(start)
    for blk in blocks:
        dev = jax.device_put(blk)
        rows = []
        for st in (fused, split):
            st.tile_train_step(dev, info)
            rows.append(st.fetch_metrics())
        # [objv, num_ex, acc, wdelta2, pos, neg]: the margins decide all
        # but wdelta2
        np.testing.assert_array_equal(np.delete(rows[0], 3),
                                      np.delete(rows[1], 3))
        assert rows[1][3] > 0
        np.testing.assert_allclose(rows[0][3], rows[1][3], rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(fused.tile_eval_step(dev, info)[5]),
            np.asarray(split.tile_eval_step(dev, info)[5]))
    assert fused.step_kernel[0] == "split"        # the eval step's record
    fused._tile_step(info, "train", spill)
    assert fused.step_kernel[:2] == ("fused", why)
    for st in (fused, split):
        assert isinstance(st.slots, tbl.PlaneTable)
        assert _crossings(st) == 1               # the assigned start table
    np.testing.assert_array_equal(np.asarray(fused.slots),
                                  np.asarray(split.slots))
    assert np.any(np.asarray(fused.slots) != start)


def test_other_handles_step_on_planes():
    """A handle without an unstacked update goes through push() on the
    stacked planes inside the step; the touched-bucket mask holds."""
    rng = np.random.default_rng(3)
    info = make_info(SPEC)
    blocks = _blocks(rng, SPEC, 2, False)
    planar = _store(SPEC.nb, "fused", algo="adagrad")
    stacked = _store(SPEC.nb, "fused", algo="adagrad",
                     param_dtype="bfloat16")
    assert isinstance(planar.slots, tbl.PlaneTable)
    assert not isinstance(stacked.slots, tbl.PlaneTable)
    for blk in blocks:
        planar.tile_train_step(jax.device_put(blk), info)
        stacked.tile_train_step(jax.device_put(blk), info)
    got = np.asarray(planar.slots)
    assert got.shape == (SPEC.nb, 2) and np.any(got != 0)
    untouched = np.asarray(stacked.slots.astype(jnp.float32))[:, 1] == 0
    assert np.all(got[untouched] == 0) and _crossings(planar) == 0


def test_a_stacked_table_keeps_its_overflow_lists():
    """param_dtype=bfloat16 keeps the (nb, slots) table and the one tile
    step it had: put_block leaves its blocks whole, empty lists too."""
    rng = np.random.default_rng(4)
    info = make_info(SPEC, ovf_cap=OC)
    store = _store(SPEC.nb, param_dtype="bfloat16")
    for blk in _blocks(rng, SPEC, 2, False):
        dev = store.put_block(dict(
            blk, ovf_b=np.full(OC, 0xFFFFFFFF, np.uint32),
            ovf_r=np.zeros(OC, np.uint32)))
        assert "ovf_b" in dev
        store.tile_train_step(dev, info)
    assert store.step_kernel[:2] == ("fused", "")
    assert store.slots.dtype == jnp.bfloat16 and store.slots.shape == (
        SPEC.nb, 3)
    assert np.any(np.asarray(store.slots.astype(jnp.float32)) != 0)
    assert _crossings(store) == 0


def test_an_empty_overflow_list_stays_on_the_host():
    """put_block leaves an overflow list with no pair behind, and the
    block then takes the in-place step; one pair keeps the spill step."""
    rng = np.random.default_rng(5)
    info = make_info(SPEC, ovf_cap=OC)
    pw, labels = make_block(rng, SPEC)
    empty = {"pw": pw, "labels": labels,
             "ovf_b": np.full(OC, 0xFFFFFFFF, np.uint32),
             "ovf_r": np.zeros(OC, np.uint32)}
    (spilled,) = _blocks(rng, SPEC, 1, True)
    a, b = _store(SPEC.nb), _store(SPEC.nb)
    dev = a.put_block(empty)
    assert sorted(dev) == ["labels", "pw"]
    a.tile_train_step(dev, info)
    assert a.step_kernel[:2] == ("fused", IN_PLACE)
    b.tile_train_step(jax.device_put(empty), info)       # the spill step
    assert b.step_kernel[:2] == ("fused", "")
    np.testing.assert_array_equal(np.asarray(a.slots), np.asarray(b.slots))
    dev = a.put_block(spilled)
    assert "ovf_b" in dev
    a.tile_train_step(dev, info)
    assert a.step_kernel[:2] == ("fused", "")


# -- (b) the crossing and the checkpoint -------------------------------------

def test_stacked_to_planes_and_back_is_the_identity():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((3 * tilemm.TILE, 3)).astype(np.float32)
    planes = tbl.to_planes(jnp.asarray(x))
    assert [p.shape for p in planes.planes] == [(3, 128, 128)] * 3
    np.testing.assert_array_equal(np.asarray(tbl.to_stacked(planes)), x)
    np.testing.assert_array_equal(np.asarray(planes), x)
    assert planes.shape == x.shape and planes.dtype == np.float32


INDEXINGS = {
    "rows_of_col0": lambda t, i: t[i, 0],
    "all_of_col": lambda t, i: t[:, 2],
    "ellipsis_col": lambda t, i: t[..., 1],
    "first_row": lambda t, i: t[:1],
    "keys": lambda t, i: t[i],
    "col_slice": lambda t, i: t[i, :2],
    "at_set": lambda t, i: t.at[3, 0].set(-1.0),
    "zeros_like": lambda t, i: jnp.zeros_like(t),
    "astype": lambda t, i: np.asarray(t.astype("float32")),
    "as_float64": lambda t, i: np.asarray(t, np.float64),
}


@pytest.mark.parametrize("form", sorted(INDEXINGS))
def test_a_plane_table_reads_like_the_stacked_array(form):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2 * tilemm.TILE, 3))
                    .astype(np.float32))
    idx = jnp.asarray([5, 0, 20000, 5], jnp.int32)
    read = INDEXINGS[form]
    got, want = read(tbl.to_planes(x), idx), read(x, idx)
    assert np.shape(got) == np.shape(want)
    assert np.asarray(got).dtype == np.asarray(want).dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_checkpoint_bytes_are_those_of_the_stacked_table(tmp_path):
    """A planar store's checkpoint file is byte for byte what the same
    state written as one (nb, slots) array gives (the format before the
    planes), and it loads back into a store that goes on stepping."""
    from wormhole_tpu.parallel.checkpoint import Checkpointer
    rng = np.random.default_rng(7)
    info = make_info(SPEC)
    blocks = [jax.device_put(b) for b in _blocks(rng, SPEC, 3, False)]
    store = _store(SPEC.nb)
    for blk in blocks[:2]:
        store.tile_train_step(blk, info)
    assert isinstance(store.slots, tbl.PlaneTable)
    planar, stacked = tmp_path / "planar", tmp_path / "stacked"
    Checkpointer(str(planar)).save(2, store.state_pytree())
    Checkpointer(str(stacked)).save(
        2, {"slots": jnp.asarray(np.asarray(store.slots)),
            "t": np.int64(store.t)})
    name = "ckpt_v2.msgpack"
    assert (planar / name).read_bytes() == (stacked / name).read_bytes()
    assert _crossings(store) == 0         # stacked on the host, not here

    fresh = _store(SPEC.nb)
    ver, state = Checkpointer(str(planar)).load(fresh.state_pytree())
    assert ver == 2
    fresh.restore_pytree(state)
    assert fresh.t == store.t
    np.testing.assert_array_equal(np.asarray(fresh.slots),
                                  np.asarray(store.slots))
    store.tile_train_step(blocks[2], info)
    fresh.tile_train_step(blocks[2], info)
    assert _crossings(fresh) == 1         # the restored array, taken across
    np.testing.assert_array_equal(np.asarray(fresh.slots),
                                  np.asarray(store.slots))


def test_paths_that_want_the_array_cross_and_are_counted():
    """The sparse step asks for (nb, slots) and gets it, once; the next
    tile step takes the table back; both crossings are on the timer and
    the state is what a store that never left the array computes."""
    from wormhole_tpu.data.feed import SparseBatch
    rng = np.random.default_rng(11)
    info = make_info(SPEC)
    blocks = [jax.device_put(b) for b in _blocks(rng, SPEC, 2, False)]
    keys = np.arange(0, 64, dtype=np.int32) * 97
    batch = SparseBatch(
        cols=jnp.asarray(rng.integers(0, 64, (32, 4)), jnp.int32),
        vals=jnp.ones((32, 4), jnp.float32),
        labels=jnp.asarray(rng.integers(0, 2, 32), jnp.float32),
        row_mask=jnp.ones(32, jnp.float32), uniq_keys=jnp.asarray(keys),
        key_mask=jnp.ones(64, jnp.float32))
    planar = _store(SPEC.nb)
    stacked = _store(SPEC.nb, param_dtype="float32")
    stacked._planar = False                      # the table as it was
    stacked.slots = jnp.asarray(np.asarray(stacked.slots))
    for st in (planar, stacked):
        st.tile_train_step(blocks[0], info)
        st.train_step(batch)
        st.tile_train_step(blocks[1], info)
    assert _crossings(planar) == 2 and _crossings(stacked) == 0
    assert planar.timer.totals["table_cross"] > 0
    np.testing.assert_array_equal(np.asarray(planar.slots),
                                  np.asarray(stacked.slots))


# -- (c) the benchmark's probes, as they are ---------------------------------

@pytest.fixture(scope="module")
def probed():
    """A planar store after tile steps, and the benchmark's own hooks."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.configs.criteo_ftrl import system as hooks
    rng = np.random.default_rng(13)
    info = make_info(SPEC)
    store = _store(SPEC.nb)
    for blk in _blocks(rng, SPEC, 3, False):
        store.tile_train_step(jax.device_put(blk), info)
    return types.SimpleNamespace(store=store), hooks


@pytest.mark.parametrize("probe,col", [("grad_norms", 2),
                                       ("change_norms", 0)])
def test_benchmark_norm_probes_agree_with_numpy(probed, probe, col):
    app, hooks = probed
    before = _crossings(app.store)
    got = getattr(hooks, probe)(app, {}, 0)["w"]
    want = np.sqrt(np.sum(np.asarray(app.store.slots, np.float64)[:, col]
                          ** 2))
    assert want > 0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert _crossings(app.store) == before


def test_benchmark_state_probe_and_fence_cross_nothing(probed):
    app, hooks = probed
    buckets = np.random.default_rng(0).integers(0, SPEC.nb, 4096)
    got = hooks.state(app, {}, 0, buckets)["w"]
    np.testing.assert_array_equal(
        got, np.asarray(app.store.slots, np.float64)[buckets, 0])
    jax.block_until_ready(app.store.slots)       # benchmark/system.py fence
    assert isinstance(app.store.slots, tbl.PlaneTable)
    assert _crossings(app.store) == 0


# -- (d) what the in-place step does around its kernel -----------------------

def _leaf_eqns(jaxpr):
    """Equations of a jaxpr with calls opened, kernels left closed."""
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        if eqn.primitive.name == "pallas_call" or not inner:
            yield eqn
        else:
            for sub in inner:
                yield from _leaf_eqns(getattr(sub, "jaxpr", sub))


def test_nothing_table_sized_outside_the_kernel():
    """In the fused_update train step no equation but the pallas_call
    has a result of nb elements or more: no slice, cast, stack or
    reduction over the table is left in XLA. (Traced, not run, at a
    table large enough that the metric tail's rows x bins stay under
    it, as they do at the real size.)"""
    from wormhole_tpu.data.crec import CRec2Info
    nb = 1024 * tilemm.TILE
    info = CRec2Info(nnz=0, block_rows=2 * tilemm.RSUB,
                     total_rows=2 * tilemm.RSUB, nb=nb, subblocks=2,
                     cap=128, ovf_cap=0)
    spec = info.spec
    assert spec.fuse > 1                  # the benchmark's kind of spec
    store = _store(SPEC.nb)
    step = store._tile_step(info, "train", False)
    assert store.step_kernel[:2] == ("fused", IN_PLACE)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    plane = like((spec.tiles, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    jaxpr = jax.make_jaxpr(step)(
        tbl.PlaneTable([plane] * 3),
        {"pw": like(spec.pairs_shape, jnp.uint32),
         "labels": like((spec.block_rows,), jnp.uint8)},
        like((), jnp.int32), like((), jnp.float32),
        like((TableCheckpoint.MACC_LEN,), jnp.float32))
    eqns = list(_leaf_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    big = [(e.primitive.name, v.aval.shape) for e in eqns
           if e.primitive.name != "pallas_call"
           for v in e.outvars if v.aval.size >= nb]
    assert big == []
    assert sum(v.aval.size >= nb for v in kernels[0].outvars) == 3
