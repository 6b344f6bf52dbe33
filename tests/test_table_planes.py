"""The table kept as slot planes in the tile kernels' layout
(learners/table.py), and the store's door between that form and the
``(nb, slots)`` array: planar tile steps against the split oracle, the
identity of the crossing, the checkpoint's bytes, the benchmark's probes,
and a structural guard on what the in-place step does around its kernel."""

import functools
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
from wormhole_tpu.models import fm as fm_model
from wormhole_tpu.ops import tilemm
from wormhole_tpu.ops.penalty import L1L2

from test_tilemm_fused import (SPEC, SPECK2, make_block, make_info,
                               make_pairs, make_spill_block)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OC = 1536
# step_kernel's second field for a wide&deep block with an overflow list
WD_SPILL = ("wide&deep spill needs the pull channels in HBM for the COO "
            "scatter between the phases")


def _store(nb, kernel="fused", algo="ftrl", **cfg):
    return ShardedStore(
        StoreConfig(num_buckets=nb, loss="logit", tile_step_kernel=kernel,
                    **cfg),
        create_handle(algo, L1L2(0.05, 0.1), LearnRate(0.1, 1.0)))


def _fm_store(nb, kernel="fused", dim=4, rt=None):
    from wormhole_tpu.models.fm import FMConfig, FMStore
    return FMStore(FMConfig(num_buckets=nb, dim=dim, loss="logit", l1=0.5,
                            l2=0.05, seed=7, tile_step_kernel=kernel), rt)


def _wd_store(nb, kernel="split", dim=4, rt=None, **cfg):
    """Wide&deep at a rate three steps from a random table stay finite
    at; ``split`` is what the published widths resolve on the chip (the
    in-kernel tower wants more VMEM than there is)."""
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    return WideDeepStore(WideDeepConfig(
        num_buckets=nb, dim=dim, hidden=(16, 8), loss="logit", seed=7,
        lr_alpha=0.01, tile_step_kernel=kernel, **cfg), rt)


# the cases that hold for any store that keeps planes
EVERY_STORE = pytest.mark.parametrize("make", [
    pytest.param(_store, id="ftrl"), pytest.param(_fm_store, id="fm"),
    pytest.param(_wd_store, id="wide_deep")])
# the two whose table is [w, v, cg_w, cg_v] a bucket
EMBEDDING_STORES = pytest.mark.parametrize("make", [
    pytest.param(_fm_store, id="fm"),
    pytest.param(_wd_store, id="wide_deep")])


def _as_it_was(store):
    """The same store on the (nb, slots) array it kept before the planes."""
    store._planar = False
    store.slots = jnp.asarray(np.asarray(store.slots))
    return store


def _assert_same_table(make, got, want):
    """FTRL's and FM's planar steps are their stacked steps to the bit
    (guarded products). Wide&deep's update is plain XLA on both sides, and
    the compiler contracts a fusion over planes and one over slices of
    (nb, slots) differently: a last bit in a few hundred values. Held two
    decades under the cell's ``state_rel_rms`` limit (4e-3), value by
    value."""
    if make is _wd_store:
        np.testing.assert_allclose(got, want, rtol=4e-5, atol=1e-7)
    else:
        np.testing.assert_array_equal(got, want)


def _crossings(store) -> int:
    return store.timer.counts.get("table_cross", 0)


def _back(make) -> int:
    """What the way back to planes counts. FTRL's and FM's tile steps ask
    ``_tile_table()``, which takes a stacked array across in a pass of its
    own: 1. Wide&deep's next tile step takes the stacked array as it is
    and gives planes back, the change of form inside its update pass: no
    pass, no count (and ``tests/benchmark`` holds it to that: a stacked
    table assigned, tile steps, no ``table_cross`` on the timer)."""
    return 0 if make is _wd_store else 1


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


@pytest.mark.parametrize("spill,hosts", [
    pytest.param(False, 1, id="fused_update"),
    pytest.param(True, 1, id="fused_spill"),
    pytest.param(False, 2, id="fused_pushes")])
def test_planar_fm_steps_match_the_stacked_split_oracle(spill, hosts,
                                                        monkeypatch):
    """Three FM steps from a random table: the planar fused steps (operand
    formed in VMEM from the w and v planes; without an overflow list the
    update inside the kernel, with one a push plane a channel and one
    elementwise update over planes, which a step of a multi-process run
    takes without a list too) against the split kernel pair on the
    stacked (nb, 2(1+k)) array: margins and the metric row to the bit
    (the progress number to rounding) and all 2(1+k) channels to the bit
    (FMAdaGrad's guarded products). The planar table stays planes."""
    rng = np.random.default_rng(31)
    k = 4
    monkeypatch.setattr(jax, "process_count", lambda: hosts)
    info = make_info(SPEC, ovf_cap=OC if spill else 0)
    start = (rng.standard_normal((SPEC.nb, 2 * (1 + k))) * 0.1).astype(
        np.float32)
    start[:, 1 + k:] = np.abs(start[:, 1 + k:])
    planar = _fm_store(SPEC.nb, "fused")
    oracle = _as_it_was(_fm_store(SPEC.nb, "split"))
    for st in (planar, oracle):
        st.slots = jnp.asarray(start)
    for blk in _blocks(rng, SPEC, 3, spill):
        dev = jax.device_put(blk)
        rows = []
        for st in (planar, oracle):
            st.tile_train_step(dev, info)
            rows.append(st.fetch_metrics())
        # the progress number's sum runs over a plane here, a column of
        # the stacked array there: equal to rounding, all else to the bit
        np.testing.assert_array_equal(np.delete(rows[0], 3),
                                      np.delete(rows[1], 3))
        assert rows[1][3] > 0
        np.testing.assert_allclose(rows[0][3], rows[1][3], rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(planar.tile_eval_step(dev, info)[5]),
            np.asarray(oracle.tile_eval_step(dev, info)[5]))
    planar._tile_step(info, "train", spill)
    in_place = not spill and hosts == 1
    assert planar.step_kernel[:2] == (
        "fused", fm_model.IN_PLACE if in_place else "")
    assert isinstance(planar.slots, tbl.PlaneTable)
    assert len(planar.slots.planes) == 2 * (1 + k)
    assert not isinstance(oracle.slots, tbl.PlaneTable)
    assert _crossings(planar) == 1 and _crossings(oracle) == 0
    got = np.asarray(planar.slots)
    np.testing.assert_array_equal(got, np.asarray(oracle.slots))
    changed = np.any(got != start, axis=1)
    assert 0 < changed.sum() < SPEC.nb        # touched buckets only
    assert np.all(got[changed][:, 1 + k] > 0)


def _wd_step_as_it_stood(cfg, n_layers, spec, table, mlp, accum, blk):
    """One wide&deep tile step on the stacked (nb, 2(1+k)) table as it
    stood before the planes (PR 34), from the stacked helpers: the pull
    operand sliced and concatenated, ``forward_pulls`` and
    ``backward_pushes`` with their transposes and the (nb, ch) overflow
    scatter, ``where`` over (nb, 1+k) pieces, the table concatenated
    again. -> (table, mlp, accum, margins)."""
    from wormhole_tpu.ops.loss import create_loss
    k = cfg.dim
    _, dual_fn = create_loss(cfg.loss)
    lab = blk["labels"]
    row_mask = (lab != jnp.uint8(255)).astype(jnp.float32)
    labels = jnp.minimum(lab, 1).astype(jnp.float32)
    ovf = (blk.get("ovf_b"), blk.get("ovf_r"))
    theta, cg = table[:, :1 + k], table[:, 1 + k:]
    wpull = jnp.concatenate([theta[:, 0][:, None], theta[:, 1:]], axis=1)
    pulls = tilemm.forward_pulls(blk["pw"], wpull, spec, *ovf)
    deep, vjp = jax.vjp(lambda m, x: tilemm.mlp_forward(m, x, n_layers),
                        mlp, pulls[:, 1:])
    margin = pulls[:, 0] + deep
    dual = dual_fn(margin, labels, row_mask)
    g_mlp, g_pooled = vjp(dual)
    push = tilemm.backward_pushes(
        blk["pw"], jnp.concatenate([dual[:, None], g_pooled,
                                    row_mask[:, None]], axis=1), spec, *ovf)
    touched = push[:, 1 + k] > 0
    g_v = push[:, 1:1 + k] + cfg.l2_v * theta[:, 1:] * touched[:, None]
    grads = jnp.concatenate([push[:, :1], g_v], axis=1)
    cg_new = jnp.where(touched[:, None],
                       jnp.sqrt(cg * cg + grads * grads), cg)
    theta_new = jnp.where(
        touched[:, None],
        theta - cfg.lr_alpha / (cfg.lr_beta + cg_new) * grads, theta)
    accum = jax.tree.map(lambda a, g: jnp.sqrt(a * a + g * g), accum, g_mlp)
    mlp = jax.tree.map(
        lambda p, g, a: p - cfg.lr_alpha_dense / (cfg.lr_beta + a) * g,
        mlp, g_mlp, accum)
    return (jnp.concatenate([theta_new, cg_new], axis=1), mlp, accum,
            margin)


def _short_spill_blocks(rng, spec, n, oc):
    """Blocks whose hot bucket passes its (subblock, tile) cap by a dozen
    pairs: an overflow list short beside even this table's lane rows."""
    out = []
    for _ in range(n):
        buckets, rows = make_pairs(rng, 3000, spec)
        there = np.sum((buckets // tilemm.TILE == 1)
                       & (rows // tilemm.RSUB == 0))
        n_hot = spec.cap - there + 12
        buckets = np.concatenate(
            [buckets, np.full(n_hot, 7 * tilemm.TILE // 4, np.int64)])
        rows = np.concatenate(
            [rows, rng.integers(0, tilemm.RSUB, n_hot).astype(np.int64)])
        pw, ovb, ovr = tilemm.encode_block(buckets, rows, spec)
        assert 0 < len(ovb) <= oc
        blk = {"pw": pw, "ovf_b": np.full(oc, 0xFFFFFFFF, np.uint32),
               "ovf_r": np.zeros(oc, np.uint32),
               "labels": rng.integers(0, 2, spec.block_rows).astype(np.uint8)}
        blk["ovf_b"][:len(ovb)], blk["ovf_r"][:len(ovr)] = ovb, ovr
        out.append(blk)
    return out


@pytest.mark.parametrize("oc", [
    pytest.param(OC, id="spill"), pytest.param(32, id="short_list"),
    pytest.param(0, id="no_list")])
def test_planar_wide_deep_steps_match_the_stacked_steps(oc):
    """Three wide&deep steps from a random table, blocks with and
    without an overflow list. The planar store (33 theta planes rounded
    into the pull kernel's operand in one op, the pushes left as the
    kernel wrote them, the overflow pairs gathered from planes and
    scattered into the tiled pushes, ONE pass over planes; its first step
    takes the assigned stacked start as the held store does and gives
    planes back) against (1) the same store held stacked, whose every step
    goes through the (nb, ch) helpers at the kernels' edges, slices the
    planes out of (nb, 2(1+k)) for the update and stacks them again, and
    (2) the step as it stood on the stacked table, written here from the
    stacked helpers. Margins, metric row, tower and accumulators of (1)
    to the bit but for the table and the progress number
    (``_assert_same_table``); (2) fuses the update otherwise, so it is
    held by the cell's own measure, the relative rms of each leaf, at
    1e-6: over three decades under the cell's limit of 4e-3 (it reads 3e-8
    at most here). The planar table stays planes and never crosses."""
    rng = np.random.default_rng(37)
    k = 4
    info = make_info(SPEC, ovf_cap=oc)
    # a list of 32 is short beside the table's 256 lane rows and is ONE
    # scatter of such rows, as in the cell; 1536 go a plane at a time
    # (tilemm.spill_push_scatter_lanes)
    blocks = (_short_spill_blocks(rng, SPEC, 3, oc) if oc == 32
              else _blocks(rng, SPEC, 3, bool(oc)))
    start = (rng.standard_normal((SPEC.nb, 2 * (1 + k))) * 0.1).astype(
        np.float32)
    start[:, 1 + k:] = np.abs(start[:, 1 + k:])
    planar, held = _wd_store(SPEC.nb), _as_it_was(_wd_store(SPEC.nb))
    for st in (planar, held):
        st.slots = jnp.asarray(start)
    stood = jax.jit(functools.partial(
        _wd_step_as_it_stood, planar.cfg, planar.n_layers, info.spec))
    ref = (jnp.asarray(start), planar.mlp, planar.mlp_accum)

    def rel_rms(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        return np.sqrt(np.sum((got - want) ** 2) / np.sum(want ** 2))

    for blk in blocks:
        dev = jax.device_put(blk)
        *ref, margin = stood(*ref, dev)
        np.testing.assert_allclose(
            np.asarray(planar.tile_eval_step(dev, info)[5]),
            np.asarray(margin), rtol=2e-5, atol=1e-6)
        rows = []
        for st in (planar, held):
            st.tile_train_step(dev, info)
            rows.append(st.fetch_metrics())
        np.testing.assert_array_equal(np.delete(rows[0], 3),
                                      np.delete(rows[1], 3))
        assert rows[1][3] > 0
        np.testing.assert_allclose(rows[0][3], rows[1][3], rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(planar.tile_eval_step(dev, info)[5]),
            np.asarray(held.tile_eval_step(dev, info)[5]))
    assert planar.step_kernel[0] == "split"
    assert isinstance(planar.slots, tbl.PlaneTable)
    assert len(planar.slots.planes) == 2 * (1 + k)
    assert not isinstance(held.slots, tbl.PlaneTable)
    # the assigned start table became planes inside the first step
    assert _crossings(planar) == 0 and _crossings(held) == 0
    got = np.asarray(planar.slots)
    _assert_same_table(_wd_store, got, np.asarray(held.slots))
    for tree, other, stood_tree in ((planar.mlp, held.mlp, ref[1]),
                                    (planar.mlp_accum, held.mlp_accum,
                                     ref[2])):
        for name, leaf in tree.items():
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(other[name]))
            assert rel_rms(leaf, stood_tree[name]) < 1e-6, name
    want = np.asarray(ref[0])
    for name, cols in (("w", slice(0, 1)), ("v", slice(1, 1 + k)),
                       ("cg", slice(1 + k, None))):
        assert rel_rms(got[:, cols], want[:, cols]) < 1e-6, name
    changed = np.any(got != start, axis=1)
    assert 0 < changed.sum() < SPEC.nb        # touched buckets only
    np.testing.assert_array_equal(changed, np.any(want != start, axis=1))


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


@EVERY_STORE
def test_an_empty_overflow_list_stays_on_the_host(make):
    """put_block leaves an overflow list with no pair behind, and the
    block then takes the step that has no spill to scatter (FTRL: the
    in-place one, and so for FM); one pair keeps the spill step."""
    rng = np.random.default_rng(5)
    info = make_info(SPEC, ovf_cap=OC)
    pw, labels = make_block(rng, SPEC)
    empty = {"pw": pw, "labels": labels,
             "ovf_b": np.full(OC, 0xFFFFFFFF, np.uint32),
             "ovf_r": np.zeros(OC, np.uint32)}
    (spilled,) = _blocks(rng, SPEC, 1, True)
    a, b = make(SPEC.nb), make(SPEC.nb)
    # wide&deep's two programs are both the split pair, resolved from the
    # file's geometry (its blocks CAN spill): the one without a list skips
    # the gather and the scatter
    no_spill, with_spill = {
        _store: (("fused", IN_PLACE), ("fused", "")),
        _fm_store: (("fused", fm_model.IN_PLACE), ("fused", "")),
        _wd_store: (("split", WD_SPILL), ("split", WD_SPILL))}[make]
    dev = a.put_block(empty)
    assert sorted(dev) == ["labels", "pw"]
    a.tile_train_step(dev, info)
    assert a.step_kernel[:2] == no_spill
    assert [key[2] for key in a._tile_cache] == [False]
    b.tile_train_step(jax.device_put(empty), info)       # the spill step
    assert b.step_kernel[:2] == with_spill
    assert [key[2] for key in b._tile_cache] == [True]
    np.testing.assert_array_equal(np.asarray(a.slots), np.asarray(b.slots))
    dev = a.put_block(spilled)
    assert "ovf_b" in dev
    a.tile_train_step(dev, info)
    assert a.step_kernel[:2] == with_spill
    assert sorted(key[2] for key in a._tile_cache) == [False, True]
    assert _crossings(a) == 0 and _crossings(b) == 0


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


@EVERY_STORE
def test_checkpoint_bytes_are_those_of_the_stacked_table(make, tmp_path):
    """A planar store's checkpoint file is byte for byte what the same
    state written as one (nb, slots) array gives (the format before the
    planes), and it loads back into a store that goes on stepping."""
    from wormhole_tpu.parallel.checkpoint import Checkpointer
    rng = np.random.default_rng(7)
    info = make_info(SPEC)
    blocks = [jax.device_put(b) for b in _blocks(rng, SPEC, 3, False)]
    store = make(SPEC.nb)
    for blk in blocks[:2]:
        store.tile_train_step(blk, info)
    assert isinstance(store.slots, tbl.PlaneTable)
    planar, stacked = tmp_path / "planar", tmp_path / "stacked"
    Checkpointer(str(planar)).save(2, store.state_pytree())
    Checkpointer(str(stacked)).save(
        2, dict(store.state_pytree(),       # wide&deep: the tower as well
                slots=jnp.asarray(np.asarray(store.slots))))
    name = "ckpt_v2.msgpack"
    assert (planar / name).read_bytes() == (stacked / name).read_bytes()
    assert _crossings(store) == 0         # stacked on the host, not here

    fresh = make(SPEC.nb)
    ver, state = Checkpointer(str(planar)).load(fresh.state_pytree())
    assert ver == 2
    fresh.restore_pytree(state)
    assert fresh.t == store.t
    np.testing.assert_array_equal(np.asarray(fresh.slots),
                                  np.asarray(store.slots))
    store.tile_train_step(blocks[2], info)
    fresh.tile_train_step(blocks[2], info)
    assert _crossings(fresh) == _back(make)     # the restored array
    np.testing.assert_array_equal(np.asarray(fresh.slots),
                                  np.asarray(store.slots))


@EVERY_STORE
def test_paths_that_want_the_array_cross_and_are_counted(make):
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
    planar = make(SPEC.nb)
    stacked = _as_it_was(make(SPEC.nb))
    for st in (planar, stacked):
        st.tile_train_step(blocks[0], info)
        st.train_step(batch)
        st.tile_train_step(blocks[1], info)
    assert _crossings(planar) == 1 + _back(make)
    assert _crossings(stacked) == 0
    assert planar.timer.totals["table_cross"] > 0
    _assert_same_table(make, np.asarray(planar.slots),
                       np.asarray(stacked.slots))


@EVERY_STORE
def test_the_pager_crosses_once_and_is_counted(make):
    """bigmodel/paged.py moves rows of the hot table by index: it asks a
    planar store for the array (PagedStore._table), one counted crossing,
    and the table stays the array while the pager owns it; the next tile
    step takes it back."""
    from wormhole_tpu.bigmodel import PagedStore
    rng = np.random.default_rng(23)
    hot = make(SPEC.nb)
    width = hot.slots.shape[1]
    cold = rng.standard_normal((2 * SPEC.nb, width)).astype(np.float32)
    paged = PagedStore(hot, 2 * SPEC.nb, cold_init=cold)
    assert isinstance(hot.slots, tbl.PlaneTable) and _crossings(hot) == 0
    buckets = np.array([5, SPEC.nb + 7, 2 * SPEC.nb - 1])
    plan = paged.pager.plan(buckets)
    paged.stage_fresh(plan)
    paged.apply_plan(plan)                       # the fill's scatter
    assert not isinstance(hot.slots, tbl.PlaneTable)
    assert _crossings(hot) == 1
    np.testing.assert_array_equal(np.asarray(hot.slots)[plan.slots],
                                  cold[buckets])
    np.testing.assert_array_equal(paged.flush(), cold)   # the gather
    assert _crossings(hot) == 1                  # already the array
    info = make_info(SPEC)
    hot.tile_train_step(jax.device_put(_blocks(rng, SPEC, 1, False)[0]), info)
    assert isinstance(hot.slots, tbl.PlaneTable)
    assert _crossings(hot) == 1 + _back(make)


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


@EMBEDDING_STORES
def test_fm_benchmark_reads_and_writes_of_a_planar_table(make):
    """Everything benchmark/configs/criteo_fm/system.py does to
    ``store.slots``, on a planar FMStore: it reads ``.sharding``, replaces
    the table by a donated jit of ``slots.at[:, 1:1+k].set(v0)`` with that
    ``out_shardings`` and assigns the stacked result (ONE crossing, at the
    next tile step), then probes with ``astype``, ``s[:, 0]``,
    ``s[:, 1:1+k]``, ``s[:, 1+k]``, ``s[:, 2+k:]`` and ``slots[idx, :1+k]``
    (no crossing). ``criteo_wide_deep/system.py`` does the same to a
    WideDeepStore with the same ``_v0`` and table probes, but for the
    ``out_shardings``, which it leaves out."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.configs.criteo_fm import system as hooks
    k, nb, scale, seed = 4, SPEC.nb, 0.01, (1 << 31) + 12345
    store = make(nb)
    assert isinstance(store.slots, tbl.PlaneTable)
    sharding = store.slots.sharding
    assert sharding == jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def seeded(slots, salt):               # make_app's, line for line
        return slots.at[:, 1:1 + k].set(hooks._v0(nb, k, salt, scale))

    salt = jnp.uint32(hooks._salt(seed))
    placed = {"out_shardings": sharding} if make is _fm_store else {}
    store.slots = jax.jit(seeded, donate_argnums=(0,), **placed)(
        store.slots, salt)
    assert store.slots.shape == (nb, 2 * (1 + k))
    assert not isinstance(store.slots, tbl.PlaneTable)
    v0 = np.asarray(hooks._v0(nb, k, salt, scale))
    np.testing.assert_array_equal(np.asarray(store.slots)[:, 1:1 + k], v0)
    assert _crossings(store) == 0

    rng = np.random.default_rng(17)
    info = make_info(SPEC)
    for blk in _blocks(rng, SPEC, 3, False):
        store.tile_train_step(jax.device_put(blk), info)
    assert isinstance(store.slots, tbl.PlaneTable)
    assert _crossings(store) == _back(make)      # the assigned array, once

    app = types.SimpleNamespace(store=store)
    config = {"dim": k, "num_buckets": nb, "hyper": {"init_scale": scale}}
    full = np.asarray(store.slots, np.float64)
    got = hooks.change_norms(app, config, seed)
    np.testing.assert_allclose(got["w"], np.linalg.norm(full[:, 0]),
                               rtol=1e-6)
    np.testing.assert_allclose(
        got["v"], np.linalg.norm(full[:, 1:1 + k] - v0), rtol=1e-5)
    got = hooks.grad_norms(app, config, seed)
    np.testing.assert_allclose(got["w"], np.linalg.norm(full[:, 1 + k]),
                               rtol=1e-6)
    np.testing.assert_allclose(got["v"], np.linalg.norm(full[:, 2 + k:]),
                               rtol=1e-6)
    assert got["w"] > 0 and got["v"] > 0
    buckets = rng.integers(0, nb, 4096)
    rows = hooks.state(app, config, seed, buckets)
    np.testing.assert_array_equal(rows["w"], full[buckets, 0])
    np.testing.assert_array_equal(rows["v"], full[buckets, 1:1 + k])
    jax.block_until_ready(store.slots)           # benchmark/system.py fence
    assert isinstance(store.slots, tbl.PlaneTable)
    assert _crossings(store) == _back(make)


@EMBEDDING_STORES
def test_fm_paths_that_want_the_array_cross_and_are_counted(make, tmp_path):
    """serve_params, save_model/load_model and the mesh step ask a planar
    FMStore (a planar WideDeepStore) for (nb, 2(1+k)) and get it, one
    counted crossing each time the table is planes; the next tile step
    takes it back."""
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rng = np.random.default_rng(19)
    info = make_info(SPEC)
    blocks = [jax.device_put(b) for b in _blocks(rng, SPEC, 4, False)]
    rt = MeshRuntime(mesh=make_mesh("data:1", jax.devices()[:1]))
    store = make(SPEC.nb, rt=rt)
    k = store.cfg.dim
    assert isinstance(store.slots, tbl.PlaneTable)

    store.tile_train_step(blocks[0], info)
    before = np.asarray(store.slots)
    params = store.serve_params()                          # serving
    assert params["slots"].shape == (SPEC.nb, 2 * (1 + k))
    np.testing.assert_array_equal(np.asarray(params["slots"]), before)
    assert _crossings(store) == 1
    store.serve_params()
    assert _crossings(store) == 1                # already the array

    back = _back(make)
    store.tile_train_step(blocks[1], info)                 # and back
    assert isinstance(store.slots, tbl.PlaneTable)
    assert _crossings(store) == 1 + back
    store.save_model(str(tmp_path / "fm"), rank=0)         # the export
    assert _crossings(store) == 2 + back
    saved = np.load(tmp_path / "fm_0.npz")
    np.testing.assert_array_equal(saved["w"], np.asarray(store.slots)[:, 0])
    np.testing.assert_array_equal(saved["v"],
                                  np.asarray(store.slots)[:, 1:1 + k])
    store.tile_train_step(blocks[2], info)
    assert _crossings(store) == 2 + 2 * back
    fresh = make(SPEC.nb)
    fresh.load_model(str(tmp_path / "fm_0.npz"))           # the import
    assert _crossings(fresh) == 1
    np.testing.assert_array_equal(np.asarray(fresh.slots)[:, :1 + k],
                                  np.column_stack([saved["w"], saved["v"]]))
    fresh.tile_train_step(blocks[2], info)
    assert isinstance(fresh.slots, tbl.PlaneTable)
    assert _crossings(fresh) == 1 + back

    group = {key: val[None] for key, val in blocks[3].items()}
    store.tile_train_step_mesh(group, info)                # the mesh step
    assert _crossings(store) == 3 + 2 * back
    assert store.slots.shape == (SPEC.nb, 2 * (1 + k))
    twin = _as_it_was(make(SPEC.nb))
    for blk in blocks:
        twin.tile_train_step(blk, info)
    np.testing.assert_allclose(np.asarray(store.slots),
                               np.asarray(twin.slots), rtol=1e-5, atol=1e-7)


# -- (d) what the steps do around their kernels ------------------------------

def _leaf_eqns(jaxpr):
    """Equations of a jaxpr with calls opened, kernels (and scatters,
    whose combiner is a jaxpr too) left closed."""
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        if (eqn.primitive.name == "pallas_call"
                or eqn.primitive.name.startswith("scatter") or not inner):
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


def test_fm_step_forms_nothing_table_sized_outside_the_kernel():
    """The one-device FM train step over channel planes, traced at a
    table whose plane outgrows every block-sized array. Without an
    overflow list: ONE pallas_call takes the 2(1+k) planes as they are
    and gives them back, and no other equation has a result of nb
    elements or more — no concatenate, pad, slice, transpose, cast or
    (nb, ch) array, not even the pushes. With one: the call takes the
    nine w and v planes and gives ten push planes, and outside it every
    plane-sized result is a plane, made by the spill scatters and the
    elementwise AdaGrad pass. (What the v5e compiler makes of it is
    test_tpu_compile's to say.)"""
    from wormhole_tpu.data.crec import CRec2Info
    k = 8
    nb = 1024 * tilemm.TILE
    info = CRec2Info(nnz=0, block_rows=2 * tilemm.RSUB,
                     total_rows=2 * tilemm.RSUB, nb=nb, subblocks=2,
                     cap=128, ovf_cap=1024)
    spec = info.spec
    store = _fm_store(SPEC.nb, dim=k)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    plane = like(tbl.plane_shape(nb), jnp.float32)

    def trace(spill):
        step = store._tile_step(info, "train", spill)
        block = {"pw": like(spec.pairs_shape, jnp.uint32),
                 "labels": like((spec.block_rows,), jnp.uint8)}
        if spill:
            block.update(ovf_b=like((1024,), jnp.uint32),
                         ovf_r=like((1024,), jnp.uint32))
        jaxpr = jax.make_jaxpr(step)(
            tbl.PlaneTable([plane] * (2 * (1 + k))), block,
            like((), jnp.int32), like((), jnp.float32),
            like((TableCheckpoint.MACC_LEN,), jnp.float32))
        eqns = list(_leaf_eqns(jaxpr.jaxpr))
        (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
        big = [e for e in eqns if e.primitive.name != "pallas_call"
               and any(v.aval.size >= nb for v in e.outvars)]
        planes_of = lambda vs: sum(v.aval.shape == plane.shape for v in vs)
        return call, big, planes_of

    call, big, planes_of = trace(False)
    assert store.step_kernel[:2] == ("fused", fm_model.IN_PLACE)
    assert planes_of(call.invars) == planes_of(call.outvars) == 2 * (1 + k)
    assert big == []

    call, big, planes_of = trace(True)
    assert store.step_kernel[:2] == ("fused", "")
    assert planes_of(call.invars) == 1 + k
    assert planes_of(call.outvars) == k + 2
    shapes = {v.aval.shape for e in big for v in e.outvars
              if v.aval.size >= nb}
    assert shapes <= {plane.shape, (nb,)}     # a plane, or the same bytes
    allowed = {"add", "sub", "mul", "div", "sqrt", "gt", "select_n",
               "sign", "abs", "max", "neg", "integer_pow", "square",
               "reshape", "scatter-add", "scatter_add"}
    names = {e.primitive.name for e in big}
    assert names <= allowed, names - allowed


@pytest.mark.parametrize("oc", [pytest.param(16, id="lane_rows"),
                                pytest.param(1536, id="plane_by_plane")])
def test_plane_helpers_match_the_stacked_ones(oc):
    """What the planar multi-channel step hands the kernels and does
    around them, against the (nb, ch) forms: the bfloat16 operand of
    ``plane_operand`` is the transposed, rounded (nb, ch) array of
    ``_build_fwd_multi``; the overflow pulls gathered plane by plane are
    ``spill_pull_rows``; the overflow pushes scattered into the tiled
    pushes are ``spill_push_scatter`` (a short list as ONE scatter of
    lane rows, a long one a plane at a time; sums of a bucket listed
    more than twice may take another order)."""
    rng = np.random.default_rng(41)
    T, ch = SPEC.tiles, 6
    nb, A, B = SPEC.nb, tilemm.A_HI, tilemm.B_LO
    w = rng.standard_normal((nb, ch)).astype(np.float32)
    push = rng.standard_normal((nb, ch)).astype(np.float32)
    dual = jnp.asarray(rng.standard_normal((SPEC.block_rows, ch))
                       .astype(np.float32))
    ovf_b = np.full(oc, 0xFFFFFFFF, np.uint32)
    n = oc * 3 // 4
    ovf_b[:n] = rng.integers(0, nb, n)
    ovf_b[:n:3] = ovf_b[0]                    # one bucket, many pairs
    ovf_r = rng.integers(0, 4 * SPEC.block_rows, oc).astype(np.uint32)
    ovf_b, ovf_r = jnp.asarray(ovf_b), jnp.asarray(ovf_r)

    def tiled(x):          # (nb, ch) as the kernels lay it on the lanes
        return (jnp.asarray(x).reshape(T, A, B, ch).transpose(0, 1, 3, 2)
                .reshape(T, A, ch * B))

    planes = tbl.split(jnp.asarray(w))
    np.testing.assert_array_equal(
        np.asarray(tilemm.plane_operand(planes).astype(jnp.float32)),
        np.asarray(tiled(w).astype(jnp.bfloat16).astype(jnp.float32)))
    np.testing.assert_array_equal(
        np.asarray(tilemm.plane_spill_pull_rows(planes, ovf_b, ovf_r, SPEC)),
        np.asarray(tilemm.spill_pull_rows(jnp.asarray(w), ovf_b, ovf_r,
                                          SPEC)))
    got = np.asarray(tbl.join(tilemm.spill_push_scatter_lanes(
        tiled(push), dual, ovf_b, ovf_r, SPEC)))
    want = np.asarray(tilemm.spill_push_scatter(
        jnp.asarray(push), dual, ovf_b, ovf_r, SPEC))
    assert np.any(want != push)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    once = np.ones(nb, bool)
    once[int(ovf_b[0])] = False
    np.testing.assert_array_equal(got[once], want[once])
    np.testing.assert_array_equal(
        np.asarray(tbl.join(tilemm.push_planes(tiled(push)))), push)


def test_wide_deep_step_forms_nothing_table_shaped_outside_the_kernels():
    """The one-device wide&deep train step over channel planes, traced at
    a table whose plane outgrows every block-sized array, with and
    without an overflow list: TWO pallas_calls, the pull kernel taking the
    bfloat16 (T, A_HI, (1+k)*B_LO) operand and the push kernel giving
    float32 (T, A_HI, (k+2)*B_LO), and outside them no equation has a
    result shaped (nb, anything): every result of nb elements or more is
    a plane (or the same bytes flat), the operand or the tiled pushes,
    made by a convert, the one concatenate, lane slices, the one
    scatter-add and the elementwise AdaGrad pass. No transpose, pad or
    stack. (What the v5e compiler makes of it is test_tpu_compile's to
    say.)"""
    from wormhole_tpu.data.crec import CRec2Info
    k = 32
    nb = 1024 * tilemm.TILE
    info = CRec2Info(nnz=0, block_rows=2 * tilemm.RSUB,
                     total_rows=2 * tilemm.RSUB, nb=nb, subblocks=2,
                     cap=128, ovf_cap=1024)
    spec = info.spec
    store = _wd_store(SPEC.nb, dim=k)

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    plane = like(tbl.plane_shape(nb), jnp.float32)
    tiled = lambda ch: plane.shape[:2] + (ch * tilemm.B_LO,)
    mlp = jax.tree.map(lambda a: like(a.shape, a.dtype), store.mlp)
    for spill in (False, True):
        step = store._tile_step(info, "train", spill)
        assert store.step_kernel[0] == "split"
        block = {"pw": like(spec.pairs_shape, jnp.uint32),
                 "labels": like((spec.block_rows,), jnp.uint8)}
        if spill:
            block.update(ovf_b=like((1024,), jnp.uint32),
                         ovf_r=like((1024,), jnp.uint32))
        jaxpr = jax.make_jaxpr(step)(
            tbl.PlaneTable([plane] * (2 * (1 + k))), mlp, mlp, block,
            like((), jnp.int32), like((), jnp.float32),
            like((TableCheckpoint.MACC_LEN,), jnp.float32))
        eqns = list(_leaf_eqns(jaxpr.jaxpr))
        pull, push = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert [(v.aval.shape, v.aval.dtype) for v in pull.invars
                if v.aval.size >= nb] == [(tiled(1 + k), jnp.bfloat16)]
        assert [(v.aval.shape, v.aval.dtype) for v in push.outvars] == [
            (tiled(k + 2), jnp.float32)]
        big = [e for e in eqns if e.primitive.name != "pallas_call"
               and any(v.aval.size >= nb for v in e.outvars)]
        shapes = {v.aval.shape for e in big for v in e.outvars
                  if v.aval.size >= nb}
        assert shapes <= {plane.shape, (nb,), tiled(1 + k), tiled(k + 2)}
        allowed = {"add", "sub", "mul", "div", "sqrt", "gt", "select_n",
                   "integer_pow", "square", "convert_element_type",
                   "concatenate", "slice", "reshape"}
        if spill:
            allowed |= {"scatter-add", "scatter_add"}
        names = {e.primitive.name for e in big}
        assert names <= allowed, names - allowed
        assert sum(e.primitive.name == "concatenate" for e in big) == 1
        assert sum(e.primitive.name.startswith("scatter")
                   for e in big) == int(spill)


# -- (e) planes on a mesh: the linear store's server shards -------------------

MESH = "data:2,model:2"


def _mesh_store(nb=SPEC.nb, algo="ftrl", **cfg):
    """The linear store on four host devices: two key-range shards of the
    table, each held by the two chips of a DATA pair."""
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rt = MeshRuntime(mesh=make_mesh(MESH, jax.devices()[:4]))
    return ShardedStore(
        StoreConfig(num_buckets=nb, loss="logit", **cfg),
        create_handle(algo, L1L2(0.05, 0.1), LearnRate(0.1, 1.0)), rt)


def _rows_over_model(store, full):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(jnp.asarray(full),
                          NamedSharding(store.rt.mesh, P("model", None)))


def _mesh_as_it_was(store):
    """The same mesh store on the (nb, slots) rows over MODEL it kept
    before the planes: its mesh step slices a stacked shard."""
    store._planar = False
    store.slots = _rows_over_model(store, np.asarray(store.slots))
    return store


def _groups(rng, n, spill):
    """``n`` groups of two blocks, stacked on the DATA axis as the mesh
    step takes them."""
    return [{k: np.stack([b[k] for b in pair]) for k in pair[0]}
            for pair in (_blocks(rng, SPEC, 2, spill) for _ in range(n))]


def _is_planes_over_model(table) -> bool:
    return (isinstance(table, tbl.PlaneTable)
            and all(tuple(p.sharding.spec) == ("model", None, None)
                    for p in table.planes))


@pytest.mark.parametrize("algo,spill", [
    ("ftrl", False), ("ftrl", True), ("adagrad", True)])
def test_planar_mesh_step_is_the_stacked_mesh_step_to_the_bit(algo, spill):
    """Three groups through the mesh step on planes and through the same
    step on stacked shards: the tables are equal bit for bit, metrics and
    all, and neither store crosses. AdaGrad with L1 takes the masked push
    (a bucket no pair names keeps its slots)."""
    rng = np.random.default_rng(31)
    info = make_info(SPEC, ovf_cap=OC if spill else 0)
    groups = _groups(rng, 3, spill)
    planar = _mesh_store(algo=algo)
    assert planar._planar and _is_planes_over_model(planar.slots)
    stacked = _mesh_as_it_was(_mesh_store(algo=algo))
    for group in groups:
        for st in (planar, stacked):
            st.tile_train_step_mesh(group, info)
        assert _is_planes_over_model(planar.slots)
        assert not isinstance(stacked.slots, tbl.PlaneTable)
    got, want = np.asarray(planar.slots), np.asarray(stacked.slots)
    assert np.abs(want[:, 0]).sum() > 0
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(planar.fetch_metrics(),
                                  stacked.fetch_metrics())
    np.testing.assert_array_equal(
        np.asarray(planar.tile_eval_step_mesh(groups[0], info)[5]),
        np.asarray(stacked.tile_eval_step_mesh(groups[0], info)[5]))
    assert _crossings(planar) == 0 and _crossings(stacked) == 0


@pytest.mark.parametrize("make", [
    pytest.param(_fm_store, id="fm"), pytest.param(_wd_store, id="wide_deep")])
def test_embedding_stores_stay_stacked_on_a_mesh(make):
    """FM's and wide&deep's mesh steps slice a stacked shard: on a mesh
    they keep (nb, slots) rows over MODEL and never cross; the linear
    store, whose mesh step takes planes, says so of itself."""
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rt = MeshRuntime(mesh=make_mesh(MESH, jax.devices()[:4]))
    store = make(SPEC.nb, rt=rt)
    assert not type(store).mesh_step_takes_planes
    assert ShardedStore.mesh_step_takes_planes
    assert not store._planar
    assert not isinstance(store.slots, tbl.PlaneTable)
    assert tuple(store.slots.sharding.spec) == ("model", None)
    assert not type(store).can_be_planar(rt, np.float32, SPEC.nb)
    assert ShardedStore.can_be_planar(rt, np.float32, SPEC.nb)
    assert not ShardedStore.can_be_planar(rt, jnp.bfloat16, SPEC.nb)
    assert not ShardedStore.can_be_planar(rt, np.float32, 3 * tilemm.TILE)
    # one device: every store's answer is what it was
    assert type(store).can_be_planar(None, np.float32, SPEC.nb)


@pytest.fixture(scope="module")
def mesh_probed():
    """A mesh store after three groups with lists, its twin on the stacked
    table those planes came from, and both four-chip cells' hooks,
    imported as they are."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.configs.criteo_ftrl_clicklog_ps4 import system as clicklog
    from benchmark.configs.criteo_ftrl_ps4 import system as ps4
    rng = np.random.default_rng(37)
    info = make_info(SPEC, ovf_cap=OC)
    store = _mesh_store()
    for group in _groups(rng, 3, True):
        store.tile_train_step_mesh(group, info)
    assert _is_planes_over_model(store.slots)
    twin = _mesh_as_it_was(_mesh_store())
    twin.slots = _rows_over_model(twin, np.asarray(store.slots))
    return (types.SimpleNamespace(store=store),
            types.SimpleNamespace(store=twin), {"ps4": ps4,
                                                "clicklog": clicklog})


@pytest.mark.parametrize("cell", ["ps4", "clicklog"])
def test_four_chip_probes_read_sharded_planes_as_the_stacked_table(
        mesh_probed, cell):
    """``w_squares``, ``cg_squares`` and ``w_rows`` of both four-chip
    ``system.py`` files (``shard_map`` with ``P(MODEL, None)`` over
    ``store.slots``, ``slots[:, col]``, ``slots[idx, 0]``,
    ``slots.shape[0]``) give the same numbers from the sharded PlaneTable
    as from the stacked table it came from, and cross nothing."""
    app, twin, hooks = mesh_probed
    hooks = hooks[cell]
    full = np.asarray(app.store.slots, np.float64)
    for probe, col in (("grad_norms", 2), ("change_norms", 0)):
        got = getattr(hooks, probe)(app, {}, 0)["w"]
        want = getattr(hooks, probe)(twin, {}, 0)["w"]
        assert got > 0
        np.testing.assert_allclose(got, want, rtol=1e-6)
        np.testing.assert_allclose(got, np.linalg.norm(full[:, col]),
                                   rtol=1e-6)
    # both sides of the shard boundary and its edges
    buckets = np.concatenate([
        [0, SPEC.nb // 2 - 1, SPEC.nb // 2, SPEC.nb - 1],
        np.random.default_rng(0).integers(0, SPEC.nb, 4096)])
    got = hooks.state(app, {}, 0, buckets)["w"]
    np.testing.assert_array_equal(got, hooks.state(twin, {}, 0, buckets)["w"])
    np.testing.assert_array_equal(got, full[buckets, 0])
    assert np.abs(got).sum() > 0
    jax.block_until_ready(app.store.slots)       # benchmark/system.py fence
    assert _is_planes_over_model(app.store.slots)
    assert _crossings(app.store) == 0


def test_a_sharded_plane_table_names_its_shards_in_rows(mesh_probed):
    """``addressable_shards`` of planes over a mesh reads as the stacked
    table's: a device, its ROWS of (nb, slots), and those rows' values
    (``tests/benchmark`` and ``save_model`` put the shards end to end)."""
    app, twin, _hooks = mesh_probed

    def shards(table):
        return {s.device.id: ((s.index[0].start or 0, s.index[0].stop),
                              np.asarray(s.data))
                for s in table.addressable_shards}

    got, want = shards(app.store.slots), shards(twin.store.slots)
    assert sorted(got) == sorted(want) and len(got) == 4
    for dev, (rows, values) in want.items():
        assert got[dev][0] == rows
        np.testing.assert_array_equal(got[dev][1], values)
    assert app.store.slots.is_fully_addressable


def test_a_mesh_table_zeroed_in_place_stays_planes():
    """``criteo_ftrl_clicklog_ps4/seeds.py`` starts every seed from
    ``jit(lambda s: s * 0, donate_argnums=0)(store.slots)``: planes in,
    planes out, where they were."""
    rng = np.random.default_rng(41)
    store = _mesh_store()
    store.tile_train_step_mesh(_groups(rng, 1, False)[0], make_info(SPEC))
    assert np.abs(np.asarray(store.slots)).sum() > 0
    store.slots = jax.jit(lambda s: s * 0, donate_argnums=0)(store.slots)
    assert _is_planes_over_model(store.slots)
    assert not np.asarray(store.slots).any()
    store.tile_train_step_mesh(_groups(rng, 1, False)[0], make_info(SPEC))
    assert _crossings(store) == 0


def test_a_mesh_pass_crosses_nothing_and_a_sparse_batch_once(tmp_path, rng):
    """A whole pass of the learner over a crec2 file on the 2 x 2 mesh
    (MeshGroupFeed, the mesh step, the pass end's ``nnz_weight``) counts
    no ``table_cross`` and leaves planes; a sparse batch after it asks
    for (nb, val_len) and gets it, rows over MODEL, one counted crossing;
    the next mesh pass takes the table back, one more."""
    from test_mesh_feed import BR, NB, make_app, make_rows, write_file
    from wormhole_tpu.data.feed import SparseBatch
    n = 4 * BR
    keys, labels = make_rows(rng, n)
    path = tmp_path / "c.crec2"
    write_file(path, keys, labels)
    app = make_app(path, MESH)
    store = app.store
    assert store._planar and _is_planes_over_model(store.slots)
    assert app.run().num_ex == n
    assert app.timer.totals["mesh_steps"] == 2
    assert _is_planes_over_model(store.slots)
    assert _crossings(store) == 0

    batch = SparseBatch(
        cols=jnp.asarray(rng.integers(0, 64, (32, 4)), jnp.int32),
        vals=jnp.ones((32, 4), jnp.float32),
        labels=jnp.asarray(rng.integers(0, 2, 32), jnp.float32),
        row_mask=jnp.ones(32, jnp.float32),
        uniq_keys=jnp.asarray(np.arange(0, 64, dtype=np.int32) * 97),
        key_mask=jnp.ones(64, jnp.float32))
    before = np.asarray(store.slots)
    store.train_step(batch)
    assert _crossings(store) == 1
    assert store.slots.shape == (NB, 3)
    assert tuple(store.slots.sharding.spec) == ("model", None)
    touched = np.asarray(batch.uniq_keys)
    rest = np.setdiff1d(np.arange(NB), touched)
    np.testing.assert_array_equal(np.asarray(store.slots)[rest],
                                  before[rest])
    app.process(str(path), 0, 1)
    assert _is_planes_over_model(store.slots)
    assert _crossings(store) == 2


def test_mesh_checkpoint_round_trip_keeps_the_planes(tmp_path):
    """A mesh store's checkpoint: ``state_pytree`` hands the writer the
    (nb, val_len) global array a stacked mesh store hands it, rows over
    MODEL, stacked shard by shard on the chips (a counted pass; the store
    keeps its planes and the host sees nothing before the writer asks);
    the file is byte for byte the stacked store's; loaded into a fresh
    mesh store it becomes planes again, a column to each plane's shards,
    with no crossing, and both go on stepping to the same table."""
    from wormhole_tpu.parallel.checkpoint import Checkpointer
    rng = np.random.default_rng(43)
    info = make_info(SPEC, ovf_cap=OC)
    groups = _groups(rng, 3, True)
    store = _mesh_store()
    for group in groups[:2]:
        store.tile_train_step_mesh(group, info)
    state = store.state_pytree()
    assert isinstance(state["slots"], jax.Array)
    assert state["slots"].shape == (SPEC.nb, 3)
    assert tuple(state["slots"].sharding.spec) == ("model", None)
    assert _is_planes_over_model(store.slots) and _crossings(store) == 1
    planar, stacked = tmp_path / "planar", tmp_path / "stacked"
    Checkpointer(str(planar)).save(2, state)
    Checkpointer(str(stacked)).save(
        2, dict(state, slots=_rows_over_model(store,
                                              np.asarray(store.slots))))
    name = "ckpt_v2.msgpack"
    assert (planar / name).read_bytes() == (stacked / name).read_bytes()

    fresh = _mesh_store()
    ver, loaded = Checkpointer(str(planar)).load(fresh.state_pytree())
    assert ver == 2
    fresh.restore_pytree(loaded)
    assert fresh.t == store.t
    assert _is_planes_over_model(fresh.slots)
    np.testing.assert_array_equal(np.asarray(fresh.slots),
                                  np.asarray(store.slots))
    crossed = _crossings(fresh)              # the template it was asked for
    for st in (store, fresh):
        st.tile_train_step_mesh(groups[2], info)
    assert _crossings(fresh) == crossed and _crossings(store) == 1
    np.testing.assert_array_equal(np.asarray(fresh.slots),
                                  np.asarray(store.slots))
