"""Fused one-grid train step vs the split fwd/bwd oracle — BITWISE.

The fused kernel (`tile_step_kernel=fused`, ops/tilemm.py) promises bit
parity with the split pallas pair it replaces: same margins, same
gradient, same post-update w/z/n slots. These tests pin that contract in
interpret mode on CPU, at the tilemm level (kernel vs the composed
fwd -> dual -> bwd chain) and at the store level (whole train steps,
slots AND the packed metric accumulator), across linear / FM /
wide&deep. Round 8 widens the contract: the phase-shared one-hot cache
(`tile_onehot_cache`) must replay bitwise-identical planes, capped-
overflow blocks fuse via the pre-aggregated spill operand, and
spill-free wide&deep blocks fuse via the in-kernel MLP phase (that one
at float tolerance: its dense phase sums in another order) — only the
mesh shard stays structurally split.
"""

import dataclasses

import numpy as np
import pytest

from wormhole_tpu.ops import tilemm

SPEC = tilemm.TileSpec(nb=2 * tilemm.TILE, subblocks=2, cap=1280,
                       group=2, tiles_step=2)
# K>1 chained-tile geometry: the pairs re-view into fuse=2 chains, so
# the one-hot cache is structurally excluded there (plane layout does
# not align with the bwd view) — parity is fused-uncached vs split
SPECK2 = tilemm.TileSpec(nb=4 * tilemm.TILE, subblocks=2, cap=128,
                         group=2, tiles_step=4, fuse=2)
# a spec whose cache planes blow the VMEM budget model (2^26 buckets)
SPEC_BIG = tilemm.TileSpec(nb=1 << 26, subblocks=2, cap=512, group=2,
                           tiles_step=16)


def make_pairs(rng, n_pairs, spec=SPEC):
    buckets = rng.integers(0, spec.nb, size=n_pairs).astype(np.int64)
    rows = rng.integers(0, spec.block_rows, size=n_pairs).astype(np.int64)
    return buckets, rows


def make_block(rng, spec=SPEC, n_pairs=3000, pad_rows=100):
    """Encoded block + u8 labels (255 = padding) for store-level steps."""
    buckets, rows = make_pairs(rng, n_pairs, spec)
    pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
    assert not len(ovb)
    labels = rng.integers(0, 2, size=spec.block_rows).astype(np.uint8)
    if pad_rows:
        labels[-pad_rows:] = 255
    return pw, labels


def make_spill_block(rng, spec=SPEC, oc=1536):
    """Encoded block with a hot bucket past `cap` -> COO overflow."""
    buckets, rows = make_pairs(rng, 3000, spec)
    hot = 7 * tilemm.TILE // 4
    buckets = np.concatenate([buckets, np.full(1400, hot, np.int64)])
    rows = np.concatenate(
        [rows, rng.integers(0, spec.block_rows, size=1400).astype(np.int64)])
    pw, ovb, ovr = tilemm.encode_block(buckets, rows, spec)
    assert len(ovb) > 0
    pad_b = np.full(oc, 0xFFFFFFFF, np.uint32)
    pad_r = np.zeros(oc, np.uint32)
    pad_b[:len(ovb)], pad_r[:len(ovr)] = ovb, ovr
    labels = rng.integers(0, 2, size=spec.block_rows).astype(np.uint8)
    return pw, labels, pad_b, pad_r


def make_info(spec=SPEC, ovf_cap=0):
    from wormhole_tpu.data.crec import CRec2Info
    return CRec2Info(nnz=0, block_rows=spec.block_rows,
                     total_rows=spec.block_rows, nb=spec.nb,
                     subblocks=spec.subblocks, cap=spec.cap,
                     ovf_cap=ovf_cap)


def test_resolve_step_kernel():
    """Structural inadmissibility always wins and always says why; the
    resolution is a StepResolution dataclass carrying the one-hot cache
    decision alongside the kernel + split reason."""
    r = tilemm.resolve_step_kernel
    res = r("fused", spec=SPEC)
    assert isinstance(res, tilemm.StepResolution)
    assert (res.kernel, res.why) == ("fused", "")
    assert res.cache and res.cache_record == "onehot_cache=on"
    assert r("split").kernel == "split"
    assert r("split").why == "forced"
    # round 8: a plain spill block no longer forces split — the
    # pre-aggregated spill margins ride into the kernel as an operand
    res = r("fused", ovf_cap=64, spec=SPEC)
    assert res.kernel == "fused" and res.why == ""
    res = r("fused", mesh=True)
    assert res.kernel == "split" and "mesh" in res.why
    # wide&deep now fuses when the MLP phase fits the VMEM budget...
    res = r("fused", deep=True, spec=SPEC, dim=4, hidden=(8,))
    assert res.kernel == "fused"
    # ...but wd spill still needs the pull channels in HBM,
    res = r("fused", deep=True, ovf_cap=64, spec=SPEC, dim=4, hidden=(8,))
    assert res.kernel == "split" and "spill" in res.why
    # a missing spec can't be budgeted,
    res = r("fused", deep=True)
    assert res.kernel == "split" and "spec" in res.why
    # and oversized hidden widths blow the budget (recorded in MB)
    res = r("fused", deep=True, spec=SPEC, dim=4,
            hidden=(1 << 14, 1 << 14))
    assert res.kernel == "split" and "VMEM" in res.why and "MB" in res.why
    res = r("auto")                # CPU backend under the test runner
    assert res.kernel == "split" and "backend" in res.why
    with pytest.raises(ValueError, match="tile_step_kernel"):
        r("bogus")
    with pytest.raises(ValueError, match="tile_onehot_cache"):
        r("fused", onehot_cache="bogus")


def test_resolve_onehot_cache_decision():
    """The cache half: the VMEM budget model gates `auto`, a forced
    `on` overrides the budget but never the structural exclusions, and
    every `off` names its reason in the record string."""
    r = tilemm.resolve_step_kernel
    assert r("fused", spec=SPEC, onehot_cache="off").cache_record == \
        "onehot_cache=off:forced off"
    # split resolution shares no phases, whatever the knob says
    res = r("split", spec=SPEC, onehot_cache="on")
    assert not res.cache and "no phases" in res.cache_why
    # multi-channel kernels already share one one-hot build
    res = r("fused", spec=SPEC, channels=6, onehot_cache="on")
    assert not res.cache and "multi-channel" in res.cache_why
    # K>1 chains re-view the pairs; the staged planes don't align
    res = r("fused", spec=SPECK2, onehot_cache="on")
    assert not res.cache and "fuse>1" in res.cache_why
    # no spec -> nothing to budget
    assert not r("fused").cache
    # the budget model: SPEC's planes fit, SPEC_BIG's don't...
    assert tilemm.onehot_cache_bytes(SPEC) <= tilemm.VMEM_EXTRA_BUDGET
    assert tilemm.onehot_cache_bytes(SPEC_BIG) > tilemm.VMEM_EXTRA_BUDGET
    res = r("fused", spec=SPEC_BIG)
    assert not res.cache and "MB" in res.cache_why
    # ...but a forced `on` measures past it
    assert r("fused", spec=SPEC_BIG, onehot_cache="on").cache


def test_fused_spans_are_device_compute():
    """The fused dispatches are single pallas calls: their ledger spans
    must bucket as pure device work, and stay in SPAN_TABLE so
    lint_spans keeps covering them."""
    from wormhole_tpu.obs import ledger
    for span in ("tilemm:fused_step", "tilemm:fused_multi",
                 "tilemm:fused_cached", "tilemm:mlp_phase"):
        assert ledger.SPAN_TABLE[span] == "device_compute"
        assert ledger.span_bucket(span) == "device_compute"


@pytest.mark.parametrize("loss,exact_dense", [
    ("logit", True), ("hinge", False),
    ("square_hinge", True), ("square", False)])
def test_fused_step_grad_bitwise(loss, exact_dense):
    """Kernel-level: one-grid margins+dual+grad == the split chain
    (fwd pallas -> XLA dual [-> nudge] -> bwd pallas), bit for bit —
    and the one-hot cache replay must not change a single bit."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.learners.store import _nudge_zero_dual
    from wormhole_tpu.ops.loss import create_loss

    rng = np.random.default_rng(3)
    buckets, rows = make_pairs(rng, 4000)
    pw, _, _ = tilemm.encode_block(buckets, rows, SPEC)
    w = (rng.standard_normal(SPEC.nb) * 0.1).astype(np.float32)
    labels = (rng.random(SPEC.block_rows) < 0.4).astype(np.float32)
    mask = np.ones(SPEC.block_rows, np.float32)
    mask[-64:] = 0.0
    _, dual_fn = create_loss(loss)

    @jax.jit
    def split(pw, w, labels, mask):
        margin = tilemm.forward_margins(pw, w, SPEC)
        dual = dual_fn(margin, labels, mask)
        if not exact_dense:
            dual = _nudge_zero_dual(dual, labels, mask)
        return margin, tilemm.backward_grad(pw, dual, SPEC)

    def make_fused(cache):
        @jax.jit
        def fused(pw, w, labels, mask):
            return tilemm.fused_step_grad(pw, w, labels, mask, SPEC,
                                          loss, exact_dense, cache=cache)
        return fused

    args = (jnp.asarray(pw), jnp.asarray(w), jnp.asarray(labels),
            jnp.asarray(mask))
    mg_s, g_s = split(*args)
    for cache in (False, True):
        mg_f, g_f = make_fused(cache)(*args)
        np.testing.assert_array_equal(np.asarray(mg_f), np.asarray(mg_s))
        np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_s))


def test_fused_step_grad_bitwise_k2():
    """The fuse=2 chained-tile geometry keeps fused/split parity; the
    cache is structurally excluded there (resolver says why)."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.ops.loss import create_loss

    spec = SPECK2
    rng = np.random.default_rng(12)
    buckets, rows = make_pairs(rng, 700, spec)
    pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
    assert not len(ovb)
    w = (rng.standard_normal(spec.nb) * 0.1).astype(np.float32)
    labels = (rng.random(spec.block_rows) < 0.4).astype(np.float32)
    mask = np.ones(spec.block_rows, np.float32)
    _, dual_fn = create_loss("logit")

    @jax.jit
    def split(pw, w, labels, mask):
        margin = tilemm.forward_margins(pw, w, spec)
        dual = dual_fn(margin, labels, mask)
        return margin, tilemm.backward_grad(pw, dual, spec)

    @jax.jit
    def fused(pw, w, labels, mask):
        return tilemm.fused_step_grad(pw, w, labels, mask, spec,
                                      "logit", True)

    args = (jnp.asarray(pw), jnp.asarray(w), jnp.asarray(labels),
            jnp.asarray(mask))
    mg_s, g_s = split(*args)
    mg_f, g_f = fused(*args)
    np.testing.assert_array_equal(np.asarray(mg_f), np.asarray(mg_s))
    np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_s))
    res = tilemm.resolve_step_kernel("fused", spec=spec,
                                     onehot_cache="on")
    assert res.kernel == "fused" and not res.cache
    assert "fuse>1" in res.cache_why


def test_fused_spill_grad_bitwise():
    """Round 8: a capped-overflow block fuses — the pre-aggregated
    spill margins enter the kernel as one extra operand summed into the
    phase-boundary dual, and the grad-side COO scatter runs in XLA on
    the emitted margins. Bitwise vs the audited split spill path, with
    and without the one-hot cache."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.learners.store import _nudge_zero_dual
    from wormhole_tpu.ops.loss import create_loss

    rng = np.random.default_rng(2)
    pw, labels_u8, pad_b, pad_r = make_spill_block(rng)
    w = (rng.standard_normal(SPEC.nb) * 0.1).astype(np.float32)
    labels = np.minimum(labels_u8, 1).astype(np.float32)
    mask = (labels_u8 != 255).astype(np.float32)
    _, dual_fn = create_loss("hinge")

    @jax.jit
    def split(pw, w, labels, mask, ob, orow):
        margin = tilemm.forward_margins(pw, w, SPEC, ob, orow)
        dual = _nudge_zero_dual(dual_fn(margin, labels, mask),
                                labels, mask)
        return margin, tilemm.backward_grad(pw, dual, SPEC, ob, orow)

    def make_fused(cache):
        @jax.jit
        def fused(pw, w, labels, mask, ob, orow):
            sp = tilemm.spill_margin_rows(w, ob, orow, SPEC)
            margin, g = tilemm.fused_step_grad(
                pw, w, labels, mask, SPEC, "hinge", False, cache=cache,
                spill_margins=sp)
            dual = _nudge_zero_dual(dual_fn(margin, labels, mask),
                                    labels, mask)
            return margin, tilemm.spill_grad_scatter(g, dual, ob, orow,
                                                     SPEC)
        return fused

    args = [jnp.asarray(x) for x in (pw, w, labels, mask, pad_b, pad_r)]
    mg_s, g_s = split(*args)
    for cache in (False, True):
        mg_f, g_f = make_fused(cache)(*args)
        np.testing.assert_array_equal(np.asarray(mg_f), np.asarray(mg_s))
        np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_s))


def test_fused_step_update_bitwise():
    """Kernel-level in-place FTRL: the update that runs inside the grid
    (the gradient never reaches HBM) produces the same post-update
    w/z/n slots as split grad -> handle.push — cached and uncached."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.ops.loss import create_loss
    from wormhole_tpu.ops.penalty import L1L2

    rng = np.random.default_rng(4)
    buckets, rows = make_pairs(rng, 4000)
    pw, _, _ = tilemm.encode_block(buckets, rows, SPEC)
    s32 = (rng.standard_normal((SPEC.nb, 3)) * 0.1).astype(np.float32)
    s32[:, 2] = np.abs(s32[:, 2])           # n slot is a running sum-sq
    labels = (rng.random(SPEC.block_rows) < 0.4).astype(np.float32)
    mask = np.ones(SPEC.block_rows, np.float32)
    handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
    _, dual_fn = create_loss("logit")

    @jax.jit
    def split(pw, s32, labels, mask):
        w = handle.weights(s32)
        margin = tilemm.forward_margins(pw, w, SPEC)
        dual = dual_fn(margin, labels, mask)
        grad = tilemm.backward_grad(pw, dual, SPEC)
        return margin, handle.push(s32, grad, jnp.float32(0),
                                   jnp.float32(0))

    def make_fused(cache):
        @jax.jit
        def fused(pw, s32, labels, mask):
            from wormhole_tpu.learners import table as tbl
            margin, planes, wd2 = tilemm.fused_step_update(
                pw, tbl.split(s32), labels, mask, SPEC, "logit", handle,
                cache=cache)
            return margin, tbl.join(planes), wd2
        return fused

    args = (jnp.asarray(pw), jnp.asarray(s32), jnp.asarray(labels),
            jnp.asarray(mask))
    mg_s, new_s = split(*args)
    d0 = np.asarray(new_s)[:, 0].astype(np.float64) - s32[:, 0]
    for cache in (False, True):
        mg_f, new_f, wd2 = make_fused(cache)(*args)
        np.testing.assert_array_equal(np.asarray(mg_f), np.asarray(mg_s))
        np.testing.assert_array_equal(np.asarray(new_f), np.asarray(new_s))
        # the in-kernel progress number: only its summation order differs
        np.testing.assert_allclose(float(wd2), np.sum(d0 * d0), rtol=1e-6)


def _run_linear(blocks, info, kernel, loss, algo, seed=1, cache="auto"):
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.learners.handles import LearnRate, create_handle
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2

    st = ShardedStore(
        StoreConfig(num_buckets=info.nb, loss=loss,
                    tile_step_kernel=kernel, tile_onehot_cache=cache),
        create_handle(algo, L1L2(1.0, 0.1), LearnRate(0.1, 1.0)))
    rng = np.random.default_rng(seed)
    st.slots = jnp.asarray(
        (rng.standard_normal(st.slots.shape) * 0.1).astype(np.float32))
    for blk in blocks:
        st.tile_train_step(blk, info)
    jax.block_until_ready(st.slots)
    return np.asarray(st.slots), np.asarray(st._macc), st.step_kernel


@pytest.mark.parametrize("loss,algo,resolved", [
    ("logit", "ftrl", "in_place"),
    ("hinge", "adagrad", ""),
    ("square_hinge", "ftrl", "in_place")])
def test_store_step_parity(loss, algo, resolved):
    """Whole linear train steps: slots AND the packed metric accumulator
    stay bitwise across kernels AND cache settings, including padded
    (label 255) rows. The forced-fused store must have resolved the
    expected variant; step_kernel records the cache decision."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    info = make_info()
    blocks = []
    for _ in range(2):
        pw, labels = make_block(rng)
        blocks.append({"pw": jnp.asarray(pw), "labels": jnp.asarray(labels)})
    # SPEC's cache planes fit the VMEM budget, so auto admits the cache
    s_f, m_f, k_f = _run_linear(blocks, info, "fused", loss, algo)
    s_s, m_s, k_s = _run_linear(blocks, info, "split", loss, algo)
    s_n, m_n, k_n = _run_linear(blocks, info, "fused", loss, algo,
                                cache="off")
    from wormhole_tpu.learners.store import IN_PLACE
    why = IN_PLACE if resolved == "in_place" else ""
    assert k_f == ("fused", why, "onehot_cache=on")
    assert k_s == ("split", "forced",
                   "onehot_cache=off:split path shares no phases")
    assert k_n == ("fused", why, "onehot_cache=off:forced off")
    np.testing.assert_array_equal(s_f, s_s)
    np.testing.assert_array_equal(s_n, s_s)
    for m in (m_f, m_n):
        # the progress number sum((w_new - w_old)**2) is summed inside
        # the in-place kernel, in another order than XLA's: equal to
        # rounding there, and every other metric to the bit
        np.testing.assert_array_equal(np.delete(m, 3), np.delete(m_s, 3))
        np.testing.assert_allclose(m[3], m_s[3], rtol=1e-6)
    assert m_s[3] > 0


def test_fm_store_step_parity():
    """FM: the multi-channel one-grid step (pulls and pushes never in
    HBM; without an overflow list the AdaGrad update runs inside the
    kernel, a tile at a time) keeps slots and metrics bitwise: the
    update's products are ``*one``-guarded (models/fm.FMAdaGrad), so
    the kernel and the XLA pass round alike. The one-hot cache is
    structurally off for multi-channel kernels."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.models.fm import FMConfig, FMStore

    rng = np.random.default_rng(6)
    info = make_info()
    blocks = []
    for _ in range(2):
        pw, labels = make_block(rng)
        blocks.append({"pw": jnp.asarray(pw), "labels": jnp.asarray(labels)})

    def run(kernel):
        st = FMStore(FMConfig(num_buckets=info.nb, dim=4, loss="logit",
                              l1=0.5, l2=0.05, seed=7,
                              tile_step_kernel=kernel))
        for blk in blocks:
            st.tile_train_step(blk, info)
        jax.block_until_ready(st.slots)
        return np.asarray(st.slots), np.asarray(st._macc), st.step_kernel

    from wormhole_tpu.models.fm import IN_PLACE
    s_f, m_f, k_f = run("fused")
    s_s, m_s, k_s = run("split")
    assert k_f[:2] == ("fused", IN_PLACE)
    assert k_f[2].startswith("onehot_cache=off:multi-channel")
    assert k_s[0] == "split"
    np.testing.assert_array_equal(s_f, s_s)
    # the progress number is summed inside the kernel, a lane apiece, in
    # another order than XLA's (as test_store_step_parity's): equal to
    # rounding, and every other metric to the bit
    np.testing.assert_array_equal(np.delete(m_f, 3), np.delete(m_s, 3))
    np.testing.assert_allclose(m_f[3], m_s[3], rtol=1e-6)
    assert m_s[3] > 0


def test_fm_store_spill_fused_bitwise():
    """FM spill blocks fuse too: the pre-aggregated spill pulls ride in
    as a grid operand and the kernel emits the dual channels for the
    XLA push scatter. Whole-store bitwise vs the split spill path."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.models.fm import FMConfig, FMStore

    rng = np.random.default_rng(13)
    oc = 1536
    pw, labels, pad_b, pad_r = make_spill_block(rng, oc=oc)
    blk = {"pw": jnp.asarray(pw), "labels": jnp.asarray(labels),
           "ovf_b": jnp.asarray(pad_b), "ovf_r": jnp.asarray(pad_r)}
    info = make_info(ovf_cap=oc)

    def run(kernel):
        st = FMStore(FMConfig(num_buckets=info.nb, dim=4, loss="logit",
                              l1=0.5, l2=0.05, seed=7,
                              tile_step_kernel=kernel))
        st.tile_train_step(blk, info)
        jax.block_until_ready(st.slots)
        return np.asarray(st.slots), np.asarray(st._macc), st.step_kernel

    s_f, m_f, k_f = run("fused")
    s_s, m_s, k_s = run("split")
    assert k_f[:2] == ("fused", "")
    assert k_s[0] == "split"
    np.testing.assert_array_equal(s_f, s_s)
    np.testing.assert_array_equal(m_f, m_s)


def test_wide_deep_fused_parity():
    """Round 8: spill-free wide&deep blocks fuse — the MLP forward and
    backward run in-kernel at the phase boundary. Whole-store parity
    vs split: slots, MLP params, AdaGrad accumulators and metrics. At
    float tolerance, not bitwise: the in-kernel tower walks the grid in
    1024-row chunks (the only layout the chip's compiler accepts, see
    tilemm._make_wd_step_kernel), so it sums the same products in
    another order than the split path's whole-block ``x @ W``."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.models.wide_deep import (WideDeepConfig,
                                               WideDeepStore)

    rng = np.random.default_rng(7)
    info = make_info()
    pw, labels = make_block(rng)
    blk = {"pw": jnp.asarray(pw), "labels": jnp.asarray(labels)}

    def run(kernel):
        st = WideDeepStore(WideDeepConfig(num_buckets=info.nb, dim=4,
                                          hidden=(8,), seed=3,
                                          tile_step_kernel=kernel))
        st.tile_train_step(blk, info)
        jax.block_until_ready(st.slots)
        return (np.asarray(st.slots),
                {k: np.asarray(v) for k, v in st.mlp.items()},
                {k: np.asarray(v) for k, v in st.mlp_accum.items()},
                np.asarray(st._macc), st.step_kernel)

    s_f, mlp_f, acc_f, m_f, k_f = run("fused")
    s_s, mlp_s, acc_s, m_s, k_s = run("split")
    assert k_f[:2] == ("fused", "")
    assert k_s[0] == "split" and k_s[1] == "forced"
    tol = dict(rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(s_f, s_s, **tol)
    np.testing.assert_allclose(m_f, m_s, **tol)
    for key in mlp_s:
        np.testing.assert_allclose(mlp_f[key], mlp_s[key], **tol)
        np.testing.assert_allclose(acc_f[key], acc_s[key], **tol)


def test_wide_deep_vmem_fallback_and_spill_split():
    """wide&deep still records a split reason when the MLP phase blows
    the VMEM budget (oversized hidden) or the block spills."""
    from wormhole_tpu.models.wide_deep import (WideDeepConfig,
                                               WideDeepStore)

    info = make_info()
    st = WideDeepStore(WideDeepConfig(num_buckets=info.nb, dim=4,
                                      hidden=(1 << 14, 1 << 14), seed=3,
                                      tile_step_kernel="fused"))
    st._tile_step(info, "train")
    assert st.step_kernel[0] == "split"
    assert "VMEM" in st.step_kernel[1]
    st2 = WideDeepStore(WideDeepConfig(num_buckets=info.nb, dim=4,
                                       hidden=(8,), seed=3,
                                       tile_step_kernel="fused"))
    st2._tile_step(make_info(ovf_cap=64), "train")
    assert st2.step_kernel[0] == "split"
    assert "spill" in st2.step_kernel[1]


def test_spill_block_fused_bitwise():
    """Round 8: a capped-overflow block (hot bucket past `cap`) fuses
    via the spill-margin operand — the forced-fused store must resolve
    FUSED now (the round-6 structural downgrade is gone) and keep the
    audited split spill path's exact bits, cache on and off."""
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    oc = 1536
    pw, labels, pad_b, pad_r = make_spill_block(rng, oc=oc)
    blk = {"pw": jnp.asarray(pw), "labels": jnp.asarray(labels),
           "ovf_b": jnp.asarray(pad_b), "ovf_r": jnp.asarray(pad_r)}
    info = make_info(ovf_cap=oc)

    s_f, m_f, k_f = _run_linear([blk], info, "fused", "logit", "ftrl")
    s_s, m_s, k_s = _run_linear([blk], info, "split", "logit", "ftrl")
    s_n, m_n, k_n = _run_linear([blk], info, "fused", "logit", "ftrl",
                                cache="off")
    # the spill block resolves fused (grad-emitting variant: the COO
    # scatter needs the grad in HBM, so no in-place fused_update)
    assert k_f == ("fused", "", "onehot_cache=on")
    assert k_s[0] == "split"
    assert k_n == ("fused", "", "onehot_cache=off:forced off")
    np.testing.assert_array_equal(s_f, s_s)
    np.testing.assert_array_equal(m_f, m_s)
    np.testing.assert_array_equal(s_n, s_s)
    np.testing.assert_array_equal(m_n, m_s)


def test_mesh_shard_unaffected_by_step_kernel():
    """The data:2,model:4 mesh path always runs the split shard_map step
    (psums sit between the phases); the knob must neither break it nor
    change its bits."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh

    rng = np.random.default_rng(9)
    nb = 4 * tilemm.TILE            # one tile per model shard
    spec = tilemm.make_spec(nb, subblocks=2, cap=1280)
    from wormhole_tpu.data.crec import CRec2Info
    info = CRec2Info(nnz=8, block_rows=spec.block_rows,
                     total_rows=2 * spec.block_rows, nb=nb,
                     subblocks=2, cap=spec.cap, ovf_cap=0)
    blocks = {"pw": [], "labels": []}
    for _ in range(2):
        buckets, rows = make_pairs(rng, 3000, spec)
        pw, ovb, _ = tilemm.encode_block(buckets, rows, spec)
        assert not len(ovb)
        labels = (rng.random(spec.block_rows) < 0.4).astype(np.uint8)
        blocks["pw"].append(pw)
        blocks["labels"].append(labels)
    blocks = {k: np.stack(v) for k, v in blocks.items()}

    def run(kernel):
        rt = MeshRuntime.create()
        rt.mesh = make_mesh("data:2,model:4", jax.devices()[:8])
        st = ShardedStore(
            StoreConfig(num_buckets=nb, loss="logit",
                        tile_step_kernel=kernel),
            FTRLHandle(penalty=L1L2(0.1, 0.01), lr=LearnRate(0.5, 1.0)),
            rt)
        st.tile_train_step_mesh(blocks, info)
        return np.asarray(jax.device_get(st.slots))

    np.testing.assert_array_equal(run("fused"), run("split"))
