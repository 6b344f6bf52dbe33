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

The stores' whole step programs are compiled in files of their own
(``test_tpu_compile_linear.py``, ``_fm.py``, ``_wide_deep.py``; the shared
fixtures are ``tpu_compile_helpers.py``). The cases of a file run one after
another in one process — two processes
compiling for the TPU at once collide on libtpu's lock file — and with
the persistent compilation cache off: a described-topology executable
is written to the cache but cannot be read back without a chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from wormhole_tpu.ops import histmm, tilemm

from tpu_compile_helpers import (CRITEO, NB, _ftrl, _hot_form,  # noqa: F401
                                 compiled_not_interpreted, v5e)


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
