"""What the ``test_tpu_compile*.py`` files share: the described v5e, the
switch from the Pallas interpreter to the Mosaic compiler, and the shapes
of the flagship geometry. One file a store, so that ``--dist loadfile``
spreads the minutes these compiles take over the workers (they were one
file, the longest on the run's critical path); each worker that is given
one loads libtpu in its own process and compiles there."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from wormhole_tpu.ops import tilemm

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


def _hot_form(spec, tiles, vtiles):
    """``(ovf_u, ovf_pw)`` of a hot form of ``tiles`` hot tiles."""
    hs = tilemm.hot_spec(tiles * vtiles, spec.subblocks)
    return [((tiles * tilemm.TILE,), jnp.uint32),
            (hs.pairs_shape, jnp.uint32)]
