"""Stage-by-stage timing of the fwd tile kernel (dev diagnostic).

Builds cumulative variants of the fwd kernel to locate where the time
goes: D0 relayout+astype only, D1 +ohhi build, D2 +gather matmul,
D3 +pick matmul, D4 full kernel (= tilemm fwd). Results are WRONG for
all but D4 — timing only.
"""
from __future__ import annotations

import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, ".")
from wormhole_tpu.ops import tilemm  # noqa: E402
from wormhole_tpu.ops.tilemm import (  # noqa: E402
    A_HI, B_LO, RH, RL, HI_SH, HI_M, LO_SH, LO_M, RLO_SH, RLO_M,
    RHI_SH, RHI_M, _oh_rep, _mask_sel, _ohT_vec)

NB = 1 << 22
ROWS = 98304
NNZ = 39


from scripts.ktune import _force, timeit  # noqa: E402  (shared harness)


def _kernel(spec, stage, pw_ref, w_ref, mg_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        mg_ref[:] = jnp.zeros_like(mg_ref)

    S, GS, C, N = spec.subblocks, spec.group, spec.cap, spec.n
    ones_pick = jnp.ones((B_LO, RL), jnp.bfloat16)
    for g in range(S // GS):
        mgs = [mg_ref[g * GS + j] for j in range(GS)]
        for tb in range(spec.tiles_step):
            wt = w_ref[tb]
            pc = pw_ref[tb, g].astype(jnp.int32)
            rep = pc[:, None]
            if stage == 0:          # relayout + one astype pass
                x = (rep & 127).astype(jnp.bfloat16) * ones_pick[:1]
                for j in range(GS):
                    mgs[j] += x[j * 64:(j + 1) * 64, :].astype(jnp.float32)
                continue
            ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)
            if stage == 1:          # + ohhi build
                for j in range(GS):
                    mgs[j] += ohhi[j * 64:(j + 1) * 64, :].astype(
                        jnp.float32)
                continue
            if stage == 21:         # gather vs a CONSTANT rhs
                m = jnp.dot(ohhi, ones_pick,
                            preferred_element_type=jnp.float32)
                for j in range(GS):
                    mgs[j] += m[j * 64:(j + 1) * 64, :]
                continue
            if stage == 22:         # gather, rhs = wt of tile 0 only
                m = jnp.dot(ohhi, w_ref[0],
                            preferred_element_type=jnp.float32)
                for j in range(GS):
                    mgs[j] += m[j * 64:(j + 1) * 64, :]
                continue
            m = jnp.dot(ohhi, wt, preferred_element_type=jnp.float32)
            if stage == 23:         # TWO varying-rhs gathers
                m2 = jnp.dot(ohhi, w_ref[(tb + 1) % spec.tiles_step],
                             preferred_element_type=jnp.float32)
                for j in range(GS):
                    mgs[j] += m[j * 64:(j + 1) * 64, :] \
                        + m2[j * 64:(j + 1) * 64, :]
                continue
            if stage == 2:          # + gather matmul
                for j in range(GS):
                    mgs[j] += m[j * 64:(j + 1) * 64, :]
                continue
            wp = jnp.dot(_mask_sel(rep, LO_SH, LO_M, m), ones_pick,
                         preferred_element_type=jnp.float32)
            if stage == 3:          # + pick matmul
                for j in range(GS):
                    mgs[j] += wp[j * 64:(j + 1) * 64, :]
                continue
            rhs = _mask_sel(rep, RLO_SH, RLO_M, wp)
            for j in range(GS):
                rhiT = _ohT_vec(pc[j * C:(j + 1) * C], RHI_SH, RHI_M,
                                RH, C)
                mgs[j] += jnp.dot(rhiT, rhs[j * C:(j + 1) * C],
                                  preferred_element_type=jnp.float32)
        for j in range(GS):
            mg_ref[g * GS + j] = mgs[j]


def build(spec, stage):
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks

    @jax.jit
    def fwd(pw, w):
        wt = w.reshape(T, A_HI, B_LO).astype(jnp.bfloat16)
        return pl.pallas_call(
            partial(_kernel, spec, stage),
            grid=(T // TB,),
            in_specs=[
                pl.BlockSpec((TB, SG, N), lambda t: (t, 0, 0)),
                pl.BlockSpec((TB, A_HI, B_LO), lambda t: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((S, RH, RL), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
        )(pw, wt)

    return fwd


def main():
    from wormhole_tpu.parallel.mesh import (enable_compile_cache,
                                            require_tpu)
    enable_compile_cache()
    require_tpu(__file__)     # a timing harness: no CPU fallback
    from wormhole_tpu.data.crec import default_cap
    spec = tilemm.make_spec(NB, ROWS // tilemm.RSUB, default_cap(NNZ, NB))
    print("spec:", spec)
    rng = np.random.default_rng(0)
    buckets = rng.integers(0, NB, size=ROWS * NNZ, dtype=np.int64)
    rows = np.repeat(np.arange(ROWS, dtype=np.int64), NNZ)
    pw_np, _, _ = tilemm.encode_block(buckets, rows, spec)
    w_np = rng.normal(0, 0.1, NB).astype(np.float32)
    pw, w = jax.device_put(pw_np), jax.device_put(w_np)
    stages = [int(s) for s in sys.argv[1:]] or [0, 1, 2, 3, 4]
    prev = 0.0
    for st in stages:
        t = timeit(build(spec, st), pw, w)
        print(f"stage {st}: {t*1e3:7.3f} ms  (delta {(t-prev)*1e3:+7.3f})")
        prev = t


if __name__ == "__main__":
    main()
