"""Kernel floor attribution — where do the fwd-kernel milliseconds go?

Round-4 established the tile kernels are NOT MXU-shape-bound (deleting a
whole matmul was time-neutral under separate timing). This harness makes
the diagnosis quantitative: an incremental-deletion series over the fwd
kernel, every variant timed INTERLEAVED in the same windows
(min-of-windows per variant), so per-stage deltas are trustworthy:

  F0 full            the production kernel body
  F1 -hist           per-subblock histogram matmuls (+their rhiT builds)
  F2 -rlo-mask       the row-lo spread select
  F3 -pick           the ones-matmul lane pick
  F4 -lo-mask        the bucket-lo select
  F5 -gather         the OH(hi) @ W matmul
  F6 builds-only     ohhi build + accumulate (the irreducible floor probe)
  I8 i8-gather       ohhi as int8 with an i8xi8 MXU dot on a quantized W
                     (an untried lever — timing only; the i8
                     product is NOT numerically usable for f32 models)
  HO hoisted-builds  one-hot builds hoisted out of the tile loop (probes
                     whether builds serialize with the matmuls or overlap)

If stage deltas add up to ~F0, the units serialize and the floor model is
sum-of-stages; if F0 << sum, Mosaic overlaps and the floor is max().

Usage: python scripts/kfloor.py [reps] [windows]
"""
from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, ".")

from wormhole_tpu.ops import tilemm  # noqa: E402
from wormhole_tpu.ops.tilemm import (A_HI, B_LO, HI_M, HI_SH, LO_M, LO_SH,  # noqa: E402
                                     RH, RHI_M, RHI_SH, RL, RLO_M, RLO_SH,
                                     TileSpec, _mask_sel, _oh_rep, _ohT_vec)

NB = 1 << 22
ROWS = 98304
NNZ = 39


def _lanepack_kernel(spec: TileSpec, only: bool, pw_ref, x_ref, w_ref,
                     mg_ref):
    """The full fwd chain with the pair-word RELAYOUT replaced by a
    static single-lane slice of a lane-packed pairs array x_ref
    (SG, N, TB): each tile's words sit in one LANE, so getting them onto
    sublanes is a native lane-broadcast (within-vreg) instead of the
    cross-vreg lanes->sublanes relayout that dominates the kernel.
    ``only`` mirrors the onlyrelay probe (slice+accumulate, no chain)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        mg_ref[:] = jnp.zeros_like(mg_ref)

    S, GS, C, N = spec.subblocks, spec.group, spec.cap, spec.n
    TB = spec.tiles_step
    ones_pick = jnp.ones((B_LO, RL), jnp.bfloat16)
    for g in range(S // GS):
        mgs = [mg_ref[g * GS + j] for j in range(GS)]
        xg = x_ref[0, g].astype(jnp.int32)       # (N, TB) words on lanes
        for tb in range(TB):
            rep = xg[:, tb:tb + 1]               # lane slice, no relayout
            if only:
                for j in range(GS):
                    mgs[j] += (rep[j * C:j * C + RH]
                               .astype(jnp.float32)
                               * jnp.ones((RH, RL), jnp.float32))
                continue
            wt = w_ref[tb]
            pc = pw_ref[tb, g].astype(jnp.int32)
            ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)
            m = jnp.dot(ohhi, wt, preferred_element_type=jnp.float32)
            wp = jnp.dot(_mask_sel(rep, LO_SH, LO_M, m), ones_pick,
                         preferred_element_type=jnp.float32)
            rhs = _mask_sel(rep, RLO_SH, RLO_M, wp)
            for j in range(GS):
                rhiT = _ohT_vec(pc[j * C:(j + 1) * C], RHI_SH, RHI_M,
                                RH, C)
                mgs[j] += jnp.dot(rhiT, rhs[j * C:(j + 1) * C],
                                  preferred_element_type=jnp.float32)
        for j in range(GS):
            mg_ref[g * GS + j] = mgs[j]


def build_lanepack(spec: TileSpec, only: bool):
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks

    @jax.jit
    def fwd(pw, x, w):
        wt = w.reshape(T, A_HI, B_LO).astype(jnp.bfloat16)
        return pl.pallas_call(
            partial(_lanepack_kernel, spec, only),
            grid=(T // TB,),
            in_specs=[
                pl.BlockSpec((TB, SG, N), lambda t: (t, 0, 0)),
                pl.BlockSpec((1, SG, N, TB), lambda t: (t, 0, 0, 0)),
                pl.BlockSpec((TB, A_HI, B_LO), lambda t: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((S, RH, RL), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
        )(pw, x, wt)

    return fwd


def _variant_kernel(spec: TileSpec, stage: str, pw_ref, w_ref, mg_ref):
    """The fwd kernel with later stages progressively deleted.

    stage one of: full, nohist, norlo, nopick, nolo, nogather, builds,
    i8, hoist."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        mg_ref[:] = jnp.zeros_like(mg_ref)

    S, GS, C, N = spec.subblocks, spec.group, spec.cap, spec.n
    TB = spec.tiles_step
    ones_pick = jnp.ones((B_LO, RL), jnp.bfloat16)
    for g in range(S // GS):
        mgs = [mg_ref[g * GS + j] for j in range(GS)]
        if stage == "onlyrelay":
            # the relayout alone: one (N,1) lanes->sublanes per (g,tb),
            # consumed by a trivial accumulate
            for tb in range(TB):
                rep = pw_ref[tb, g].astype(jnp.int32)[:, None]
                for j in range(GS):
                    mgs[j] += (rep[j * C:j * C + RH]
                               .astype(jnp.float32) * jnp.ones(
                                   (RH, RL), jnp.float32))
            for j in range(GS):
                mg_ref[g * GS + j] = mgs[j]
            continue
        if stage == "batchrelay":
            # ONE relayout per g covering every tile's pairs; the full
            # production chain otherwise — probes whether the relayout
            # cost is per-issue (latency) or per-element (throughput)
            pc_all = pw_ref[:, g].reshape(TB * N).astype(jnp.int32)
            rep_all = pc_all[:, None]
            for tb in range(TB):
                wt = w_ref[tb]
                pc = pw_ref[tb, g].astype(jnp.int32)
                rep = rep_all[tb * N:(tb + 1) * N]
                ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)
                m = jnp.dot(ohhi, wt, preferred_element_type=jnp.float32)
                wp = jnp.dot(_mask_sel(rep, LO_SH, LO_M, m), ones_pick,
                             preferred_element_type=jnp.float32)
                rhs = _mask_sel(rep, RLO_SH, RLO_M, wp)
                for j in range(GS):
                    rhiT = _ohT_vec(pc[j * C:(j + 1) * C], RHI_SH,
                                    RHI_M, RH, C)
                    mgs[j] += jnp.dot(rhiT, rhs[j * C:(j + 1) * C],
                                      preferred_element_type=jnp.float32)
            for j in range(GS):
                mg_ref[g * GS + j] = mgs[j]
            continue
        if stage == "norelay":
            # no relayout at all: a synthetic iota rep stands in (wrong
            # results, same op structure) — delta vs full == the whole
            # relayout bill
            for tb in range(TB):
                wt = w_ref[tb]
                pc = pw_ref[tb, g].astype(jnp.int32)
                rep = (jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0)
                       * (tb + 1))
                ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)
                m = jnp.dot(ohhi, wt, preferred_element_type=jnp.float32)
                wp = jnp.dot(_mask_sel(rep, LO_SH, LO_M, m), ones_pick,
                             preferred_element_type=jnp.float32)
                rhs = _mask_sel(rep, RLO_SH, RLO_M, wp)
                for j in range(GS):
                    rhiT = _ohT_vec(pc[j * C:(j + 1) * C], RHI_SH,
                                    RHI_M, RH, C)
                    mgs[j] += jnp.dot(rhiT, rhs[j * C:(j + 1) * C],
                                      preferred_element_type=jnp.float32)
            for j in range(GS):
                mg_ref[g * GS + j] = mgs[j]
            continue
        if stage == "hoist":
            # builds for tb=0 reused across the tile loop: same matmul
            # count, 1/tiles_step the VPU build work
            pc0 = pw_ref[0, g].astype(jnp.int32)
            rep0 = pc0[:, None]
            ohhi0 = _oh_rep(rep0, HI_SH, HI_M, N, 128)
            rhiTs0 = [_ohT_vec(pc0[j * C:(j + 1) * C], RHI_SH, RHI_M,
                               RH, C) for j in range(GS)]
        for tb in range(spec.tiles_step):
            if stage == "hoist":
                wt = w_ref[tb]
                m = jnp.dot(ohhi0, wt, preferred_element_type=jnp.float32)
                wp = jnp.dot(_mask_sel(rep0, LO_SH, LO_M, m), ones_pick,
                             preferred_element_type=jnp.float32)
                rhs = _mask_sel(rep0, RLO_SH, RLO_M, wp)
                for j in range(GS):
                    mgs[j] += jnp.dot(rhiTs0[j], rhs[j * C:(j + 1) * C],
                                      preferred_element_type=jnp.float32)
                continue
            wt = w_ref[tb]
            pc = pw_ref[tb, g].astype(jnp.int32)
            rep = pc[:, None]
            if stage == "i8":
                iota = jax.lax.broadcasted_iota(jnp.int32, (N, 128), 1)
                ohhi8 = (((rep >> HI_SH) & HI_M) == iota).astype(jnp.int8)
                w8 = wt.astype(jnp.int8)      # timing stand-in quantize
                m = jax.lax.dot_general(
                    ohhi8, w8, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32).astype(jnp.float32)
            else:
                ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)
                if stage == "builds":
                    for j in range(GS):
                        mgs[j] += ohhi[j * C:j * C + RH, :RL].astype(
                            jnp.float32)
                    continue
                if stage == "nogather":
                    m = (rep & 0x7FFFFF).astype(jnp.float32) * ohhi.astype(
                        jnp.float32)[:, :128]
                else:
                    m = jnp.dot(ohhi, wt,
                                preferred_element_type=jnp.float32)
            if stage == "nolo" or stage == "nogather":
                wp_in = m.astype(jnp.bfloat16)
            else:
                wp_in = _mask_sel(rep, LO_SH, LO_M, m)
            if stage == "nopick":
                wp = m
            else:
                wp = jnp.dot(wp_in, ones_pick,
                             preferred_element_type=jnp.float32)
            if stage == "norlo" or stage == "nopick":
                rhs = wp.astype(jnp.bfloat16)
            else:
                rhs = _mask_sel(rep, RLO_SH, RLO_M, wp)
            if stage == "nohist":
                for j in range(GS):
                    mgs[j] += rhs[j * C:j * C + RH, :RL].astype(jnp.float32)
            else:
                rhiTs = [_ohT_vec(pc[j * C:(j + 1) * C], RHI_SH, RHI_M,
                                  RH, C) for j in range(GS)]
                for j in range(GS):
                    mgs[j] += jnp.dot(rhiTs[j], rhs[j * C:(j + 1) * C],
                                      preferred_element_type=jnp.float32)
        for j in range(GS):
            mg_ref[g * GS + j] = mgs[j]


def build_variant(spec: TileSpec, stage: str):
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks

    @jax.jit
    def fwd(pw, w):
        wt = w.reshape(T, A_HI, B_LO).astype(jnp.bfloat16)
        return pl.pallas_call(
            partial(_variant_kernel, spec, stage),
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


def _force(o):
    float(np.asarray(o.ravel()[0]))


def main():
    from wormhole_tpu.parallel.mesh import (enable_compile_cache,
                                            require_tpu)
    enable_compile_cache()
    require_tpu(__file__)     # a timing harness: no CPU fallback
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 15
    windows = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    from wormhole_tpu.data.crec import default_cap
    spec = tilemm.make_spec(NB, ROWS // tilemm.RSUB, default_cap(NNZ, NB))
    print("spec:", spec, flush=True)
    rng = np.random.default_rng(0)
    buckets = rng.integers(0, NB, size=ROWS * NNZ, dtype=np.int64)
    rows = np.repeat(np.arange(ROWS, dtype=np.int64), NNZ)
    pw_np, _, _ = tilemm.encode_block(buckets, rows, spec)
    w_np = rng.normal(0, 0.1, NB).astype(np.float32)
    pw, w = jax.device_put(pw_np), jax.device_put(w_np)

    # lane-packed pairs: (T, SG, N) -> (T//TB, SG, N, TB), words of the
    # 16 tiles of one grid step side by side on lanes
    TB = spec.tiles_step
    x_np = (pw_np.reshape(spec.tiles // TB, TB, pw_np.shape[1],
                          pw_np.shape[2])
            .transpose(0, 2, 3, 1).copy())
    x = jax.device_put(x_np)

    stages = ["full", "nohist", "norlo", "nopick", "nolo", "nogather",
              "builds", "i8", "hoist", "onlyrelay", "norelay",
              "lanepack", "lanepackonly"]
    fns = {}
    for s in stages:
        t0 = time.perf_counter()
        try:
            if s.startswith("lanepack"):
                raw = build_lanepack(spec, s == "lanepackonly")
                fn = (lambda pw_, w_, _r=raw: _r(pw_, x, w_))
            else:
                fn = build_variant(spec, s)
            _force(fn(pw, w))          # compile
            fns[s] = fn
            print(f"  compiled {s:12s} in {time.perf_counter()-t0:6.1f}s",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — i8 may be rejected
            print(f"  {s}: COMPILE FAILED: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)
    if "lanepack" in fns and "full" in fns:
        d = np.max(np.abs(np.asarray(fns["lanepack"](pw, w))
                          - np.asarray(fns["full"](pw, w))))
        print(f"  lanepack vs full: max|diff| = {d:.3e}", flush=True)
    # burn-in past the post-compile ramp
    for _ in range(60):
        o = fns["full"](pw, w)
    _force(o)
    best = {s: float("inf") for s in fns}
    for _ in range(windows):
        for s in fns:                  # interleaved: same windows
            t0 = time.perf_counter()
            for _ in range(reps):
                o = fns[s](pw, w)
            _force(o)
            best[s] = min(best[s], (time.perf_counter() - t0) / reps)
    full = best.get("full", float("nan"))
    print(f"\n{'stage':10s} {'ms':>8s} {'delta vs full':>14s}")
    for s in stages:
        if s in best:
            print(f"{s:10s} {best[s]*1e3:8.3f} "
                  f"{(full-best[s])*1e3:+13.3f}")
    # additivity check: do the stage deltas reconstruct the total?
    chain = ["nohist", "norlo", "nopick", "nolo", "nogather"]
    if all(s in best for s in chain):
        deltas = []
        prev = full
        for s in chain:
            deltas.append(prev - best[s])
            prev = best[s]
        print("\nstage costs (serialized-model attribution):")
        for s, d in zip(["hist", "rlo-mask", "pick", "lo-mask", "gather"],
                        deltas):
            print(f"  {s:10s} {d*1e3:8.3f} ms")
        print(f"  residual (builds+grid): {best['nogather']*1e3:.3f} ms")


if __name__ == "__main__":
    main()
