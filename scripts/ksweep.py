"""Interleaved kernel A/B sweep.

Variants are timed in ALTERNATING short windows, so whatever disturbs
the host or the device for a while disturbs every variant alike, and
the per-variant MINIMUM is compared. Sweeps cap (pad waste vs
exact-overflow scatter cost) and tiles_step.

Usage: python scripts/ksweep.py [caps] [tbs]   e.g. 1280,1408,1536 8,16
"""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from wormhole_tpu.ops import tilemm  # noqa: E402

NB = 1 << 22
ROWS = 98304
NNZ = 39


def _force(o):
    float(np.asarray(jax.tree_util.tree_leaves(o)[0].ravel()[0]))


def build_variant(cap: int, tb: int, rng):
    # fuse=1: swept tiles_step values need not divide an auto-picked fuse
    spec = dataclasses.replace(
        tilemm.make_spec(NB, ROWS // tilemm.RSUB, cap), tiles_step=tb,
        fuse=1)
    buckets = rng.integers(0, NB, size=ROWS * NNZ, dtype=np.int64)
    rows = np.repeat(np.arange(ROWS, dtype=np.int64), NNZ)
    pw_np, ovb, ovr = tilemm.encode_block(buckets, rows, spec)
    oc = max(128, -(-len(ovb) // 128) * 128) if len(ovb) else 0
    print(f"cap={cap} tb={tb}: overflow {len(ovb)} pairs (oc={oc})")
    pw = jax.device_put(pw_np)
    if oc:
        ovb_p = np.full(oc, 0xFFFFFFFF, np.uint32)
        ovr_p = np.zeros(oc, np.uint32)
        ovb_p[:len(ovb)] = ovb
        ovr_p[:len(ovr)] = ovr
        ovb_d, ovr_d = jax.device_put(ovb_p), jax.device_put(ovr_p)
    else:
        ovb_d = ovr_d = None
    w = jax.device_put(rng.normal(0, 0.1, NB).astype(np.float32))
    dual = jax.device_put(rng.normal(0, 1.0, ROWS).astype(np.float32))

    @jax.jit
    def step(w, dual):
        mg = tilemm.forward_margins(pw, w, spec, ovb_d, ovr_d)
        g = tilemm.backward_grad(pw, dual, spec, ovb_d, ovr_d)
        return mg, g

    return step, (w, dual)


def main():
    from wormhole_tpu.parallel.mesh import (enable_compile_cache,
                                            require_tpu)
    enable_compile_cache()
    require_tpu(__file__)     # a timing harness: no CPU fallback
    caps = [int(c) for c in (sys.argv[1].split(",") if len(sys.argv) > 1
                             else ["1408"])]
    tbs = [int(t) for t in (sys.argv[2].split(",") if len(sys.argv) > 2
                            else ["16"])]
    rng = np.random.default_rng(0)
    variants = {}
    for cap in caps:
        for tb in tbs:
            variants[(cap, tb)] = build_variant(cap, tb, rng)
    # compile + burn everything first
    for step, args in variants.values():
        for _ in range(40):
            o = step(*args)
        _force(o)
    best = {k: float("inf") for k in variants}
    REPS, WINDOWS = 5, 12
    for _ in range(WINDOWS):
        for k, (step, args) in variants.items():   # interleave
            t0 = time.perf_counter()
            o = None
            for _ in range(REPS):
                o = step(*args)
            _force(o)
            best[k] = min(best[k], (time.perf_counter() - t0) / REPS)
    for (cap, tb), t in sorted(best.items()):
        print(f"cap={cap} tb={tb}: {t*1e3:7.3f} ms/step "
              f"-> {ROWS/t/1e6:.2f} M ex/s (fwd+bwd)")


if __name__ == "__main__":
    main()
