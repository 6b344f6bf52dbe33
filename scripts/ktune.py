"""Kernel tuning harness for ops/tilemm.py — times fwd/bwd separately
on real TPU hardware, checks them against the exact numpy oracle, and
sweeps tiles_step. Not part of the bench; a dev tool.

Usage: python scripts/ktune.py [reps] [tb1,tb2,...]
       python scripts/ktune.py --kernel fused|split|both|cached|both3 \
           [--windows N] [--burn N] [reps]

``--kernel`` times the full FTRL train step instead of the bare
fwd/bwd pair; ``both`` is the A/B mode — each window times split and
fused back-to-back, so the per-window ratio holds even when the
absolute times drift. ``cached`` drives the fused step with the
phase-shared one-hot cache forced on; ``both3`` is the round-8
three-way interleave: each window runs split, fused, and fused+cache
back-to-back and reports both per-window ratios. The cached modes
drop to a narrow-block geometry (one subblock, nnz=16, same bucket
space) where the resolver's auto genuinely admits the cache — at the
default wide geometry the planes need ~2.1 GB of VMEM and the kernel
would not compile on a TPU, so there is nothing to measure there.
"""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import numpy as np

sys.path.insert(0, ".")

from wormhole_tpu.ops import tilemm  # noqa: E402

NB = 1 << 22
ROWS = 98304
NNZ = 39


def _force(o):
    """Force real completion: a D2H read of one element."""
    float(np.asarray(jax.tree_util.tree_leaves(o)[0].ravel()[0]))


def timeit(fn, *args, reps=15, burn=100, windows=10):
    """Min-of-windows: the MIN over several short windows is the
    least-disturbed reading and is what A/B decisions should use."""
    o = None
    for _ in range(burn):
        o = fn(*args)
    _force(o)
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        o = None
        for _ in range(reps):
            o = fn(*args)
        _force(o)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def _build_ab_steps(spec, which):
    """Jitted full train steps for the --kernel A/B: the split oracle
    (fwd pallas_call -> XLA dual -> bwd pallas_call -> XLA push) and
    the fused one-grid step with the in-place FTRL update."""
    import jax.numpy as jnp

    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.ops.loss import create_loss
    from wormhole_tpu.ops.penalty import L1L2

    handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
    _, dual_fn = create_loss("logit")
    steps = {}
    if which in ("split", "both", "both3"):
        @jax.jit
        def split_step(pw, s32, labels, mask):
            w = handle.weights(s32)
            margin = tilemm.forward_margins(pw, w, spec)
            dual = dual_fn(margin, labels, mask)
            grad = tilemm.backward_grad(pw, dual, spec)
            new = handle.push(s32, grad, jnp.float32(0), jnp.float32(0))
            return margin, new
        steps["split"] = split_step
    if which in ("fused", "both", "both3"):
        @jax.jit
        def fused_step(pw, s32, labels, mask):
            return tilemm.fused_step_update(pw, s32, labels, mask,
                                            spec, "logit", handle)
        steps["fused"] = fused_step
    if which in ("cached", "both3"):
        # cache forced past the resolver's VMEM budget model — this is
        # the measurement mode the `on` knob exists for
        @jax.jit
        def cached_step(pw, s32, labels, mask):
            return tilemm.fused_step_update(pw, s32, labels, mask,
                                            spec, "logit", handle,
                                            cache=True)
        steps["cached"] = cached_step
    return handle, steps


def _kernel_ab(spec, pw, which, reps, windows=10, burn=20):
    """Time the resolved train-step kernels; in ``both`` mode each
    window runs split then fused back-to-back and the reported ratio
    is the median of the per-window ratios."""
    rng = np.random.default_rng(1)
    handle, steps = _build_ab_steps(spec, which)
    s32 = jax.device_put(
        rng.normal(0, 0.1, (spec.nb, handle.val_len)).astype(np.float32))
    labels = jax.device_put(
        (rng.random(spec.block_rows) < 0.5).astype(np.float32))
    mask = jax.device_put(np.ones(spec.block_rows, np.float32))
    for name, fn in steps.items():
        o = None
        for _ in range(burn):
            o = fn(pw, s32, labels, mask)
        _force(o)
    best = {name: float("inf") for name in steps}
    ratios = {"split/fused": [], "fused/cached": []}
    for _ in range(windows):
        win = {}
        for name, fn in steps.items():
            t0 = time.perf_counter()
            o = None
            for _ in range(reps):
                o = fn(pw, s32, labels, mask)
            _force(o)
            win[name] = (time.perf_counter() - t0) / reps
            best[name] = min(best[name], win[name])
        if "split" in win and "fused" in win:
            ratios["split/fused"].append(win["split"] / win["fused"])
        if "fused" in win and "cached" in win:
            ratios["fused/cached"].append(win["fused"] / win["cached"])
    for name, t in best.items():
        print(f"{name:6s} step {t*1e3:7.3f} ms -> "
              f"{spec.block_rows/t/1e6:.2f} M ex/s")
    for label, rs in ratios.items():
        if rs:
            print(f"{label} ratio: median {np.median(rs):.3f} "
                  f"min {min(rs):.3f} max {max(rs):.3f} "
                  f"({len(rs)} interleaved windows)")


def main():
    from wormhole_tpu.parallel.mesh import (enable_compile_cache,
                                            require_tpu)
    enable_compile_cache()
    require_tpu(__file__)     # a timing harness: no CPU fallback
    args = list(sys.argv[1:])
    kernel = None
    if "--kernel" in args:
        i = args.index("--kernel")
        kernel = args[i + 1]
        if kernel not in ("fused", "split", "both", "cached", "both3"):
            raise SystemExit(f"--kernel must be fused|split|both|"
                             f"cached|both3, got {kernel!r}")
        del args[i:i + 2]
    windows, burn = 10, 20
    if "--windows" in args:
        i = args.index("--windows")
        windows = int(args[i + 1])
        del args[i:i + 2]
    if "--burn" in args:
        i = args.index("--burn")
        burn = int(args[i + 1])
        del args[i:i + 2]
    reps = int(args[0]) if len(args) > 0 else 20
    tbs = ([int(x) for x in args[1].split(",")]
           if len(args) > 1 else [])
    from wormhole_tpu.data.crec import default_cap
    rows_n, nnz = ROWS, NNZ
    if kernel in ("cached", "both3"):
        # cache-admissible narrow geometry (see module docstring)
        rows_n, nnz = tilemm.RSUB, 16
    spec = tilemm.make_spec(NB, rows_n // tilemm.RSUB,
                            default_cap(nnz, NB))
    print("spec:", spec)

    rng = np.random.default_rng(0)
    buckets = rng.integers(0, NB, size=rows_n * nnz, dtype=np.int64)
    rows = np.repeat(np.arange(rows_n, dtype=np.int64), nnz)
    pw_np, ovb, _ = tilemm.encode_block(buckets, rows, spec)
    print(f"overflow pairs: {len(ovb)}")
    w_np = rng.normal(0, 0.1, NB).astype(np.float32)
    dual_np = rng.normal(0, 1.0, rows_n).astype(np.float32)
    # device-resident operands: numpy args would re-upload ~90 MB per
    # call through the host transport and swamp the kernel timing
    pw, w, dual = (jax.device_put(x) for x in (pw_np, w_np, dual_np))

    if kernel is not None:
        # full-train-step A/B on the same encoded block; overflow pairs
        # are dropped from BOTH paths (the fused kernel is dense-only,
        # so the comparison stays operand-identical)
        _kernel_ab(spec, pw, kernel, reps, windows=windows, burn=burn)
        return

    slots = spec.tiles * spec.subblocks * spec.cap
    # MXU N-row pass floor: passes x slots x 16384 MAC @ 98.5e12 MAC/s
    floor = 3 * slots * 16384 / 98.5e12

    fwd, bwd = tilemm._build_fwd(spec), tilemm._build_bwd(spec)
    mg = np.asarray(fwd(pw, w))
    g = np.asarray(bwd(pw, dual))
    om = tilemm.forward_margins_ref(buckets, rows, w_np, ROWS)
    og = tilemm.backward_grad_ref(buckets, rows, dual_np, NB)
    print(f"max|dmargin|={np.max(np.abs(mg - om)):.3e} "
          f"max|dgrad|={np.max(np.abs(g - og)):.3e} (bf16-value rounding)")
    t_f = timeit(fwd, pw, w, reps=reps)
    t_b = timeit(bwd, pw, dual, reps=reps)
    tot = t_f + t_b
    print(f"fwd {t_f*1e3:7.3f} ms (floor-frac {floor/t_f:.3f})  "
          f"bwd {t_b*1e3:7.3f} ms (floor-frac {floor/t_b:.3f})  "
          f"tot {tot*1e3:.2f} ms -> {ROWS/tot/1e6:.2f} M ex/s")

    for tb in tbs:
        f = spec.fuse            # keep the production fuse when tb
        while f > 1 and tb % f:  # allows it, else largest divisor —
            f //= 2              # sweep rows stay comparable to base
        sp = dataclasses.replace(spec, tiles_step=tb, fuse=f)
        f2, b2 = tilemm._build_fwd(sp), tilemm._build_bwd(sp)
        t_f = timeit(f2, pw, w, reps=reps)
        t_b = timeit(b2, pw, dual, reps=reps)
        tot = t_f + t_b
        print(f"TB={tb:2d}: fwd {t_f*1e3:7.3f} bwd {t_b*1e3:7.3f} "
              f"tot {tot*1e3:.2f} ms -> {ROWS/tot/1e6:.2f} M ex/s")


if __name__ == "__main__":
    main()
