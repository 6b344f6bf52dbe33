#!/usr/bin/env python3
"""``correct``'s four numbers of ``criteo_ftrl_clicklog_ps4``'s cell at many
seeds in ONE process that holds the four chips: where the limits' sound tails
and the controls' readings in ``README.md`` and ``PERF.md`` come from.

    python3 benchmark/configs/criteo_ftrl_clicklog_ps4/seeds.py \
        --first 4300000101 --count 12 --controls 6 \
        --out chiprun_out/seeds.jsonl

A whole run of the cell spends a minute of four chips on three check groups.
Here each seed costs the groups alone: the cell's own format writes them (two
98,304-line blocks a group), ``AsyncSGD.process`` steps them on a table
zeroed between seeds through the cell's own conf lines and tokens, and the
harness's own comparison (``benchmark.check``: ``numbers``, ``verdict``, the
configuration's limits) holds them to the configuration's plain reference,
which runs beside the chips on a pool of host processes that never touch JAX.
A line a seed: the four numbers, ``correct``, and

- ``rounded_list``: the program against a reference that rounds EVERY listed
  pair to bfloat16 too (``exact_pairs=None``): the control the harness's
  ``check.controls`` cannot name, through the same ``verdict``; ``refused_by``
  lists the numbers that refuse it, and must not be empty;
- ``controls`` (the first ``--controls`` seeds): the reference in
  ``check.controls``' precisions and in float32 operands against itself.

``--plant TILES`` runs every seed of the call with ONE fault underneath the
mesh step: the upper MODEL shard disowns the listed pairs of its first
``TILES`` tiles of buckets (a listed pair dropped at the shard boundary).
Such a call must read ``correct: false`` at every seed; it is the upper
reading of ``grad_norm_rel`` at the cell's size. ``--cpu 1`` is the rehearsal
at the tests' sizes on four host devices; its numbers are no device's.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CONFIG, MIX, GROUP = os.path.basename(HERE), "mesh4_stream_text_fields", 2
TINY_NB, TINY_ROWS = 1 << 20, 16384   # a fourteenth of the pairs listed


def load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def cell_files(tiny: bool) -> tuple:
    config = load(f"benchmark/configs/{CONFIG}/config.json")
    traffic = dict(load(f"benchmark/traffic/{MIX}.json"), blocks=GROUP)
    if tiny:
        from wormhole_tpu.data.crec import default_cap
        swap = {"num_buckets": TINY_NB, "text_block_rows": TINY_ROWS}
        config["program"]["conf"] = [
            f"{k} = {swap[k]}" if (k := c.split(" = ")[0]) in swap else c
            for c in config["program"]["conf"]]
        config.update(num_buckets=TINY_NB, subblocks=2, block_rows=TINY_ROWS)
        config["tile"]["cap"] = default_cap(int(config["nnz"]), TINY_NB)
        config["check"]["sample"] = 4096
    return config, traffic


def host_side(args: tuple) -> dict:
    """A pool worker's one seed: its check files and the reference's numbers
    (a worker lives one seed, so that it holds one seed's arrays)."""
    seed, workdir, controls, tiny = args
    from benchmark import check
    config, traffic = cell_files(tiny)
    fmt = importlib.import_module(f"benchmark.formats.{traffic['format']}")
    reference = importlib.import_module(
        f"benchmark.configs.{CONFIG}.reference")
    os.makedirs(workdir, exist_ok=True)
    src = fmt.Source(config, traffic, workdir, seed, GROUP)
    src.begin()
    src.write_file(0)
    counts = src.end()
    os.remove(src.files[0])                 # the pass file is not read here
    steps = check.merge_groups(src.reference_blocks(), GROUP)
    stated = check.stated_precision(config, check.merge_exact_pairs(
        src.check_overflow, src.check_blocks, GROUP))
    expected, ref = check.run_reference(reference, config, steps, seed,
                                        **stated)
    buckets = check.sample_buckets(ref, seed, int(config["check"]["sample"]))
    expected["state"] = ref.state(buckets)
    rounded, _ = check.run_reference(reference, config, steps, seed,
                                     buckets=buckets,
                                     **dict(stated, exact_pairs=None))
    ctl = {}
    if controls:
        for name, precision in dict(config["check"]["controls"],
                                    exact_operands={"operands": None}).items():
            got, _ = check.run_reference(reference, config, steps, seed,
                                         buckets=buckets,
                                         **dict(stated, **precision))
            ctl[name] = check.numbers(got, expected)
    return {"files": list(src.check_files), "expected": expected,
            "rounded": rounded, "buckets": buckets, "controls": ctl,
            "list_fault": ref.list_fault, "room": src.info.ovf_cap,
            "overflow_pairs_per_block": counts["overflow_pairs_per_block"]}


def plant_dropped_at_the_boundary(tiles: int, nb_local: int) -> None:
    """The upper MODEL shard disowns the listed pairs of its first ``tiles``
    tiles of buckets; set before the mesh step is first traced."""
    import jax.numpy as jnp
    from wormhole_tpu.learners import store
    from wormhole_tpu.ops.tilemm import TILE
    real = store.shard_range_mask

    def mask(ovb, off, nb_l):
        valid, idx = real(ovb, off, nb_l)
        bi = ovb.astype(jnp.int32)
        valid = valid & ~((ovb != jnp.uint32(0xFFFFFFFF)) & (bi >= nb_local)
                          & (bi < nb_local + tiles * TILE))
        return valid, jnp.where(valid, idx, 0)
    store.shard_range_mask = mask


def refused_by(nums: dict, limits: dict) -> list:
    from benchmark import check
    return [name for name in limits
            if not check.verdict(nums, {name: limits[name]})[0]]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--first", type=int, required=True)
    ap.add_argument("--count", type=int, required=True)
    ap.add_argument("--workers", type=int, default=10)
    ap.add_argument("--controls", type=int, default=6)
    ap.add_argument("--plant", type=int, default=0, metavar="TILES")
    ap.add_argument("--out", required=True)
    ap.add_argument("--tmp", default=os.path.join(
        ROOT, "benchmark", ".cache", "seeds"))
    ap.add_argument("--cpu", type=int, default=0)
    a = ap.parse_args()
    tiny = bool(a.cpu)
    if tiny:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    seeds = [a.first + i for i in range(a.count)]

    # the pool first, its children held to the CPU and off the chips
    import multiprocessing as mp
    was = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    pool = mp.get_context("spawn").Pool(a.workers, maxtasksperchild=1)
    jobs = [pool.apply_async(host_side, ((
        s, os.path.join(a.tmp, str(s)), i < a.controls, tiny),))
        for i, s in enumerate(seeds)]
    if not tiny:
        os.environ.pop("JAX_PLATFORMS")
        if was is not None:
            os.environ["JAX_PLATFORMS"] = was
    try:
        chip_side(a, seeds, jobs, tiny)
    finally:
        pool.terminate()                # every result has been read, or
        pool.join()                     # the run has failed


def chip_side(a, seeds: list, jobs: list, tiny: bool) -> None:
    """The process that holds the chips: one app, every seed's three groups
    through it, each compared as its host side arrives."""
    import jax
    import numpy as np
    from benchmark import check, system
    config, traffic = cell_files(tiny)
    system.place_compile_cache()
    print("device:", system.device_record(4, not tiny), flush=True)
    hooks = importlib.import_module(f"benchmark.configs.{CONFIG}.system")
    if a.plant:
        plant_dropped_at_the_boundary(a.plant,
                                      int(config["num_buckets"]) // 2)
    os.makedirs(a.tmp, exist_ok=True)
    conf = os.path.join(a.tmp, "cell.conf")
    with open(conf, "w") as f:
        f.write(f"train_data = {a.tmp}/none.txt\n")
        f.write("\n".join(config["program"]["conf"]) + "\n")
    tokens = [f"{k}={v}" for k, v in traffic["program"].items()]
    app = hooks.make_app(conf, tokens, config, seeds[0])
    zero = jax.jit(lambda s: s * 0, donate_argnums=0)
    limits = check.limits_of(config, MIX)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    with open(a.out, "a") as out:
        for seed, job in zip(seeds, jobs):
            h = job.get()
            t0 = time.time()
            app.store.slots = zero(app.store.slots)
            observed = {"losses": []}
            for i, path in enumerate(h["files"]):
                prog = app.process(path, 0, 1)
                prog.merge(app.flush_metrics())
                # a pass ended: fresh histograms, as AsyncSGD.run assigns
                app._crec_hist = [np.zeros(512), np.zeros(512)]
                if (prog.count, prog.num_ex) != (
                        1, GROUP * config["block_rows"]):
                    raise RuntimeError(f"seed {seed} step {i}: {prog.count} "
                                       f"steps, {prog.num_ex} rows")
                observed["losses"].append(prog.objv / prog.num_ex)
                if i == 0:
                    observed["grad_norms"] = hooks.grad_norms(app, config,
                                                              seed)
                os.remove(path)
            observed["change_norms"] = hooks.change_norms(app, config, seed)
            observed["state"] = hooks.state(app, config, seed, h["buckets"])
            nums = check.numbers(observed, h["expected"])
            against_rounded = check.numbers(observed, h["rounded"])
            rec = {"seed": seed, "planted_tiles": a.plant,
                   "correct": bool(check.verdict(nums, limits)[0]
                                   and not h["list_fault"]),
                   "numbers": nums, "refused_by": refused_by(nums, limits),
                   "rounded_list": {
                       "numbers": against_rounded,
                       "refused_by": refused_by(against_rounded, limits)},
                   "controls": {
                       name: {"numbers": got,
                              "refused_by": refused_by(got, limits)}
                       for name, got in h["controls"].items()},
                   "list_fault": h["list_fault"],
                   "overflow_pairs_per_block": h["overflow_pairs_per_block"],
                   "room": h["room"], "program_s": time.time() - t0,
                   "counters": hooks.counters(app)}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
    print("peak_bytes_in_use a chip:", [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:4]], flush=True)


if __name__ == "__main__":
    main()
