#!/usr/bin/env python3
"""``correct``'s four numbers of ``criteo_fm_clicklog.replay_fields`` at many
seeds in ONE process that holds the chip: where the limits' sound tails and
the controls' readings in ``README.md`` and ``PERF.md`` come from.

    python3 benchmark/configs/criteo_fm_clicklog/seeds.py \
        --first 4700000101 --count 46 --controls 6 \
        --out chiprun_out/fm_seeds.jsonl

A whole run of the cell spends two minutes of the chip on three check steps.
Here each seed costs the steps alone: the cell's own format writes the seed's
three check blocks (a crec2 file through ``CRec2Writer``), ``system.seed_table``
writes the seed's weights into the planes, ``AsyncSGD.process`` steps the
blocks as the harness's ``step_block`` does, on one app built through the
cell's own conf lines and tokens, and the harness's own comparison
(``benchmark.check``: ``numbers``, ``verdict``, the configuration's limits)
holds what the probes read to the configuration's plain reference, which runs
beside the chip on a pool of host processes that never touch it (a worker
lives one seed). A line a seed: the four numbers, ``correct``, and

- ``rounded_list``: the program against a reference that rounds EVERY listed
  pair to bfloat16 too (``exact_pairs=None``): the control the harness's
  ``check.controls`` cannot name, through the same ``verdict``;
  ``refused_by`` lists the numbers that refuse it, and must not be empty;
- ``controls`` (the first ``--controls`` seeds): the reference in
  ``check.controls``' precisions and in float32 operands against itself, and
  the program against a reference handed the seed's lists with one pair
  dropped and with one pair doubled (``planted_dropped``,
  ``planted_doubled``: its own check of the list finds it, every loss is
  NaN, ``loss_rel`` refuses).

``--cpu 1`` is the rehearsal at the tests' sizes on the CPU; its numbers are
no device's.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CONFIG, MIX = os.path.basename(HERE), "replay_fields"
TINY_NB, TINY_ROWS = 1 << 18, 16384


def load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def cell_files(tiny: bool) -> tuple:
    """The cell's configuration and mix, the mix cut to the checked blocks;
    ``tiny``: at the CPU tests' sizes (the stated cap the program's there)."""
    config = load(f"benchmark/configs/{CONFIG}/config.json")
    traffic = dict(load(f"benchmark/traffic/{MIX}.json"),
                   blocks=int(config["check"]["steps"]), files=1)
    if tiny:
        from wormhole_tpu.data.crec import default_cap
        config["program"]["conf"] = [
            f"num_buckets = {TINY_NB}" if c.startswith("num_buckets") else c
            for c in config["program"]["conf"]]
        config.update(num_buckets=TINY_NB, subblocks=2, block_rows=TINY_ROWS)
        config["tile"]["cap"] = default_cap(int(config["nnz"]), TINY_NB)
        config["check"]["sample"] = 4096
        traffic["ovf_cap"] = 262144
    return config, traffic


def check_blocks_of(config: dict, traffic: dict, workdir: str, seed: int):
    """The seed's check file through the cell's own format: the source, with
    its checked blocks and their lists as the harness would hold them."""
    fmt = importlib.import_module(
        f"benchmark.formats.{traffic.get('format', 'crec2')}")
    os.makedirs(workdir, exist_ok=True)
    src = fmt.Source(config, traffic, workdir, seed, 1)
    src.begin()
    src.write_file(0)
    src.end()
    return src


def refused_by(nums: dict, limits: dict) -> list:
    from benchmark import check
    return [name for name in limits
            if not check.verdict(nums, {name: limits[name]})[0]]


def planted(lists: list, how: str) -> list:
    """The lists with step 1's first pair dropped, or doubled."""
    b, r = (np.asarray(x) for x in lists[1])
    bad = (b[1:], r[1:]) if how == "dropped" else (np.r_[b, b[:1]],
                                                   np.r_[r, r[:1]])
    return [lists[0], bad, *lists[2:]]


def host_side(args: tuple) -> dict:
    """A pool worker's one seed: its check file and the reference's numbers
    (a worker lives one seed, so that it holds one seed's arrays)."""
    seed, workdir, controls, tiny = args
    from benchmark import check
    config, traffic = cell_files(tiny)
    reference = importlib.import_module(
        f"benchmark.configs.{CONFIG}.reference")
    src = check_blocks_of(config, traffic, workdir, seed)
    blocks = src.reference_blocks()
    stated = check.stated_precision(config, src.check_overflow)
    expected, ref = check.run_reference(reference, config, blocks, seed,
                                        **stated)
    buckets = check.sample_buckets(ref, seed, int(config["check"]["sample"]))
    expected["state"] = ref.state(buckets)
    variants = {"rounded": dict(stated, exact_pairs=None)}
    ctl = {}
    if controls:
        for name, precision in dict(
                config["check"]["controls"],
                exact_operands={"operands": None}).items():
            got, _ = check.run_reference(reference, config, blocks, seed,
                                         buckets=buckets,
                                         **dict(stated, **precision))
            ctl[name] = check.numbers(got, expected)
        for how in ("dropped", "doubled"):
            variants[f"planted_{how}"] = dict(
                stated, exact_pairs=planted(stated["exact_pairs"], how))
    against, faults = {}, {}
    for name, precision in variants.items():
        against[name], bad = check.run_reference(
            reference, config, blocks, seed, buckets=buckets, **precision)
        faults[name] = bad.list_fault
    return {"parts": [src.check_part(i) for i in range(len(blocks))],
            "expected": expected, "against": against, "faults": faults,
            "buckets": buckets, "controls": ctl,
            "list_fault": ref.list_fault,
            "listed": [len(b) for b, _r in src.check_overflow]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--first", type=int, required=True)
    ap.add_argument("--count", type=int, required=True)
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--controls", type=int, default=6)
    ap.add_argument("--out", required=True)
    ap.add_argument("--tmp", default=os.path.join(
        ROOT, "benchmark", ".cache", "fm_seeds"))
    ap.add_argument("--cpu", type=int, default=0)
    a = ap.parse_args()
    tiny = bool(a.cpu)
    seeds = [a.first + i for i in range(a.count)]

    chip_side(a, seeds, tiny)


def chip_side(a, seeds: list, tiny: bool) -> None:
    """The process that holds the chip: one app, every seed's three blocks
    through it, each compared as its host side arrives."""
    import multiprocessing as mp
    import jax
    from benchmark import check, system
    config, traffic = cell_files(tiny)
    system.place_compile_cache()
    print("device:", system.device_record(1, not tiny), flush=True)
    hooks = importlib.import_module(f"benchmark.configs.{CONFIG}.system")
    os.makedirs(a.tmp, exist_ok=True)
    conf = os.path.join(a.tmp, "cell.conf")
    with open(conf, "w") as f:
        f.write(f"train_data = {a.tmp}/none.crec2\n")
        f.write("\n".join(config["program"]["conf"]) + "\n")
    tokens = [f"{k}={v}" for k, v in traffic["program"].items()]
    if tiny:
        tokens.append("tile_step_kernel=fused")
    app = hooks.make_app(conf, tokens, config, seeds[0])
    jax.block_until_ready(app.store.slots)
    limits = check.limits_of(config, MIX)
    rows = int(config["block_rows"])
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    # the pool once the table stands (FMStore's host draw of v0 takes 13 GB
    # of the host for a moment), its children held to the CPU and off the
    # chip: this process's JAX has the chip by now, so the variable reaches
    # the children alone, those that replace a finished worker too
    os.environ["JAX_PLATFORMS"] = "cpu"
    with mp.get_context("spawn").Pool(a.workers, maxtasksperchild=1) as pool, \
            open(a.out, "a") as out:
        jobs = [pool.apply_async(host_side, ((
            s, os.path.join(a.tmp, str(s)), i < a.controls, tiny),))
            for i, s in enumerate(seeds)]
        for seed, job in zip(seeds, jobs):
            h = job.get()
            t0 = time.time()
            hooks.seed_table(app.store, config, seed)
            observed = {"losses": []}
            for i, part in enumerate(h["parts"]):
                prog = app.process(*part)
                prog.merge(app.flush_metrics())
                # a pass ended: fresh histograms, as AsyncSGD.run assigns
                app._crec_hist = [np.zeros(512), np.zeros(512)]
                if (prog.count, prog.num_ex) != (1, rows):
                    raise RuntimeError(f"seed {seed} step {i}: {prog.count} "
                                       f"steps, {prog.num_ex} rows")
                observed["losses"].append(prog.objv / prog.num_ex)
                if i == 0:
                    observed["grad_norms"] = hooks.grad_norms(app, config,
                                                              seed)
            observed["change_norms"] = hooks.change_norms(app, config, seed)
            observed["state"] = hooks.state(app, config, seed, h["buckets"])
            app._feeds.clear()          # the seed's resident blocks go
            os.remove(h["parts"][0][0])
            nums = check.numbers(observed, h["expected"])
            rec = {"seed": seed,
                   "correct": bool(check.verdict(nums, limits)[0]
                                   and not h["list_fault"]),
                   "numbers": nums, "refused_by": refused_by(nums, limits),
                   "list_fault": h["list_fault"], "listed": h["listed"],
                   "controls": {
                       name: {"numbers": got,
                              "refused_by": refused_by(got, limits)}
                       for name, got in h["controls"].items()},
                   "program_s": time.time() - t0,
                   "counters": hooks.counters(app)}
            for name, expected in h["against"].items():
                got = check.numbers(observed, expected)
                rec["rounded_list" if name == "rounded" else name] = {
                    "numbers": got, "refused_by": refused_by(got, limits),
                    "list_fault": h["faults"][name]}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
    print("peak_bytes_in_use:", (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0), flush=True)


if __name__ == "__main__":
    main()
