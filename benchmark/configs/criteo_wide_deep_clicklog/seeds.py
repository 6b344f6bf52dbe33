#!/usr/bin/env python3
"""``correct``'s four numbers of ``criteo_wide_deep_clicklog.replay_fields`` at
many seeds in ONE process that holds the chip: where the limits' sound tails
and the controls' readings in ``README.md`` and ``PERF.md`` come from.

    python3 benchmark/configs/criteo_wide_deep_clicklog/seeds.py \
        --first 5100000101 --count 30 --controls 6 \
        --out chiprun_out/wd_seeds.jsonl

A whole run of the cell spends three minutes of the chip on three check
steps. Here each seed costs the steps alone: the cell's own format writes the
seed's three check blocks (a crec2 file through ``CRec2Writer``),
``system.seed_table`` writes the seed's weights onto the planes and the
tower, ``AsyncSGD.process`` steps the blocks as the harness's ``step_block``
does, on one app built through the cell's own conf lines and tokens, and the
harness's own comparison (``benchmark.check``: ``numbers``, ``verdict``, the
configuration's limits) holds what the probes read to the configuration's
plain reference, which runs beside the chip on a pool of host processes that
never touch it (a worker lives one seed and holds 6.4 GB; three fit the
one-chip machine's host). A line a seed: the four numbers, ``correct``,
``refused_by``, ``state_rel_rms`` leaf by leaf (``state_leaves``: ``m_list``
is the leaf that sees the list's precision, ``reference.py``), the store's
counters, and at the first ``--controls`` seeds

- ``rounded_list``: the program against a reference that rounds EVERY listed
  pair to bfloat16 too (``exact_pairs=None``): the control the harness's
  ``check.controls`` cannot name, through the same ``verdict``;
  ``refused_by`` lists the numbers that refuse it, and must not be empty.

``--dump DIR`` keeps what the probes read a seed (but the sample of ``v``),
so that a control made later on any host reads the same program.

``--host-only 1`` needs no chip: the reference in ``check.controls``'
precisions, in float32 operands and with every listed pair rounded too,
against itself at the first ``--controls`` seeds (what ``--control 1`` prints
in a whole run, two minutes a reference; ``--only NAME``: that one alone), a
line a seed under ``controls``. ``--cpu 1`` is the rehearsal at the tests'
sizes on the CPU; its numbers are no device's.
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
TINY_MODEL = ["dim=8", "hidden=64,32", "lr_alpha=0.001",
              "lr_alpha_dense=0.001"]


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
        program = config["program"]
        program["conf"] = [
            f"num_buckets = {TINY_NB}" if c.startswith("num_buckets") else c
            for c in program["conf"]]
        program["model_conf"] = TINY_MODEL
        config.update(num_buckets=TINY_NB, subblocks=2, block_rows=TINY_ROWS,
                      dim=8, hidden=[64, 32])
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


def state_leaves(observed: dict, expected: dict) -> dict:
    """``state_rel_rms`` leaf by leaf (``check.numbers`` keeps the worst)."""
    return {leaf: float(np.linalg.norm(observed["state"][leaf] - ref)
                        / max(np.linalg.norm(ref), 1e-300))
            for leaf, ref in expected["state"].items()}


def refused_by(nums: dict, limits: dict) -> list:
    from benchmark import check
    return [name for name in limits
            if not check.verdict(nums, {name: limits[name]})[0]]


def host_side(args: tuple) -> dict:
    """A pool worker's one seed: its check file and the reference's numbers
    (a worker lives one seed, so that it holds one seed's arrays)."""
    seed, workdir, rounded, controls, only, tiny = args
    from benchmark import check
    config, traffic = cell_files(tiny)
    reference = importlib.import_module(
        f"benchmark.configs.{CONFIG}.reference")
    src = check_blocks_of(config, traffic, workdir, seed)
    blocks = src.reference_blocks()
    stated = check.stated_precision(config, src.check_overflow)
    t0 = time.time()
    expected, ref = check.run_reference(reference, config, blocks, seed,
                                        **stated)
    reference_s = time.time() - t0
    buckets = check.sample_buckets(ref, seed, int(config["check"]["sample"]))
    expected["state"] = ref.state(buckets)
    against, ctl = {}, {}
    if rounded:
        against["rounded_list"], _ = check.run_reference(
            reference, config, blocks, seed, buckets=buckets,
            **dict(stated, exact_pairs=None))
    if controls:
        # each differs from the reference in what its name says alone;
        # rounded_list: every listed pair rounded too, reference against
        # reference (what the program reads against it is ``rounded`` above)
        variants = dict(config["check"]["controls"],
                        exact_operands={"operands": None},
                        rounded_list={"exact_pairs": None})
        for name, precision in variants.items():
            if only and name != only:
                continue
            got, _ = check.run_reference(reference, config, blocks, seed,
                                         buckets=buckets,
                                         **dict(stated, **precision))
            ctl[name] = check.numbers(got, expected)
    return {"parts": [src.check_part(i) for i in range(len(blocks))],
            "expected": expected, "against": against, "buckets": buckets,
            "singles": ref.singles,
            "controls": ctl, "list_fault": ref.list_fault,
            "reference_s": reference_s,
            "listed": [len(b) for b, _r in src.check_overflow]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--first", type=int, required=True)
    ap.add_argument("--count", type=int, required=True)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--threads", type=int, default=4,
                    help="BLAS threads a worker")
    ap.add_argument("--controls", type=int, default=6)
    ap.add_argument("--out", required=True)
    ap.add_argument("--dump", default="",
                    help="a directory for what the probes read a seed")
    ap.add_argument("--tmp", default=os.path.join(
        ROOT, "benchmark", ".cache", "wd_seeds"))
    ap.add_argument("--cpu", type=int, default=0)
    ap.add_argument("--host-only", type=int, default=0)
    ap.add_argument("--only", default="",
                    help="with --host-only: this one control alone")
    a = ap.parse_args()
    seeds = [a.first + i for i in range(a.count)]
    os.makedirs(a.tmp, exist_ok=True)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
    if a.host_only:
        host_only(a, seeds[:a.controls], bool(a.cpu))
    else:
        chip_side(a, seeds, bool(a.cpu))


def pool_of(a):
    """The workers: spawned, held to the CPU and off the chip, a few BLAS
    threads each; a worker lives one seed. The variables are set here, after
    this process's own numpy and JAX are up, so they reach the children
    alone, those that replace a finished worker too."""
    import multiprocessing as mp
    os.environ["JAX_PLATFORMS"] = "cpu"
    for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
        os.environ[var] = str(a.threads)
    return mp.get_context("spawn").Pool(a.workers, maxtasksperchild=1)


def host_only(a, seeds: list, tiny: bool) -> None:
    """The reference's lower-precision controls against itself: no chip."""
    config, _traffic = cell_files(tiny)
    limits = config["check"]["limits"]
    with pool_of(a) as pool, open(a.out, "a") as out:
        jobs = [pool.apply_async(host_side, ((
            s, os.path.join(a.tmp, str(s)), False, True, a.only, tiny),))
            for s in seeds]
        for seed, job in zip(seeds, jobs):
            h = job.get()
            os.remove(h["parts"][0][0])
            rec = {"seed": seed, "list_fault": h["list_fault"],
                   "listed": h["listed"], "reference_s": h["reference_s"],
                   "controls": {
                       name: {"numbers": got,
                              "refused_by": refused_by(got, limits)}
                       for name, got in h["controls"].items()}}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)


def chip_side(a, seeds: list, tiny: bool) -> None:
    """The process that holds the chip: one app, every seed's three blocks
    through it, each compared as its host side arrives."""
    import jax
    from benchmark import check, system
    config, traffic = cell_files(tiny)
    system.place_compile_cache()
    print("device:", system.device_record(1, not tiny), flush=True)
    hooks = importlib.import_module(f"benchmark.configs.{CONFIG}.system")
    reference = importlib.import_module(
        f"benchmark.configs.{CONFIG}.reference")
    conf = os.path.join(a.tmp, "cell.conf")
    with open(conf, "w") as f:
        f.write(f"train_data = {a.tmp}/none.crec2\n")
        f.write("\n".join(config["program"]["conf"]) + "\n")
    tokens = [f"{k}={v}" for k, v in traffic["program"].items()]
    app = hooks.make_app(conf, tokens, config, seeds[0])
    jax.block_until_ready(app.store.slots)
    limits = check.limits_of(config, MIX)
    rows = int(config["block_rows"])
    # the pool once the table stands (WideDeepStore's host draw of v0 takes
    # 6 GB of the host for a moment)
    with pool_of(a) as pool, open(a.out, "a") as out:
        jobs = [pool.apply_async(host_side, ((
            s, os.path.join(a.tmp, str(s)), i < a.controls, False, "",
            tiny),))
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
            # the buckets of m_list, which the worker's reference found
            if h["singles"] is not None:
                reference.SINGLES[seed] = h["singles"]
            observed["state"] = hooks.state(app, config, seed, h["buckets"])
            reference.SINGLES.pop(seed, None)
            app._feeds.clear()          # the seed's resident blocks go
            os.remove(h["parts"][0][0])
            nums = check.numbers(observed, h["expected"])
            rec = {"seed": seed,
                   "correct": bool(check.verdict(nums, limits)[0]
                                   and not h["list_fault"]),
                   "numbers": nums, "refused_by": refused_by(nums, limits),
                   "state_leaves": state_leaves(observed, h["expected"]),
                   "singles": len(observed["state"].get("m_list", ())),
                   "list_fault": h["list_fault"], "listed": h["listed"],
                   "losses": observed["losses"],
                   "program_s": time.time() - t0,
                   "reference_s": h["reference_s"],
                   "counters": hooks.counters(app)}
            for name, expected in h["against"].items():
                got = check.numbers(observed, expected)
                rec[name] = {"numbers": got,
                             "refused_by": refused_by(got, limits),
                             "state_leaves": state_leaves(observed,
                                                          expected)}
            if a.dump:
                # what the probes read, but for the sample of v (33 MB a
                # seed): a control made later, on any host, reads these
                os.makedirs(a.dump, exist_ok=True)
                small = {f"state.{k}": v for k, v
                         in observed["state"].items() if k != "v"}
                np.savez_compressed(
                    os.path.join(a.dump, f"{seed}.npz"),
                    losses=observed["losses"], singles=h["singles"],
                    **{f"{kind}.{k}": v for kind in ("grad_norms",
                                                     "change_norms")
                       for k, v in observed[kind].items()}, **small)
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print("memory:", json.dumps({k: stats.get(k) for k in (
        "peak_bytes_in_use", "bytes_in_use", "bytes_reserved",
        "bytes_limit")}), flush=True)


if __name__ == "__main__":
    main()
