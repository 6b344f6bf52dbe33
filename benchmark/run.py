#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

Loads, warms, checks, measures, prints one JSON object as its last line,
exits. Everything about a cell is found by name: the cell in
``BENCHMARK.json``, its configuration under ``benchmark/configs/<config>/``,
its traffic mix in ``benchmark/traffic/<mix>.json``, each metric in
``benchmark/metrics/<metric>.json``, which names its reader. Without a TPU,
with fewer chips than the cell asks for, or with an interpreted kernel, the
run fails and prints no result line: there is no fallback. See README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import statistics                    # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (has "
                   f"{[c['name'] for c in bench['workloads']]})")


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The metrics of ``kind`` that this cell reports: those that list it
    under ``workloads``, and those that list nothing."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader_of(root: str, metric: str):
    spec = load_json(os.path.join(root, "benchmark", "metrics",
                                  f"{metric}.json"))
    module, _, func = spec["reader"].partition(":")
    return getattr(importlib.import_module(module), func)


def config_module(config: str, part: str):
    return importlib.import_module(f"benchmark.configs.{config}.{part}")


def merge(base: dict, patch) -> dict:
    out = dict(base)
    for k, v in (patch or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def check_first_steps(sut, hooks, reference, config: dict, limits: dict,
                      seed: int, control: bool) -> dict:
    """The first steps through the window's own call and feed, on the object
    the window then drives, against the plain reference. Returns `correct`,
    the seconds of the program's side and of the reference's (which are not
    set-up), and the distinct buckets a step touches."""
    from benchmark import check
    t = time.perf_counter()
    observed = {"losses": []}
    for i in range(sut.check_steps):
        observed["losses"].append(sut.step_block(i))
        if i == 0:
            observed["grad_norms"] = hooks.grad_norms(sut.app, config, seed)
    observed["change_norms"] = hooks.change_norms(sut.app, config, seed)
    program_s = time.perf_counter() - t

    # the reference: host, float64, each path's operands rounded as the
    # configuration states (the file's overflow pairs are handed to it
    # where the configuration states a precision for that path)
    t = time.perf_counter()
    steps = check.merge_groups(sut.check_blocks, sut.group)
    stated = check.stated_precision(config, check.merge_exact_pairs(
        sut.check_overflow, sut.check_blocks, sut.group))
    expected, ref = check.run_reference(reference, config, steps, seed,
                                        **stated)
    buckets = check.sample_buckets(ref, seed, int(config["check"]["sample"]))
    expected["state"] = ref.state(buckets)
    reference_s = time.perf_counter() - t

    t = time.perf_counter()
    observed["state"] = hooks.state(sut.app, config, seed, buckets)
    program_s += time.perf_counter() - t
    correct, lines = check.verdict(check.numbers(observed, expected), limits)
    for i, (a, b) in enumerate(zip(observed["losses"], expected["losses"])):
        say(f"check step {i}: loss {a:.8f} (reference {b:.8f})")
    say(f"check grad norms {observed['grad_norms']} (reference "
        f"{expected['grad_norms']}); change norms "
        f"{observed['change_norms']} (reference {expected['change_norms']})")
    for line in lines:
        say(line)
    exact = stated.get("exact_pairs")
    say(f"check: reference took {reference_s:.2f}s (not set-up); "
        f"{len(buckets)} sampled buckets of {len(ref.ids)} touched; pairs "
        "taken unrounded a step (the file's overflow lists): "
        f"{[len(b) for b, _r in exact] if exact is not None else 'none'}")
    if control:
        t = time.perf_counter()
        variants = dict(config["check"]["controls"],
                        exact_operands={"operands": None})
        for name, precision in variants.items():
            # each differs from the reference in what its name says alone
            got, _ = check.run_reference(reference, config, steps, seed,
                                         buckets=buckets,
                                         **dict(stated, **precision))
            say(f"control {name} {json.dumps(precision)}: "
                f"{json.dumps(check.numbers(got, expected))}")
        if exact is not None:
            # what the program reads against a reference that rounds the
            # overflow pairs too (the reference of PRs 25-27)
            rounded, _ = check.run_reference(
                reference, config, steps, seed, buckets=buckets,
                **dict(stated, exact_pairs=None))
            say("program against every pair rounded (not compared): "
                f"{json.dumps(check.numbers(observed, rounded))}")
        reference_s += time.perf_counter() - t
    return {"correct": correct, "program_s": program_s,
            "reference_s": reference_s, "distinct": expected["distinct"]}


def traced_window(sut, seconds: float, trace_dir: str) -> dict:
    """The window under the profiler, a ``bench_pass`` annotation a pass."""
    import jax
    from benchmark import system, trace_reduce
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        return system.measure(
            sut, seconds,
            lambda: jax.profiler.TraceAnnotation(trace_reduce.PASS_SPAN))
    finally:
        jax.profiler.stop_trace()


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             need_tpu: bool = True, root: str = ROOT, workdir=None,
             config_patch=None, traffic_patch=None, control: bool = False,
             extra_conf=(), keep_trace=None) -> dict:
    """The whole run; returns the result object. ``need_tpu=False`` and the
    two patches are the CPU tests' hook (a function argument only they
    pass): the script has no option or variable for them. ``control`` also
    computes the lower-precision controls of ``correct`` and prints them."""
    import numpy as np
    from benchmark import check, peaks, system, trace_reduce
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find_cell(bench, workload)
    chips = int(cell["chips"])
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = merge(load_json(os.path.join(root, cfg_entry["file"])),
                   config_patch)
    traffic = merge(load_json(os.path.join(
        root, "benchmark", "traffic", f"{cell['traffic']}.json")),
        traffic_patch)
    hooks = config_module(cell["config"], "system")
    reference = config_module(cell["config"], "reference")
    roofline = config_module(cell["config"], "roofline")
    workdir = workdir or os.path.join(root, "benchmark", ".cache", workload)
    trace_dir = os.path.join(workdir, "trace")

    sut = system.TrainSystem(config, traffic, hooks, workdir, seed,
                             chips=chips, extra_conf=extra_conf)
    parts = {}
    try:
        t_data = time.perf_counter()
        pending = sut.begin_data()      # written while the device comes up
        # -- platform: compile cache first, then the device -----------------
        cache_dir = system.place_compile_cache()
        device = system.device_record(chips, need_tpu)
        import jax
        say(f"device: {json.dumps(device)}; jax {jax.__version__}; compile "
            f"cache: {cache_dir or 'off'}")
        say(f"cell {workload}: config {cell['config']} (num_buckets=2**"
            f"{int(config['num_buckets']).bit_length() - 1}), traffic "
            f"{cell['traffic']} ({traffic['regime']}), seed {seed}")
        parts["platform_s"] = time.perf_counter() - t_data
        with system.CompileWatch() as setup_watch:
            # -- set-up: table, data, first steps, warm passes --------------
            t = time.perf_counter()
            sut.build()
            sut.fence()
            parts["table_s"] = time.perf_counter() - t
            work = sut.end_data(pending)
            parts["data_s"] = time.perf_counter() - t_data
            say(f"work per block: {json.dumps(work)}")

            checked = check_first_steps(
                sut, hooks, reference, config,
                check.limits_of(config, cell["traffic"]), seed, control)
            parts["first_steps_s"] = checked["program_s"]
            kernel = sut.kernel_record()
            say(f"step kernel: {json.dumps(kernel)}")
            if need_tpu and kernel["pallas_interpret"]:
                raise RuntimeError("the tile kernels are interpreted")
            want = traffic.get("step_kernel",
                               config["program"]["step_kernel"])
            if need_tpu and kernel["step_kernel"] != want:
                raise RuntimeError(
                    f"the train step resolved {kernel['step_kernel']!r}, "
                    f"the cell states {want!r}")

            t = time.perf_counter()
            for _ in range(int(traffic["warm_passes"])):
                sut.run_pass()
            parts["warm_passes_s"] = time.perf_counter() - t
        setup_s = time.perf_counter() - T_START - checked["reference_s"]
        parts["compile_s"] = setup_watch.compile_s
        say(f"compile cache: {setup_watch.hits} hits, {setup_watch.misses} "
            f"misses; {setup_watch.compiles} backend compiles, "
            f"{setup_watch.compile_s:.1f}s in the compiler")
        say("set-up parts (s): " + json.dumps(
            {k: round(v, 3) for k, v in parts.items()})
            + f"; setup_s = {setup_s:.3f}")

        # -- the window --------------------------------------------------
        window = (traced_window(sut, seconds, trace_dir) if trace
                  else system.measure(sut, seconds))
        if window["compiles"]:
            raise RuntimeError(
                f"{window['compiles']} compile(s) inside the timed window "
                f"({window['compile_s']:.1f}s): every shape must be warmed "
                "in set-up")
        pass_s = [p[0] for p in window["passes"]]
        rows_pass = window["passes"][0][1]
        say(f"window: {window['window_s']:.3f}s, {len(pass_s)} passes, "
            f"{window['steps']} steps, {window['rows']} rows; window rate "
            f"{window['rows'] / window['window_s']:.1f} ex/s; pass-median "
            f"rate {rows_pass / statistics.median(pass_s):.1f} ex/s "
            f"(pass seconds {' '.join(f'{p:.3f}' for p in pass_s)})")
        say("timers in the window (s): " + json.dumps(
            {k: round(v, 4) for k, v in sorted(window["timers"].items())
             if v}))

        attempted = len(pass_s) * sut.nblocks // sut.group
        failed = attempted - window["steps"]
        if not np.isfinite(window["objv"]) or \
                window["rows"] != len(pass_s) * sut.nblocks * sut.block_rows:
            failed = attempted
        window["blocks"] = window["steps"] * sut.group
        peak = sut.memory_peak_bytes()
        say(f"device memory: {json.dumps(sut.memory_stats())}")
        reading = {"window": window, "setup_s": setup_s, "config": config,
                   "traffic": traffic, "memory_peak_bytes": peak,
                   "trace": None, "least_s_per_step": None}
        if need_tpu:
            distinct = int(checked["distinct"])
            step_work = roofline.block_work(
                config, work["pairs_per_block"] * sut.group,
                sut.block_rows * sut.group, distinct)
            least, bound_by = peaks.least_seconds(
                step_work, peaks.peaks_of(device["kind"]))
            reading["least_s_per_step"] = least
            say(f"roofline: a step needs {json.dumps(step_work)} "
                f"({distinct} distinct buckets): at least "
                f"{least * 1e6:.1f} us, bound by {bound_by}")
        result = {"correct": bool(checked["correct"] and failed == 0),
                  "attempted": attempted, "failed": failed, "metrics": {},
                  "device": dict(device, memory_peak_bytes=peak)}
        if trace and not need_tpu:
            say("device metrics: not measured (no TPU in this run)")
        elif trace:
            xplane = trace_reduce.find_xplane(trace_dir)
            if keep_trace:
                shutil.copyfile(xplane, keep_trace)
            reduced = trace_reduce.reduce_trace(trace_reduce.load(xplane),
                                                chips=chips)
            if not reduced or reduced["busy_s"] <= 0.0:
                raise RuntimeError("the trace shows no operation on the "
                                   "device")
            reading["trace"] = reduced
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            say(f"trace: {json.dumps(reduced)}")
        kind = "per_layer" if trace else "end_to_end"
        for m in metrics_of(bench, workload, kind):
            value = reader_of(root, m["name"])(reading)
            if value is None:
                say(f"metric {m['name']}: nothing to read, left out")
                continue
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": m["unit"]}
        return result
    finally:
        sut.close()
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also print the lower-precision controls of "
                         "`correct` (not part of the driver's command)")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="copy the run's .xplane.pb here (by hand only)")
    ap.add_argument("--conf", action="append", default=[],
                    metavar="KEY=VAL", help="extra program conf tokens, for "
                    "the control that switches on a lower-precision path of "
                    "the program (by hand only)")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), control=bool(args.control),
                      extra_conf=tuple(args.conf),
                      keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
