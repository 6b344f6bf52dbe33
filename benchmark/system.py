"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports ``wormhole_tpu`` (with the
per-configuration hooks in ``configs/<name>/system.py``). It goes through the
entry points a user calls: a conf file and ``key=value`` tokens into
``AsyncSGD``, data through the normal ``CRec2Writer``, every pass through
``AsyncSGD.process`` and ``flush_metrics`` as ``AsyncSGD.run`` does. It reads
the program's own ``Timer`` and sets nothing in the program.
"""

from __future__ import annotations

import contextlib
import importlib
import os
import time

import numpy as np

STREAM = "stream"      # a traffic mix's regime is this or "replay"


class CompileWatch:
    """Counts JAX's own compile events while it is open: seconds in the
    backend compiler, persistent-cache hits and misses (a copy of
    ``chip_smoke.CompileWatch``)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = self.hits = self.misses = 0

    def _on_duration(self, event, secs, **_kw):
        if event == self.COMPILE:
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


def place_compile_cache() -> str:
    """The program's own rule: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``.jax_cache/`` at the checkout root; a CPU-pinned process keeps none.
    The traceback of the caller is left out of op locations, so that a
    line moved in the harness does not change a kernel's cache key."""
    import jax
    from wormhole_tpu.parallel.mesh import enable_compile_cache
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    return enable_compile_cache()


def device_record(chips: int, need_tpu: bool) -> dict:
    """The device as JAX reports it. With ``need_tpu`` a missing TPU, or
    fewer chips than the cell asks for, raises: there is no fallback."""
    import jax
    if need_tpu:
        from wormhole_tpu.parallel.mesh import require_tpu
        require_tpu("the benchmark")
    dev = jax.devices()
    if need_tpu and len(dev) < chips:
        raise RuntimeError(f"the cell asks for {chips} chip(s), JAX sees "
                           f"{len(dev)}")
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": chips if need_tpu else len(dev)}


class TrainSystem:
    """One ``AsyncSGD`` app over crec2 files made from the seed."""

    def __init__(self, config: dict, traffic: dict, hooks, workdir: str,
                 seed: int, chips: int = 1, extra_conf=()):
        self.config, self.traffic, self.hooks = config, traffic, hooks
        self.workdir, self.seed, self.chips = workdir, int(seed), chips
        self.extra_conf = tuple(extra_conf)
        self.regime = traffic["regime"]
        self.block_rows = int(config["block_rows"])
        self.check_steps = int(config["check"]["steps"])
        self.nfiles = int(traffic.get("files", 1))
        # blocks a step: the data axis of the mesh reads that many at the
        # same weights (one on one chip)
        mesh = str(traffic["program"].get("mesh_shape", "data:1"))
        self.group = int(dict(a.split(":") for a in mesh.split(","))
                         .get("data", 1))
        self.per_file = 0            # blocks a file, set by begin_data
        self.files: list = []
        self.check_blocks: list = []
        # the (bucket, row) pairs each of them has on the file's COO
        # overflow list, set by end_data
        self.check_overflow: list = []
        self.app = None
        self._pool = None

    @property
    def nblocks(self) -> int:
        return self.nfiles * self.per_file

    # -- data ---------------------------------------------------------------

    def _writer(self, path: str):
        from wormhole_tpu.data.crec import CRec2Writer
        cfg = self.config
        return CRec2Writer(path, nnz=int(cfg["nnz"]),
                           nb=int(cfg["num_buckets"]),
                           subblocks=int(cfg["subblocks"]),
                           ovf_cap=int(self.traffic["ovf_cap"]))

    def _write_file(self, k: int) -> None:
        """File ``k`` holds blocks [k * per_file, (k + 1) * per_file) of
        the stream that the seed names, through the normal writer."""
        gen = importlib.import_module(self.traffic["generator"])
        with self._writer(self.files[k]) as w:
            for i in range(k * self.per_file, (k + 1) * self.per_file):
                keys, labels = gen.make_block(self.traffic, self.seed, i,
                                              self.block_rows)
                w.append(keys, labels)
                if i < self.check_steps * self.group:
                    self.check_blocks.append((keys, labels))

    def begin_data(self):
        """Start writing the cell's blocks: ``files`` files side by side on
        as many threads (a number the traffic file fixes, not the machine),
        while the caller brings up the device and the table. Returns what
        ``end_data`` waits for."""
        from concurrent.futures import ThreadPoolExecutor
        from wormhole_tpu.data.crec import read_header2
        cfg, tr = self.config, self.traffic
        gen = importlib.import_module(tr["generator"])
        if gen.nnz_of(tr) != int(cfg["nnz"]):
            raise ValueError(f"traffic {tr['name']} has {gen.nnz_of(tr)} "
                             f"fields, the configuration {cfg['nnz']}")
        os.makedirs(self.workdir, exist_ok=True)
        self.files = [os.path.join(self.workdir, f"data{k}.crec2")
                      for k in range(self.nfiles)]
        with self._writer(self.files[0]) as w:     # header only: geometry
            if w.block_rows != self.block_rows:
                raise ValueError(f"writer blocks hold {w.block_rows} rows, "
                                 f"the configuration says {self.block_rows}")
        self._info = read_header2(self.files[0])
        if "blocks" in tr:
            blocks = int(tr["blocks"])
        else:
            blocks = -(-int(tr["resident_bytes"]) // self._info.block_bytes)
        per_file = max(-(-blocks // self.nfiles),
                       self.check_steps * self.group)
        self.per_file = -(-per_file // self.group) * self.group
        self._pool = ThreadPoolExecutor(self.nfiles)
        return [self._pool.submit(self._write_file, k)
                for k in range(self.nfiles)]

    def end_data(self, pending) -> dict:
        """Wait for the files. A stream cell's file is then read through
        once, so that the window reads from the page cache. Keeps the
        overflow pairs of the checked blocks, as the file has them, and
        returns the work counts."""
        from wormhole_tpu.data.crec import iter_packed2
        for f in pending:
            f.result()
        cfg, info = self.config, self._info
        through = self.files if self.regime == STREAM else self.files[:1]
        ovf, self.check_overflow = [], []
        for path in through:
            for views, _rows in iter_packed2(path):
                valid = views["ovf_b"] != np.uint32(0xFFFFFFFF)
                ovf.append(int(valid.sum()))
                if len(self.check_overflow) < len(self.check_blocks):
                    # the checked blocks are the first of the first file
                    self.check_overflow.append((views["ovf_b"][valid],
                                                views["ovf_r"][valid]))
        return {"blocks": self.nblocks, "files": self.nfiles,
                "rows_per_block": self.block_rows,
                "pairs_per_block": self.block_rows * int(cfg["nnz"]),
                "overflow_pairs_per_block": [min(ovf), max(ovf)],
                "block_bytes": info.block_bytes, "cap": info.cap,
                "ovf_cap": info.ovf_cap, "spec": str(info.spec),
                "file_bytes": sum(os.path.getsize(f) for f in self.files)}

    # -- the app ------------------------------------------------------------

    def build(self) -> None:
        conf = os.path.join(self.workdir, "cell.conf")
        with open(conf, "w") as f:
            f.write(f"train_data = {self.files[0]}\n")
            f.write("\n".join(self.config["program"]["conf"]) + "\n")
        tokens = [f"{k}={v}" for k, v in self.traffic["program"].items()]
        tokens.extend(self.extra_conf)
        self.app = self.hooks.make_app(conf, tokens, self.config, self.seed)

    def kernel_record(self) -> dict:
        """What the train step resolved to. The mesh step has one form,
        the split kernel pair with psums between, and records nothing."""
        from wormhole_tpu.ops import tilemm
        kernel, why, cache = getattr(
            self.app.store, "step_kernel",
            ("split", "mesh psums sit between the phases", "-"))
        return {"step_kernel": kernel, "why": why or "-", "cache": cache,
                "pallas_interpret": bool(tilemm._interpret())}

    def fence(self) -> None:
        import jax
        jax.block_until_ready(self.app.store.slots)

    def _flush(self, prog):
        app = self.app
        prog.merge(app.flush_metrics())
        # per-pass AUC histogram, reset as run() does at a pass end
        app._crec_hist = [np.zeros(512), np.zeros(512)]
        return prog

    def step_block(self, i: int) -> float:
        """Step ``i`` alone (one block, or one group of blocks on a mesh),
        through the window's own call and feed: a part of a file is a range
        of its blocks. Returns the step's mean loss."""
        prog = self._flush(self.app.process(
            self.files[0], i, self.per_file // self.group))
        if prog.count != 1 or prog.num_ex != self.block_rows * self.group:
            raise RuntimeError(f"step {i}: {prog.count} steps, "
                               f"{prog.num_ex} rows")
        return prog.objv / prog.num_ex

    def run_pass(self) -> tuple:
        """One whole pass over every file, the metric flush that ends a
        pass in ``AsyncSGD.run``, and a fence: (rows, steps, objective)."""
        prog = self.app.process(self.files[0], 0, 1)
        for path in self.files[1:]:
            prog.merge(self.app.process(path, 0, 1))
        self._flush(prog)
        self.fence()
        return int(prog.num_ex), int(prog.count), float(prog.objv)

    def close(self) -> None:
        """Stop the writer threads, wait for them, remove the data."""
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None
        for path in self.files:
            try:
                os.remove(path)
            except OSError:
                pass

    def timers(self) -> dict:
        return dict(self.app.timer.totals)

    def memory_stats(self) -> dict:
        import jax
        return dict(jax.devices()[0].memory_stats() or {})

    def memory_peak_bytes(self) -> int:
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        return int(max(peaks))


def measure(system: TrainSystem, seconds: float,
            on_pass=contextlib.nullcontext) -> dict:
    """The timed window: whole passes between two fences, until the first
    pass that ends past ``seconds``; each runs inside ``on_pass()`` (the
    traced run's annotation). Compiles inside it are counted."""
    import gc
    system.fence()
    before = system.timers()
    gc.collect()
    gc.freeze()
    passes, rows, steps, objv = [], 0, 0, 0.0
    with CompileWatch() as watch:
        t0 = time.perf_counter()
        while True:
            tp = time.perf_counter()
            with on_pass():
                r, s, o = system.run_pass()
            now = time.perf_counter()
            passes.append((now - tp, r))
            rows, steps, objv = rows + r, steps + s, objv + o
            if now - t0 >= seconds:
                break
        window = now - t0
    gc.unfreeze()
    after = system.timers()
    return {"window_s": window, "rows": rows, "steps": steps, "objv": objv,
            "passes": passes, "compiles": watch.compiles,
            "compile_s": watch.compile_s,
            "timers": {k: after[k] - before.get(k, 0.0) for k in after}}
