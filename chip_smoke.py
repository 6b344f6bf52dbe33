#!/usr/bin/env python3
"""chip_smoke.py — does the flagship trainer still start on the chip?

Drives the criteo FTRL main path once, through the entry points a user
calls, at the flagship's full width (39 features a row, 2**22 buckets,
98,304-row crec2 blocks), a few blocks deep, on ONE TPU chip in ONE
process:

  1. seeded criteo-shaped data with a planted model, written through the
     normal ``CRec2Writer`` under ``chip_smoke_data/`` (fixed, git-ignored);
  2. training through the CLI's own path (``async_sgd.app_from_argv`` +
     ``run()``, which is all ``async_sgd.main`` does) with the README's
     conf, ending in the predict pass (``test_data``/``pred_out``) so the
     pull-only kernel runs too;
  3. two more passes on the warm program, timed, with no compile allowed;
  4. checks against a plain float64 numpy FTRL over the same rows.

Any failed check or exception exits non-zero and prints no result line;
no phase is caught and skipped. Without a TPU the script fails at once:
it never falls back to the CPU backend or Pallas interpret mode.

``--chips 4`` runs ONLY the ``data:2,model:2`` mesh path on four chips
and the one-chip run it is compared with.

The last line of stdout is the contract's
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(HERE, "chip_smoke_data")
SEED = 0

# FTRL settings of the README's conf (lambda = [L1, L2], lr_eta, lr_beta)
L1, L2, ALPHA, BETA = 1.0, 0.1, 0.1, 1.0

# Stated tolerances against the float64 reference. The kernels round the
# weights (forward) and the duals (backward) to bf16, 2**-9 relative per
# value; everything else accumulates in f32.
TOL_OBJV_REL = 2e-3       # per-pass mean objective
TOL_W_REL = 2e-2          # max |w - w_ref| over max |w_ref|
TOL_PRED_ABS = 5e-3       # predicted probability, per test row
TOL_AUC_ABS = 2e-2        # the device AUC is read off 512 margin bins
                          # (ops/metrics.margin_hist): coarse while the
                          # margins are still small
# mesh (two blocks per update) against one chip (one block per update):
# different batch sizes, so only the same neighbourhood is claimed
TOL_MESH_VS_ONE_OBJV_REL = 0.10
TOL_MESH_VS_ONE_AUC_ABS = 0.05


@dataclass(frozen=True)
class Size:
    """What a run is cut to. ``FULL`` is what the driver runs; the CPU
    test (tests/test_chip_smoke.py) passes a tiny one — that argument is
    the test-only hook, there is no option or variable for it."""
    num_buckets: int = 1 << 22
    subblocks: int = 12          # x 8192 rows = 98,304-row blocks
    nnz: int = 39
    train_blocks: int = 4
    vocab: int = 20_000          # values per field in the planted model
    warm_passes: int = 2
    mesh_shape: str = "data:2,model:2"   # the --chips 4 path
    conf: tuple = ()             # extra key=val tokens for the trainer


FULL = Size()


class SmokeFailure(AssertionError):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)
    say(f"ok: {msg}")


class CompileWatch:
    """Counts JAX's own compile events while it is open: seconds in the
    backend compiler, and persistent-cache hits and misses."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

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


# -- data --------------------------------------------------------------------

def make_rows(rng, rows: int, size: Size, w_true: np.ndarray):
    """Criteo-shaped rows from the planted model: one value per field,
    label ~ Bernoulli(sigmoid(sum of the values' true weights))."""
    v = rng.integers(0, size.vocab, size=(rows, size.nnz), dtype=np.int64)
    f = np.arange(size.nnz, dtype=np.int64)[None, :]
    # an odd multiplier is a bijection mod 2**32: one key per (field, value)
    keys = ((f * size.vocab + v) * 2654435761 % (1 << 32)).astype(np.uint32)
    keys[keys == 0xFFFFFFFF] = 0          # the format's missing-slot sentinel
    margin = w_true[f, v].sum(axis=1)
    labels = (rng.random(rows) < 1.0 / (1.0 + np.exp(-margin)))
    return keys, labels.astype(np.uint8)


def make_data(size: Size, workdir: str) -> dict:
    """train.crec2 (``train_blocks`` full blocks) and test.crec2 (one),
    through the normal writer; the rows are kept for the reference."""
    from wormhole_tpu.data.crec import CRec2Writer, read_header2
    os.makedirs(workdir, exist_ok=True)
    rng = np.random.default_rng(SEED)
    w_true = rng.normal(0.0, 0.5, size=(size.nnz, size.vocab))
    block_rows = size.subblocks * 8192
    out = {"train": os.path.join(workdir, "train.crec2"),
           "test": os.path.join(workdir, "test.crec2"),
           "pred": os.path.join(workdir, "pred.txt")}
    t0 = time.perf_counter()
    for name, nblocks in (("train", size.train_blocks), ("test", 1)):
        blocks = []
        with CRec2Writer(out[name], nnz=size.nnz, nb=size.num_buckets,
                         subblocks=size.subblocks) as w:
            for _ in range(nblocks):
                keys, labels = make_rows(rng, block_rows, size, w_true)
                w.append(keys, labels)
                blocks.append((keys, labels))
        out[name + "_blocks"] = blocks
    info = read_header2(out["train"])
    say(f"data: {size.train_blocks}+1 blocks of {info.block_rows} rows x "
        f"{info.nnz} features, nb=2**{size.num_buckets.bit_length() - 1}, "
        f"cap={info.cap}, ovf_cap={info.ovf_cap}, spec={info.spec}, "
        f"written in {time.perf_counter() - t0:.1f}s under {workdir}")
    check(info.total_rows == size.train_blocks * block_rows,
          f"train file holds {info.total_rows} rows")
    return out


# -- the plain reference -----------------------------------------------------

class Oracle:
    """Dense float64 FTRL-proximal over hashed buckets, straight from the
    update rule (sgd_server_handle.h:111-141): nothing of the tile path,
    only the key fold it shares with the writer."""

    def __init__(self, nb: int):
        self.nb = nb
        self.w = np.zeros(nb)
        self.z = np.zeros(nb)
        self.cg = np.zeros(nb)

    def pairs(self, keys: np.ndarray):
        from wormhole_tpu.data.hashing import fold_keys32
        rows = np.repeat(np.arange(keys.shape[0]), keys.shape[1])
        return fold_keys32(keys.reshape(-1), self.nb), rows

    def margins(self, keys: np.ndarray, buckets=None, rows=None):
        if buckets is None:
            buckets, rows = self.pairs(keys)
        return np.bincount(rows, weights=self.w[buckets],
                           minlength=keys.shape[0])

    def step(self, group) -> tuple:
        """One update from a group of blocks read at the SAME weights
        (one block on one chip, ``data`` blocks on a mesh). Returns
        (sum of objectives, margins, labels) of the group."""
        grad = np.zeros(self.nb)
        objv, ms, ys = 0.0, [], []
        for keys, labels in group:
            buckets, rows = self.pairs(keys)
            m = self.margins(keys, buckets, rows)
            y = 2.0 * labels - 1.0
            objv += np.logaddexp(0.0, -y * m).sum()
            dual = -y / (1.0 + np.exp(y * m))
            grad += np.bincount(buckets, weights=dual[rows],
                                minlength=self.nb)
            ms.append(m)
            ys.append(labels)
        cg = np.sqrt(self.cg * self.cg + grad * grad)
        self.z += grad - (cg - self.cg) / ALPHA * self.w
        self.cg = cg
        self.w = (-np.sign(self.z) * np.maximum(np.abs(self.z) - L1, 0.0)
                  / ((BETA + cg) / ALPHA + L2))
        return objv, np.concatenate(ms), np.concatenate(ys)

    def train_pass(self, blocks, group: int = 1) -> dict:
        objv, ms, ys = 0.0, [], []
        for i in range(0, len(blocks), group):
            o, m, y = self.step(blocks[i:i + group])
            objv += o
            ms.append(m)
            ys.append(y)
        m, y = np.concatenate(ms), np.concatenate(ys)
        return {"objv": objv / len(m), "auc": auc(y, m)}


def auc(labels: np.ndarray, margins: np.ndarray) -> float:
    """Rank-statistic AUC, tied margins sharing their average rank (a
    whole block is tied at 0 before the first update)."""
    _, inv, counts = np.unique(margins, return_inverse=True,
                               return_counts=True)
    ranks = (np.cumsum(counts) - (counts - 1) / 2.0)[inv]
    pos = labels > 0
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


# -- driving the trainer -----------------------------------------------------

def train_argv(size: Size, data: dict, *extra: str) -> list:
    """The README's conf, written as a conf file the CLI reads
    (repeated ``lambda`` lines only collapse there), plus CLI tokens."""
    conf = os.path.join(os.path.dirname(data["train"]), "smoke.conf")
    with open(conf, "w") as f:
        f.write(f"""train_data = {data['train']}
data_format = crec2
algo = ftrl
max_delay = 4
lambda = {L1}
lambda = {L2}
lr_eta = {ALPHA}
lr_beta = {BETA}
num_buckets = {size.num_buckets}
max_data_pass = 1
""")
    return [conf, *size.conf, *extra]


def pass_stats(prog) -> dict:
    n = max(prog.num_ex, 1)
    return {"rows": int(prog.num_ex), "steps": int(prog.count),
            "objv": prog.objv / n,
            "auc": prog.auc / max(prog.count, 1)}


def drive(app, size: Size, data: dict, oracle: Oracle, group: int) -> dict:
    """run() once (cold: compiles), then ``warm_passes`` timed passes on
    the warm program; the oracle takes the same passes. Returns the
    per-pass readings of both."""
    import jax
    rows_written = size.train_blocks * size.subblocks * 8192
    ours, ref = [], []
    with CompileWatch() as cold:
        t0 = time.perf_counter()
        prog = app.run()
        jax.block_until_ready(app.store.slots)
        cold_s = time.perf_counter() - t0
    ours.append(pass_stats(prog))
    ref.append(oracle.train_pass(data["train_blocks"], group))
    w_after_run = oracle.w.copy()
    say(f"cold run (1 pass, compile included): {cold_s:.1f}s, of which "
        f"{cold.compile_s:.1f}s in {cold.compiles} backend compiles; "
        f"compile cache: {cold.hits} hits, {cold.misses} misses")
    warm_s = []
    with CompileWatch() as warm:
        for _ in range(size.warm_passes):
            # per-pass AUC histogram, reset as run() does at a pass end
            app._crec_hist = [np.zeros(512), np.zeros(512)]
            t0 = time.perf_counter()
            prog = app.process(data["train"], 0, 1)
            prog.merge(app.flush_metrics())
            jax.block_until_ready(app.store.slots)
            warm_s.append(time.perf_counter() - t0)
            ours.append(pass_stats(prog))
            ref.append(oracle.train_pass(data["train_blocks"], group))
    steps = ours[-1]["steps"]
    say(f"warm passes: {[round(s, 4) for s in warm_s]} s for {steps} steps "
        f"each = {1e3 * min(warm_s) / max(steps, 1):.2f} ms a step at best "
        f"(host feed included; a smoke reading, not a benchmark)")
    check(warm.compiles == 0,
          f"no compile in the warm passes ({warm.compiles})")
    for i, (o, r) in enumerate(zip(ours, ref)):
        say(f"pass {i}: rows={o['rows']} steps={o['steps']} "
            f"objv={o['objv']:.6f} (reference {r['objv']:.6f}) "
            f"auc={o['auc']:.4f} (reference {r['auc']:.4f})")
        check(o["rows"] == rows_written,
              f"pass {i} processed the {rows_written} rows written")
        check(np.isfinite(o["objv"]), f"pass {i} objective is finite")
        check(abs(o["objv"] - r["objv"]) <= TOL_OBJV_REL * r["objv"],
              f"pass {i} objective within {TOL_OBJV_REL:g} relative of "
              "the float64 reference")
        check(abs(o["auc"] - r["auc"]) <= TOL_AUC_ABS,
              f"pass {i} AUC within {TOL_AUC_ABS:g} of the reference")
    check(all(a["objv"] > b["objv"] for a, b in zip(ours, ours[1:])),
          "objective falls pass over pass: "
          + " > ".join(f"{o['objv']:.4f}" for o in ours))
    w = np.asarray(app.store.handle.weights(
        app.store.slots.astype("float32")), np.float64)
    nnz, nnz_ref = int((w != 0).sum()), int((oracle.w != 0).sum())
    err = np.abs(w - oracle.w).max() / np.abs(oracle.w).max()
    say(f"slots: {nnz} nonzero weights (reference {nnz_ref}), "
        f"max|w - w_ref| / max|w_ref| = {err:.2e}")
    check(nnz > 0, "training changed the slots")
    check(err <= TOL_W_REL,
          f"trained weights within {TOL_W_REL:g} of the reference")
    return {"ours": ours, "ref": ref, "w_after_run": w_after_run,
            "cold_s": cold_s, "compile_s": cold.compile_s,
            "warm_s": warm_s}


def check_kernel(app, want: str, need_tpu: bool) -> None:
    from wormhole_tpu.ops import tilemm
    kernel, why, cache = app.store.step_kernel
    interp = tilemm._interpret()
    say(f"step kernel: {kernel} (why: {why or '-'}; {cache}); "
        f"pallas interpret mode: {interp}")
    if need_tpu:
        check(kernel == want, f"the train step resolved {want}")
        check(not interp, "the kernels are compiled, not interpreted")


def platform_phase(need_tpu: bool, chips: int) -> dict:
    """Compile cache first (nothing may compile before it is placed),
    then the device. Off the TPU this raises: no fallback."""
    from wormhole_tpu.parallel.mesh import enable_compile_cache, require_tpu
    cache_dir = enable_compile_cache()
    import jax
    if need_tpu:
        require_tpu("chip_smoke.py")
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    where = ("JAX_COMPILATION_CACHE_DIR"
             if os.environ.get("JAX_COMPILATION_CACHE_DIR")
             else "fixed in-checkout default")
    say(f"device: {json.dumps(device)}; jax {jax.__version__}; compile "
        f"cache: {f'{cache_dir} ({where})' if cache_dir else 'off'}")
    if need_tpu:
        check(device["platform"] == "tpu", "jax.devices()[0] is a TPU")
        check(device["count"] == chips,
              f"this run wants exactly {chips} chip(s), "
              f"JAX sees {device['count']}")
    return device


def parser_phase() -> None:
    """Which text parser is live, and that the native one (built from
    native/parse.cc by `make` on first use) agrees with the Python spec."""
    from wormhole_tpu.data import native
    from wormhole_tpu.data.parsers import parse_criteo_chunk
    if not native.available():
        say("parser: python (native NOT live: "
            f"{native.build_error() or 'library absent or disabled'})")
        return
    line = ("1\t" + "\t".join(str(i) for i in range(13)) + "\t"
            + "\t".join(f"{i * 2654435761 % (1 << 32):08x}"
                        for i in range(26)) + "\n").encode()
    got = native.get_parser("criteo")(line * 3)
    want = parse_criteo_chunk(line * 3)
    check(np.array_equal(got.index, want.index)
          and np.array_equal(got.label, want.label),
          "parser: native, and it agrees with the Python parser")


def one_chip(size: Size, need_tpu: bool, workdir: str) -> None:
    from wormhole_tpu.learners import async_sgd
    parser_phase()
    data = make_data(size, workdir)
    app = async_sgd.app_from_argv(train_argv(
        size, data, f"test_data={data['test']}", f"pred_out={data['pred']}"))
    oracle = Oracle(size.num_buckets)
    res = drive(app, size, data, oracle, group=1)
    check_kernel(app, "fused", need_tpu)
    # the predict pass ran at the end of run(): compare the file it wrote
    # with the reference's margins at the weights it had THEN
    test_keys, _ = data["test_blocks"][0]
    then = Oracle(size.num_buckets)
    then.w = res["w_after_run"]
    want = 1.0 / (1.0 + np.exp(-then.margins(test_keys)))
    got = np.loadtxt(data["pred"])
    check(got.shape == want.shape,
          f"predict wrote one line per test row ({len(want)})")
    err = float(np.abs(got - want).max())
    say(f"predict: max |p - p_ref| over {len(want)} rows = {err:.2e}")
    check(np.isfinite(got).all() and err <= TOL_PRED_ABS,
          f"predictions within {TOL_PRED_ABS:g} of the float64 reference")


def four_chips(size: Size, need_tpu: bool, workdir: str) -> None:
    """The data x model mesh path, and the one-chip run it is compared
    with: same data, same seed, one process holding all the chips."""
    import jax
    from wormhole_tpu.learners import async_sgd
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import load_config
    data = make_data(size, workdir)
    app = async_sgd.app_from_argv(train_argv(
        size, data, f"mesh_shape={size.mesh_shape}"))
    mesh = app.rt.mesh
    D = app.rt.data_axis_size
    say(f"mesh: {dict(mesh.shape)} over {mesh.size} devices")
    # what the feed hands the step: look at the first group it dispatches
    fed = {}
    step = app.store.tile_train_step_mesh

    def spy(blocks, info, tau=0.0):
        if not fed:
            fed.update({k: [(s.device.id, s.data.shape)
                            for s in v.addressable_shards]
                        for k, v in blocks.items()
                        if hasattr(v, "addressable_shards")})
        return step(blocks, info, tau)

    app.store.tile_train_step_mesh = spy
    res = drive(app, size, data, Oracle(size.num_buckets), group=D)
    say("mesh step kernel: split (resolve_step_kernel: mesh psums sit "
        "between the phases the fusion joins)")
    if need_tpu:
        from wormhole_tpu.ops import tilemm
        check(not tilemm._interpret(),
              "the kernels are compiled, not interpreted")
    # the linear store keeps its table on a mesh as one plane a slot,
    # each split over MODEL on its tile axis (learners/table.py)
    from wormhole_tpu.learners import table as tbl
    table = app.store.slots
    check(isinstance(table, tbl.PlaneTable),
          "the mesh store's table is one plane a slot")
    plane_shards = [[(s.device.id, s.data.shape)
                     for s in p.addressable_shards] for p in table.planes]
    say(f"plane 0 shards (device, shape): {plane_shards[0]}")
    say(f"fed block shards (device, shape): {fed}")
    nb_local = size.num_buckets // app.rt.model_axis_size
    shard = tbl.plane_shape(nb_local)
    check(all(len({d for d, _ in shards}) == mesh.size
              and all(shape == shard for _, shape in shards)
              for shards in plane_shards),
          f"every device holds a {shard} shard of each of the "
          f"{len(plane_shards)} planes")
    crossings = app.store.timer.counts.get("table_cross", 0)
    check(crossings == 0, f"the table never changed form ({crossings} "
          "table_cross)")
    check("pw" in fed and len({d for d, _ in fed["pw"]}) == mesh.size
          and all(shape[0] == 1 for _, shape in fed["pw"]),
          "a fed group is spread over every device, one block a data index")
    # the one-chip run it is compared with
    conf, *tokens = train_argv(size, data)
    one = async_sgd.AsyncSGD(
        load_config(conf, tokens),
        MeshRuntime(mesh=make_mesh("data:1", jax.devices()[:1])))
    res1 = drive(one, size, data, Oracle(size.num_buckets), group=1)
    check_kernel(one, "fused", need_tpu)
    a, b = res["ours"][-1], res1["ours"][-1]
    say(f"last pass, mesh vs one chip: objv {a['objv']:.6f} vs "
        f"{b['objv']:.6f}, auc {a['auc']:.4f} vs {b['auc']:.4f}")
    check(abs(a["objv"] - b["objv"]) <= TOL_MESH_VS_ONE_OBJV_REL * b["objv"]
          and abs(a["auc"] - b["auc"]) <= TOL_MESH_VS_ONE_AUC_ABS,
          f"mesh and one chip agree (objective {TOL_MESH_VS_ONE_OBJV_REL:g} "
          f"relative, AUC {TOL_MESH_VS_ONE_AUC_ABS:g}) — each also matched "
          "its own reference above")


def run(size: Size = FULL, chips: int = 1, need_tpu: bool = True,
        workdir: str = DATA_DIR) -> dict:
    """All phases in order; returns the device record. ``size`` and
    ``need_tpu`` are the CPU test's hook."""
    t0 = time.perf_counter()
    device = platform_phase(need_tpu, chips)
    if chips == 1:
        one_chip(size, need_tpu, workdir)
    else:
        four_chips(size, need_tpu, workdir)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data:2,model:2 mesh path and "
                         "the one-chip run it is compared with")
    args = ap.parse_args(argv)
    device = run(FULL, chips=args.chips)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
