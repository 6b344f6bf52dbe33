"""Headline benchmark: END-TO-END streaming FTRL throughput (examples/sec).

Mirrors the reference's flagship number — sparse logistic regression via
FTRL on criteo-shaped data at 9.5M examples/sec on 5 EC2 c4.8x machines
(100 workers + 100 servers, minibatch=100K, max_delay=4;
learn/linear/guide/criteo.md:205-210). That number includes the data
pipeline, so the headline here does too: the exact production path
`AsyncSGD.process` runs — crec2 tile-grouped blocks -> prefetch feed ->
fused tile-matmul FTRL step (ops/tilemm.py) with the max_delay window.

Two end-to-end rates are reported:
  * cold  — first pass, blocks stream disk -> host -> device (the
    host->device hop is PCIe on a directly attached chip).
  * steady — later passes with `cache_device=on`: blocks replay from HBM
    (multi-pass training; dataset must fit device memory). This is the
    headline: it measures the full framework loop (scheduler, feed,
    dispatch window, harvest, metrics) at device speed, the way the
    reference's number measures its steady-state mid-training rate.

The tile step is MXU-bound, not HBM-bound, so alongside the HBM roofline
the bench reports achieved MXU TFLOP/s for the step.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
All timings end in a forced D2H read: JAX returns before the device
finishes, so a clock stopped without one measures the enqueue.

The phases that report device rates need a TPU and main() refuses them
on any other platform; the host-only phases (``_HOST_PHASES``) run
anywhere and their records say that no device was used. One process
per chip: no phase starts a child that needs the chip this process
holds, and no phase re-runs itself on forced CPU devices. A failed
phase makes the exit code non-zero.

``--phases a,b,c`` runs a subset; ``--budget SECONDS`` (default 840)
skips phases not yet started when the budget expires, and long phases
additionally poll the deadline BETWEEN rounds/stages, returning partial
results tagged ``budget_truncated`` — either way the summary JSON
always prints, instead of a harness timeout killing the whole run with
nothing parseable on stdout (the round-5 rc=124).
``--out FILE`` (default bench_summary.json) additionally rewrites the
summary ATOMICALLY after every finished phase, so even a hard kill
(SIGKILL, OOM) mid-phase leaves every already-measured number on disk. The
e2e_stream / e2e_text phases time the same pass serial
(pipeline_workers=0) and pipelined and report the speedup plus the
feed's stall counters.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from collections import deque

import numpy as np

BASELINE_EX_PER_SEC = 9.5e6  # criteo.md:208-210

MINIBATCH = 100_000          # criteo_s3.conf minibatch=100000 (v1 paths)
NNZ_PAD = 64                 # sparse path: 39 feats/row, padded bucket 64
CRITEO_NNZ = 39
KPAD = 1 << 20               # unique hashed keys per 100K-row sparse batch
NUM_BUCKETS = 1 << 22        # hashed model buckets (FLAGS_max_key analogue)
MAX_DELAY = 4                # criteo_s3.conf max_delay=4
E2E_ROWS = 1_376_256         # crec2 file: 14 blocks x 98304 rows (~266 MB)
E2E_SECONDS = 12.0           # timed steady-state window
TEXT_ROWS = 120_000          # criteo text sample for the text-path number

# public peak HBM bandwidth / bf16 matmul throughput by device kind
HBM_PEAK = {"TPU v4": 1228.0, "TPU v5 lite": 819.0, "TPU v5e": 819.0,
            "TPU v5": 2765.0, "TPU v5p": 2765.0, "TPU v6 lite": 1640.0,
            "TPU v6e": 1640.0}
MXU_PEAK_TF = {"TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5e": 197.0,
               "TPU v5": 459.0, "TPU v5p": 459.0, "TPU v6 lite": 918.0,
               "TPU v6e": 918.0}

# absolute perf_counter() deadline derived from --budget in main(); 0
# disables. The phase loop's between-PHASE check alone cannot save a run
# whose single phase overruns (the round-5 gbdt rc=124: the harness
# killed the process mid-phase and --out never saw the later phases), so
# long phases also poll _deadline_passed() BETWEEN rounds/stages and
# return partial results tagged "budget_truncated".
_DEADLINE = 0.0


def _deadline_passed() -> bool:
    return _DEADLINE > 0 and time.perf_counter() > _DEADLINE


def make_sparse_batch(rng, num_buckets: int):
    from wormhole_tpu.data.feed import SparseBatch
    k = int(KPAD * 0.9)
    uniq = np.zeros(KPAD, np.int32)
    uniq[:k] = np.sort(rng.choice(num_buckets, size=k, replace=False))
    key_mask = np.zeros(KPAD, np.float32)
    key_mask[:k] = 1.0
    cols = rng.integers(0, k, size=(MINIBATCH, NNZ_PAD)).astype(np.int32)
    vals = np.zeros((MINIBATCH, NNZ_PAD), np.float32)
    vals[:, :CRITEO_NNZ] = 1.0  # criteo rows: 39 binary/int features
    labels = (rng.random(MINIBATCH) < 0.25).astype(np.float32)
    row_mask = np.ones(MINIBATCH, np.float32)
    return SparseBatch(cols=cols, vals=vals, labels=labels,
                       row_mask=row_mask, uniq_keys=uniq, key_mask=key_mask)


def write_crec2(path: str, rows: int, rng, subblocks: int = 12) -> None:
    from wormhole_tpu.data.crec import CRec2Writer
    with CRec2Writer(path, nnz=CRITEO_NNZ, nb=NUM_BUCKETS,
                     subblocks=subblocks) as w:
        chunk = 200_000
        done = 0
        while done < rows:
            n = min(chunk, rows - done)
            keys = rng.integers(0, 1 << 32, size=(n, CRITEO_NNZ),
                                dtype=np.uint32)
            keys[keys == 0xFFFFFFFF] = 0
            labels = (rng.random(n) < 0.25).astype(np.uint8)
            w.append(keys, labels)
            done += n


def write_criteo_text(path: str, rows: int, rng) -> None:
    """Vectorized synthetic criteo text (label \\t 13 ints \\t 26 cats)."""
    ints = rng.integers(0, 65536, size=(rows, 13)).astype("U6")
    cats = rng.integers(0, 1 << 32, size=(rows, 26))
    labels = (rng.random(rows) < 0.25).astype(np.int64).astype("U1")
    with open(path, "w") as f:
        for i in range(rows):
            f.write(labels[i] + "\t" + "\t".join(ints[i]) + "\t"
                    + "\t".join(f"{c:08x}" for c in cats[i]) + "\n")


def make_app(cfg_kwargs):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    import jax
    rt = MeshRuntime.create()
    n_dev = len(jax.devices())
    if n_dev > 1:
        model = 2 if n_dev % 2 == 0 else 1
        rt.mesh = make_mesh(f"data:{n_dev // model},model:{model}")
    cfg = Config(**cfg_kwargs)
    cfg.lambda_ = [1.0, 0.1]
    return AsyncSGD(cfg, rt)


def bench_e2e_crec2(path: str) -> dict:
    """The headline: AsyncSGD.process over crec2 with the device cache.

    Pass 1 (cold) streams disk->device and fills the cache; the timed
    window then measures steady-state passes. The window is enforced per
    process() call (one pass over a ~0.3s file), bounding total runtime."""
    import jax
    app = make_app(dict(train_data=path, data_format="crec2",
                        max_delay=MAX_DELAY, num_buckets=NUM_BUCKETS,
                        cache_device=True, lr_eta=0.1, disp_itv=1e12))
    t0 = time.perf_counter()
    prog = app.process(path, 0, 1)        # cold pass: stream + compile
    jax.block_until_ready(app.store.slots)
    float(np.asarray(app.store.slots[0, 0]))
    cold_s = time.perf_counter() - t0
    cold_rows = prog.num_ex
    # warm the cached-replay path before opening the timed window
    # (~10 passes, bounded at 25 s)
    warm_t0 = time.perf_counter()
    for _ in range(10):
        app.process(path, 0, 1)
        if time.perf_counter() - warm_t0 > 25.0:
            break
    jax.block_until_ready(app.store.slots)
    float(np.asarray(app.store.slots[0, 0]))
    app.flush_metrics()                   # don't credit warmup rows below
    app.timer.totals.clear()
    app.timer.counts.clear()
    # several drain-inclusive windows; every window is itself an honest
    # rows/elapsed with the deferred-metric flush and a forced D2H read
    # INSIDE the clock
    windows = []          # (rate, passes) per window — kept consistent
    for _ in range(5):
        t0 = time.perf_counter()
        rows = 0
        wpasses = 0
        while True:
            prog = app.process(path, 0, 1)
            rows += prog.num_ex
            wpasses += 1
            if time.perf_counter() - t0 >= E2E_SECONDS / 2:
                break
        rows += app.flush_metrics().num_ex
        jax.block_until_ready(app.store.slots)
        float(np.asarray(app.store.slots[0, 0]))
        windows.append((rows / (time.perf_counter() - t0), wpasses))
        if _deadline_passed():
            break       # best-of-fewer windows, but the summary lands
    prof = {k: round(app.timer.totals.get(k, 0.0), 3)
            for k in ("put", "dispatch", "wait")}
    from wormhole_tpu.data.crec import read_header2
    info = read_header2(path)
    best_rate, best_passes = max(windows)
    rates = sorted(w for w, _ in windows)
    median_rate = rates[len(rates) // 2]
    # dispersion guard: when the windows disagree, flag it so "best"
    # cannot silently flatter
    dispersion = best_rate / max(median_rate, 1e-9)
    return {"ex_per_sec": best_rate, "passes": best_passes,
            "estimator": "best_of_5_windows",
            "median_ex_per_sec": median_rate,
            "window_dispersion_best_over_median": round(dispersion, 3),
            "windows_contended": bool(dispersion > 1.1),
            "window_ex_per_sec": [round(w, 1) for w, _ in windows],
            "cold_ex_per_sec": cold_rows / cold_s,
            # cumulative over ALL windows (not just the best one)
            "pipeline_profile_all_windows_sec": prof,
            "bytes_per_row": round(info.block_bytes / info.block_rows, 1)}


def _timed_pass(app, path: str, part: int, nparts: int,
                workers: int):
    """One process() pass with the feed pipeline set to ``workers``;
    returns (rows/sec, feed_stats snapshot). The feed is rebuilt per
    pass when the device cache is off, so flipping the knob on ONE app
    compares serial vs pipelined without duplicate jit compiles."""
    import jax
    app.cfg.pipeline_workers = workers
    app.feed_stats = {"feed_stall": 0.0, "feed_batches": 0, "ring_max": 0}
    t0 = time.perf_counter()
    prog = app.process(path, part, nparts)
    rows = prog.num_ex + app.flush_metrics().num_ex
    jax.block_until_ready(app.store.slots)
    float(np.asarray(app.store.slots[0, 0]))
    return rows / (time.perf_counter() - t0), dict(app.feed_stats)


def bench_e2e_stream(path: str) -> dict:
    """The NON-cached regime: every pass re-streams disk -> host ->
    device (cache_device off) — the number on record for the
    streaming-1TB-from-S3 shape of the reference's run; the
    host->device hop is the chip host's PCIe.

    The same part is timed twice — serial fallback (pipeline_workers=0)
    then the staged DeviceFeed pipeline — so the speedup and the stage
    stall counters land in the summary."""
    from wormhole_tpu.data.crec import read_header2
    app = make_app(dict(train_data=path, data_format="crec2",
                        max_delay=MAX_DELAY, num_buckets=NUM_BUCKETS,
                        cache_device=False, lr_eta=0.1, disp_itv=1e12))
    # parts keep this phase's wall time bounded (the rate is the same);
    # nparts derives from the file so every part holds >=1 block and the
    # warm part really compiles before the timed part streams
    nparts = max(1, min(4, read_header2(path).num_blocks))
    app.process(path, 0, nparts)           # compile + transport warm
    serial, _ = _timed_pass(app, path, 1 % nparts, nparts, workers=0)
    piped, stats = _timed_pass(app, path, 1 % nparts, nparts, workers=2)
    return {"ex_per_sec": piped,
            "serial_ex_per_sec": serial,
            "pipeline_speedup": round(piped / max(serial, 1e-9), 3),
            "feed_stall_sec": round(stats["feed_stall"], 3),
            "feed_batches": stats["feed_batches"],
            "ring_max": stats["ring_max"]}


def bench_e2e_text(path: str) -> dict:
    """Reference-format (criteo text) end-to-end: the dense text fast
    path (native chunk -> crec-block assembly -> dense-apply step),
    serial vs pipelined on the same app like the stream phase. Also
    reports the HOST ingest rate alone (parse+fold+assemble, no device
    feed), both serial and with parallel assembly workers."""
    app = make_app(dict(train_data=path, data_format="criteo",
                        max_delay=MAX_DELAY,
                        num_buckets=NUM_BUCKETS, lr_eta=0.1, disp_itv=1e12))
    app.process(path, 0, 1)  # warmup/compile
    serial, _ = _timed_pass(app, path, 0, 1, workers=0)
    piped, stats = _timed_pass(app, path, 0, 1, workers=2)
    # host ingest alone: the TextCRecFeed producer with no device hop
    from wormhole_tpu.data.crec import TextCRecFeed

    def ingest(workers):
        feed = TextCRecFeed(path, text_fmt="criteo", nnz=CRITEO_NNZ,
                            device_put=lambda x: x, workers=workers)
        t0 = time.perf_counter()
        irows = sum(r for _, _, r in feed)
        return irows / (time.perf_counter() - t0)

    ingest(0)                              # warm (page cache, parser)
    ingest_serial = ingest(0)
    ingest_piped = ingest(2)
    return {"ex_per_sec": piped,
            "serial_ex_per_sec": serial,
            "pipeline_speedup": round(piped / max(serial, 1e-9), 3),
            "feed_stall_sec": round(stats["feed_stall"], 3),
            "feed_batches": stats["feed_batches"],
            "ring_max": stats["ring_max"],
            "host_ingest_rows_per_sec": ingest_piped,
            "host_ingest_serial_rows_per_sec": ingest_serial,
            "host_ingest_speedup": round(
                ingest_piped / max(ingest_serial, 1e-9), 3)}


def bench_tile_online(path: str) -> dict:
    """The ISSUE-5 comparison: the SAME criteo text rows through the
    three runtime routes — (a) the gather/scatter SparseBatch path
    (tile_online=off, text_dense=off), (b) the online tile-encode path
    (tile_online=on: fold + tile-group on the feed's prep workers, MXU
    tile step on device), (c) the same rows pre-converted to a crec2
    file and replayed. (b)/(a) is what online encoding buys a streaming
    format; (c)/(b) is what pre-conversion still buys on top (it should
    approach 1.0 when the encode stage hides behind device compute —
    the residual is the reported encode-stall fraction)."""
    import jax

    def timed(app):
        app.feed_stats = {"feed_stall": 0.0, "feed_batches": 0,
                          "ring_max": 0}
        app.timer.totals.clear()
        app.timer.counts.clear()
        t0 = time.perf_counter()
        prog = app.process(path_of[app], 0, 1)
        rows = prog.num_ex + app.flush_metrics().num_ex
        jax.block_until_ready(app.store.slots)
        float(np.asarray(app.store.slots[0, 0]))
        elapsed = time.perf_counter() - t0
        return rows / elapsed, elapsed

    path_of: dict = {}
    out: dict = {}

    def run(variant, cfg_kwargs, data_path):
        app = make_app(dict(max_delay=MAX_DELAY, num_buckets=NUM_BUCKETS,
                            cache_device=False, lr_eta=0.1, disp_itv=1e12,
                            **cfg_kwargs))
        path_of[app] = data_path
        app.process(data_path, 0, 1)       # compile + transport warm
        rate, elapsed = timed(app)
        out[f"{variant}_ex_per_sec"] = rate
        return app, elapsed

    # (a) scatter runtime path — the pre-PR route for any text stream
    run("scatter", dict(train_data=path, data_format="criteo",
                        text_dense=False, tile_online="off"), path)
    if _deadline_passed():
        out["budget_truncated"] = True
        return out
    # (b) online tile encode (forced: `auto` needs the TPU backend)
    app, elapsed = run("online", dict(train_data=path,
                                      data_format="criteo",
                                      tile_online="on"), path)
    enc = app.timer.totals.get("encode", 0.0)
    enc_stall = app.timer.totals.get("encode_stall", 0.0)
    out["encode_sec"] = enc
    out["encode_stall_frac"] = enc_stall / max(elapsed, 1e-9)
    out["online_vs_scatter_speedup"] = (
        out["online_ex_per_sec"] / max(out["scatter_ex_per_sec"], 1e-9))
    if _deadline_passed():
        out["budget_truncated"] = True
        return out
    # (c) the same rows pre-converted to crec2 (the throughput ceiling):
    # stream the text through the parser once, unpack the packed v1
    # blocks, and append the real rows to a writer — identical hashed
    # keys, so (b) and (c) run bit-identical device blocks
    from wormhole_tpu.data.crec import (CRec2Writer, CRecInfo, PAD_LABEL,
                                        TextCRecFeed, unpack_block)
    c2 = path + ".conv.crec2"
    feed = TextCRecFeed(path, text_fmt="criteo", nnz=CRITEO_NNZ,
                        device_put=lambda x: x, workers=2)
    with CRec2Writer(c2, nnz=CRITEO_NNZ, nb=NUM_BUCKETS) as w:
        for _dev, packed, _rows in feed:
            src = CRecInfo(nnz=CRITEO_NNZ,
                           block_rows=packed.nbytes // (CRITEO_NNZ * 4 + 1),
                           total_rows=0)
            keys, labels = unpack_block(packed, src)
            real = labels != PAD_LABEL
            w.append(keys[real], labels[real])
    try:
        run("crec2", dict(train_data=c2, data_format="crec2"), c2)
        out["crec2_vs_online_speedup"] = (
            out["crec2_ex_per_sec"] / max(out["online_ex_per_sec"], 1e-9))
    finally:
        try:
            os.remove(c2)
        except OSError:
            pass
    return out


def _median_window(fn, repeats=5):
    times = []
    for _ in range(repeats):
        times.append(fn())
        if _deadline_passed():
            break   # a median of fewer windows beats a blown budget
    return sorted(times)[len(times) // 2]


def bench_device_sparse() -> float:
    """The fused sparse step on device-resident batches (text formats'
    path; per-batch Localizer keys)."""
    import jax
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.data.loader import dense_batch_sharding
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    rng = np.random.default_rng(0)
    rt = MeshRuntime.create()
    n_dev = len(jax.devices())
    if n_dev > 1:
        model = 2 if n_dev % 2 == 0 else 1
        rt.mesh = make_mesh(f"data:{n_dev // model},model:{model}")
    handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
    store = ShardedStore(StoreConfig(num_buckets=NUM_BUCKETS, loss="logit"),
                         handle, rt)
    sharding = dense_batch_sharding(rt)
    batches = [jax.device_put(make_sparse_batch(rng, NUM_BUCKETS), sharding)
               for _ in range(4)]
    inflight: deque = deque()

    def window(steps):
        t0 = time.perf_counter()
        for i in range(steps):
            while len(inflight) > MAX_DELAY:
                jax.block_until_ready(inflight.popleft())
            inflight.append(store.train_step(batches[i % 4]))
        while inflight:
            jax.block_until_ready(inflight.popleft())
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))  # force real completion (D2H)
        return time.perf_counter() - t0

    window(5)  # warmup
    elapsed = _median_window(lambda: window(30))
    return 30 * MINIBATCH / elapsed


def bench_bigmodel() -> dict:
    """Host-resident cold tier (bigmodel/paged.py): the bucket space
    grows 16x past the device hot-set budget while the per-step rate is
    held against a dense anchor — the same batch geometry on a plain
    store sized to the hot tier, everything device-resident. The
    Criteo-like key mix (90% of keys from a core inside the hot budget,
    10% uniform over the full space) is what makes tiering viable: the
    LFU working set absorbs the core while the accumulated uniform tail
    overflows the hot tier and exercises the evict/writeback path.
    Paging traffic is reported both in the phase record and as
    ``page/*`` registry counters (bench_check gates bytes_h2d > 0 and
    the paged/dense rate ratio floor)."""
    import jax
    from wormhole_tpu.bigmodel import PagedStore
    from wormhole_tpu.data.feed import SparseBatch
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    rng = np.random.default_rng(7)
    HOT = 1 << 16
    NB = 1 << 20                 # 16x past the hot budget
    MB, NNZ, KP = 4096, 8, 1 << 14
    STEPS = 48
    core = rng.choice(NB, size=int(HOT * 3 / 4), replace=False)

    def mk_batch(rng):
        k = int(KP * 0.9)
        keys = np.unique(np.concatenate([
            rng.choice(core, size=int(k * 0.9), replace=False),
            rng.integers(0, NB, size=k - int(k * 0.9))]))
        k = keys.size
        uniq = np.zeros(KP, np.int64)
        uniq[:k] = keys
        key_mask = np.zeros(KP, np.float32)
        key_mask[:k] = 1.0
        cols = rng.integers(0, k, size=(MB, NNZ)).astype(np.int32)
        vals = np.ones((MB, NNZ), np.float32)
        labels = (rng.random(MB) < 0.25).astype(np.float32)
        return SparseBatch(cols=cols, vals=vals, labels=labels,
                           row_mask=np.ones(MB, np.float32),
                           uniq_keys=uniq, key_mask=key_mask)

    batches = [mk_batch(rng) for _ in range(24)]

    def mk_handle():
        return FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))

    hot = ShardedStore(StoreConfig(num_buckets=HOT, loss="logit"),
                       mk_handle())
    # late_window at the feed-safety minimum: with 24 distinct batches
    # the re-use distance of an evicted bucket (24 plans) clears the
    # window, so refills stage through the transfer ring (overlapped)
    # instead of the synchronous consumer-side late path.
    from wormhole_tpu.bigmodel import late_window_for
    ps = PagedStore(hot, NB, late_window=late_window_for(2, 2))

    def paged_window(steps):
        src = (batches[i % len(batches)] for i in range(steps))
        t0 = time.perf_counter()
        ps.train_sparse(src, workers=2, ring_depth=2)
        jax.block_until_ready(ps.hot.slots)
        return time.perf_counter() - t0

    paged_window(6)   # warmup: compiles + fills the working set
    paged_s = _median_window(lambda: paged_window(STEPS), repeats=3)

    # dense anchor: identical geometry folded into the hot-size table,
    # fully device-resident, batches pre-placed (its best case)
    anchor = ShardedStore(StoreConfig(num_buckets=HOT, loss="logit"),
                          mk_handle())
    import dataclasses as _dc
    dev = [jax.device_put(_dc.replace(
               b, uniq_keys=(np.asarray(b.uniq_keys) % HOT)))
           for b in batches]

    def anchor_window(steps):
        t0 = time.perf_counter()
        for i in range(steps):
            anchor.train_step(dev[i % len(dev)])
        jax.block_until_ready(anchor.slots)
        return time.perf_counter() - t0

    anchor_window(6)  # warmup
    dense_s = _median_window(lambda: anchor_window(STEPS), repeats=3)

    stats = ps.stats()
    ps.to_registry()
    paged_rate = STEPS * MB / paged_s
    dense_rate = STEPS * MB / dense_s
    return {
        "bigmodel_ex_per_sec": round(paged_rate, 1),
        "dense_anchor_ex_per_sec": round(dense_rate, 1),
        "bigmodel_over_dense": round(paged_rate / dense_rate, 4),
        "nb_total": NB,
        "hot_buckets": HOT,
        "nb_over_hot": NB // HOT,
        "bytes_h2d": int(stats["bytes_h2d"]),
        "bytes_d2h": int(stats["bytes_d2h"]),
        "pages_in": int(stats["pages_in"]),
        "pages_out": int(stats["pages_out"]),
        "late_fills": int(stats["late_fills"]),
        "hit_rate": round(stats["hit_rate"], 4),
    }


def make_tile_stores() -> dict:
    """One store per tile-step flavor, shared by the absolute-rate
    phases AND bench_channel_ratios — each store's fused step compiles
    once per bench run instead of once per phase (jit caches are per
    store instance, and a cold compile of one tile step is minutes)."""
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.models.fm import FMConfig, FMStore
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    from wormhole_tpu.ops.penalty import L1L2
    handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
    return {
        "scalar": ShardedStore(StoreConfig(num_buckets=NUM_BUCKETS,
                                           loss="logit"), handle),
        "fm": FMStore(FMConfig(num_buckets=NUM_BUCKETS, dim=8)),
        "wd": WideDeepStore(WideDeepConfig(num_buckets=NUM_BUCKETS,
                                           dim=16, hidden=(64, 32))),
    }


def bench_device_tile(path: str, store=None) -> dict:
    """The tile-matmul step on HBM-resident crec2 blocks; overhead-
    cancelled timing (t(2N)-t(N))/N with a forced D2H read."""
    import jax
    from wormhole_tpu.data.crec import PackedFeed, read_header2
    store = store if store is not None else make_tile_stores()["scalar"]
    info = read_header2(path)
    blocks = []
    for dev, _host, _rows in PackedFeed(path, 0, 1, fmt="crec2"):
        blocks.append(dev)
        if len(blocks) >= 4:
            break

    def run(steps):
        t0 = time.perf_counter()
        for i in range(steps):
            store.tile_train_step(blocks[i % len(blocks)], info)
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))
        return time.perf_counter() - t0

    run(3)  # warmup
    # overhead-cancelled difference of MEDIANS (median-of-5 per window
    # size): fixed per-window costs cancel in t(2N) - t(N)
    n = 20
    t1 = _median_window(lambda: run(n))
    t2 = _median_window(lambda: run(2 * n))
    per_step = max((t2 - t1) / n, 1e-9)
    spec = info.spec
    # MXU flops per block: W-dot + pick + row dots, fwd and bwd
    pairs_padded = spec.tiles * spec.subblocks * spec.cap
    flops = 2 * pairs_padded * (128 * 128 + 128 * 64 + 128 * 64) * 2
    # HBM bytes: slots r/w, W bf16 w+r, G w+r, pairs r
    step_bytes = (2 * NUM_BUCKETS * 3 * 4 + 2 * NUM_BUCKETS * 2
                  + 2 * NUM_BUCKETS * 4 + 2 * info.pairs_bytes)
    return {"ex_per_sec": info.block_rows / per_step,
            "step_ms": per_step * 1e3,
            "block_rows": info.block_rows,
            "mxu_tflops": flops / per_step / 1e12,
            "hbm_gbps": step_bytes / per_step / 1e9,
            "step_bytes": step_bytes}


def bench_device_fm(path: str, store=None) -> float:
    """The FM (k=8) multi-channel tile step on HBM-resident crec2
    blocks — the stretch-model fast path (pooled pulls + split pushes,
    ops/tilemm multi-channel kernels)."""
    import jax
    from wormhole_tpu.data.crec import PackedFeed, read_header2
    store = store if store is not None else make_tile_stores()["fm"]
    info = read_header2(path)
    blocks = []
    for dev, _host, _rows in PackedFeed(path, 0, 1, fmt="crec2"):
        blocks.append(dev)
        if len(blocks) >= 2:
            break

    def run(steps):
        t0 = time.perf_counter()
        for i in range(steps):
            store.tile_train_step(blocks[i % len(blocks)], info)
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))
        return time.perf_counter() - t0

    run(3)  # warmup/compile
    n = 6
    t1 = _median_window(lambda: run(n), repeats=3)
    t2 = _median_window(lambda: run(2 * n), repeats=3)
    per_step = max((t2 - t1) / n, 1e-9)
    return info.block_rows / per_step


def bench_device_wide_deep(path: str, store=None) -> float:
    """The wide&deep multi-channel tile step on HBM-resident crec2
    blocks (wide scalar + pooled embedding pulls feeding the MLP)."""
    import jax
    from wormhole_tpu.data.crec import PackedFeed, read_header2
    store = store if store is not None else make_tile_stores()["wd"]
    info = read_header2(path)
    blocks = []
    for dev, _host, _rows in PackedFeed(path, 0, 1, fmt="crec2"):
        blocks.append(dev)
        if len(blocks) >= 2:
            break

    def run(steps):
        t0 = time.perf_counter()
        for i in range(steps):
            store.tile_train_step(blocks[i % len(blocks)], info)
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))
        return time.perf_counter() - t0

    run(3)  # warmup/compile
    n = 6
    t1 = _median_window(lambda: run(n), repeats=3)
    t2 = _median_window(lambda: run(2 * n), repeats=3)
    per_step = max((t2 - t1) / n, 1e-9)
    return info.block_rows / per_step


def bench_device_dense_apply() -> float:
    """The crec v1 / text_dense fused step on a device-resident raw
    block buffer (on-device key fold + full-width scatter apply) — the
    slow-but-exact cousin of the tile step, measured so the v1 path has
    a number of its own."""
    import jax
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    rng = np.random.default_rng(3)
    R, N = 16384, CRITEO_NNZ       # text_block_rows default x criteo nnz
    handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
    store = ShardedStore(StoreConfig(num_buckets=NUM_BUCKETS,
                                     loss="logit"), handle)
    blocks = []
    for _ in range(2):
        keys = rng.integers(0, 1 << 32, size=R * N, dtype=np.uint32)
        keys[keys == 0xFFFFFFFF] = 0
        labels = (rng.random(R) < 0.25).astype(np.uint8)
        packed = np.concatenate([keys.view(np.uint8),
                                 labels.view(np.uint8)])
        blocks.append(jax.device_put(packed))

    def run(steps):
        t0 = time.perf_counter()
        for i in range(steps):
            store.dense_train_step(blocks[i % 2], R, N)
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))
        return time.perf_counter() - t0

    run(3)
    n = 10
    t1 = _median_window(lambda: run(n), repeats=3)
    t2 = _median_window(lambda: run(2 * n), repeats=3)
    per_step = max((t2 - t1) / n, 1e-9)
    return R / per_step


def bench_channel_ratios(path: str, stores=None) -> dict:
    """Scalar vs FM vs wide&deep tile steps timed INTERLEAVED in the
    same windows, so whatever disturbs a window disturbs all three
    alike and the ratios hold. Pass the stores the absolute-rate phases
    used so their compiled steps are reused."""
    import jax
    from wormhole_tpu.data.crec import PackedFeed, read_header2
    info = read_header2(path)
    blocks = []
    for dev, _h, _r in PackedFeed(path, 0, 1, fmt="crec2"):
        blocks.append(dev)
        if len(blocks) >= 2:
            break
    stores = stores if stores is not None else make_tile_stores()

    def run(store, steps):
        t0 = time.perf_counter()
        for i in range(steps):
            store.tile_train_step(blocks[i % len(blocks)], info)
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))
        return time.perf_counter() - t0

    for s in stores.values():
        run(s, 2)                      # compile/warm
    # ratio PER interleaved pass, then the median: a per-store min could
    # pair timings from different passes — the very error the
    # interleaving exists to exclude
    fm_r, wd_r = [], []
    for _ in range(5):
        t = {k: run(s, 4) / 4 for k, s in stores.items()}
        fm_r.append(t["fm"] / t["scalar"])
        wd_r.append(t["wd"] / t["scalar"])
        if _deadline_passed():
            break       # each pass is a complete interleaved ratio
    fm_r.sort()
    wd_r.sort()
    return {"fm_step_over_scalar": round(fm_r[len(fm_r) // 2], 2),
            "wd_step_over_scalar": round(wd_r[len(wd_r) // 2], 2)}


def bench_tile_fused(path: str) -> dict:
    """Fused one-grid train step vs the split fwd/bwd oracle on
    IDENTICAL crec2 blocks, timed interleaved in the same windows (the
    bench_channel_ratios methodology) so the fused/split ratio does
    not depend on when a window ran. The same windows also
    interleave a cache-on vs cache-off A/B of the fused step on a
    narrow-block view (one subblock, nnz=16): the phase-shared one-hot
    cache stages 512 B of VMEM planes per padded slot, so wide criteo
    blocks (~4M slots) can never fit the budget — narrow blocks are
    the regime the resolver's auto admits the cache in, and forcing it
    past the budget on the file geometry would just fail to compile.
    scripts/bench_check.py gates ``fused_over_split`` with
    --min-fused-ratio and ``cached_over_fused`` with
    --min-cached-ratio: a fused kernel slower than the two calls it
    replaces — or a cache replay slower than the rebuild it skips —
    fails the trajectory. The phase also records how the resolver
    treats a spill view of the same file and a wide&deep store: both
    must come back fused (round 8 widened the admissibility — spill
    blocks pass pre-aggregated margins as a grid operand, wide&deep
    runs its MLP phase in-kernel)."""
    import dataclasses

    import jax
    from wormhole_tpu.data.crec import PackedFeed, default_cap, read_header2
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    from wormhole_tpu.ops import tilemm
    from wormhole_tpu.ops.penalty import L1L2
    # the bench file carries a spill capacity; the handful of overflow
    # pairs is dropped from BOTH timed paths (ovf_cap=0 view of the
    # same blocks) so the comparison is operand-identical — the spill
    # path's fused resolution is recorded separately below instead of
    # folded into the timing
    raw = read_header2(path)
    info = dataclasses.replace(raw, ovf_cap=0)
    blocks = []
    for dev, _h, _r in PackedFeed(path, 0, 1, fmt="crec2"):
        blocks.append(dev)
        if len(blocks) >= 2:
            break

    def mk(mode):
        return ShardedStore(
            StoreConfig(num_buckets=NUM_BUCKETS, loss="logit",
                        tile_step_kernel=mode),
            FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0)))

    stores = {"fused": mk("fused"), "split": mk("split")}

    # narrow-block cached A/B operands: same bucket space, one subblock
    # of nnz=16 rows, where auto admits the cache (res_n.cache_record
    # below is published and gated as proof)
    handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
    n_nnz, n_rows = 16, tilemm.RSUB
    spec_n = tilemm.make_spec(NUM_BUCKETS, 1,
                              default_cap(n_nnz, NUM_BUCKETS))
    res_n = tilemm.resolve_step_kernel("fused", spec=spec_n)
    rng = np.random.default_rng(0)
    pw_n, _, _ = tilemm.encode_block(
        rng.integers(0, NUM_BUCKETS, n_rows * n_nnz),
        np.repeat(np.arange(n_rows), n_nnz), spec_n)
    pw_n = jax.device_put(pw_n)
    s32_n = jax.device_put(np.zeros((NUM_BUCKETS, handle.val_len),
                                    np.float32))
    labels_n = jax.device_put((rng.random(n_rows) < 0.5)
                              .astype(np.float32))
    mask_n = jax.device_put(np.ones(n_rows, np.float32))

    def _mk_nstep(cache):
        @jax.jit
        def step(pw, s32, labels, mask):
            return tilemm.fused_step_update(pw, s32, labels, mask,
                                            spec_n, "logit", handle,
                                            cache=cache)
        return step

    nsteps = {"fused": _mk_nstep(False), "cached": _mk_nstep(True)}

    def run(store, steps):
        t0 = time.perf_counter()
        for i in range(steps):
            store.tile_train_step(blocks[i % len(blocks)], info)
        jax.block_until_ready(store.slots)
        float(np.asarray(store.slots[0, 0]))
        return time.perf_counter() - t0

    def run_n(fn, steps):
        t0 = time.perf_counter()
        o = None
        for _ in range(steps):
            o = fn(pw_n, s32_n, labels_n, mask_n)
        jax.block_until_ready(o)
        float(np.asarray(o[1].ravel()[0]))
        return time.perf_counter() - t0

    for s in stores.values():
        run(s, 2)                      # compile/warm
    for fn in nsteps.values():
        run_n(fn, 2)
    best = {m: float("inf") for m in stores}
    bestn = {m: float("inf") for m in nsteps}
    ratios, cratios = [], []
    for _ in range(5):
        t = {m: run(s, 4) / 4 for m, s in stores.items()}
        tn = {m: run_n(fn, 2) / 2 for m, fn in nsteps.items()}
        for m, v in t.items():
            best[m] = min(best[m], v)
        for m, v in tn.items():
            bestn[m] = min(bestn[m], v)
        # ratio per interleaved pass, median across passes — a
        # per-store min could pair timings from different passes
        ratios.append(t["split"] / t["fused"])
        cratios.append(tn["fused"] / tn["cached"])
        if _deadline_passed():
            break
    ratios.sort()
    cratios.sort()
    # admissibility records (no timing): the spill view of the bench
    # file and a wide&deep store must both resolve fused — building the
    # step closure is enough to populate step_kernel, nothing compiles
    spill = mk("fused")
    spill._tile_step(dataclasses.replace(raw, ovf_cap=max(raw.ovf_cap, 64)),
                     "train")
    wd = WideDeepStore(WideDeepConfig(num_buckets=NUM_BUCKETS, dim=16,
                                      hidden=(64, 32),
                                      tile_step_kernel="fused"))
    wd._tile_step(info, "train")
    return {
        "tile_fused_ex_per_sec": round(info.block_rows / best["fused"], 1),
        "tile_split_ex_per_sec": round(info.block_rows / best["split"], 1),
        # narrow-block geometry (n_rows rows x nnz=16) — its own
        # absolute rate; only the RATIO compares like with like
        "tile_cached_ex_per_sec": round(n_rows / bestn["cached"], 1),
        "tile_narrow_fused_ex_per_sec": round(n_rows / bestn["fused"], 1),
        "fused_over_split": round(ratios[len(ratios) // 2], 3),
        "cached_over_fused": round(cratios[len(cratios) // 2], 3),
        "resolved_kernel": stores["fused"].step_kernel[0],
        "cache_record": res_n.cache_record,
        "spill_resolved_kernel": spill.step_kernel[0],
        "wd_resolved_kernel": wd.step_kernel[0]}


def bench_kmeans() -> dict:
    """k-means iteration time at the MNIST-784 shape (BASELINE.json's
    learn/kmeans config: dense 60000 x 784, k=10). One BSP iteration =
    MXU cosine assignment + scatter stats over all batches."""
    import jax
    from wormhole_tpu.data.feed import DenseBatch
    from wormhole_tpu.models.kmeans import KMeans, KMeansConfig
    rng = np.random.default_rng(0)
    n, f, k, mb = 60_000, 784, 10, 10_000
    cfg = KMeansConfig(num_clusters=k, num_features=f, max_nnz=f,
                       minibatch_size=mb, max_iter=3)
    km = KMeans(cfg)
    cols = np.broadcast_to(np.arange(f, dtype=np.int32), (mb, f))
    batches = []
    for _ in range(n // mb):
        x = rng.random((mb, f), np.float32)  # MNIST-like dense [0,1)
        batches.append(DenseBatch(
            cols=jax.device_put(np.ascontiguousarray(cols)),
            vals=jax.device_put(x),
            labels=jax.device_put(np.zeros(mb, np.float32)),
            row_mask=jax.device_put(np.ones(mb, np.float32))))
    state = km.init_centroids(batches)
    state, _ = km.one_iteration(state, batches)  # compile
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, objv = km.one_iteration(state, batches)
        times.append(time.perf_counter() - t0)
        if _deadline_passed():
            break
    it_s = sorted(times)[len(times) // 2]
    return {"iter_sec": it_s, "rows_per_sec": n / it_s,
            "shape": [n, f, k]}


def bench_lbfgs() -> dict:
    """L-BFGS iteration time at the RCV1 shape (BASELINE.json's
    learn/lbfgs-linear config: 20242 x 47236 sparse, ~74 nnz/row).
    One iteration = full-data CalcGrad + two-loop direction + Armijo
    line search on cached directional margins (the reference's
    per-iteration structure, lbfgs.h:198-212)."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.data.feed import DenseBatch
    from wormhole_tpu.models.linear import LinearObjective
    from wormhole_tpu.solver.lbfgs import LBFGSConfig, LBFGSSolver
    rng = np.random.default_rng(1)
    n, F, nnz, mb = 20_242, 47_236, 74, 10_121  # 2 padded batches
    batches = []
    done = 0
    while done < n:
        b = min(mb, n - done)
        cols = rng.integers(0, F, size=(mb, nnz)).astype(np.int32)
        vals = rng.random((mb, nnz), np.float32)
        labels = (rng.random(mb) < 0.5).astype(np.float32)
        mask = np.zeros(mb, np.float32)
        mask[:b] = 1.0
        batches.append(DenseBatch(cols=jax.device_put(cols),
                                  vals=jax.device_put(vals),
                                  labels=jax.device_put(labels),
                                  row_mask=jax.device_put(mask)))
        done += b
    obj = LinearObjective(batches, F, "logit", reg_l2=1.0)
    w0 = jnp.zeros(F, jnp.float32)
    warm = LBFGSSolver(LBFGSConfig(memory=10, max_iter=2), obj)
    warm.run(w0)                      # compile grad/objv/directional
    # full-data CalcGrad alone (pure device work, one D2H): the stable
    # anchor — the full iteration below includes the host-side line
    # search and its per-alpha D2H round trips
    def one_grad():
        t0 = time.perf_counter()
        _, g = obj.calc_grad(w0)
        jax.block_until_ready(g)
        float(np.asarray(g.ravel()[0]))
        return time.perf_counter() - t0

    one_grad()                        # warm
    grad_s = _median_window(one_grad)
    iters = 8
    solver = LBFGSSolver(LBFGSConfig(memory=10, max_iter=iters), obj)
    t0 = time.perf_counter()
    solver.run(w0)
    it_s = (time.perf_counter() - t0) / max(len(solver.history), 1)
    return {"iter_sec": it_s, "calc_grad_sec": grad_s,
            "shape": [n, F, nnz]}


def bench_gbdt() -> dict:
    """GBDT rounds/sec at a fixed Higgs-shaped slice (dense 200K x 28,
    depth 6, 256 bins — the BASELINE.json learn/xgboost config shrunk
    5x) — in-memory AND external-memory (streamed BinnedCache through
    data/pipeline.DeviceFeed) variants. Right-sized per PR 2: the fixed
    200K row count and 1<<16 chunk rows (4 chunks: 3 full + ragged tail)
    keep the phase a couple of minutes while still exercising
    multi-chunk streaming, and per-round ROW rates are reported so the
    in-memory vs external comparison survives workload resizing."""
    from wormhole_tpu.models.gbdt import (BinnedCache, GBDT, GBDTConfig,
                                          quantile_bins)
    from wormhole_tpu.ops import histmm
    rng = np.random.default_rng(2)
    n, F, depth, chunk_rows = 200_000, 28, 6, 1 << 16
    x = rng.standard_normal((n, F)).astype(np.float32)
    y = ((x[:, 0] + 0.5 * x[:, 3] + 0.3 * rng.standard_normal(n)) > 0
         ).astype(np.float32)
    # external-memory rounds right-sized to 2 (was 3): per-round rates
    # are what's reported, and the external variant pays the warm-up
    # compile at two extra shapes (chunk + ragged tail) — three timed
    # rounds of it were the largest single block in the round-5 rc=124
    warm_rounds, rounds, ext_rounds = 1, 3, 2
    m1 = GBDT(GBDTConfig(num_round=warm_rounds, max_depth=depth))
    m1.fit(x, y)                      # compile all level shapes
    m2 = GBDT(GBDTConfig(num_round=rounds, max_depth=depth))
    t0 = time.perf_counter()
    m2.fit(x, y)
    in_mem = (time.perf_counter() - t0) / rounds
    out = {"round_sec_in_memory": in_mem, "rounds_per_sec": 1.0 / in_mem,
           # per-round row rates: directly comparable across workload
           # sizes and between the two variants
           "rows_per_sec_in_memory": n / in_mem,
           "hist_kernel": histmm.resolve_kernel(
               m2.cfg.gbdt_hist_kernel, num_feat=F,
               num_bins=m2.cfg.num_bins),
           # counters from the PR-2 instrumentation: level-hist kernel
           # seconds and chunk-feed consumer stalls, per timed round
           "hist_sec_per_round_in_memory": m2.progress.gbdt_hist / rounds,
           "chunk_rows": chunk_rows, "shape": [n, F, depth]}
    if _deadline_passed():
        out["budget_truncated"] = True
        return out                    # in-memory numbers still land
    # external: stream the binned cache (built once here, honestly timed
    # separately from the per-round cost like xgboost's #cache reuse)
    bins, cuts = quantile_bins(x, 256)
    # per-run dir: concurrent bench invocations must not share the cache
    cache_path = os.path.join(tempfile.mkdtemp(prefix="wh_bench_gbdt_"),
                              "higgs.cache")
    t0 = time.perf_counter()
    cache = BinnedCache.create(cache_path, F, chunk_rows)
    for lo in range(0, n, chunk_rows):
        cache.append(bins[lo:lo + chunk_rows])
    cache.close()
    out["cache_build_sec"] = time.perf_counter() - t0
    cache = BinnedCache.open(cache_path)
    out["num_chunks"] = cache.num_chunks

    def _cleanup():
        try:
            os.remove(cache_path)
            os.rmdir(os.path.dirname(cache_path))
        except OSError:
            pass

    if _deadline_passed():
        _cleanup()
        out["budget_truncated"] = True
        return out
    # warm the chunk-shaped compiles (tree-build + predict at the chunk
    # and ragged-tail shapes) so the timed region measures rounds, not JIT
    m3w = GBDT(GBDTConfig(num_round=warm_rounds, max_depth=depth))
    m3w.cuts = cuts
    m3w._boost_external(cache, y)
    if _deadline_passed():
        _cleanup()
        out["budget_truncated"] = True
        return out
    m3 = GBDT(GBDTConfig(num_round=ext_rounds, max_depth=depth))
    m3.cuts = cuts
    t0 = time.perf_counter()
    m3._boost_external(cache, y)
    ext = (time.perf_counter() - t0) / ext_rounds
    _cleanup()
    out.update({
        "round_sec_external": ext,
        "rounds_per_sec_external": 1.0 / ext,
        "rows_per_sec_external": n / ext,
        "external_over_in_memory": ext / in_mem,
        "hist_sec_per_round_external": m3.progress.gbdt_hist / ext_rounds,
        "chunk_stall_sec_per_round":
            m3.progress.gbdt_chunk_stall / ext_rounds})
    return out


def bench_comm_filters() -> dict:
    """The ps-lite filter chain (parallel/filters.py): wire-byte
    reduction on a representative gradient-histogram payload, plus the
    lossy-training parity check — L-BFGS driven through the chain's
    error-fed 8-bit quantizer must land within 1e-3 relative of the
    unfiltered final objective. Single-process ``allreduce_tree`` is an
    identity, so the phase drives ``FilterChain.roundtrip`` directly:
    the full wire codec (quantize + RLE + zlib + key-caching headers +
    residual carry), minus only the allgather transport."""
    import jax.numpy as jnp
    from wormhole_tpu.data.feed import DenseBatch
    from wormhole_tpu.models.linear import LinearObjective
    from wormhole_tpu.parallel.filters import FilterChain
    from wormhole_tpu.solver.lbfgs import LBFGSConfig, LBFGSSolver
    rng = np.random.default_rng(7)
    # payload shaped like a gbdt level histogram sync (site
    # "gbdt/level_hist"): (grad, hess) sums over nodes x features x
    # bins, ~90% empty cells — each node sees a data slice, so most
    # (feature, bin) pairs never fire
    nodes, Fh, bins = 64, 28, 256

    def make_hists():
        g = np.zeros((nodes, Fh, bins), np.float32)
        h = np.zeros((nodes, Fh, bins), np.float32)
        mask = rng.random(g.shape) < 0.1
        k = int(mask.sum())
        g[mask] = rng.standard_normal(k).astype(np.float32)
        h[mask] = rng.random(k).astype(np.float32)
        return g, h

    chain = FilterChain(filters={"key_caching", "fixing_float",
                                 "compressing"}, quant_bits=8)
    hist_rounds = 10
    err = 0.0
    t0 = time.perf_counter()
    for _ in range(hist_rounds):
        tree = make_hists()
        got = chain.roundtrip(tree, "bench/grad_hist")
        err = max(err, max(float(np.max(np.abs(a - b)))
                           for a, b in zip(tree, got)))
    codec_s = time.perf_counter() - t0
    out = {"wire_ratio": round(chain.ratio(), 2),
           "bytes_raw": chain.stats["bytes_raw"],
           "bytes_wire": chain.stats["bytes_wire"],
           "quant_bits": 8, "hist_rounds": hist_rounds,
           "hist_shape": [nodes, Fh, bins],
           "max_abs_roundtrip_err": err,
           "codec_mb_per_sec": round(
               chain.stats["bytes_raw"] / 1e6 / max(codec_s, 1e-9), 1)}
    if _deadline_passed():
        out["budget_truncated"] = True
        return out
    # parity: same data, same solver, one run unfiltered and one with
    # every _cross_host fold routed through a fresh chain's loopback
    # (the "linear/grad" site quantizes with error feedback; objv and
    # line-search sites reduce exact, so Armijo sees true losses)
    n2, F2, nnz2, mb2 = 8_192, 4_096, 32, 4_096
    batches = []
    for i in range(n2 // mb2):
        cols = rng.integers(0, F2, size=(mb2, nnz2)).astype(np.int32)
        vals = rng.random((mb2, nnz2), np.float32)
        labels = (rng.random(mb2) < 0.5).astype(np.float32)
        batches.append(DenseBatch(
            cols=cols, vals=vals, labels=labels,
            row_mask=np.ones(mb2, np.float32)))
    w0 = jnp.zeros(F2, jnp.float32)
    scfg = LBFGSConfig(memory=10, max_iter=12)
    obj_a = LinearObjective(batches, F2, "logit", reg_l2=1.0)
    fa = float(obj_a.objv(LBFGSSolver(scfg, obj_a).run(w0).w))
    obj_b = LinearObjective(batches, F2, "logit", reg_l2=1.0)
    grad_chain = FilterChain(filters={"key_caching", "fixing_float",
                                      "compressing"}, quant_bits=8,
                             min_bytes=0)
    obj_b._cross_host = lambda tree, site: grad_chain.roundtrip(tree, site)
    fb = float(obj_b.objv(LBFGSSolver(scfg, obj_b).run(w0).w))
    rel = abs(fb - fa) / max(abs(fa), 1e-12)
    out.update({"unfiltered_final_objv": fa, "filtered_final_objv": fb,
                "objv_rel_diff": rel,
                "objv_within_1e-3": bool(rel < 1e-3),
                "grad_wire_ratio": round(grad_chain.ratio(), 2)})
    return out


def bench_async_ps() -> dict:
    """Bounded-staleness exchange engine (wormhole_tpu/ps): window
    throughput vs ``staleness_tau`` on a synthetic stream where the
    simulated device step and the simulated wire round-trip are
    comparable — the regime the engine exists for. The engine is real
    (drain thread, gate-by-count, measured delays); the transport is a
    sleep plus ``FilterChain.roundtrip`` on the "ps/delta" site, so the
    wire-byte accounting exercises the exact codec the multihost path
    ships through. tau=0 serializes compute and exchange; tau>=1 must
    overlap them (ex_per_sec strictly above tau=0, overlap_frac > 0) —
    scripts/bench_check.py auto-gates every *_ex_per_sec key."""
    from wormhole_tpu.parallel.filters import FilterChain
    from wormhole_tpu.ps import ExchangeEngine
    rng = np.random.default_rng(5)
    nb = 1 << 16
    windows = 24
    mb = 1024               # examples per window
    t_compute = 0.010       # simulated device step per window
    t_wire = 0.010          # simulated DCN latency per exchange
    grads = []
    for _ in range(4):
        g = np.zeros(nb, np.float32)
        idx = rng.integers(0, nb, size=4096)
        g[idx] = rng.standard_normal(idx.size).astype(np.float32)
        grads.append(g)
    out = {"windows": windows, "examples_per_window": mb,
           "sim_compute_s": t_compute, "sim_wire_s": t_wire}
    for tau in (0, 1, 2):
        chain = FilterChain(filters={"key_caching", "fixing_float",
                                     "compressing"}, quant_bits=8,
                            min_bytes=0)
        eng = ExchangeEngine(tau)
        applied = 0
        t0 = time.perf_counter()
        try:
            for i in range(windows):
                time.sleep(t_compute)               # the device step
                g = grads[i % len(grads)]
                eng.submit(lambda g=g: (time.sleep(t_wire),
                                        chain.roundtrip(g, "ps/delta"))[1])
                for tk in eng.gate():
                    eng.note_applied(tk)
                    applied += 1
            for tk in eng.quiesce():
                eng.note_applied(tk)
                applied += 1
        finally:
            eng.stop()
        wall = time.perf_counter() - t0
        assert applied == windows
        key = f"tau{tau}"
        out[f"{key}_ex_per_sec"] = round(windows * mb / wall, 1)
        out[f"{key}_overlap_frac"] = round(
            eng.delays.overlap_fraction(), 4)
        out[f"{key}_wall_s"] = round(wall, 3)
        out[f"{key}_bytes_wire"] = chain.stats["bytes_wire"]
        out[f"{key}_wire_ratio"] = round(chain.ratio(), 2)
        if _deadline_passed():
            out["budget_truncated"] = True
            return out
    out["overlap_speedup"] = round(
        out["tau1_ex_per_sec"] / max(out["tau0_ex_per_sec"], 1e-9), 3)
    return out


def bench_scale_curve(workdir: str, rng) -> list:
    """Tile-step rate vs model size: the crec2
    pairs array scales as tiles x cap with cap floored at 128, so at
    nb >= ~2^26 with 39 nnz/row padding dominates. Measure the curve at
    2^22 / 2^24 / 2^26 and publish it (docs/perf.md discusses the regime
    boundary)."""
    import jax
    from wormhole_tpu.data.crec import CRec2Writer, PackedFeed, read_header2
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    out = []
    rows = 98_304 * 2
    for nb_log in (22, 24, 26):
        if out and _deadline_passed():
            break       # partial curve: each entry stands alone
        nb = 1 << nb_log
        path = os.path.join(workdir, f"scale_{nb_log}.crec2")
        with CRec2Writer(path, nnz=CRITEO_NNZ, nb=nb) as w:
            done = 0
            while done < rows:
                m = min(200_000, rows - done)
                keys = rng.integers(0, 1 << 32, size=(m, CRITEO_NNZ),
                                    dtype=np.uint32)
                keys[keys == 0xFFFFFFFF] = 0
                w.append(keys, (rng.random(m) < 0.25).astype(np.uint8))
                done += m
        info = read_header2(path)
        handle = FTRLHandle(penalty=L1L2(1.0, 0.1), lr=LearnRate(0.1, 1.0))
        store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                             handle)
        blocks = []
        for dev, _h, _r in PackedFeed(path, 0, 1, fmt="crec2"):
            blocks.append(dev)
            if len(blocks) >= 2:
                break

        def run(steps):
            t0 = time.perf_counter()
            for i in range(steps):
                store.tile_train_step(blocks[i % len(blocks)], info)
            jax.block_until_ready(store.slots)
            float(np.asarray(store.slots[0, 0]))
            return time.perf_counter() - t0

        run(3)
        n = 10
        t1 = _median_window(lambda: run(n), repeats=3)
        t2 = _median_window(lambda: run(2 * n), repeats=3)
        per_step = max((t2 - t1) / n, 1e-9)
        spec = info.spec
        slots = spec.tiles * spec.subblocks * spec.cap
        real = rows // 2 * CRITEO_NNZ  # pairs per block (one block timed)
        out.append({"nb_log2": nb_log, "cap": spec.cap,
                    "step_ms": round(per_step * 1e3, 2),
                    "ex_per_sec": round(info.block_rows / per_step, 1),
                    "pad_frac": round(1.0 - real / slots, 3)})
        try:
            os.remove(path)
        except OSError:
            pass
    return out


def bench_serve() -> dict:
    """Online serving (wormhole_tpu/serve): fixed-QPS open-loop client
    against the admission-batching front-end, solo and co-resident with
    a live training loop on the same chip.

    Open-loop means arrival times are fixed in advance (t0 + i/qps) and
    never wait on responses — the honest way to measure a latency SLO,
    since a closed-loop client self-throttles exactly when the server
    is slow (coordinated omission). Reported per stage: exact p50/p99
    request latency and achieved QPS. Mid-phase the checkpoint poller
    hot-swaps a new model version under load; the compile counter must
    stay at 1 (one geometry = one compile, swaps retrace nothing). The
    co-resident stage runs training ticks on the main thread while the
    client submits from another — the train-rate ratio vs. solo is the
    interference number docs/serving.md budgets."""
    import jax
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.obs.metrics import Registry
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.checkpoint import Checkpointer
    from wormhole_tpu.serve import (ForwardStep, ServeFrontend,
                                    ServeRunner, SnapshotPoller)
    import threading

    nb = 1 << 16
    qps = 400.0
    stage_reqs = 1200            # ~3s of open-loop traffic per stage
    batch_rows, max_nnz, deadline_ms = 64, 32, 5.0
    rng = np.random.default_rng(11)
    store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                         FTRLHandle(penalty=L1L2(1.0, 0.1),
                                    lr=LearnRate(0.1, 1.0)))
    reg = Registry()

    # a training minibatch for the co-resident loop (and the mid-phase
    # model delta the swap must make visible)
    train_batch = jax.device_put(make_serve_train_batch(rng, nb))

    def train_tick():
        m = store.train_step(train_batch, tau=0.0)
        jax.block_until_ready(m)

    train_tick()                 # compile the train step outside timing
    # the serving tier owns a SNAPSHOT, never the live table: the fused
    # train step donates its slots buffer, so an alias of the live array
    # dies on the next tick — the poller's first load is what gives the
    # forward an independent model to serve
    fwd = ForwardStep.from_store(store)
    reqs = [rng.choice(nb, size=int(rng.integers(8, max_nnz)),
                       replace=False) for _ in range(stage_reqs)]

    def open_loop(fe, n0, n1) -> dict:
        t0 = time.perf_counter()
        pending = []
        for i in range(n0, n1):
            target = t0 + (i - n0) / qps
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            pending.append(fe.submit(reqs[i]))
        for r in pending:
            r.result(timeout=30)
        return {"n": n1 - n0,
                "achieved_qps": (n1 - n0) / (time.perf_counter() - t0)}

    workdir = tempfile.mkdtemp(prefix="wh_bench_serve_")
    ckpt = Checkpointer(workdir, is_writer=True)
    template = jax.tree.map(np.asarray, store.state_pytree())
    ckpt.save(1, store.state_pytree())

    out = {"qps_target": qps, "batch_rows": batch_rows,
           "deadline_ms": deadline_ms}
    # -- stage 1: solo serving, hot-swap at half-traffic ------------------
    fe = ServeFrontend(fwd, batch_rows=batch_rows, max_nnz=max_nnz,
                       deadline_ms=deadline_ms, registry=reg)
    poller = SnapshotPoller(ckpt, template, fwd, poll_itv=0.1)
    assert poller.poll_once(), "v1 snapshot must load before traffic"
    poller.start()
    fe.submit(reqs[0]).result(timeout=30)   # compile outside the window
    half = stage_reqs // 2
    a1 = open_loop(fe, 0, half)
    train_tick()                            # move the model, commit v2
    ckpt.save(2, store.state_pytree())
    a2 = open_loop(fe, half, stage_reqs)
    # the poller runs every 0.1s; the second half of traffic takes ~1.5s
    deadline = time.perf_counter() + 5.0
    while poller.swaps == 0 and time.perf_counter() < deadline:
        time.sleep(0.05)
    poller.stop()
    solo = fe.stats()
    fe.close()
    solo["achieved_qps"] = round(
        (a1["n"] + a2["n"]) / (a1["n"] / a1["achieved_qps"]
                               + a2["n"] / a2["achieved_qps"]), 1)
    out["solo"] = solo
    out["hot_swap"] = {"swaps": poller.swaps,
                       "serving_version": poller.version,
                       "recompiles": fwd.compiles - 1}
    if _deadline_passed():
        out["budget_truncated"] = True
        return out

    # -- stage 2: train-rate baseline (no serving traffic) ----------------
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < 1.5:
        train_tick()
        n += 1
    out["train_solo_steps_per_sec"] = round(n / (time.perf_counter() - t0),
                                            1)
    if _deadline_passed():
        out["budget_truncated"] = True
        return out

    # -- stage 3: co-resident serve + train on the same chip --------------
    fe = ServeFrontend(fwd, batch_rows=batch_rows, max_nnz=max_nnz,
                       deadline_ms=deadline_ms, registry=reg)
    runner = ServeRunner(fe, train_tick=train_tick)
    co: dict = {}
    client = threading.Thread(
        target=lambda: co.update(open_loop(fe, 0, stage_reqs)),
        daemon=True)
    t0 = time.perf_counter()
    client.start()
    while client.is_alive():
        runner.run(seconds=0.2)
    client.join()
    co_steps = runner.train_steps / (time.perf_counter() - t0)
    cores = fe.stats()
    runner.close()
    cores["achieved_qps"] = round(co["achieved_qps"], 1)
    cores["train_steps_per_sec"] = round(co_steps, 1)
    out["coresident"] = cores
    out["train_interference_frac"] = round(
        1.0 - co_steps / max(out["train_solo_steps_per_sec"], 1e-9), 4)
    out["serve_recompiles_total"] = fwd.compiles - 1
    for fn in os.listdir(workdir):
        try:
            os.remove(os.path.join(workdir, fn))
        except OSError:
            pass
    try:
        os.rmdir(workdir)
    except OSError:
        pass
    return out


def make_serve_train_batch(rng, nb: int):
    """A small sparse train minibatch for the serve phase's co-resident
    training loop (full-size MINIBATCH would dwarf the serve forwards)."""
    from wormhole_tpu.data.feed import SparseBatch
    mb, nnz, k = 4096, 32, 8192
    uniq = np.zeros(k, np.int32)
    uniq[:k] = np.sort(rng.choice(nb, size=k, replace=False))
    cols = rng.integers(0, k, size=(mb, nnz)).astype(np.int32)
    vals = np.ones((mb, nnz), np.float32)
    labels = (rng.random(mb) < 0.25).astype(np.float32)
    return SparseBatch(cols=cols, vals=vals, labels=labels,
                       row_mask=np.ones(mb, np.float32), uniq_keys=uniq,
                       key_mask=np.ones(k, np.float32))


def bench_serve_fleet() -> dict:
    """Run the fleet phase with the cyclic GC paused: the open-loop
    client allocates tens of thousands of ServeResult futures per
    second, and a mid-stage gen-2 collection stalls every serving
    thread for tens of ms — at p99 granularity that poisons whole
    levels (measured: sporadic 40-100ms tails that vanish with GC
    off). The futures are acyclic, so refcounting reclaims them
    either way."""
    import gc
    enabled = gc.isenabled()
    gc.disable()
    try:
        return _bench_serve_fleet_measured()
    finally:
        if enabled:
            gc.enable()


def _bench_serve_fleet_measured() -> dict:
    """Fleet serving (wormhole_tpu/serve/fleet.py): N pull-only
    frontend replicas behind the consistent-hash/spill router, model
    freshness shipped as quantized deltas over the transport layer, and
    deadline-aware shedding under overload.

    Every stage runs a FRESH fleet so latency reservoirs never mix
    across operating points. Stages:

    - replica sweep: R in {1, 2, 4}. Per R the fleet is first flood-
      calibrated (un-paced burst through the warmed replicas — the
      capacity the paced levels must respect; deriving every level
      from the R=1 number instead would guarantee R>1 overload on a
      host whose replicas share cores), then swept over offered
      fractions of that capacity with an open-loop client (same
      coordinated-omission rationale as bench_serve). Per R:
      ``qps_at_slo`` = highest achieved rate whose MERGED fleet p99
      stays inside the SLO ceiling, plus the 1->4 scaling ratio. The
      deliberately-overloaded probe level reports its tail as
      ``sat_p99_ms`` — a saturated open-loop queue's tail is
      unbounded-noise by construction (it measures stage length, not
      the server), so it must not ride bench_check's p99 trend gate;
    - router: hash vs spill at R=4 under the same sub-SLO load;
    - overload: R=2 at 2x and 5x qps_at_slo with a ShedPolicy armed by
      a serve/p99_ms ceiling objective (engage at 0.8x the bound —
      BEFORE the budget burns). Reports the shed fraction, the merged
      p99 of requests actually served, and the SLO burn rate from a
      phase-local tracker sampling the p99 gauge;
    - snapshot cadence: K model versions shipped while training ticks
      move the model between publishes. ``cadence_ratio`` = what K
      disk-polls would read per replica (full checkpoint file x K)
      over what the wire actually carried per replica (bytes_wire).

    NOTE: replica threads share this host's single core, so scaling
    sits near 1x by construction; bench_check's --min-fleet-scaling is
    CPU-calibrated and docs/serving.md documents the >= 1.6x target a
    real multi-chip fleet gates at."""
    import jax
    import threading
    from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.obs.metrics import Registry
    from wormhole_tpu.obs.slo import Objective, SLOTracker
    from wormhole_tpu.ops.penalty import L1L2
    from wormhole_tpu.parallel.checkpoint import Checkpointer
    from wormhole_tpu.serve import (ForwardStep, ServeFleet,
                                    ServeShedError, ShedPolicy)

    nb = 1 << 16
    batch_rows, max_nnz, deadline_ms = 64, 32, 5.0
    slo_ms = 25.0
    rng = np.random.default_rng(23)
    store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                         FTRLHandle(penalty=L1L2(1.0, 0.1),
                                    lr=LearnRate(0.1, 1.0)))
    train_batch = jax.device_put(make_serve_train_batch(rng, nb))

    def train_tick():
        m = store.train_step(train_batch, tau=0.0)
        jax.block_until_ready(m)

    train_tick()                     # compile + move the model off init

    def serve_params():
        # owned HOST copy of the store's current serve params (fleet
        # replicas and the publisher base must never alias the donated
        # training buffers)
        return jax.tree.map(np.array, ForwardStep.from_store(store).params)

    base_params = serve_params()

    def owned_forwards(n):
        fwds = [ForwardStep.from_store(store) for _ in range(n)]
        for f in fwds:
            f.swap(jax.tree.map(jax.numpy.asarray, base_params))
        return fwds

    def make_fleet(n, **kw):
        return ServeFleet(owned_forwards(n), batch_rows=batch_rows,
                          max_nnz=max_nnz, deadline_ms=deadline_ms, **kw)

    reqs = [rng.choice(nb, size=int(rng.integers(8, max_nnz)),
                       replace=False) for _ in range(4000)]

    def warm(fleet):
        # warm EVERY replica directly (routing warms only the owner of
        # the probe key; a cold replica's first batch pays thread start
        # + first dispatch, which at p99 granularity poisons the whole
        # reservoir on short stages)
        for _ in range(2):
            for w in [fe.submit(reqs[0]) for fe in fleet.frontends]:
                w.result(timeout=60)

    def open_loop(fleet, n, qps, prio=None):
        """Open-loop client (qps <= 0: un-paced flood). Shed futures
        fail with ServeShedError — counted, never raised."""
        t0 = time.perf_counter()
        pending = []
        for i in range(n):
            if qps > 0:
                target = t0 + i / qps
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
            p = 0 if prio is None else prio[i % len(prio)]
            pending.append(fleet.submit(reqs[i % len(reqs)], priority=p))
        ok = shed = 0
        for r in pending:
            try:
                r.result(timeout=60)
                ok += 1
            except ServeShedError:
                shed += 1
        dt = time.perf_counter() - t0
        return {"n": n, "ok": ok, "shed": shed,
                "offered_qps": qps if qps > 0 else n / dt,
                "achieved_qps": n / dt}

    out = {"batch_rows": batch_rows, "deadline_ms": deadline_ms,
           "slo_ms": slo_ms}

    # -- stage 1+2: per-R capacity calibration + qps_at_slo sweep ---------
    # sub-capacity fractions bracket the operating range; the 1.1x probe
    # exists so qps_at_slo is a real maximum (the SLO boundary is shown
    # breached), not just "last level tried"
    levels = (0.5, 0.75, 0.9, 1.1)
    sweep: dict = {}
    caps: dict = {}
    for n_rep in (1, 2, 4):
        fl = make_fleet(n_rep)
        warm(fl)
        cal = open_loop(fl, 1200, 0.0)
        fl.close()
        caps[n_rep] = cal["achieved_qps"]
        lev_out: dict = {}
        best = best_p99 = 0.0
        for frac in levels:
            offered = caps[n_rep] * frac
            n = int(min(max(offered * 1.2, 300), 2400))
            fl = make_fleet(n_rep)
            warm(fl)
            r = open_loop(fl, n, offered)
            agg = fl.stats()["aggregate"]
            fl.close()
            p99 = agg.get("p99_ms", float("inf"))
            rec = {"offered_qps": r["offered_qps"],
                   "achieved_qps": r["achieved_qps"]}
            rec["p99_ms" if frac < 1.0 else "sat_p99_ms"] = p99
            lev_out[f"x{frac:g}"] = rec
            # the saturation probe never competes for qps_at_slo: a
            # flood whose tail happens to land inside the SLO is still
            # not an operating point anyone offered
            if frac < 1.0 and p99 <= slo_ms and r["achieved_qps"] > best:
                best, best_p99 = r["achieved_qps"], p99
            if _deadline_passed():
                break
        sweep[f"r{n_rep}"] = {"capacity_qps": caps[n_rep],
                              "levels": lev_out, "qps_at_slo": best,
                              "p99_at_slo_ms": best_p99}
        if _deadline_passed():
            out["budget_truncated"] = True
            break
    out["capacity_qps"] = caps.get(1, 0.0)
    out["replicas"] = sweep
    q1 = sweep.get("r1", {}).get("qps_at_slo", 0.0)
    q4 = sweep.get("r4", {}).get("qps_at_slo", 0.0)
    if q1 > 0 and q4 > 0:
        out["scaling_1to4"] = q4 / q1
    if out.get("budget_truncated"):
        return out
    capacity = caps[1]

    # -- stage 3: router policy compare (R=4, same sub-SLO load) ----------
    offered = caps[4] * 0.75
    n = int(min(max(offered * 1.2, 300), 2400))
    rc: dict = {}
    for policy in ("hash", "spill"):
        fl = make_fleet(4, router_policy=policy)
        warm(fl)
        r = open_loop(fl, n, offered)
        st = fl.stats()
        fl.close()
        rc[policy] = {"achieved_qps": r["achieved_qps"],
                      "p99_ms": st["aggregate"].get("p99_ms", 0.0),
                      "spilled": st["router"]["spilled"]}
    out["router_compare"] = rc
    if _deadline_passed():
        out["budget_truncated"] = True
        return out

    # -- stage 4: overload + deadline-aware shedding (R=2) ----------------
    base_rate = sweep.get("r2", {}).get("qps_at_slo") or caps[2] * 0.9
    objective = Objective("serve_p99", "serve/p99_ms", slo_ms,
                          kind="ceiling")
    priomix = [1, 0, 1, 1, 0]        # 40% interactive / 60% sheddable
    over: dict = {}
    for mult in (2.0, 5.0):
        reg = Registry()
        fl = make_fleet(2, registry=reg,
                        shed=ShedPolicy(objective=objective,
                                        engage_frac=0.8, storm_n=64))
        warm(fl)
        trk = SLOTracker([objective], window_s=30.0)
        stop = threading.Event()
        gauge = reg.get("serve/p99_ms")

        def sample(trk=trk, stop=stop, gauge=gauge):
            # skip the arming transient: a production SLO window
            # (minutes) amortizes a cold ramp, a ~2s stage cannot —
            # sampling it would measure startup, not the controller
            if stop.wait(0.75):
                return
            while not stop.is_set():
                trk.observe({"mono": time.monotonic(),
                             "serve/p99_ms": gauge.value})
                stop.wait(0.05)

        smp = threading.Thread(target=sample, daemon=True)
        smp.start()
        offered = base_rate * mult
        # long enough (~2s of traffic) for the p99 gauge (0.5s refresh)
        # to track the shed controller's steady state — a sub-second
        # burst measures only the arming transient and reports a burn
        # that is pure startup noise
        n = int(min(max(offered * 1.5, 600), 40_000))
        r = open_loop(fl, n, offered, prio=priomix)
        stop.set()
        smp.join()
        agg = fl.stats()["aggregate"]
        fl.close()
        over[f"x{mult:g}"] = {
            "offered_qps": r["offered_qps"],
            "achieved_qps": r["achieved_qps"],
            "shed_frac": r["shed"] / r["n"],
            "shed_storms": reg.get("serve/shed_storms").value,
            # p99 of requests actually SERVED — the SLO the fleet holds
            # by degrading bulk traffic, not a claim about shed requests
            "p99_ms": agg.get("p99_ms", 0.0),
            "burn": trk.burns()["serve_p99"]}
        if _deadline_passed():
            out["overload"] = over
            out["budget_truncated"] = True
            return out
    out["overload"] = over

    # -- stage 5: snapshot cadence — delta wire vs disk-poll bytes --------
    workdir = tempfile.mkdtemp(prefix="wh_bench_fleet_")
    ckpt = Checkpointer(workdir, is_writer=True)
    K = 10
    fl = make_fleet(2, full_every=8)
    version = 0
    try:
        for _ in range(K):
            train_tick()
            train_tick()
            version += 1
            fl.publish(serve_params(), version)
            deadline = time.perf_counter() + 30
            while (any(v < version for v in fl.versions())
                   and time.perf_counter() < deadline):
                time.sleep(0.005)
        snap = dict(fl.stats()["snapshot"])
    finally:
        fl.close()
    # what ONE disk-poll replica reads per version on the same cadence
    ckpt.save(version, store.state_pytree())
    ckpt_bytes = os.path.getsize(
        os.path.join(workdir, f"ckpt_v{version}.msgpack"))
    out["snapshot"] = {
        "versions": K,
        "full_frames": snap["full_frames"],
        "delta_frames": snap["delta_frames"],
        "bytes_raw": snap["bytes_raw"],
        "bytes_wire": snap["bytes_wire"],
        "chain_wire_ratio": snap["wire_ratio"],
        "full_ckpt_bytes": ckpt_bytes,
        "wire_bytes_per_version": snap["bytes_wire"] / K,
        "cadence_ratio": ckpt_bytes * K / max(snap["bytes_wire"], 1)}
    for fn in os.listdir(workdir):
        try:
            os.remove(os.path.join(workdir, fn))
        except OSError:
            pass
    try:
        os.rmdir(workdir)
    except OSError:
        pass
    return out


def bench_chaos() -> dict:
    """Elastic recovery drill (wormhole_tpu/ft): SIGKILL one of 4 mp
    ranks mid-epoch via the deterministic chaos injector, let the
    supervised launcher detect the death, drain the survivors through a
    block-boundary checkpoint, and relaunch — once shrunk to 3 ranks
    (``--ft-elastic shrink``) and once at the original world
    (``fixed``). Reported per scenario: wall time, relaunch count, the
    per-attempt world read back from the attempt-scoped heartbeat dirs,
    and the recovered final validation objv vs an undisturbed baseline
    run (the recovery-quality number docs/fault_tolerance.md budgets;
    tolerance rationale lives there too)."""
    import re
    import subprocess
    import sys
    import textwrap
    from wormhole_tpu.obs import read_heartbeats

    repo = os.path.dirname(os.path.abspath(__file__))
    workdir = tempfile.mkdtemp(prefix="wh_bench_chaos_")
    rng = np.random.default_rng(17)
    dim = 64
    for k in range(2):                       # 2 files x 400 planted rows
        lines = []
        for _ in range(400):
            y = rng.random() < 0.5
            feats = sorted(rng.choice(np.arange(2, dim), size=6,
                                      replace=False))
            toks = [f"{0 if y else 1}:1"] + [f"{j}:1" for j in feats]
            lines.append(f"{int(y)} " + " ".join(toks))
        with open(os.path.join(workdir, f"part{k}.libsvm"), "w") as f:
            f.write("\n".join(lines) + "\n")
    pattern = os.path.join(workdir, "part*.libsvm")
    cfg_common = ["data_format=libsvm", "num_buckets=4096",
                  "minibatch=100", "max_nnz=16", "key_pad=256",
                  "lr_eta=0.5", "max_delay=1", "disp_itv=1e12",
                  f"train_data={pattern}", "num_parts_per_file=4",
                  "max_data_pass=3"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}

    def launch(name, extra_cfg, flags, timeout=420):
        script = os.path.join(workdir, f"body_{name}.py")
        with open(script, "w") as f:
            f.write(textwrap.dedent(f"""
                from wormhole_tpu.learners.async_sgd import AsyncSGD
                from wormhole_tpu.utils.config import load_config
                from wormhole_tpu.ft import supervisor as ft
                cfg = load_config(None, {cfg_common + extra_cfg!r})
                app = AsyncSGD(cfg)
                app.run()
                if not ft.drain_requested():
                    pooled = []
                    vp = app._multihost_pass(cfg.train_data, "val",
                                             pooled)
                    objv = vp.objv / max(vp.num_ex, 1)
                    print(f"OK rank {{app.rt.rank}} objv={{objv:.6f}}")
            """))
        t0 = time.perf_counter()
        # CPU simulation: `--cluster mp` pins every rank to
        # JAX_PLATFORMS=cpu (launcher._spawn), so no child asks for the
        # chip this process may hold; the record is stamped _NO_DEVICE
        r = subprocess.run(
            [sys.executable, "-m", "wormhole_tpu.parallel.launcher",
             "-n", "4", "--cluster", "mp", *flags, "--",
             sys.executable, script],
            capture_output=True, text=True, timeout=timeout, cwd=repo,
            env=env)
        return r, time.perf_counter() - t0

    def attempts_report(hb_dir) -> list:
        """One row per launch attempt, from the attempt-scoped
        heartbeat dirs (attempt 0 writes the base dir itself)."""
        ks = [0]
        if os.path.isdir(hb_dir):
            ks += sorted(int(m.group(1)) for m in
                         (re.match(r"^attempt(\d+)$", n)
                          for n in os.listdir(hb_dir)) if m)
        rows = []
        for k in ks:
            d = hb_dir if k == 0 else os.path.join(hb_dir, f"attempt{k}")
            ranks = sorted(read_heartbeats(d)) if os.path.isdir(d) else []
            if ranks or k == 0:
                rows.append({"attempt": k, "world": len(ranks),
                             "ranks": ranks})
        return rows

    def final_objv(stdout) -> float:
        vals = re.findall(r"OK rank \d+ objv=([0-9.]+)", stdout)
        if not vals:
            raise RuntimeError("no final objv line in worker output")
        return float(vals[-1])      # global metric: identical per rank

    # -- undisturbed baseline ---------------------------------------------
    r, base_wall = launch("baseline",
                          [f"checkpoint_dir={workdir}/ckpt_base"], ())
    if r.returncode != 0:
        if "Multiprocess computations aren't" in r.stdout + r.stderr:
            return {"skipped": "jax CPU backend lacks multiprocess "
                               "collectives in this environment"}
        raise RuntimeError(
            f"baseline mp run failed rc={r.returncode}: "
            f"{(r.stderr or r.stdout)[-800:]}")
    base = final_objv(r.stdout)
    out = {"world": 4, "kill": {"rank": 1, "block": 3},
           "tol_rel": 0.25,
           "baseline": {"objv": round(base, 6),
                        "wall_s": round(base_wall, 1)}}

    # -- kill drills: shrink and fixed relaunch ---------------------------
    for mode in ("shrink", "fixed"):
        if _deadline_passed():
            out["budget_truncated"] = True
            break
        hb_dir = os.path.join(workdir, f"hb_{mode}")
        r, wall = launch(
            mode,
            [f"checkpoint_dir={workdir}/ckpt_{mode}",
             "chaos_kill_rank=1", "chaos_kill_block=3"],
            ("--restarts", "2", "--ft-dead-after", "30",
             "--ft-elastic", mode, "--comm-timeout", "8",
             "--heartbeat-dir", hb_dir))
        row = {"wall_s": round(wall, 1), "rc": r.returncode,
               "relaunches": r.stderr.count("supervised relaunch"),
               "attempts": attempts_report(hb_dir)}
        if r.returncode == 0:
            objv = final_objv(r.stdout)
            row["objv"] = round(objv, 6)
            row["objv_delta_rel"] = round(
                abs(objv - base) / max(abs(base), 1e-9), 4)
            row["within_tol"] = row["objv_delta_rel"] <= out["tol_rel"]
        else:
            row["error"] = (r.stderr or r.stdout)[-400:]
        out[mode] = row
    return out


def bench_rejoin() -> dict:
    """Live-rejoin drill (wormhole_tpu/ft/drill.py): kill one of 3
    in-process ranks mid-pass while an open-loop serve client runs
    against a hot-swapped snapshot, detect via heartbeat silence,
    re-queue only the dead rank's shards, and admit a rejoiner through
    the version-vector handshake + bounded delta replay — survivors
    never restart. Reported: serve p99 THROUGH the cycle
    (``rejoin_p99_ms``, gated like the serve phase's tails), recovery
    debt (detection → admission, ``recovery_debt_s`` — absolute ceiling
    in scripts/bench_check.py), replayed window count, and final objv
    vs an undisturbed baseline drill."""
    from wormhole_tpu.ft.drill import run_rejoin_drill

    workdir = tempfile.mkdtemp(prefix="wh_bench_rejoin_")
    base = run_rejoin_drill(os.path.join(workdir, "base"), kill=None)
    out = {"tol_rel": 0.25,
           "baseline": {"objv": round(base["objv"], 6),
                        "wall_s": base["wall_s"],
                        "windows": base["windows"],
                        "serve_p99_ms": round(
                            base["serve"]["p99_ms"], 2)}}
    if _deadline_passed():
        out["budget_truncated"] = True
        return out
    rec = run_rejoin_drill(os.path.join(workdir, "kill"))
    rj = rec.get("rejoin") or {}
    objv = rec["objv"]
    out.update({
        "world": rec["world"],
        "windows": rec["windows"],
        "detect_s": (rec.get("kill") or {}).get("detect_s"),
        "threads_per_rank": rec["threads_per_rank"],
        # serve tail THROUGH kill->detect->replay->admit; the _LAT_PAT
        # suffix puts it under bench_check's latency gate automatically
        "rejoin_p99_ms": round(rec["serve"]["p99_ms"], 2),
        "serve_requests": rec["serve"]["requests"],
        "snapshot_swaps": rec["serve"]["swaps"],
        "recovery_debt_s": rj.get("recovery_debt_s"),
        "replayed_windows": rj.get("replayed"),
        "replay_depth": rec["replay_depth"],
        "handshake_s": rj.get("handshake_s"),
        "join_idx": rj.get("join_idx"),
        "membership_epoch": rj.get("epoch"),
        "admitted_within_bound": rj.get("admitted_within_bound"),
        "slots_rel_err": rj.get("slots_rel_err"),
        "objv": round(objv, 6),
        "objv_delta_rel": round(
            abs(objv - base["objv"]) / max(abs(base["objv"]), 1e-9), 4),
        "wall_s": rec["wall_s"],
    })
    out["within_tol"] = out["objv_delta_rel"] <= out["tol_rel"]
    return out


MULTICHIP_ROWS = 163_840     # 10 blocks x 16384 rows (subblocks=2)
MULTICHIP_WINDOW = 6.0       # timed window per shape


def _mc_app(path: str, shape: str, n_dev: int):
    """One app per mesh shape (each store instance owns its jit
    closures)."""
    import jax
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    rt = MeshRuntime.create()
    rt.mesh = make_mesh(shape, jax.devices()[:n_dev])
    cfg = Config(train_data=path, data_format="crec2",
                 num_buckets=NUM_BUCKETS, max_delay=MAX_DELAY,
                 lr_eta=0.1, disp_itv=1e12)
    cfg.lambda_ = [1.0, 0.1]
    return AsyncSGD(cfg, rt)


def _mc_timed(app, path: str, mesh: bool) -> dict:
    """One timed segment on a warmed app: stream passes until
    the window closes. The rate is rows/elapsed with the deferred-metric
    flush and a forced D2H read inside the clock (same honesty rules as
    the e2e phases); the mesh feed telemetry is read back as registry
    deltas because the registry is process-global across segments."""
    import jax
    from wormhole_tpu.obs.metrics import mesh_feed_gauges
    gauges = mesh_feed_gauges(app.obs.registry)
    gauges[0].value = 0.0                # skew mean: set per process()
    gauges[1].value = 0.0                # skew max (agg=max): reset
    c0 = [g.value for g in gauges]       # counters: delta per segment
    t0 = time.perf_counter()
    rows = 0
    passes = 0
    while True:
        prog = app.process(path, 0, 1)
        rows += prog.num_ex
        passes += 1
        if passes >= 1 and (time.perf_counter() - t0 >= MULTICHIP_WINDOW
                            or _deadline_passed()):
            break
    rows += app.flush_metrics().num_ex
    jax.block_until_ready(app.store.slots)
    float(np.asarray(app.store.slots[0, 0]))
    rec = {"ex_per_sec": rows / (time.perf_counter() - t0),
           "passes": passes}
    if mesh:
        rec.update({
            "dispatch_skew_ms": round(gauges[0].value, 3),
            "dispatch_skew_ms_max": round(gauges[1].value, 3),
            "feed_groups": int(gauges[2].value - c0[2]),
            "pad_blocks": int(gauges[3].value - c0[3]),
        })
    wire = app.obs.registry.get("comm/bytes_wire")
    rec["comm_bytes_wire"] = int(wire.value) if wire else 0
    return rec


def _mc_warm(app, path: str) -> None:
    import jax
    app.process(path, 0, 1)              # compile + ramp
    jax.block_until_ready(app.store.slots)
    float(np.asarray(app.store.slots[0, 0]))
    app.flush_metrics()


def _bench_multichip_inline() -> dict:
    """Mesh scale-out sweep over the local devices: for each mesh shape
    (pure data-parallel, then data x model splits) run the mesh pass
    (sharded DeviceFeed: the transfer ring hands each chip its slice of
    a D-group on its (data, model) NamedSharding so H2D overlaps the
    mesh step) over the SAME crec2 rows. Reports per-shape ex/s,
    speedup and scaling efficiency vs a single-chip anchor (the
    single-device process() path on devices[0]), per-group dispatch-skew
    straggler telemetry, and comm/bytes_wire (0 in single-process runs
    — reported, not invented). The file uses subblocks=2 blocks (16384
    rows) so a D-wide group is a fine dispatch unit, and is sized so a
    full single-device pass fits the window even on a core-starved fake
    CPU mesh (each fake device gets a slice of the host). On a fake CPU
    mesh the devices
    share host cores, so scaling_efficiency ~ 1/n is expected — the
    gates in scripts/bench_check.py are calibrated against the measured
    trajectory, not an ideal-scaling fantasy."""
    import jax
    n = len(jax.devices())
    workdir = tempfile.mkdtemp(prefix="wh_bench_mc_")
    path = os.path.join(workdir, "mc.crec2")
    rng = np.random.default_rng(7)
    write_crec2(path, MULTICHIP_ROWS, rng, subblocks=2)
    out = {"n_devices": n, "rows": MULTICHIP_ROWS,
           "window_sec": MULTICHIP_WINDOW}
    try:
        app0 = _mc_app(path, "data:1", 1)
        _mc_warm(app0, path)
        anchor = _mc_timed(app0, path, mesh=False)
        del app0
        rate0 = anchor["ex_per_sec"]
        out["anchor_ex_per_sec"] = round(rate0, 1)
        out["anchor_passes"] = anchor["passes"]
        print(f"[bench] multichip anchor data:1 {rate0:,.0f} ex/s",
              file=sys.stderr, flush=True)
        shapes = [(f"data:{n}", n)]
        if n >= 4 and n % 2 == 0:
            shapes.append((f"data:{n // 2},model:2", n))
        if n >= 8 and n % 4 == 0:
            shapes.append((f"data:{n // 4},model:4", n))
        out["shapes"] = {}
        for shape, nd in shapes:
            if _deadline_passed():
                out["budget_truncated"] = True
                break
            app = _mc_app(path, shape, nd)
            _mc_warm(app, path)
            ring = _mc_timed(app, path, mesh=True)
            del app
            print(f"[bench] multichip {shape} ring "
                  f"{ring['ex_per_sec']:,.0f} ex/s",
                  file=sys.stderr, flush=True)
            rec = {"ring_ex_per_sec": round(ring["ex_per_sec"], 1),
                   "speedup_vs_anchor": round(
                       ring["ex_per_sec"] / max(rate0, 1e-9), 3),
                   "scaling_efficiency": round(
                       ring["ex_per_sec"] / max(rate0 * nd, 1e-9), 4)}
            for k in ("passes", "dispatch_skew_ms", "dispatch_skew_ms_max",
                      "feed_groups", "pad_blocks", "comm_bytes_wire"):
                rec[k] = ring[k]
            out["shapes"][shape] = rec
    finally:
        try:
            os.remove(path)
            os.rmdir(workdir)
        except OSError:
            pass
    return out


def _needs_devices(n: int):
    """None when this process sees at least ``n`` devices, else the
    record a mesh phase returns in place of numbers. A phase never
    re-runs itself in a child on forced CPU devices: on a chip machine
    that would put CPU numbers inside a summary whose ``device_kind`` is
    the chip's, and a child that wanted the chip could not have it."""
    import jax
    have = len(jax.devices())
    if have >= n:
        return None
    return {"skipped": f"needs {n} devices, this process has {have}"}


def bench_multichip() -> dict:
    """Sharded multichip scale-out (tentpole of the mesh-feed PR): the
    shape sweep over this process's devices, so the mesh
    feed, NamedSharding device_put and shard_map step span real
    devices. With one device it reports that it needs more."""
    return _needs_devices(2) or _bench_multichip_inline()


def _bench_hierarchy_inline() -> dict:
    """The measured hierarchy sweep; needs >= 8 devices in-process."""
    import threading
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from wormhole_tpu.parallel.mesh import shard_map_compat
    from wormhole_tpu.obs.metrics import default_registry
    from wormhole_tpu.parallel.filters import FilterChain
    from wormhole_tpu.parallel.transport import (
        BusWire, HierarchicalTransport, MeshTransport, SimBus,
        TransportStack, ici_ring_bytes)
    from wormhole_tpu.ps import ExchangeEngine

    devs = jax.devices()
    nb = 1 << 14          # bucket-space delta width (f32)
    windows = 40
    rows_per_window = 4096  # notional examples folded into one delta
    lr = 0.05
    out = {"buckets": nb, "windows": windows,
           "rows_per_window_per_host": rows_per_window,
           "devices": len(devs)}

    def parse_shape(s):
        pairs = [tok.split(":") for tok in s.split(",")]
        return [(name, int(n)) for name, n in pairs]

    configs = []
    for hosts, shape_s in ((2, "data:2,model:2"), (2, "data:4"),
                           (4, "data:2")):
        axes = parse_shape(shape_s)
        per = int(np.prod([n for _, n in axes]))
        if hosts * per <= len(devs):
            configs.append((hosts, shape_s, axes, per))
    if not configs:
        raise RuntimeError(
            f"hierarchy needs >= 8 devices in-process, have {len(devs)}")

    ici_counter = default_registry().counter(
        "comm/bytes_ici",
        help="in-mesh collective payload bytes moved over ICI "
             "(modeled from the dispatched step's psum shapes)")

    for hosts, shape_s, axes, per in configs:
        tok = "".join(f"{name[0]}{n}" for name, n in axes)
        names = tuple(name for name, _ in axes)
        d = dict(axes).get("data", 1)
        m = dict(axes).get("model", 1)
        # per-participant ring cost of the step's two psums of the
        # (nb,) f32 delta — the modeled ICI leg, distinct from the
        # measured wire leg below
        ici_b = ici_ring_bytes(4 * nb, d) + ici_ring_bytes(4 * nb, m)

        # one tiny-but-real mesh step per host: each device folds its
        # own data shard into a bucket-space gradient and the psums
        # reduce it to the host-level delta inside the compiled step
        meshes = [Mesh(np.asarray(devs[h * per:(h + 1) * per])
                       .reshape([n for _, n in axes]), names)
                  for h in range(hosts)]

        def make_step(mesh):
            def step(w, x):
                # nonzero at w=0 so the deltas actually evolve (an
                # all-zero delta would reduce to cache hits on the wire)
                g = jnp.tanh(x[0] * (1.0 + w)) / (d * m)
                for ax in names:
                    g = jax.lax.psum(g, ax)
                return g
            return jax.jit(shard_map_compat(
                step, mesh, in_specs=(P(), P(names[0])), out_specs=P()))

        steps = [make_step(mesh) for mesh in meshes]
        rng = np.random.default_rng(11)
        host_x = [rng.standard_normal((d, nb)).astype(np.float32)
                  for _ in range(hosts)]
        # warm the compile cache outside the timed region
        for h in range(hosts):
            np.asarray(steps[h](np.zeros(nb, np.float32), host_x[h]))

        for tau in (0, 1):
            bus = SimBus(hosts)
            chains = [FilterChain(filters={"key_caching", "fixing_float",
                                           "compressing"}, quant_bits=8,
                                  min_bytes=0) for _ in range(hosts)]
            txs = [HierarchicalTransport(
                       MeshTransport(site="mesh/step"),
                       TransportStack(wire=BusWire(bus, h),
                                      chain=chains[h]),
                       engine=ExchangeEngine(tau))
                   for h in range(hosts)]
            applied = [0] * hosts
            errs = []

            def run_host(h):
                try:
                    w = np.zeros(nb, np.float32)
                    tx = txs[h]
                    for _ in range(windows):
                        delta = tx.local_dispatch(
                            steps[h], w, host_x[h], ici_bytes=ici_b)
                        tx.submit_delta(np.asarray(delta))
                        for tk in tx.gate():
                            w = w - lr * np.asarray(tk.result)
                            applied[h] += 1
                    for tk in tx.quiesce():
                        w = w - lr * np.asarray(tk.result)
                        applied[h] += 1
                except Exception as e:   # surfaced below, not swallowed
                    errs.append(f"host{h}: {e!r}")

            ici0 = ici_counter.value
            t0 = time.perf_counter()
            threads = [threading.Thread(target=run_host, args=(h,),
                                        daemon=True)
                       for h in range(hosts)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            for tx in txs:
                tx.stop()
            if errs:
                raise RuntimeError("; ".join(errs))
            assert applied == [windows] * hosts
            raw = sum(c.stats["bytes_raw"] for c in chains)
            wire = sum(c.stats["bytes_wire"] for c in chains)
            assert wire > 0, "cross-host leg moved no measured bytes"
            k = f"h{hosts}_{tok}_tau{tau}"
            out[f"{k}_ex_per_sec"] = round(
                windows * rows_per_window * hosts / wall, 1)
            out[f"{k}_wall_s"] = round(wall, 3)
            out[f"{k}_bytes_raw"] = raw
            out[f"{k}_bytes_wire"] = wire
            out[f"{k}_wire_ratio"] = round(raw / max(wire, 1), 2)
            out[f"{k}_bytes_ici"] = int(ici_counter.value - ici0)
            if _deadline_passed():
                out["budget_truncated"] = True
                return out
        base = out.get(f"h{hosts}_{tok}_tau0_ex_per_sec")
        ov = out.get(f"h{hosts}_{tok}_tau1_ex_per_sec")
        if base and ov:
            out[f"h{hosts}_{tok}_tau1_vs_tau0"] = round(ov / base, 3)
    return out


def bench_hierarchy() -> dict:
    """2D hierarchical exchange (tentpole of the unified-transport PR):
    H simulated hosts, each an ICI ``(data, model)`` mesh whose step
    psums the bucket-space delta intra-host, exchanging only host-level
    deltas cross-host through each host's own quant8+zlib FilterChain
    over an in-process SimBus — real encoded bytes, measured (not
    modeled) on the wire leg; the ICI leg is the modeled
    ``comm/bytes_ici`` ring cost. Sweeps hosts x mesh-shape x tau over
    eight devices of this process; with fewer it reports that it needs
    them."""
    return _needs_devices(8) or _bench_hierarchy_inline()


def _socket_delta_program(wire, spec: dict) -> dict:
    """The measured exchange program, IDENTICAL for the socket children
    and the in-process SimBus baseline: seeded per-rank delta windows
    allreduced at site ``hier/delta`` (quant8+zlib via the FilterChain),
    then root-fanned snapshot broadcasts at site ``serve/snapshot``
    (the lossy-gated op="sum" path the serve fleet ships). The sha256
    over every reduced/decoded buffer is the tau=0 parity witness: all
    ranks of both wires must produce the same digest bit-for-bit."""
    import hashlib
    import threading
    from wormhole_tpu.obs import ledger as _ledger
    from wormhole_tpu.obs import trace as _trace
    from wormhole_tpu.parallel.filters import FilterChain
    from wormhole_tpu.parallel.transport import TransportStack

    chain = FilterChain(filters={"key_caching", "fixing_float",
                                 "compressing"},
                        quant_bits=8, min_bytes=0)
    stack = TransportStack(wire=wire, chain=chain)
    rank = wire.rank()
    nb, windows = spec["buckets"], spec["windows"]
    rng = np.random.default_rng(1000 + rank)
    deltas = [rng.standard_normal(nb).astype(np.float32)
              for _ in range(windows)]
    snap_rng = np.random.default_rng(77)
    snaps = [snap_rng.standard_normal(nb).astype(np.float32)
             for _ in range(spec["snapshots"])]
    digest = hashlib.sha256()
    stack.sync("socket_wire_start")
    t0 = time.perf_counter()
    for w in range(windows):
        red = stack.allreduce(deltas[w], op="sum", site="hier/delta")
        digest.update(np.asarray(red).tobytes())
    delta_wall = time.perf_counter() - t0
    d_raw, d_wire = chain.stats["bytes_raw"], chain.stats["bytes_wire"]
    t1 = time.perf_counter()
    for s in snaps:
        got = stack.broadcast(s, root=0, op="sum", site="serve/snapshot")
        digest.update(np.asarray(got).tobytes())
    snap_wall = time.perf_counter() - t1
    stack.sync("socket_wire_end")
    wall = time.perf_counter() - t0
    led = _ledger.build(_trace.events(), wall_s=wall,
                        tid=threading.get_ident())
    return {
        "rank": rank,
        "digest": digest.hexdigest(),
        "delta_wall_s": delta_wall,
        "snap_wall_s": snap_wall,
        "wall_s": wall,
        "delta_bytes_raw": d_raw,
        "delta_bytes_wire": d_wire,
        "snap_bytes_raw": chain.stats["bytes_raw"] - d_raw,
        "snap_bytes_wire": chain.stats["bytes_wire"] - d_wire,
        "collective_wait_s": led["buckets_s"]["collective_wait"],
        "wire_stats": dict(getattr(wire, "stats", {}) or {}),
    }


def _socket_wire_child(spec_path: str) -> None:
    """``bench.py --socket-child <spec.json>``: one rank of the real
    multi-process loopback measurement. Builds a SocketWire from the
    launcher-style env (PROCESS_ID / NUM_PROCESSES / rendezvous dir),
    runs the shared program, and commits ``result_r<rank>.json``.
    Dispatched before argparse/jax so spawn cost stays low."""
    from wormhole_tpu.ft import watchdog as ft_watchdog
    from wormhole_tpu.obs import trace as _trace
    from wormhole_tpu.parallel.socket_wire import SocketWire

    with open(spec_path) as f:
        spec = json.load(f)
    rank = int(os.environ["PROCESS_ID"])
    _trace.enable("", ring=1 << 16)
    # blocking socket reads sit under the same PEER_LOST exit path as a
    # production run: a wedged peer exits this child with 117, and the
    # parent reports the phase failed instead of hanging
    ft_watchdog.configure(spec.get("comm_timeout_s", 120.0))
    wire = SocketWire(outbox_depth=spec.get("outbox_depth", 8),
                      timeout_s=spec.get("comm_timeout_s", 120.0))
    try:
        rec = _socket_delta_program(wire, spec)
    finally:
        wire.close()
    out = os.path.join(spec["dir"], f"result_r{rank}.json")
    tmp = f"{out}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(rec, f)
    os.replace(tmp, out)


def bench_socket_wire() -> dict:
    """Real socket wire (tentpole of the cross-host-exchange PR): spawn
    N loopback processes that mesh over TCP through the file/port
    rendezvous and run seeded delta allreduces + snapshot fan-outs
    through the full FilterChain stack, then replay the IDENTICAL
    program over in-process SimBus threads — the deterministic oracle.
    Reports wire MB/s both ways, the encode/send overlap left by the
    bounded outbox (1 - collective_wait fraction), and the tau=0
    digest parity that makes the socket numbers trustworthy: the first
    ``bytes_wire`` in this repo that crossed a kernel boundary."""
    import subprocess
    import sys
    import threading
    from wormhole_tpu.obs import trace as _trace
    from wormhole_tpu.parallel.transport import BusWire, SimBus

    hosts = 2
    spec = {"buckets": 1 << 16, "windows": 24, "snapshots": 8,
            "outbox_depth": 8, "comm_timeout_s": 120.0}
    workdir = tempfile.mkdtemp(prefix="wh_bench_sock_")
    spec["dir"] = workdir
    spec_path = os.path.join(workdir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    repo = os.path.dirname(os.path.abspath(__file__))
    rdv = os.path.join(workdir, "rdv")
    try:
        procs = []
        for r in range(hosts):
            # host processes: --socket-child returns before bench.py
            # imports JAX, so these never touch a device (JAX_PLATFORMS
            # is pinned anyway); the record is stamped _NO_DEVICE
            env = dict(os.environ)
            env.update({"PROCESS_ID": str(r),
                        "NUM_PROCESSES": str(hosts),
                        "WORMHOLE_WIRE_RENDEZVOUS": rdv,
                        "JAX_PLATFORMS": "cpu",
                        "PYTHONUNBUFFERED": "1"})
            procs.append(subprocess.Popen(
                [sys.executable, os.path.join(repo, "bench.py"),
                 "--socket-child", spec_path],
                cwd=repo, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True))
        errs = []
        for r, p in enumerate(procs):
            try:
                _out, err = p.communicate(timeout=300.0)
            except subprocess.TimeoutExpired:
                p.kill()
                _out, err = p.communicate()
                errs.append(f"rank{r}: timeout")
                continue
            if p.returncode != 0:
                errs.append(f"rank{r}: rc={p.returncode}: {err[-400:]}")
        if errs:
            raise RuntimeError("socket children failed: " +
                               "; ".join(errs))
        sock = []
        for r in range(hosts):
            with open(os.path.join(workdir, f"result_r{r}.json")) as f:
                sock.append(json.load(f))

        # SimBus oracle: same program, same seeds, in-process threads
        if not _trace.enabled():
            _trace.enable("", ring=1 << 16)
        bus = SimBus(hosts)
        sim: list = [None] * hosts
        sim_errs: list = []

        def run_sim(h):
            try:
                sim[h] = _socket_delta_program(BusWire(bus, h), spec)
            except Exception as e:
                sim_errs.append(f"host{h}: {e!r}")

        threads = [threading.Thread(target=run_sim, args=(h,),
                                    daemon=True) for h in range(hosts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if sim_errs:
            raise RuntimeError("; ".join(sim_errs))
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)

    digests = {r["digest"] for r in sock} | {r["digest"] for r in sim}
    if len(digests) != 1:
        raise RuntimeError(
            "socket-vs-sim tau=0 parity BROKEN: "
            f"socket={[r['digest'][:12] for r in sock]} "
            f"sim={[r['digest'][:12] for r in sim]}")

    def mbps(recs, bkey, wkey):
        return (sum(r[bkey] for r in recs)
                / max(max(r[wkey] for r in recs), 1e-9) / 1e6)

    raw = sum(r["delta_bytes_raw"] + r["snap_bytes_raw"] for r in sock)
    wire_b = sum(r["delta_bytes_wire"] + r["snap_bytes_wire"]
                 for r in sock)
    wstats = [r["wire_stats"] for r in sock]
    out = {
        "hosts": hosts,
        "buckets": spec["buckets"],
        "windows": spec["windows"],
        "snapshots": spec["snapshots"],
        "parity_tau0": True,
        # raw (pre-codec) payload throughput of the delta allreduce leg
        "socket_delta_mbps": mbps(sock, "delta_bytes_raw",
                                  "delta_wall_s"),
        "sim_delta_mbps": mbps(sim, "delta_bytes_raw", "delta_wall_s"),
        "socket_snapshot_mbps": mbps(sock, "snap_bytes_raw",
                                     "snap_wall_s"),
        "sim_snapshot_mbps": mbps(sim, "snap_bytes_raw", "snap_wall_s"),
        "bytes_raw": raw,
        "bytes_wire": wire_b,
        "wire_ratio": raw / max(wire_b, 1),
        # encode/send overlap bought by the bounded outbox: the wall
        # fraction NOT spent blocked inside collective spans
        "overlap_frac": 1.0 - (
            sum(r["collective_wait_s"] for r in sock)
            / max(sum(r["wall_s"] for r in sock), 1e-9)),
        "frames_sent": sum(w.get("frames_sent", 0) for w in wstats),
        "coalesced_frames": sum(w.get("coalesced_frames", 0)
                                for w in wstats),
        "sends": sum(w.get("sends", 0) for w in wstats),
        # kernel-level bytes the socket actually moved (headers incl.)
        "bytes_socket_sent": sum(w.get("bytes_sent", 0)
                                 for w in wstats),
    }
    out["socket_over_sim"] = (out["socket_delta_mbps"]
                              / max(out["sim_delta_mbps"], 1e-9))
    return out


# ordered phase registry; headline phases first so a tight budget still
# produces the metric. Phases needing the shared tile stores / the crec2
# file / the text file are tagged so a filtered run only builds what it
# uses.
PHASES = ["e2e_crec2", "device_tile", "e2e_stream", "e2e_text",
          "tile_online", "device_fm", "device_wide_deep",
          "channel_ratios", "tile_fused", "device_sparse",
          "device_dense_apply", "scale_curve", "bigmodel", "multichip",
          "hierarchy", "socket_wire",
          "serve", "serve_fleet", "comm_filters", "async_ps", "kmeans",
          "lbfgs", "gbdt", "chaos", "rejoin"]
_TEXT_PHASES = {"e2e_text", "tile_online"}
_STORE_PHASES = {"device_tile", "device_fm", "device_wide_deep",
                 "channel_ratios"}
_CREC2_PHASES = _STORE_PHASES | {"e2e_crec2", "e2e_stream", "tile_fused"}
# Phases that use no device: host exchange and codec work, and drills
# whose ranks are CPU child processes. They run on any platform and
# main() stamps their record with _NO_DEVICE. Every other phase reports
# a device rate and main() refuses it off the TPU.
_HOST_PHASES = {"comm_filters", "async_ps", "socket_wire", "chaos"}
_NO_DEVICE = "none: host-only phase, no device was used"
_DEFAULT_BUDGET = 840.0  # under the 15-min harness timeout, with margin


def _phase_telemetry(wall_s=None) -> dict:
    """Per-phase telemetry record from the trace ring (span totals,
    stall fractions, the step ledger) plus any straggler flags visible
    in the heartbeat directory. Caller resets the ring between phases
    and passes the measured phase wall time so the ledger buckets have
    a sum target (``wall_s=None`` falls back to the span extent)."""
    from wormhole_tpu.obs import (trace, ledger, read_heartbeats,
                                  StragglerDetector)
    spans = trace.summary()
    stall_s = sum(v["total_s"] for k, v in spans.items()
                  if k.endswith("_stall"))
    busy_s = sum(v["total_s"] for k, v in spans.items()
                 if not k.endswith("_stall"))
    led = ledger.build(trace.events(), wall_s=wall_s)
    ledger.to_registry(led)
    rec = {"spans": spans,
           "stall_sec": round(stall_s, 3),
           "stall_frac": round(stall_s / max(stall_s + busy_s, 1e-9), 4),
           "ledger": led,
           "dropped_spans": trace.dropped()}
    hb_dir = os.environ.get("WORMHOLE_METRICS_EXPORT", "")
    if hb_dir:
        rec["straggler_flags"] = StragglerDetector().check(
            read_heartbeats(hb_dir))
    return rec


def _summarize(results: dict, failed: dict, skipped: list, pending: list,
               kind: str, peak_hbm, peak_mxu, budget: float,
               elapsed: float, telemetry: dict = None) -> dict:
    """Build the summary JSON object from whatever phases have finished
    so far. Called after EVERY phase (not just at exit) so the --out
    file always holds the latest complete snapshot."""
    e2e = results.get("e2e_crec2")
    tile = results.get("device_tile")
    value = e2e["ex_per_sec"] if e2e else None
    extra = {
        "device_kind": kind,
        "host_cores": os.cpu_count(),
        "phases_run": sorted(results),
        "phases_failed": failed,
        "phases_skipped_budget": skipped,
        "phases_pending": pending,
        "budget_sec": budget,
        "elapsed_sec": round(elapsed, 1),
    }
    if e2e:
        extra["e2e_steady_cached"] = {
            k: (round(v, 1) if isinstance(v, float)
                and "dispersion" not in k else v)
            for k, v in e2e.items()}
        extra["e2e_cold_stream_ex_per_sec"] = round(
            e2e["cold_ex_per_sec"], 1)
    if tile:
        if value:
            extra["vs_device_step"] = round(value / tile["ex_per_sec"], 3)
        extra.update({
            "device_step_tile_examples_per_sec": round(
                tile["ex_per_sec"], 1),
            "tile_step_ms": round(tile["step_ms"], 2),
            "tile_block_rows": tile["block_rows"],
            "mxu_tflops": round(tile["mxu_tflops"], 1),
            "mxu_frac": (round(tile["mxu_tflops"] / peak_mxu, 3)
                         if peak_mxu else None),
            "hbm_gbps": round(tile["hbm_gbps"], 1),
            "hbm_peak_gbps": peak_hbm,
        })
    if "device_sparse" in results:
        extra["device_step_sparse_examples_per_sec"] = round(
            results["device_sparse"], 1)
    if "device_dense_apply" in results:
        extra["device_step_dense_apply_examples_per_sec"] = round(
            results["device_dense_apply"], 1)
    if "device_fm" in results:
        extra["device_step_fm_examples_per_sec"] = round(
            results["device_fm"], 1)
    if "device_wide_deep" in results:
        extra["device_step_wide_deep_examples_per_sec"] = round(
            results["device_wide_deep"], 1)
    if "channel_ratios" in results:
        extra["channel_step_ratios_same_window"] = \
            results["channel_ratios"]
    if "tile_fused" in results:
        extra["tile_fused_vs_split"] = results["tile_fused"]
    if "scale_curve" in results:
        extra["scale_curve_tile_step"] = results["scale_curve"]
    if "bigmodel" in results:
        extra["bigmodel"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in results["bigmodel"].items()}
    def _round_serve(v):
        if isinstance(v, dict):
            return {k: _round_serve(x) for k, x in v.items()}
        return round(v, 2) if isinstance(v, float) else v
    if "serve" in results:
        extra["serve"] = _round_serve(results["serve"])
    if "serve_fleet" in results:
        extra["serve_fleet"] = _round_serve(results["serve_fleet"])
    if "chaos" in results:
        extra["chaos_recovery"] = results["chaos"]
    if "rejoin" in results:
        extra["rejoin"] = results["rejoin"]
    if "comm_filters" in results:
        extra["comm_filters"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in results["comm_filters"].items()}
    if "async_ps" in results:
        extra["async_ps"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in results["async_ps"].items()}
    for name, key in (("kmeans", "kmeans_mnist784"),
                      ("lbfgs", "lbfgs_rcv1"),
                      ("gbdt", "gbdt_higgs200k")):
        if name in results:
            extra[key] = {k: (round(v, 4) if isinstance(v, float) else v)
                          for k, v in results[name].items()}
    if "multichip" in results:
        extra["multichip"] = results["multichip"]
    if "hierarchy" in results:
        extra["hierarchy"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in results["hierarchy"].items()}
    if "socket_wire" in results:
        extra["socket_wire"] = {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in results["socket_wire"].items()}
    if "e2e_stream" in results:
        stream = results["e2e_stream"]
        extra["e2e_stream_noncached"] = {
            k: (round(v, 1) if isinstance(v, float)
                and not k.endswith("speedup") else v)
            for k, v in stream.items()}
    if "e2e_text" in results:
        text = results["e2e_text"]
        extra["criteo_text"] = {
            k: (round(v, 1) if isinstance(v, float)
                and not k.endswith("speedup") else v)
            for k, v in text.items()}
    if "tile_online" in results:
        extra["tile_online_text_stream"] = {
            k: (round(v, 1) if isinstance(v, float)
                and k.endswith("ex_per_sec")
                else round(v, 4) if isinstance(v, float) else v)
            for k, v in results["tile_online"].items()}
    if telemetry:
        extra["telemetry"] = telemetry
    return {
        "metric": "end_to_end_examples_per_sec",
        "value": round(value, 1) if value is not None else None,
        "unit": "examples/sec",
        "vs_baseline": (round(value / BASELINE_EX_PER_SEC, 4)
                        if value is not None else None),
        "extra": extra,
    }


def _write_summary(path: str, summary: dict) -> None:
    """Atomic rewrite (tmp file in the same dir + os.replace): readers
    never see a torn file, and a run killed mid-phase leaves the last
    complete snapshot on disk instead of nothing."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def main(argv=None) -> int:
    import argparse
    import sys
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "--socket-child":
        # one rank of the socket_wire phase: handled before argparse
        # (and before the jax import) so the re-exec'd children pay
        # interpreter + numpy startup, not a full bench boot. A host
        # process: it never touches JAX, so it needs no device.
        _socket_wire_child(argv[1])
        return 0
    from wormhole_tpu.parallel.mesh import enable_compile_cache
    enable_compile_cache()
    import jax
    ap = argparse.ArgumentParser(
        description="wormhole-tpu benchmark; prints ONE summary JSON "
                    "line even when the budget truncates the run")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset of: " + ",".join(PHASES))
    ap.add_argument("--budget", type=float, default=_DEFAULT_BUDGET,
                    help="wall-clock budget (sec): phases not yet started "
                         "when it expires are skipped and the summary "
                         "still prints (<=0 disables)")
    ap.add_argument("--out", default="bench_summary.json",
                    help="summary JSON file, atomically rewritten after "
                         "EVERY phase so a killed run still leaves the "
                         "already-measured numbers on disk (empty "
                         "string disables the file; stdout always gets "
                         "the final one-line JSON)")
    ap.add_argument("--telemetry", dest="telemetry", default=True,
                    action="store_true",
                    help="record per-phase span telemetry into the "
                         "summary (ring-only, no extra files; default on)")
    ap.add_argument("--no-telemetry", dest="telemetry",
                    action="store_false")
    ap.add_argument("--trace-path", default="",
                    help="also write the accumulated spans as Chrome "
                         "trace-event JSON (view at ui.perfetto.dev)")
    ap.add_argument("--sample-itv", type=float, default=0.5,
                    help="timeline sampler interval in seconds for the "
                         "per-phase timeline block (obs/timeline.py); "
                         "0 disables the sampler")
    args = ap.parse_args(argv)
    if args.budget > 0:
        # in-phase truncation (between rounds/stages) shares the same
        # clock as the phase-skip check below, minus a margin so a
        # truncated phase still has time to wrap up and checkpoint
        global _DEADLINE
        _DEADLINE = time.perf_counter() + args.budget * 0.92
    sel = [p.strip() for p in args.phases.split(",") if p.strip()] \
        if args.phases else list(PHASES)
    unknown = sorted(set(sel) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; choose from {PHASES}")

    kind = jax.devices()[0].device_kind
    device_phases = [p for p in sel if p not in _HOST_PHASES]
    if device_phases:
        from wormhole_tpu.parallel.mesh import require_tpu
        require_tpu(f"bench.py: phases {device_phases} report device "
                    f"rates (only {sorted(_HOST_PHASES)} are host-only "
                    f"and run anywhere), so this run")
    peak_hbm = HBM_PEAK.get(kind)
    peak_mxu = MXU_PEAK_TF.get(kind)
    if "device_tile" in sel and (peak_hbm is None or peak_mxu is None):
        # the roofline shares of device_tile divide by these
        ap.error(f"device kind {kind!r} is in neither peak table "
                 f"(HBM_PEAK, MXU_PEAK_TF): add it with its source; a "
                 f"missing peak is an error, not a null")

    workdir = tempfile.mkdtemp(prefix="wh_bench_")
    rng = np.random.default_rng(0)
    crec2_path = os.path.join(workdir, "bench.crec2")
    text_path = os.path.join(workdir, "bench.criteo")
    if any(p in _CREC2_PHASES for p in sel):
        write_crec2(crec2_path, E2E_ROWS, rng)
    if any(p in _TEXT_PHASES for p in sel):
        write_criteo_text(text_path, TEXT_ROWS, rng)

    stores_box: dict = {}

    def stores() -> dict:
        # lazily built, shared across the tile phases (one compile per
        # flavor per bench run), dropped after the last phase using them
        if not stores_box:
            stores_box.update(make_tile_stores())
        return stores_box

    runners = {
        "e2e_crec2": lambda: bench_e2e_crec2(crec2_path),
        "device_tile": lambda: bench_device_tile(crec2_path,
                                                 stores()["scalar"]),
        "e2e_stream": lambda: bench_e2e_stream(crec2_path),
        "e2e_text": lambda: bench_e2e_text(text_path),
        "tile_online": lambda: bench_tile_online(text_path),
        "device_fm": lambda: bench_device_fm(crec2_path, stores()["fm"]),
        "device_wide_deep": lambda: bench_device_wide_deep(
            crec2_path, stores()["wd"]),
        "channel_ratios": lambda: bench_channel_ratios(crec2_path,
                                                       stores()),
        "tile_fused": lambda: bench_tile_fused(crec2_path),
        "device_sparse": bench_device_sparse,
        "device_dense_apply": bench_device_dense_apply,
        "scale_curve": lambda: bench_scale_curve(workdir, rng),
        "bigmodel": bench_bigmodel,
        "multichip": bench_multichip,
        "hierarchy": bench_hierarchy,
        "socket_wire": bench_socket_wire,
        "serve": bench_serve,
        "serve_fleet": bench_serve_fleet,
        "comm_filters": bench_comm_filters,
        "async_ps": bench_async_ps,
        "kmeans": bench_kmeans,
        "lbfgs": bench_lbfgs,
        "gbdt": bench_gbdt,
        "chaos": bench_chaos,
        "rejoin": bench_rejoin,
    }

    results: dict = {}
    skipped: list = []
    failed: dict = {}
    telemetry: dict = {}
    trace_events: list = []
    sampler = None
    if args.telemetry:
        # ring-only span recording (no files unless --trace-path); the
        # per-phase summaries land in the --out JSON, which records
        # where the time went, not just how much
        from wormhole_tpu.obs import trace
        trace.enable(args.trace_path, ring=1 << 18)
        if args.sample_itv > 0:
            # rolling-window sampler over the default registry: each
            # phase's samples become a `timeline` block in the summary,
            # with the sampler's own measured cost alongside so the
            # overhead claim is a number, not an assertion
            from wormhole_tpu.obs import TimelineSampler
            sampler = TimelineSampler(interval_s=args.sample_itv,
                                      ring=4096).start()
    bench_t0 = time.perf_counter()
    todo = [p for p in PHASES if p in sel]

    def checkpoint(pending: list) -> None:
        # incremental summary after every phase: a driver timeout that
        # kills the process mid-run can no longer erase measured numbers
        if not args.out:
            return
        summary = _summarize(results, failed, skipped, pending, kind,
                             peak_hbm, peak_mxu, args.budget,
                             time.perf_counter() - bench_t0, telemetry)
        try:
            _write_summary(args.out, summary)
        except OSError as e:
            print(f"[bench] cannot write {args.out}: {e}",
                  file=sys.stderr, flush=True)

    for i, name in enumerate(todo):
        if args.budget > 0 and \
                time.perf_counter() - bench_t0 > args.budget:
            skipped.extend(todo[i:])
            print(f"[bench] budget spent, skipping {todo[i:]}",
                  file=sys.stderr, flush=True)
            break
        print(f"[bench] {name}...", file=sys.stderr, flush=True)
        if sampler is not None:
            sampler.set_phase(name)
            tick_s0 = sampler.tick_s
        t0 = time.perf_counter()
        try:
            results[name] = runners[name]()
            if name in _HOST_PHASES:
                results[name]["device"] = _NO_DEVICE
        except Exception as e:
            # the summary of the other phases still prints and is
            # written, but the run has FAILED: main returns non-zero
            failed[name] = f"{type(e).__name__}: {e}"
            print(f"[bench] {name} FAILED: {failed[name]}",
                  file=sys.stderr, flush=True)
        else:
            print(f"[bench] {name} done in "
                  f"{time.perf_counter() - t0:.0f}s",
                  file=sys.stderr, flush=True)
        if args.telemetry:
            from wormhole_tpu.obs import trace
            phase_sec = time.perf_counter() - t0
            telemetry[name] = _phase_telemetry(wall_s=phase_sec)
            telemetry[name]["phase_sec"] = round(phase_sec, 3)
            if sampler is not None:
                from wormhole_tpu.obs import timeline as _timeline
                tl = _timeline.summarize(
                    [s for s in sampler.samples()
                     if s.get("phase") == name])
                tl["sampler"] = {
                    "interval_s": args.sample_itv,
                    # measured sampler cost as a fraction of phase wall
                    "overhead_frac": round(
                        (sampler.tick_s - tick_s0)
                        / max(phase_sec, 1e-9), 6)}
                telemetry[name]["timeline"] = tl
            if args.trace_path:
                trace_events.extend(trace.events())
            trace.reset()        # each phase gets the whole ring
        checkpoint(todo[i + 1:])
        if stores_box and not any(p in _STORE_PHASES
                                  for p in todo[i + 1:]):
            stores_box.clear()   # free the HBM tables for later phases

    if sampler is not None:
        sampler.stop()
    if args.telemetry and args.trace_path:
        from wormhole_tpu.obs import trace
        trace_events.extend(trace.events())
        try:
            trace.write_trace(args.trace_path, trace_events)
            print(f"[bench] trace written to {args.trace_path} "
                  f"({len(trace_events)} events; view at "
                  "ui.perfetto.dev)", file=sys.stderr, flush=True)
        except OSError as e:
            print(f"[bench] cannot write {args.trace_path}: {e}",
                  file=sys.stderr, flush=True)

    for p in (crec2_path, text_path):
        try:
            os.remove(p)
        except OSError:
            pass

    summary = _summarize(results, failed, skipped, [], kind, peak_hbm,
                         peak_mxu, args.budget,
                         time.perf_counter() - bench_t0, telemetry)
    if args.out:
        try:
            _write_summary(args.out, summary)
        except OSError as e:
            print(f"[bench] cannot write {args.out}: {e}",
                  file=sys.stderr, flush=True)
    print(json.dumps(summary))
    if failed:
        print(f"[bench] FAILED phases: {sorted(failed)}",
              file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
