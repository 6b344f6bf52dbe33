"""Online sharded-SGD driver — the flagship app (reference ``async_sgd``).

Rebuild of the three-role ps-lite program (``learn/linear/sgd/async_sgd.h``):

- the SCHEDULER's pass/workload loop (async_sgd.h:245-348) is ``run()`` +
  the WorkloadPool;
- the WORKER's minibatch pipeline (async_sgd.h:35-165) is ``process()``:
  stream → localize → pad → dispatch the fused device step, with the
  **bounded-staleness window**: at most ``max_delay`` device steps in
  flight, enforced by blocking on the oldest dispatched step's metrics
  (the reference's cond-var WaitMinibatch, async_sgd.h:81,119-142 — here
  JAX's async dispatch IS the pipeline, and ``block_until_ready``
  bookkeeping is the gate);
- the SERVER's handle application (async_sgd.h:171-239) is fused into the
  same jitted step (learners/store.py).

Validation passes use an unbounded window (eval "workloads use effectively
infinite delay", async_sgd.h:60-61). Progress rows print every ``disp_itv``
seconds in the reference's format; ``max_objv`` is the divergence kill
switch (async_sgd.h:316-319).
"""

from __future__ import annotations

import time
from collections import deque
from typing import List, Optional

import jax
import numpy as np

from wormhole_tpu import obs
from wormhole_tpu.obs import flight as obs_flight
from wormhole_tpu.data.feed import next_bucket, nnz_bucket, pad_to_batch
from wormhole_tpu.ft import chaos as ft_chaos
from wormhole_tpu.ft import supervisor as ft_supervisor
from wormhole_tpu.ft import watchdog as ft_watchdog
from wormhole_tpu.data.localizer import Localizer
from wormhole_tpu.data.minibatch import MinibatchIter
from wormhole_tpu.learners.handles import LearnRate, create_handle
from wormhole_tpu.learners.store import ShardedStore, StoreConfig
from wormhole_tpu.learners.window import (MetricAccumulator, MetricWindow,
                                          fold_row, pool_margins)
from wormhole_tpu.ops.penalty import L1L2
from wormhole_tpu.parallel.mesh import DATA_AXIS, MeshRuntime
from wormhole_tpu.sched.workload_pool import (TEST, TRAIN, VAL,
                                              ReplicatedRounds,
                                              WorkloadPool)
from wormhole_tpu.utils.config import Config
from wormhole_tpu.utils.logging import get_logger
from wormhole_tpu.utils.progress import (ModelMonitor, Progress,
                                         TimeReporter, WorkerMonitor)
from wormhole_tpu.utils.timer import Timer

log = get_logger("async_sgd")


class DivergedError(RuntimeError):
    pass


class AsyncSGD:
    """Scheduler+worker in one host process per TPU host."""

    def __init__(self, cfg: Config, runtime: Optional[MeshRuntime] = None,
                 store=None):
        """``store`` may be any object with the ShardedStore step surface
        (train_step/eval_step/nnz_weight/save_model) — the FM and wide&deep
        models plug in here with the same worker/scheduler pipeline."""
        self.cfg = cfg
        self.rt = runtime or MeshRuntime.create(
            cfg.mesh_shape, getattr(cfg, "model_shards", 0))
        if store is None:
            lam = list(cfg.lambda_) + [0.0, 0.0]
            # config.proto:34-39 — L1: λ0·‖w‖₁ + ½λ1·‖w‖²; L2: ½λ0·‖w‖²
            from wormhole_tpu.utils.config import Penalty
            if cfg.penalty == Penalty.L2:
                penalty = L1L2(lambda1=0.0, lambda2=lam[0])
            else:
                penalty = L1L2(lambda1=lam[0], lambda2=lam[1])
            handle = create_handle(cfg.algo.value, penalty,
                                   LearnRate(cfg.lr_eta, cfg.lr_beta))
            store = ShardedStore(
                StoreConfig(num_buckets=cfg.num_buckets,
                            loss=cfg.loss.value,
                            fixed_bytes=cfg.fixed_bytes,
                            lr_theta=cfg.lr_theta,
                            param_dtype=cfg.param_dtype,
                            tile_step_kernel=cfg.tile_step_kernel,
                            tile_onehot_cache=cfg.tile_onehot_cache),
                handle, self.rt)
        elif (buckets := getattr(getattr(store, "cfg", None),
                                 "num_buckets", None)) is not None \
                and buckets != cfg.num_buckets:
            # the Localizer folds keys into cfg.num_buckets; a smaller table
            # would silently clamp gathers/scatters inside jit
            raise ValueError(
                f"store has num_buckets={buckets} but config says "
                f"{cfg.num_buckets}")
        self.store = store
        if cfg.test_data and not cfg.pred_out:
            # fail at construction, not after hours of training
            raise ValueError("test_data set but pred_out empty")
        from wormhole_tpu.utils.config import check_choice
        check_choice("tile_online", cfg.tile_online, ("auto", "on", "off"))
        check_choice("tile_step_kernel", cfg.tile_step_kernel,
                     ("auto", "fused", "split"))
        check_choice("tile_onehot_cache", cfg.tile_onehot_cache,
                     ("auto", "on", "off"))
        self.localizer = Localizer(num_buckets=cfg.num_buckets,
                                   tail_freq=cfg.tail_feature_freq)
        self.pool = WorkloadPool()
        self.start_time = time.time()
        self._prev_num_ex = 0
        self.progress = Progress()
        self._max_nnz = cfg.max_nnz
        self._warned_trunc = False
        # the reference monitor chain (monitor.h + dist_monitor.h): workers
        # accumulate into a WorkerMonitor, a rate-limited TimeReporter
        # drives the scheduler display row, a ModelMonitor tracks nnz(w)
        # and weight-delta norms at pass boundaries
        self.model_monitor = ModelMonitor()
        self.reporter = TimeReporter(self._emit_row, interval=cfg.disp_itv)
        # pipeline stage profile (SURVEY §5.1); a store that times its
        # own table crossings (ShardedStore) shares the one timer
        self.timer = getattr(store, "timer", None) or Timer()
        # DeviceFeed counters (data/pipeline.py): cumulative consumer-side
        # ring stalls, batches delivered, deepest ring occupancy observed
        self.feed_stats = {"feed_stall": 0.0, "feed_batches": 0,
                           "ring_max": 0}
        # the room of the online tile encoder's overflow lists: one for
        # the job, since every pass makes a new feed (data/crec.py)
        from wormhole_tpu.data.crec import HotRoom, OverflowRoom
        self._online_room = OverflowRoom()
        # ... and, for a store whose train step takes it (on one device,
        # or a shard on a mesh), the rule and the room of the lists' hot
        # form (data/crec.HotRoom)
        self._hot_room = (HotRoom() if getattr(store, "hot_overflow", False)
                          else None)
        # the one-device tile TRAIN passes' deferred metric accumulator
        # (learners/window.py): it survives parts; flush_metrics drains it
        self._crec_acc = MetricAccumulator()
        from wormhole_tpu.parallel.checkpoint import Checkpointer
        self.ckpt = Checkpointer(cfg.checkpoint_dir)
        self._warned_ckpt = False
        # pull-only forward for predict() (serve/forward.py), built on
        # demand per predict pass when cfg.serve_predict and the store
        # has the serve surface; None routes TEST through eval_step
        self._predict_forward = None
        # telemetry hub (obs/): trace_path turns span tracing on,
        # metrics_export turns heartbeat/Prometheus files on; both off
        # (the default) leaves every instrumented path at one bool check
        self.obs = obs.setup(cfg, self.rt.rank)
        # communication filter chain (parallel/filters.py): cfg-driven,
        # process-global so every collective below — metric windows,
        # pooled AUC, model broadcast — rides the same chain
        from wormhole_tpu.parallel import filters as comm_filters
        from wormhole_tpu.parallel import transport as comm_transport
        comm_filters.install_from_config(cfg)
        # cross-host wire selection (parallel/socket_wire.py): wire=
        # socket swaps the default stack's host leg onto the TCP wire
        # before anything caches a stack reference; intra-host ICI
        # collectives are untouched
        comm_transport.install_wire_from_config(cfg)
        # fault-tolerance wiring (wormhole_tpu/ft): the collective
        # watchdog turns a hang on a dead peer into a PEER_LOST exit,
        # chaos installs the deterministic fault plan, and the drain
        # handler (active only under a supervised launcher) turns
        # SIGTERM into a block-boundary checkpoint + clean exit
        ft_watchdog.configure(cfg.comm_timeout_s)
        ft_chaos.install_from_config(cfg, self.rt.rank)
        ft_supervisor.install_drain_handler()

    # -- worker data path ---------------------------------------------------

    def _bucket_nnz(self, blk) -> int:
        """Resolve the (monotone) per-batch nnz bucket for ``blk``.

        MUST be called sequentially in stream order — each batch's bucket
        is the max over every block up to and including it, so calling it
        from the pipeline dispatcher (in order, ahead of the pad workers)
        gives bit-exact parity with the serial path. A denser later batch
        grows the bucket (one recompile) up to the 4096-entry cap — rows
        beyond the cap (or beyond a user-set cfg.max_nnz) are positionally
        truncated, loudly."""
        densest = blk.max_row_nnz()
        if not self.cfg.max_nnz:
            self._max_nnz = max(self._max_nnz, nnz_bucket(densest))
        if densest > self._max_nnz and not self._warned_trunc:
            self._warned_trunc = True
            log.warning(
                "row with %d features truncated to max_nnz=%d "
                "(set max_nnz to keep more)", densest, self._max_nnz)
        return self._max_nnz

    def _localize_pad(self, blk, max_nnz: int):
        """localize + pad one block (stateless; safe on a worker thread:
        Localizer.localize only reads config, and the bucket values were
        resolved sequentially by ``_bucket_nnz``)."""
        loc = self.localizer.localize(blk)
        kpad = self.cfg.key_pad or next_bucket(len(loc.uniq_keys), 64)
        return pad_to_batch(loc, self.cfg.minibatch, max_nnz, kpad)

    def _batches(self, file: str, part: int, nparts: int,
                 prefix: str = ""):
        """stream → localize → pad, with shape bucketing for XLA.

        With ``cfg.pipeline_workers > 0`` the stages run as a DeviceFeed
        (localize+pad on a worker pool, device transfer on its own
        thread, a ``pipeline_ring``-deep device-resident ring ahead of
        the compute loop); 0 falls back to the serial in-line path.
        Batch order, shapes and exceptions are identical either way."""
        cfg = self.cfg
        reader = MinibatchIter(file, part, nparts, cfg.data_format,
                               cfg.minibatch)
        if cfg.pipeline_workers > 0:
            yield from self._batches_pipelined(reader, prefix)
            return
        it = iter(reader)
        while True:
            with self.timer.scope(prefix + "parse"):
                blk = next(it, None)
            if blk is None:
                break
            with self.timer.scope(prefix + "localize"):
                loc = self.localizer.localize(blk)
            max_nnz = self._bucket_nnz(blk)
            kpad = (self.cfg.key_pad
                    or next_bucket(len(loc.uniq_keys), 64))
            with self.timer.scope(prefix + "pad"):
                batch = pad_to_batch(loc, cfg.minibatch, max_nnz, kpad)
            yield batch

    def _batches_pipelined(self, reader: MinibatchIter, prefix: str):
        from wormhole_tpu.data.pipeline import DeviceFeed
        cfg = self.cfg
        # multihost assembles HOST numpy batches into one global array
        # (_global_batch); transferring to device here would just force a
        # copy back — keep the identity transfer and let the global
        # assembly place the data
        host_only = jax.process_count() > 1

        def transfer(batch):
            if host_only:
                return batch
            dev = jax.device_put(batch)
            # num_real is a non-pytree attr (pad_to_batch sets it; eval
            # pooling reads it via _real_rows) — device_put drops it
            dev.num_real = getattr(batch, "num_real", None)
            return dev

        feed = DeviceFeed(reader, self._localize_pad,
                          workers=cfg.pipeline_workers,
                          ring_depth=cfg.pipeline_ring,
                          seq_ctx=self._bucket_nnz,
                          transfer=transfer,
                          bytes_read=reader.bytes_read,
                          name=(prefix or "train").rstrip("_"))
        try:
            yield from feed
        finally:
            snap = feed.drain_stats(self.timer, prefix)
            self.feed_stats["feed_stall"] += snap["consume_stall"]
            self.feed_stats["feed_batches"] += snap["batches"]
            self.feed_stats["ring_max"] = max(self.feed_stats["ring_max"],
                                              snap["ring_max"])

    def process(self, file: str, part: int, nparts: int,
                kind: str = TRAIN, pooled: Optional[list] = None) -> Progress:
        """One workload part (AsyncSGDWorker::Process, async_sgd.h:57-127).

        ``pooled``, if given on an eval/predict pass, collects
        ``(margin, label, weight)`` triples of every real row so the caller
        can compute pass-level metrics over the full eval output (the
        reference evaluates AUC over the complete pass, evaluation.h:38-68,
        not a mean of per-minibatch AUCs)."""
        if self.cfg.data_format in ("crec", "crec2") \
                or self._text_dense() or self._tile_online():
            return self._process_crec(file, part, nparts, kind, pooled)
        cfg = self.cfg
        fs0 = dict(self.feed_stats)
        max_delay = cfg.max_delay if kind == TRAIN else 1 << 30
        inflight: deque = deque()
        mon = WorkerMonitor()          # per-part metric accumulation
        local = mon.prog

        def harvest(item) -> None:
            metrics, labels, row_mask = item
            # the psum'd metric buffer flying home — the sparse-path
            # collective boundary, same span name as the crec harvest
            with obs.trace.span("collective:metrics_window",
                                cat="collective",
                                args={"site": "async_sgd/metrics_window"}):
                # host-sync: windowed harvest — gates on a metrics
                # buffer dispatched a full window ago, not this step's
                metrics = jax.block_until_ready(metrics)
            # host-sync: scalars already resolved by the window gate
            objv, num_ex, a, acc = (float(np.asarray(m))
                                    for m in metrics[:4])
            mon.update(int(num_ex), objv, a, acc)
            if kind == TRAIN and len(metrics) > 4:
                # host-sync: scalar already resolved by the window gate
                local.wdelta2 += float(np.asarray(metrics[4]))
            if pooled is not None and len(metrics) > 4:
                # host-sync: margin pooled for AUC after the window gate
                margin = np.asarray(metrics[4])
                keep = row_mask >= 0  # real rows (weight-0 rows included)
                pooled.append((margin[keep], labels[keep], row_mask[keep]))
            if kind == TRAIN:  # eval metrics must not pollute train rows
                self._display(local)

        # delay-tolerant DT2 trains through the SPLIT pull/push pipeline:
        # the pull computes the gradient + snapshot now, the push applies
        # it up to max_delay batches later — real interleaved staleness,
        # which the handle's cross-term corrects (delay_tol_handle.h
        # semantics; the fused step would have no gap to compensate)
        from wormhole_tpu.learners.handles import DT2AdaGradHandle
        use_dt2 = (kind == TRAIN
                   and isinstance(getattr(self.store, "handle", None),
                                  DT2AdaGradHandle)
                   and hasattr(self.store, "dt2_pull"))
        if use_dt2:
            pfx = ""
            for batch in self._batches(file, part, nparts, pfx):
                with self.timer.scope("dispatch"):
                    grad, snap, metrics = self.store.dt2_pull(batch)
                    inflight.append((batch, grad, snap, metrics))
                with self.timer.scope("wait"):
                    while len(inflight) > max(max_delay - 1, 0):
                        b, g, s, m = inflight.popleft()
                        self.store.dt2_push(b, g, s)
                        harvest((m, None, None))
            with self.timer.scope("wait"):
                while inflight:
                    b, g, s, m = inflight.popleft()
                    self.store.dt2_push(b, g, s)
                    harvest((m, None, None))
            self._merge_feed_progress(local, fs0)
            return local

        # eval records under its own prefix so the training pipeline
        # profile (the thing SURVEY §5.1 wants) stays unskewed
        pfx = "" if kind == TRAIN else "eval_"
        for batch in self._batches(file, part, nparts, pfx):
            # WaitMinibatch gate BEFORE dispatch (the reference parses the
            # next minibatch while steps are in flight, then waits,
            # async_sgd.h:81,119-142): after dispatch at most
            # max(max_delay, 1) device steps exist — max_delay=0 means no
            # two device steps ever overlap (host parse still pipelines,
            # matching the reference's WaitMinibatch placement).
            with self.timer.scope(pfx + "wait"):
                while len(inflight) > max(max_delay - 1, 0):
                    harvest(inflight.popleft())
            with self.timer.scope(pfx + "dispatch"):
                if kind == TRAIN:
                    m = self.store.train_step(batch,
                                              tau=float(len(inflight)))
                    inflight.append((m, None, None))
                elif kind == TEST and self._predict_forward is not None:
                    # offline predict rides the online serving forward
                    # (serve/forward.py): same pull-only margin function
                    # the serving tier compiles, exercised on every
                    # batch-predict run. Eval metrics are meaningless on
                    # unlabeled TEST data, so only the margin is real;
                    # eval_step remains the metrics oracle for VAL.
                    margin = self._predict_forward.margins(batch)
                    keep = self._real_rows(batch)
                    m = (0.0, float((keep >= 0).sum()), 0.5, 0.0, margin)
                    # host-sync: labels live on host already — no-op copy
                    inflight.append((m, np.asarray(batch.labels), keep))
                else:
                    m = self.store.eval_step(batch)
                    keep = self._real_rows(batch)
                    # host-sync: labels live on host already — no-op copy
                    inflight.append((m, np.asarray(batch.labels), keep))
        with self.timer.scope(pfx + "wait"):       # WaitMinibatch(0)
            while inflight:
                harvest(inflight.popleft())
        self._merge_feed_progress(local, fs0)
        return local

    def _merge_feed_progress(self, local: Progress, before: dict) -> None:
        """Fold this part's DeviceFeed counter deltas into its Progress
        row, so feed stalls merge/report like every other metric."""
        fs = self.feed_stats
        local.feed_stall += fs["feed_stall"] - before["feed_stall"]
        local.feed_batches += fs["feed_batches"] - before["feed_batches"]

    def _merge_pipe_snap(self, snap: Optional[dict], pfx: str,
                         local: Optional[Progress] = None) -> None:
        """Fold a packed feed's pipeline counters (PackedFeed
        .drain_pipe_stats) into the stage timer / Progress row. ``put``
        is excluded — the feed's own put_time accounting already covers
        the transfer stage on this path."""
        if not snap:
            return
        n = max(snap["batches"], 1)
        self.timer.add(pfx + "read", snap["prep"], n)
        self.timer.add(pfx + "feed_stall", snap["consume_stall"], n)
        self.timer.add(pfx + "read_stall", snap["prep_stall"], n)
        self.timer.add(pfx + "put_stall", snap["put_stall"], n)
        # the text feed's serial stages (data/crec.TextCRecFeed): the
        # reader's busy seconds and the re-blocking on its transfer thread
        for k in ("text_read", "collate"):
            if snap.get(k):
                self.timer.add(pfx + k, snap[k], n)
        if "encode" in snap:
            # online tile-encode stage (data/crec.TileOnlineFeed):
            # encode_stall is the in-order transferrer waiting on the
            # encode pool — the "is encoding the bottleneck?" signal
            self.timer.add(pfx + "encode", snap["encode"], n)
            self.timer.add(pfx + "encode_stall", snap["encode_stall"], n)
            stall_c, _ = obs.metrics.encode_counters(self.obs.registry)
            stall_c.inc(snap["encode_stall"])
            # counts, not seconds: the pairs the online encoder put on
            # the blocks' overflow lists, the slots those lists crossed
            # at (the room in force, summed over the blocks whose list
            # holds a pair), how often a block passed the room, and the
            # blocks the native encoder took (0: the numpy one is live)
            self.timer.add(pfx + "online_native_blocks",
                           snap["native_blocks"], n)
            obs.metrics.encode_native_counter(self.obs.registry).inc(
                snap["native_blocks"])
            self.timer.add(pfx + "online_overflow_pairs",
                           snap["overflow_pairs"], n)
            self.timer.add(pfx + "online_overflow_slots",
                           snap["overflow_slots"], n)
            self.timer.add(pfx + "online_room_grown", snap["room_grown"], n)
            pairs_c, room_g, grown_c = obs.metrics.online_overflow_metrics(
                self.obs.registry)
            pairs_c.inc(snap["overflow_pairs"])
            room_g.set(snap["room"])
            grown_c.inc(snap["room_grown"])
        if "stack" in snap:
            # mesh group-assembly stage (data/crec.MeshGroupFeed):
            # stack_stall is the in-order transferrer waiting on the
            # group-stack workers — the "is group assembly the
            # bottleneck?" signal for the sharded mesh feed
            self.timer.add(pfx + "stack", snap["stack"], n)
            self.timer.add(pfx + "stack_stall", snap["stack_stall"], n)
            # counts, not seconds: the slots of the groups' stacked list
            # lanes as they crossed to the chips (every member at the
            # group's widest), and the groups a member of which the stack
            # workers widened (data/crec.widen_overflow)
            self.timer.add(pfx + "mesh_overflow_slots",
                           snap["mesh_overflow_slots"], n)
            self.timer.add(pfx + "mesh_widened_groups",
                           snap["mesh_widened_groups"], n)
            slots_c, widened_c = obs.metrics.mesh_overflow_metrics(
                self.obs.registry)
            slots_c.inc(snap["mesh_overflow_slots"])
            widened_c.inc(snap["mesh_widened_groups"])
        self.feed_stats["feed_stall"] += snap["consume_stall"]
        self.feed_stats["feed_batches"] += snap["batches"]
        self.feed_stats["ring_max"] = max(self.feed_stats["ring_max"],
                                          snap["ring_max"])
        if local is not None:
            local.feed_stall += snap["consume_stall"]
            local.feed_batches += snap["batches"]

    def _text_dense(self) -> bool:
        """True when this text format streams through the dense-apply
        fast path (native chunk -> crec-block assembly; binary-feature
        formats only — libsvm may carry values, so it keeps the sparse
        path)."""
        return (self.cfg.text_dense
                and self.cfg.data_format in ("criteo", "adfea"))

    def _text_nnz(self) -> int:
        if self.cfg.data_format == "criteo":
            return 39
        if not self.cfg.max_nnz:
            raise ValueError("text_dense for adfea needs max_nnz= (the "
                             "fixed crec row width)")
        return self.cfg.max_nnz

    def _online_info(self, fmt: str, file: Optional[str]):
        """Synthetic crec2 geometry for online-encoding this stream
        (data/crec.online_info): crec v1 takes nnz/rows from the file
        header, dense text from config. ``file=None`` is the geometry
        probe used before a file is at hand — admission is bucket-count
        driven, so nominal nnz/rows stand in."""
        from wormhole_tpu.data.crec import online_info, read_header
        from wormhole_tpu.ops.tilemm import RSUB
        cfg = self.cfg
        if fmt == "crec":
            if file is None:
                return online_info(1, RSUB, cfg.num_buckets)
            src = read_header(file)
            return online_info(src.nnz, src.block_rows, cfg.num_buckets)
        return online_info(self._text_nnz(), cfg.text_block_rows,
                           cfg.num_buckets)

    def _tile_online(self, fmt: Optional[str] = None,
                     file: Optional[str] = None) -> bool:
        """Does this stream route through the online tile-encode path
        (cfg.tile_online)? ``auto`` = TPU backend + a store with the
        tile-step surface + single-process + tilemm-admissible geometry
        — the scatter/dense paths stay the oracle and fallback, the
        ``gbdt_hist_kernel`` gating pattern. ``on`` asserts
        admissibility (raises with the reason — the parity-test mode);
        ``off`` never routes. crec2 files are pre-encoded and ignore
        the knob."""
        cfg = self.cfg
        mode = cfg.tile_online
        fmt = fmt or cfg.data_format
        if mode == "off" or fmt == "crec2":
            return False
        why = None
        if fmt not in ("crec", "criteo", "adfea"):
            why = (f"format {fmt!r} is not a binary-feature streaming "
                   "format (crec/criteo/adfea)")
        elif not hasattr(self.store, "tile_train_step"):
            why = (f"store {type(self.store).__name__} has no tile "
                   "step surface")
        elif jax.process_count() > 1:
            why = "multi-process runs keep the scatter/dense paths"
        else:
            try:
                self._online_info(fmt, file).spec
            except ValueError as e:
                why = f"tilemm limits reject the geometry: {e}"
        if why is not None:
            if mode == "on":
                raise ValueError(f"tile_online=on but {why}")
            return False
        return mode == "on" or jax.default_backend() == "tpu"

    def _make_feed(self, file: str, part: int, nparts: int, fmt: str,
                   device_put=None, cache: bool = False, tile_info=None,
                   hot=None):
        from wormhole_tpu.data.crec import (PackedFeed, TextCRecFeed,
                                            TileOnlineFeed)
        workers = self.cfg.pipeline_workers
        depth = max(self.cfg.pipeline_ring, 3 if workers == 0 else 1)
        if device_put is None and (fmt == "crec2" or tile_info is not None):
            # a store that has a say in what of a tile block crosses to
            # the device ships it itself (ShardedStore.put_block)
            device_put = getattr(self.store, "put_block", None)
        if tile_info is not None and fmt != "crec2":
            # online tile encoding: the v1/text source feed keeps its
            # packed blocks on host (identity put) and the TileOnlineFeed
            # workers fold+tile-group them before the device transfer
            inner = self._make_feed(file, part, nparts, fmt,
                                    device_put=lambda x: x)
            return TileOnlineFeed(inner, tile_info, workers=workers,
                                  depth=depth, device_put=device_put,
                                  cache=cache, room=self._online_room,
                                  hot=hot)
        if fmt in ("crec", "crec2"):
            return PackedFeed(file, part, nparts, fmt=fmt, cache=cache,
                              device_put=device_put, workers=workers,
                              depth=depth, hot=hot)
        return TextCRecFeed(file, part, nparts, text_fmt=fmt,
                            nnz=self._text_nnz(),
                            block_rows=self.cfg.text_block_rows,
                            cache=cache, device_put=device_put,
                            workers=workers, depth=depth)

    def _feed(self, file: str, part: int, nparts: int, fmt: str,
              tile_info=None, hot=None):
        """Feed per (file, part), kept across data passes so cache_device
        replays HBM-resident blocks instead of re-streaming over the host
        interconnect. ``hot``: the HotRoom of a one-device train pass,
        whose blocks carry their overflow lists' hot form."""
        if not self.cfg.cache_device:
            return self._make_feed(file, part, nparts, fmt,
                                   tile_info=tile_info, hot=hot)
        key = (file, part, nparts, fmt, tile_info is not None,
               hot is not None)
        feed = self._feeds.get(key) if hasattr(self, "_feeds") else None
        if feed is None:
            feed = self._make_feed(file, part, nparts, fmt, cache=True,
                                   tile_info=tile_info, hot=hot)
            if not hasattr(self, "_feeds"):
                self._feeds = {}
            self._feeds[key] = feed
        return feed

    # deferred-window geometry: crec2-train metrics accumulate in ONE
    # on-device buffer; this caps how many steps dispatch between
    # accumulator fetches so the host can't run unboundedly ahead of the
    # device (each fetch is one async ticket, resolved a window later)
    CREC_DRAIN_CHUNK = 64   # max steps dispatched ahead of a metric fetch

    @property
    def _crec_hist(self) -> list:
        """The pass-level AUC histograms of the app's accumulator; a
        caller that ends a pass itself assigns fresh ones, as ``run``
        does."""
        return self._crec_acc.hist

    @_crec_hist.setter
    def _crec_hist(self, hist: list) -> None:
        self._crec_acc.hist = hist

    def flush_metrics(self) -> Progress:
        """Drain any deferred crec2 metrics; returns the tail Progress
        (callers merge it into their running totals)."""
        tail = Progress()
        with obs.trace.span("pass:flush", cat="pass"):
            MetricWindow(self, tail, TRAIN, None,
                         acc=self._crec_acc).drain()
        return tail

    def _process_crec(self, file: str, part: int, nparts: int,
                      kind: str, pooled: Optional[list]) -> Progress:
        """The crec/crec2 streaming fast path: packed block bytes go
        straight to the device (PackedFeed prefetch thread overlaps
        transfer with dispatch) — the host does no per-row work at all
        (SURVEY §7 hard part (d)).

        crec blocks run the fused dense-apply step (on-device key fold +
        scatter); crec2 blocks run the tile-blocked MXU step
        (ops/tilemm) whose AUC display stat comes from merged margin
        histograms rather than per-block sorts."""
        from wormhole_tpu.data.crec import (read_header, read_header2)
        cfg = self.cfg
        fmt = cfg.data_format
        online = fmt != "crec2" and self._tile_online(fmt, file)
        tile = fmt == "crec2" or online
        if fmt == "crec2":
            if not hasattr(self.store, "tile_train_step"):
                raise ValueError(
                    f"store {type(self.store).__name__} has no tile step; "
                    "crec2 streaming needs the table-backed ShardedStore")
            info = read_header2(file)
            if info.nb != cfg.num_buckets:
                raise ValueError(
                    f"{file}: crec2 was written for num_buckets={info.nb} "
                    f"but config says {cfg.num_buckets} (the tile grouping "
                    "is bucket-count specific)")
            lab_off = 0  # crec2 blocks are typed dicts; labels ride as-is
        elif online:
            # online tile encoding: the feed's workers turn v1/text
            # blocks into crec2-typed blocks; host labels ride separately
            info = self._online_info(fmt, file)
            lab_off = 0
        else:
            if not hasattr(self.store, "dense_train_step"):
                raise ValueError(
                    f"store {type(self.store).__name__} has no dense-apply "
                    "step; crec streaming needs the table-backed "
                    "ShardedStore")
            if fmt == "crec":
                info = read_header(file)
            else:
                # dense text fast path: in-memory crec blocks assembled
                # natively (TextCRecFeed); geometry comes from config
                from wormhole_tpu.data.crec import CRecInfo
                info = CRecInfo(nnz=self._text_nnz(),
                                block_rows=cfg.text_block_rows,
                                total_rows=0)
            lab_off = info.block_rows * info.nnz * 4
        has_mesh_step = hasattr(
            self.store, "tile_train_step_mesh" if tile
            else "dense_train_step_mesh") \
            and getattr(self.store, "rt", None) is not None
        local = Progress()
        # text formats ride the dense mesh step; the linear, FM and
        # wide&deep stores all provide mesh steps — a custom store
        # without one (or built without a runtime) falls through to the
        # single-device tile path on its own placement
        if self.rt.mesh.size > 1 and has_mesh_step:
            return self._process_crec_mesh(file, part, nparts, kind,
                                           pooled, info, local, fmt,
                                           online)
        max_delay = cfg.max_delay if kind == TRAIN else 1 << 30
        tau_cap = float(max(cfg.max_delay - 1, 0))
        inflight: deque = deque()
        # tile-train metrics accumulate ON DEVICE (store.fetch_metrics;
        # the app's accumulator survives across parts); eval/v1 metrics
        # ride per-step vectors in the part's window
        acc_metrics = tile and kind == TRAIN
        pfx = "" if kind == TRAIN else "eval_"
        # pass:open is the head of a pass on this thread: the window,
        # the step lookup, the feed. The feed's threads (and a mapped
        # file's mapping) start with the loop's first `next`, under its
        # first consume_stall
        with obs.trace.span("pass:open", cat="pass"):
            win = MetricWindow(self, local, kind, pooled,
                               acc=self._crec_acc if acc_metrics else None)
            step, layout = self._crec_step(
                kind, "tile" if tile else "dense", info)
            # the train step takes a long list of few buckets through
            # the hot tile; eval keeps the COO helpers
            hot = self._hot_room if tile and kind == TRAIN else None
            feed = self._feed(file, part, nparts, fmt,
                              tile_info=info if online else None, hot=hot)
            put_before, copied_before = feed.put_time, feed.host_copy_bytes
            # snapshot BEFORE iterating: the feed flips _cache_full as
            # its stream exhausts, which is mid-way through THIS part
            replay = getattr(feed, "_cache_full", False)

        def record(item) -> None:
            m, labels = item
            if not acc_metrics:
                win.add_step(m, labels, layout)

        def harvest(item) -> None:
            m = item[0]
            # host-sync: completion gate on a step dispatched last window
            jax.block_until_ready(m[0] if isinstance(m, tuple) else m)
            record(item)
            if kind == TRAIN and self.reporter.due():
                # mid-stream display drain: non-final for the accumulator
                # path — a blocking fetch of the just-started window costs
                # ~100 ms of device idle (part-end/flush drains are final)
                win.drain(final=not acc_metrics)

        def _labels_of(host) -> np.ndarray:
            if isinstance(host, dict):
                return host["labels"].copy()
            if host.nbytes == info.block_rows:
                return host            # cached item: already labels-only
            return host[lab_off:lab_off + info.block_rows].copy()

        if replay:
            # HBM-resident replay: single-device steps serialize on the
            # donated slots chain anyway, so the staleness window only
            # throttles host buffering of in-flight blocks — and cached
            # blocks are already resident. Each gate is a blocking
            # host<->device round trip, so skip intra-pass gating and
            # sync once at the end.
            max_delay = 1 << 30
        for dev, host, rows in feed:
            with self.timer.scope(pfx + "wait"):
                while len(inflight) > max(max_delay - 1, 0):
                    harvest(inflight.popleft())
            with self.timer.scope(pfx + "dispatch"):
                m = step(dev, min(float(len(inflight)), tau_cap))
                if acc_metrics:
                    win.count_step()
                inflight.append(
                    (m, None if kind == TRAIN else _labels_of(host)))
        with self.timer.scope(pfx + "wait"):
            # no per-item block_until_ready here: the window's device
            # fetch synchronizes
            while inflight:
                record(inflight.popleft())
            with obs.trace.span("pass:drain", cat="pass"):
                if acc_metrics and replay:
                    # HBM-resident replay: leave the accumulator
                    # deferred — the end-of-part fetch is a round trip
                    # per part; the caller's flush_metrics()/disp_itv
                    # drains it — but bound it (pipelined, non-final) so
                    # dispatch can't run unboundedly ahead of the device
                    win.fold()
                    if self._crec_acc.count >= self.CREC_DRAIN_CHUNK:
                        win.drain(final=False)
                else:
                    win.drain()
        with obs.trace.span("pass:close", cat="pass"):
            # the loop's last block goes here and not at the function's
            # end: a mapped file's mapping lives as long as a view of it,
            # and unmapping 1.4 GB takes tens of ms (PERF.md, PR 41)
            dev = host = None  # noqa: F841
            self.timer.add(pfx + "put", feed.put_time - put_before)
            # a count, not seconds: bytes the feed copied on the host
            self.timer.add(pfx + "host_copy_bytes",
                           feed.host_copy_bytes - copied_before)
            self._merge_pipe_snap(feed.drain_pipe_stats(None), pfx, local)
            if hot is not None:
                self._count_hot(hot.drain())
        return local

    def _count_hot(self, chose: dict) -> None:
        """What a train pass's blocks' overflow lists rode as
        (data/crec.HotRoom.drain), into the timer and the registry:
        counts, not seconds."""
        for k in ("hot_blocks", "coo_blocks", "hot_buckets"):
            self.timer.add("overflow_" + k, chose[k], 1)
        hot_c, coo_c, buckets_c, room_g = obs.metrics.overflow_hot_metrics(
            self.obs.registry)
        hot_c.inc(chose["hot_blocks"])
        coo_c.inc(chose["coo_blocks"])
        buckets_c.inc(chose["hot_buckets"])
        room_g.set(chose["hot_room"])

    def _crec_step(self, kind: str, form: str, info):
        """The step table of the crec passes: the store call for one
        block (or, ``*_mesh``, one data-axis group) as ``step(operand,
        tau)``, with the name of the metric-row layout it returns
        (learners/window.fold_row). ``tile`` takes crec2-typed blocks,
        ``dense`` packed crec v1 blocks. Mesh steps and eval steps take
        no tau."""
        s, r, n = self.store, info.block_rows, info.nnz
        train, evl, layout = {
            "tile": (lambda x, tau: s.tile_train_step(x, info, tau=tau),
                     lambda x, tau: s.tile_eval_step(x, info), "tile"),
            "dense": (lambda x, tau: s.dense_train_step(x, r, n, tau=tau),
                      lambda x, tau: s.dense_eval_step(x, r, n), "sparse"),
            "tile_mesh": (lambda x, tau: s.tile_train_step_mesh(x, info),
                          lambda x, tau: s.tile_eval_step_mesh(x, info),
                          "tile"),
            "dense_mesh": (lambda x, tau: s.dense_train_step_mesh(x, r, n),
                           lambda x, tau: s.dense_eval_step_mesh(x, r, n),
                           "tile"),
        }[form]
        return (train if kind == TRAIN else evl), layout

    def _process_crec_mesh(self, file: str, part: int, nparts: int,
                           kind: str, pooled: Optional[list],
                           info, local: Progress,
                           fmt: str = "crec2",
                           online: bool = False) -> Progress:
        """crec/crec2 over a multi-device mesh: feed blocks in groups of
        ``data_axis_size`` (short tails pad with all-PAD blocks) through
        the shard_map step — crec2 runs the tile step (model axis shards
        bucket tiles), crec v1 the mesh dense-apply step (model axis
        range-shards the folded table); data axis shards blocks either
        way. ``online`` routes a v1/text stream through the online tile
        encoder (same typed blocks as crec2).

        The feed is data/crec.MeshGroupFeed: groups come placed on the
        (data, model) NamedSharding the step takes. A train pass over a
        store whose mesh step takes it (``mesh_hot_overflow``) hands the
        feed the job's HotRoom: a group whose lists pass its rule
        crosses as a hot form a MODEL shard, and what the room chose is
        counted as on one device (``_count_hot``).

        Eval metrics are folded from batched device fetches
        (learners/window.py), and an eval pass pools one label lane a
        group (the blocks' lanes concatenated, 98 KB a block). The
        part's Timer takes three counts beside its seconds:
        ``mesh_steps``, ``ici_bytes``, ``host_copy_bytes``."""
        from wormhole_tpu.data.crec import MeshGroupFeed
        from wormhole_tpu.learners.store import mesh_group_shardings
        if jax.process_count() > 1:
            # unreachable from run() (run_multihost handles crec/crec2
            # via _multihost_pass_crec); direct process() callers must go
            # through the multihost pass for collective alignment
            raise RuntimeError(
                f"call run()/run_multihost for multi-process {fmt} — "
                "process() is single-process only")
        is_tile = fmt == "crec2" or online
        pfx = "" if kind == TRAIN else "eval_"
        with obs.trace.span("pass:open", cat="pass"):
            win = MetricWindow(self, local, kind, pooled, bounded=True)
            step, layout = self._crec_step(
                kind, "tile_mesh" if is_tile else "dense_mesh", info)
            tx = self.store.mesh_transport()
            steps_before, ici_before = tx.dispatches, tx.bytes_ici
            inner = self._make_feed(file, part, nparts, fmt,
                                    device_put=lambda x: x,
                                    tile_info=info if online else None)
            # as on one device, the train step alone takes a long list
            # of few buckets through the hot tile: here a hot form a
            # MODEL shard, made by the group feed's stack workers
            hot = (self._hot_room if is_tile and kind == TRAIN and getattr(
                self.store, "mesh_hot_overflow", False) else None)
            feed = MeshGroupFeed(
                inner, self.rt.data_axis_size,
                mesh_group_shardings(self.rt, is_tile), info, is_tile,
                workers=self.cfg.pipeline_workers,
                depth=max(self.cfg.pipeline_ring, 1), online=online,
                want_labels=kind != TRAIN and pooled is not None,
                hot=hot, hot_parts=self.rt.model_axis_size,
                hot_shardings=(mesh_group_shardings(self.rt, True, hot=True)
                               if hot is not None else None))
        for payload, labels_u8, _rows in feed:
            with self.timer.scope(pfx + "dispatch"):
                with obs.trace.span("mesh:dispatch", cat="mesh"):
                    m = step(payload, 0.0)
            if kind == TRAIN:
                win.count_step()
            else:
                win.add_step(m, labels_u8, layout)
        with self.timer.scope(pfx + "wait"):
            with obs.trace.span("pass:drain", cat="pass"):
                win.drain()
        with obs.trace.span("pass:close", cat="pass"):
            self.timer.add(pfx + "put", feed.put_time)
            self._merge_pipe_snap(feed.drain_pipe_stats(None), pfx, local)
            # counts, not seconds: the mesh dispatches of this part, the
            # ICI bytes one chip moved for them as the store's model
            # books them (store.mesh_step_ici_bytes), and the bytes the
            # feed copied on the host (0 from a mapped local file; a
            # fall-back to readinto shows as a number)
            self.timer.add(pfx + "mesh_steps", tx.dispatches - steps_before)
            self.timer.add(pfx + "ici_bytes", tx.bytes_ici - ici_before)
            self.timer.add(pfx + "host_copy_bytes", feed.host_copy_bytes)
            self._export_group_feed_stats(feed)
            if hot is not None:
                self._count_hot(hot.drain())
        return local

    def _export_group_feed_stats(self, feed) -> None:
        """Fold a MeshGroupFeed's dispatcher-side counters into the obs
        registry (obs.metrics.mesh_feed_gauges): per-group arrival skew
        — the per-device straggler signal the multichip bench reports —
        plus group/pad block counts."""
        snap = feed.skew_snapshot()
        g_skew, g_skew_max, c_groups, c_pads = \
            obs.metrics.mesh_feed_gauges(self.obs.registry)
        if snap["groups"]:
            g_skew.set(1e3 * snap["skew_sum"] / snap["groups"])
        g_skew_max.max(1e3 * snap["skew_max"])
        c_groups.inc(snap["groups"])
        c_pads.inc(snap["pad_blocks"])

    @staticmethod
    def _real_rows(batch) -> np.ndarray:
        """Per-row (real, weight) for pooled eval: real rows are the first
        ``num_real`` (set by pad_to_batch) — row_mask alone can't tell a
        padded row from a real row with example weight 0."""
        mask = np.asarray(batch.row_mask)
        n = getattr(batch, "num_real", None)
        real = (np.arange(len(mask)) < n) if n is not None else mask > 0
        return np.where(real, np.maximum(mask, 0.0), -1.0)

    # -- scheduler loop -----------------------------------------------------

    def run(self) -> Progress:
        """Pass/workload loop (AsyncSGDScheduler::Run, async_sgd.h:294-348)."""
        if jax.process_count() > 1 or self.cfg.staleness_tau >= 0:
            # the ps engine path shares the multihost pass structure even
            # on one process (the collectives take their identity fast
            # paths; the staleness semantics are what the knob buys)
            return self.run_multihost()
        run_t0 = time.monotonic()   # obs ledger: measured run wall time
        cfg = self.cfg
        worker = f"proc{self.rt.rank}"
        print(Progress.HEADER)
        # checkpoint resume at pass granularity (rabit LoadCheckPoint
        # semantics: version = completed data passes). The reference's
        # async model dies with a server; here the whole sharded state —
        # including optimizer accumulators — survives a restart.
        # (Multi-process resume lives in run_multihost, which this method
        # already dispatched to above.)
        start_pass = 0
        if cfg.checkpoint_dir and self._ckpt_ok():
            start_pass, state = self.ckpt.load(self.store.state_pytree())
            if start_pass:
                self.store.restore_pytree(state)
                log.info("resumed at data pass %d", start_pass)
        if not start_pass and cfg.model_in:
            # warm start (reference model_in + Broadcast, linear.cc:115-123);
            # a checkpoint resume supersedes it
            self._store_io("load", cfg.model_in)
            log.info("warm start from %s", cfg.model_in)
        prev_objv_ex = None
        last_saved = start_pass
        completed = start_pass
        drained = False
        for data_pass in range(start_pass, cfg.max_data_pass):
            self.obs.set_phase(f"train:pass{data_pass}")
            self.pool.clear()
            self.pool.add(cfg.train_data, cfg.num_parts_per_file, TRAIN)
            wd_before = self.progress.wdelta2
            pass_prog = Progress()
            while True:
                if ft_supervisor.drain_requested():
                    # supervised SIGTERM: stop at this part boundary,
                    # commit below, exit cleanly (docs/fault_tolerance.md)
                    drained = True
                    break
                wl = self.pool.get(worker)
                if wl is None:
                    break
                prog = self.process(wl.file, wl.part, wl.nparts, wl.kind)
                self.progress.merge(prog)
                pass_prog.merge(prog)
                self.pool.finish(wl.id)
                self._check_divergence(prog)
            if drained:
                self.progress.merge(self.flush_metrics())
                log.info("drain requested: abandoning pass %d at a part "
                         "boundary (completed=%d)", data_pass, completed)
                obs_flight.record("drain", step=completed)
                break
            tail = self.flush_metrics()
            self.progress.merge(tail)
            pass_prog.merge(tail)
            self._check_divergence(tail)   # deferred metrics still feed
            self._crec_hist = [np.zeros(512), np.zeros(512)]  # pass-level
            nnz = self.store.nnz_weight()
            self.model_monitor.update_delta(
                nnz, self.model_monitor.prog.nnz_w,
                self.progress.wdelta2 - wd_before)
            self.model_monitor.set_nnz(nnz)
            completed = data_pass + 1
            if cfg.checkpoint_dir and self._ckpt_ok() \
                    and completed % max(cfg.checkpoint_every, 1) == 0:
                self.ckpt.save(completed, self.store.state_pytree())
                last_saved = completed
            if cfg.val_data:
                vp, pass_auc = self._run_eval(cfg.val_data)
                n = max(vp.num_ex, 1)
                log.info("pass %d validation: objv=%.6f auc=%.6f acc=%.6f",
                         data_pass, vp.objv / n, pass_auc,
                         vp.acc / max(vp.count, 1))
            if self._converged(data_pass, pass_prog, prev_objv_ex):
                break
            prev_objv_ex = pass_prog.objv / max(pass_prog.num_ex, 1)
        if cfg.checkpoint_dir and self._ckpt_ok() and \
                (last_saved < completed or (drained and completed)):
            # the final pass must never be lost to checkpoint_every
            # misalignment or an epsilon early stop; a drain re-commits
            # `completed` with the freshest (mid-pass) state
            self.ckpt.save(completed, self.store.state_pytree())
        if cfg.test_data and not drained:
            self.predict(cfg.test_data, cfg.pred_out)
        if cfg.model_out and not drained:
            self._store_io("save", cfg.model_out)
        if self.timer.totals:
            log.info("pipeline profile:\n%s", self.timer.report())
        if self.obs.active:
            self.obs.finalize(step=self.progress.count,
                              num_ex=self.progress.num_ex,
                              feed_stall=self.feed_stats["feed_stall"],
                              timer=self.timer, progress=self.progress,
                              feed_stats=None,
                              wall_s=time.monotonic() - run_t0)
        return self.progress

    # -- multi-host synchronized training -----------------------------------
    #
    # The reference scales the async learner by adding worker/server
    # processes with no global barrier. The SPMD equivalent: every host
    # builds its LOCAL batch (own workload shard, own unique-key set), the
    # batches are assembled into ONE global batch — rows and key segments
    # sharded over the ``data`` axis, cols offset into the host's key
    # segment — and the same fused step runs globally: the slots
    # gather/scatter against the model-axis-sharded table IS the
    # distributed pull/push (XLA emits the collectives). Buckets touched by
    # several hosts accumulate each host's delta computed from the same
    # pre-step state — exactly the reference's async-apply semantics.
    # Shapes must match across hosts, so max_nnz and key_pad are required
    # static config here.
    #
    # Work distribution is DYNAMIC (the reference's work-stealing
    # scheduler, async_sgd.h:245-348 + workload_pool.h): every host runs an
    # identical REPLICA of the WorkloadPool and applies the same
    # finish/claim transitions, driven by one small allgather of per-host
    # (finished_part, need_part) state per global step — a host that
    # exhausts a short part claims the next unassigned part while others
    # keep streaming theirs, with no scheduler process or RPC. Straggler
    # re-execution is disabled in the replica (it keys on wall-clock
    # durations, which differ across hosts and would desynchronize the
    # replicas; lockstep SPMD steps cannot straggle at the part level
    # anyway). Host failure is a JAX job failure — recovery is
    # restart-from-checkpoint (ShardCheckpointer, saved every pass), the
    # same model rabit uses for its BSP apps.

    def _host_slot(self) -> int:
        """This host's block position along the mesh DATA axis, derived
        from the mesh itself — NOT assumed equal to process rank order
        (meshes built from reordered device lists break that assumption).

        Validates what multi-host batch assembly actually requires: each
        data-axis index is process-uniform across the model axis, and each
        process owns one contiguous run of data-axis indices."""
        mesh = self.rt.mesh
        dpa = self.rt.data_axis_size
        devs = mesh.devices.reshape(dpa, -1)
        procs = []
        for i in range(dpa):
            row = {int(d.process_index) for d in devs[i]}
            if len(row) != 1:
                raise ValueError(
                    f"data-axis index {i} spans processes {sorted(row)}; "
                    "multi-host training needs the model axis to stay "
                    "within a host (choose mesh_shape accordingly)")
            procs.append(row.pop())
        order = list(dict.fromkeys(procs))
        if len(order) != self.rt.world:
            raise ValueError(
                f"data axis covers {len(order)} processes but world is "
                f"{self.rt.world}")
        for p in set(procs):
            idx = [i for i, q in enumerate(procs) if q == p]
            if idx != list(range(idx[0], idx[-1] + 1)):
                raise ValueError(
                    f"process {p}'s data-axis indices {idx} are not "
                    "contiguous; rebuild the mesh in process order")
        return order.index(self.rt.rank)

    @staticmethod
    def _my_shard_rows(arr) -> np.ndarray:
        """This process's rows of a data-axis-sharded global array
        (deduplicating model-axis replicas)."""
        parts = {}
        for s in arr.addressable_shards:
            start = s.index[0].start or 0
            parts[start] = np.asarray(s.data)
        return np.concatenate([parts[k] for k in sorted(parts)])

    def _global_batch(self, batch):
        """Assemble per-host batches into one data-axis-sharded batch."""
        from jax.sharding import PartitionSpec as P
        from wormhole_tpu.data.feed import SparseBatch
        from wormhole_tpu.parallel.collectives import host_local_to_global
        kpad = self.cfg.key_pad
        batch = SparseBatch(
            cols=batch.cols + np.int32(self._slot * kpad),
            vals=batch.vals, labels=batch.labels, row_mask=batch.row_mask,
            uniq_keys=batch.uniq_keys, key_mask=batch.key_mask)
        return host_local_to_global(batch, self.rt.mesh, P(DATA_AXIS))

    def _empty_local_batch(self):
        from wormhole_tpu.data.feed import SparseBatch
        cfg = self.cfg
        return SparseBatch(
            cols=np.zeros((cfg.minibatch, cfg.max_nnz), np.int32),
            vals=np.zeros((cfg.minibatch, cfg.max_nnz), np.float32),
            labels=np.zeros(cfg.minibatch, np.float32),
            row_mask=np.zeros(cfg.minibatch, np.float32),
            uniq_keys=np.zeros(cfg.key_pad, np.int32),
            key_mask=np.zeros(cfg.key_pad, np.float32))

    # -- bounded-staleness engine pass (wormhole_tpu/ps) ---------------------
    #
    # With cfg.staleness_tau >= 0 the TRAIN exchange leaves the trainer
    # thread: every gradient window ships as a dense bucket-space delta
    # through the ExchangeEngine's drain thread, and the loop runs up to
    # tau windows ahead before the gate blocks. Two invariants carry the
    # correctness (ps/engine.py): ALL host collectives route through the
    # one engine thread in deterministic program order, and completed
    # windows are consumed by COUNT, never by completion timing — so
    # every rank applies the same windows at the same loop points and
    # the pass terminates after identical submission counts everywhere.
    #
    # Work distribution is STATIC here (round-robin parts per rank,
    # WorkloadPool.take_static) where the BSP passes run the dynamic
    # claim protocol: the pool's per-round control collective exists to
    # absorb stragglers, and bounded staleness already does that — a
    # slow rank delays the windows it contributes to, not the whole
    # lockstep round. Control-plane data the pass still needs (global
    # drain agreement, pass metrics) piggybacks ON the delta payload:
    # the sum-allreduce of per-rank scalars IS the control exchange, at
    # zero extra round trips — stale by at most tau windows, which only
    # costs tau trailing empty windows at the end of the pass.

    def _ctl(self, fn):
        """Run one control-plane host collective: through the engine's
        drain thread when the ps engine is live (preserving the single
        global collective order), else inline on the caller."""
        eng = getattr(self, "_engine", None)
        return eng.exchange(fn) if eng is not None else fn()

    def _ps_apply(self, ticket, local: Progress) -> bool:
        """Apply one completed delta window to the store and fold its
        globally-summed metrics; True when the window proves the pass
        globally drained (no rank fed a real batch into it)."""
        res = ticket.result
        tau = self._engine.note_applied(ticket)
        with obs.trace.span("ps:apply", cat="ps",
                            args={"tau": tau}):
            self.store.ps_push(res["grad"], tau=float(tau))
        m = np.asarray(res["metrics"], np.float64)
        if "vv" in res:
            # live-rejoin bookkeeping: the one-hot rows sum to the full
            # per-rank window-counter vector (ft/rejoin.VersionVector);
            # merge is max so replay/stale rows never regress
            self._rejoin_vv.merge_row(res["vv"])
        if m[1] > 0:
            local.objv += float(m[0])
            local.num_ex += int(m[1])
            local.count += 1
            # auc/acc shipped example-weighted so the global sum
            # renormalizes to the window's exact pooled fraction
            local.auc += float(m[2]) / m[1]
            local.acc += float(m[3]) / m[1]
            self._display(local)
        return int(res["have"]) == 0

    def _multihost_pass_ps(self, pattern: str) -> Progress:
        """One TRAIN pass through the bounded-staleness engine."""
        from wormhole_tpu.parallel.collectives import allreduce_tree
        cfg = self.cfg
        engine = self._engine
        nb = cfg.num_buckets
        local = Progress()
        pool = WorkloadPool()
        pool.add(pattern, cfg.num_parts_per_file, TRAIN)
        mine = pool.take_static(self.rt.world, self.rt.rank)

        def batches():
            for wl in mine:
                yield from self._batches(wl.file, wl.part, wl.nparts)

        it = batches()
        window = max(1, cfg.ps_window_steps)
        # version-vector piggyback, only when a replay log is live: the
        # wire payload stays byte-identical with rejoin off (tau=0
        # parity with the BSP oracle is pinned by test_ps_engine.py)
        vv_on = engine.replay is not None
        if vv_on and not hasattr(self, "_rejoin_vv"):
            from wormhole_tpu.ft.rejoin import VersionVector
            self._rejoin_vv = VersionVector(self.rt.world)
        stop = False
        while not stop:
            if ft_supervisor.drain_requested():
                # flush in-flight windows into the store before the
                # survivor checkpoint commits (run_multihost's handler)
                with self.timer.scope("wait"):
                    for tk in engine.quiesce():
                        self._ps_apply(tk, local)
                raise ft_supervisor.DrainInterrupt()
            # one window = up to ps_window_steps minibatch gradients, all
            # taken at the same weights, accumulated into one delta
            dense = np.zeros(nb, np.float32)
            mets = np.zeros(4, np.float64)
            have_local = False
            for _ in range(window):
                with self.timer.scope("parse"):
                    blk = next(it, None)
                real = blk is not None
                have_local = have_local or real
                batch = blk if real else self._empty_local_batch()
                with self.timer.scope("dispatch"):
                    grad, _snap, m = self.store.dt2_pull(batch)
                    # host scatter to the dense exchange space: the
                    # per-uniq-key gradient lands in bucket coordinates
                    # that are identical on every rank (COMPRESSING's
                    # zero-RLE eats the untouched tail on the wire)
                    np.add.at(dense, np.asarray(batch.uniq_keys),
                              np.asarray(grad) * np.asarray(batch.key_mask))
                    nex = float(np.asarray(m[1]))
                    mets += [float(np.asarray(m[0])), nex,
                             float(np.asarray(m[2])) * nex,
                             float(np.asarray(m[3])) * nex]
                if not real:
                    break   # local tail: no more empties in this window
            payload = {
                "grad": dense,
                "metrics": mets.astype(np.float32),
                "have": np.int64(have_local),
            }
            if vv_on:
                # own window count in own slot; the delta sum-allreduce
                # reconstructs the full vector at zero extra collectives
                self._rejoin_vv.bump(self.rt.rank)
                payload["vv"] = self._rejoin_vv.one_hot(self.rt.rank)
            engine.submit(
                # transport: engine — the closure executes on the drain thread
                lambda p=payload: allreduce_tree(
                    p, self.rt.mesh, "sum", site="ps/delta"))
            with self.timer.scope("wait"):
                for tk in engine.gate():
                    stop = self._ps_apply(tk, local) or stop
        with self.timer.scope("wait"):
            for tk in engine.quiesce():
                self._ps_apply(tk, local)
        return local

    def _multihost_pass(self, pattern: str, kind: str,
                        pooled: Optional[list] = None) -> Progress:
        """One synchronized pass over ``pattern`` with the replicated
        dynamic pool. The returned Progress is GLOBAL — every metric comes
        out of the global step, so all hosts compute identical values."""
        from wormhole_tpu.parallel.collectives import (allgather_tree,
                                                       allreduce_tree)
        cfg = self.cfg
        world = self.rt.world
        # rounds-based straggler re-execution: deterministic across the
        # replicated pools (see ReplicatedRounds)
        pool = WorkloadPool(straggler_factor=cfg.straggler_factor)
        pool.add(pattern, cfg.num_parts_per_file, kind)
        rr = ReplicatedRounds(pool, world, self.rt.rank)
        my_it = None
        my_wl = None
        my_skip = 0
        drained = False
        finished_id = -1
        local = Progress()
        inflight: deque = deque()
        pfx = "" if kind == TRAIN else "eval_"
        tau_cap = float(max(cfg.max_delay - 1, 0))

        def harvest(metrics) -> None:
            vals = [float(v) for v in np.asarray(
                jax.device_get(metrics[:4]))]
            local.objv += vals[0]
            local.num_ex += int(vals[1])
            local.count += 1
            local.auc += vals[2]
            local.acc += vals[3]
            if kind == TRAIN:
                self._display(local)

        while True:
            if ft_supervisor.drain_requested():
                # supervised SIGTERM: a peer is dead or dying — leave
                # the round loop BEFORE the next collective (which could
                # block on the dead rank) and let run_multihost commit
                raise ft_supervisor.DrainInterrupt()
            blk = None
            if my_it is not None:
                with self.timer.scope(pfx + "parse"):
                    blk = next(my_it, None)
                if blk is None:
                    finished_id = my_wl.id
                    my_it = None
                else:
                    rr.produced(1)
            # drained hosts stay needy: a straggler re-issue must find a
            # claimant (drained flips back off when the pool hands work)
            need = my_it is None
            # one exchange per global step:
            # (finished part, need, drained, blocks contributed)
            status = self._ctl(
                # transport: engine — control exchange on the drain thread
                lambda: allgather_tree(
                    rr.status_row(finished_id, need, drained),
                    self.rt.mesh, site="async_sgd/status"))
            finished_id = -1
            rr.advance(status)
            # identical pool transitions on every replica, in rank order
            for r in range(world):
                if status[r, 0] >= 0:
                    rr.finished(int(status[r, 0]))
            any_claimed = False
            for r in range(world):
                if status[r, 1]:
                    wl = pool.get(f"proc{r}")
                    if wl is not None:
                        any_claimed = True
                        if rr.reclaimed_from(wl, r):
                            # straggler handoff: the new holder resumes
                            # at our skip point; stop WITHOUT finishing
                            log.info("part %d re-issued to proc%d; "
                                     "abandoning at block %d", wl.id, r,
                                     rr._progress.get(wl.id, 0))
                            my_it = None
                            my_wl = None
                            rr.abandon()
                        skip = rr.claimed(r, wl)
                    else:
                        skip = 0
                    if r == self.rt.rank:
                        my_wl = wl
                        my_skip = skip
            if need:
                if my_wl is None:
                    drained = True
                else:
                    drained = False
                    my_it = self._batches(my_wl.file, my_wl.part,
                                          my_wl.nparts, pfx)
                    if my_skip:
                        from itertools import islice
                        my_it = islice(my_it, my_skip, None)
                    with self.timer.scope(pfx + "parse"):
                        blk = next(my_it, None)
                    if blk is None:       # empty part: finish next round
                        finished_id = my_wl.id
                        my_it = None
                    else:
                        rr.produced(1)
            have = int(self._ctl(
                # transport: engine — control exchange on the drain thread
                lambda b=blk: allreduce_tree(np.int64(b is not None),
                                             self.rt.mesh, "sum",
                                             site="async_sgd/have")))
            if have == 0:
                # global decision: status and the pool (hence any_claimed)
                # are identical on every replica. A pending finished_id
                # implies any_claimed (only an empty claim sets it here).
                if bool(np.all(status[:, 2])) and not any_claimed:
                    break
                continue
            batch = blk if blk is not None else self._empty_local_batch()
            gb = self._global_batch(batch)
            with self.timer.scope(pfx + "dispatch"):
                if kind == TRAIN:
                    inflight.append(self.store.train_step(
                        gb, tau=min(float(len(inflight)), tau_cap)))
                else:
                    m = self.store.eval_step(gb)
                    harvest(m)
                    if pooled is not None:
                        margins = self._my_shard_rows(m[4])
                        keep = self._real_rows(batch)
                        real = keep >= 0
                        pooled.append((margins[real],
                                       np.asarray(batch.labels)[real],
                                       np.maximum(keep[real], 0.0)))
            with self.timer.scope(pfx + "wait"):
                while len(inflight) > cfg.max_delay:
                    harvest(jax.block_until_ready(inflight.popleft()))
        with self.timer.scope(pfx + "wait"):
            while inflight:
                harvest(jax.block_until_ready(inflight.popleft()))
        return local

    def _multihost_pass_crec(self, pattern: str, kind: str,
                             pooled: Optional[list] = None) -> Progress:
        """One synchronized crec/crec2 pass across processes: every host
        runs the replicated pool, streams blocks of its claimed part, and
        the hosts' stacked blocks become ONE data-axis-sharded global
        input to the mesh step — crec2 through the tile step (model axis
        shards bucket tiles), crec v1 through the mesh dense-apply step
        (model axis range-shards the folded bucket table). A host with no
        block this round contributes all-PAD blocks, which vanish from
        every product."""
        from jax.sharding import PartitionSpec as P
        from wormhole_tpu.data.crec import (PackedFeed, mesh_pads,
                                            read_header, read_header2,
                                            stack_mesh_group)
        from wormhole_tpu.data.stream import list_files
        cfg = self.cfg
        fmt = cfg.data_format
        is_tile = fmt == "crec2"
        world = self.rt.world
        dpa = self.rt.data_axis_size
        dlocal = dpa // world          # data-axis indices per host
        # rounds-based straggler re-execution: deterministic across the
        # replicated pools (see ReplicatedRounds)
        pool = WorkloadPool(straggler_factor=cfg.straggler_factor)
        pool.add(pattern, cfg.num_parts_per_file, kind)
        rr = ReplicatedRounds(pool, world, self.rt.rank)
        my_skip = 0
        # headers are geometry-identical across a dataset's files (the
        # check below re-verifies per opened file)
        read_hdr = read_header2 if is_tile else read_header
        info = read_hdr(list_files(pattern)[0].path)
        my_it = None
        my_wl = None
        drained = False
        finished_id = -1
        local = Progress()
        pfx = "" if kind == TRAIN else "eval_"
        win = MetricWindow(self, local, kind, pooled, bounded=True)
        step, layout = self._crec_step(
            kind, "tile_mesh" if is_tile else "dense_mesh", info)
        pads = mesh_pads(info, is_tile)

        def feed_iter(wl, skip=0):
            hdr = read_hdr(wl.file)
            if fmt == "crec2":
                same = (hdr.nb == cfg.num_buckets
                        and hdr.spec == info.spec
                        and hdr.block_rows == info.block_rows
                        and hdr.nnz == info.nnz
                        and hdr.ovf_cap == info.ovf_cap)
            else:
                same = (hdr.block_rows == info.block_rows
                        and hdr.nnz == info.nnz)
            if not same:
                raise ValueError(
                    f"{wl.file}: {fmt} geometry does not match the "
                    f"dataset's first file ({hdr} vs {info}) — multihost "
                    "block shards must be shape-identical across hosts")
            # host arrays only; the global device_put happens at assembly
            it = iter(PackedFeed(wl.file, wl.part, wl.nparts,
                                 fmt=fmt, device_put=lambda x: x))
            if skip:
                # straggler handoff: resume after the blocks the original
                # holder already dispatched (read-and-drop; exactness
                # beats the saved IO)
                from itertools import islice
                it = islice(it, skip, None)
            return it

        def collect(group):
            nonlocal my_it, finished_id
            while my_it is not None and len(group) < dlocal:
                with self.timer.scope(pfx + "parse"):
                    item = next(my_it, None)
                if item is None:
                    finished_id = my_wl.id
                    my_it = None
                else:
                    group.append(item[0])
                    rr.produced(1)

        from wormhole_tpu.parallel.collectives import (
            allgather_tree, allreduce_tree, host_local_to_global)
        while True:
            if ft_supervisor.drain_requested():
                raise ft_supervisor.DrainInterrupt()
            group: list = []
            collect(group)
            # drained hosts stay needy: a straggler re-issue must find a
            # claimant (drained flips back off when the pool hands work)
            need = my_it is None
            status = self._ctl(
                # transport: engine — control exchange on the drain thread
                lambda: allgather_tree(
                    rr.status_row(finished_id, need, drained),
                    self.rt.mesh, site="async_sgd/status"))
            finished_id = -1
            rr.advance(status)
            for r in range(world):
                if status[r, 0] >= 0:
                    rr.finished(int(status[r, 0]))
            any_claimed = False
            for r in range(world):
                if status[r, 1]:
                    wl = pool.get(f"proc{r}")
                    if wl is not None:
                        any_claimed = True
                        if rr.reclaimed_from(wl, r):
                            log.info("part %d re-issued to proc%d; "
                                     "abandoning at block %d", wl.id, r,
                                     rr._progress.get(wl.id, 0))
                            my_it = None
                            my_wl = None
                            rr.abandon()
                        skip = rr.claimed(r, wl)
                    else:
                        skip = 0
                    if r == self.rt.rank:
                        my_wl = wl
                        my_skip = skip
            if need:
                if my_wl is None:
                    drained = True
                else:
                    drained = False
                    my_it = feed_iter(my_wl, my_skip)
                    collect(group)   # contribute in the claim round too
            have = int(self._ctl(
                # transport: engine — control exchange on the drain thread
                lambda g=group: allreduce_tree(np.int64(len(g)),
                                               self.rt.mesh, "sum",
                                               site="async_sgd/have")))
            if have == 0:
                # global decision: status and the pool (hence any_claimed)
                # are identical on every replica
                if bool(np.all(status[:, 2])) and not any_claimed:
                    break
                continue
            blocks, labels_u8 = stack_mesh_group(
                group, dlocal, info, pads, is_tile,
                want_labels=kind != TRAIN and pooled is not None)
            gblocks = host_local_to_global(blocks, self.rt.mesh,
                                           P(DATA_AXIS))
            with self.timer.scope(pfx + "dispatch"):
                m = step(gblocks, 0.0)
                if kind == TRAIN:
                    win.count_step()
                else:
                    # an eval round folds as it comes: its margins
                    # are a global array, read shard by shard
                    fold_row(local, [np.asarray(v) for v in m[:5]],
                             layout, kind)
                    if pooled is not None:
                        pool_margins(pooled, self._my_shard_rows(m[5]),
                                     labels_u8)
        with self.timer.scope(pfx + "wait"):
            win.drain()
        return local

    def run_multihost(self) -> Progress:
        """Multi-host scheduler loop: dynamic workload pool, per-pass
        sharded checkpoint/resume, validation passes, divergence kill
        switch, predict — the full AsyncSGDScheduler surface
        (async_sgd.h:245-348) in SPMD form. Sparse/text formats train
        through the global-batch path; crec2 trains through the mesh tile
        step with per-host block shards."""
        from wormhole_tpu.parallel.checkpoint import ShardCheckpointer
        from wormhole_tpu.parallel.collectives import allreduce_tree
        from wormhole_tpu.ops.metrics import auc_np
        run_t0 = time.monotonic()   # obs ledger: measured run wall time
        cfg = self.cfg
        crec = cfg.data_format in ("crec", "crec2")
        if crec:
            if self.rt.data_axis_size % self.rt.world:
                raise ValueError(
                    f"data axis {self.rt.data_axis_size} must be a "
                    f"multiple of world {self.rt.world} for "
                    f"{cfg.data_format} multihost (whole blocks per "
                    "data index)")
        elif not (cfg.max_nnz and cfg.key_pad):
            raise ValueError("multi-host sync training (and the ps "
                             "engine path) needs static max_nnz= and "
                             "key_pad= config")
        self._engine = None
        if cfg.staleness_tau >= 0:
            from wormhole_tpu.ps import build_engine
            # crec trains through device-level mesh steps (the model
            # exchange is XLA's, not a host collective), so the engine
            # there only owns the control-plane ordering; the sparse/
            # text TRAIN pass routes its whole delta exchange through it
            self._engine = build_engine(cfg, registry=self.obs.registry)
            log.info("ps engine on: staleness_tau=%d window_steps=%d",
                     cfg.staleness_tau, cfg.ps_window_steps)
        self._slot = self._host_slot()
        self._max_nnz = cfg.max_nnz
        ckpt = (ShardCheckpointer(cfg.checkpoint_dir)
                if cfg.checkpoint_dir else None)
        start_pass = 0
        if ckpt is not None:
            # ranks must agree on the resume point even when the
            # checkpoint dir is not shared: the slowest view wins
            ver = int(self._ctl(
                # transport: engine — control exchange on the drain thread
                lambda: allreduce_tree(np.int64(ckpt.latest_version()),
                                       self.rt.mesh, "min",
                                       site="async_sgd/ckpt_ver")))
            if ver:
                _, state = ckpt.load(self.store.state_pytree(),
                                     version=ver)
                self.store.restore_pytree(state)
                start_pass = ver
                log.info("resumed at data pass %d", start_pass)
        if not start_pass and cfg.model_in:
            # every host reads the same file → identical warm-start table
            self._store_io("load", cfg.model_in)
            log.info("warm start from %s", cfg.model_in)
        if self.rt.rank == 0:
            print(Progress.HEADER)
        prev_objv_ex = None
        last_saved = start_pass
        completed = start_pass
        drained = False
        try:
            try:
                for data_pass in range(start_pass, cfg.max_data_pass):
                    self.obs.set_phase(f"multihost:pass{data_pass}")
                    prog = (self._multihost_pass_crec(cfg.train_data,
                                                      TRAIN)
                            if crec
                            else self._multihost_pass_ps(cfg.train_data)
                            if self._engine is not None
                            else self._multihost_pass(cfg.train_data,
                                                      TRAIN))
                    self.progress.merge(prog)
                    self._check_divergence(prog)
                    completed = data_pass + 1
                    if ckpt is not None \
                            and completed % max(cfg.checkpoint_every,
                                                1) == 0:
                        self.ckpt_version = completed
                        ckpt.save(completed, self.store.state_pytree())
                        last_saved = completed
                    if cfg.val_data:
                        pooled: list = []
                        vp = (self._multihost_pass_crec(cfg.val_data, VAL,
                                                        pooled)
                              if crec
                              else self._multihost_pass(cfg.val_data, VAL,
                                                        pooled))
                        pass_auc = self._allreduce_pooled_auc(pooled)
                        n = max(vp.num_ex, 1)
                        log.info("pass %d validation: objv=%.6f auc=%.6f "
                                 "acc=%.6f", data_pass, vp.objv / n,
                                 pass_auc, vp.acc / max(vp.count, 1))
                    # prog is GLOBAL (identical on all ranks), so every
                    # rank takes the early-stop branch in the same pass
                    if self._converged(data_pass, prog, prev_objv_ex):
                        break
                    prev_objv_ex = prog.objv / max(prog.num_ex, 1)
            except ft_supervisor.DrainInterrupt:
                # supervised SIGTERM (a peer is dead): commit a survivor
                # checkpoint WITHOUT the cross-rank barrier — peers may
                # be gone, and the resume-version allreduce-min is the
                # real agreement (a version only wins when all
                # relaunched ranks hold it). Version `completed` is
                # re-committed with the freshest block-boundary state;
                # its marker already exists, so an interrupted drain
                # leaves the old commit intact.
                drained = True
                log.info("drain requested: abandoning pass at a block "
                         "boundary; committing survivor checkpoint v%d",
                         completed)
                obs_flight.record("drain_interrupt", step=completed)
                if ckpt is not None and completed:
                    self.ckpt_version = completed
                    ckpt.save(completed, self.store.state_pytree(),
                              barrier=False)
                    last_saved = completed
            if ckpt is not None and last_saved < completed:
                # the final pass must never be lost to checkpoint_every
                # misalignment or an epsilon early stop
                self.ckpt_version = completed
                ckpt.save(completed, self.store.state_pytree())
            if cfg.test_data and not drained:
                pooled = []
                if crec:
                    self._multihost_pass_crec(cfg.test_data, TEST, pooled)
                else:
                    self._multihost_pass(cfg.test_data, TEST, pooled)
                self._write_preds(pooled, f"{cfg.pred_out}_{self.rt.rank}")
            if cfg.model_out and not drained:
                self._store_io("save", cfg.model_out)
        finally:
            # the drain thread must not outlive the pass structure it
            # serializes (a later run would race two engines)
            if self._engine is not None:
                self._engine.stop()
                self._engine = None
        if self.timer.totals:
            log.info("pipeline profile:\n%s", self.timer.report())
        if self.obs.active:
            self.obs.finalize(step=self.progress.count,
                              num_ex=self.progress.num_ex,
                              feed_stall=self.feed_stats["feed_stall"],
                              timer=self.timer, progress=self.progress,
                              feed_stats=None,
                              wall_s=time.monotonic() - run_t0)
        return self.progress

    def _allreduce_pooled_auc(self, pooled: list) -> float:
        """Pass-level AUC across hosts without gathering margins: each
        host bins its own rows' (margin, label, weight) into pos/neg
        histograms; the histograms sum across hosts (dist_monitor.h
        merged-progress semantics, exact up to binning)."""
        from wormhole_tpu.parallel.collectives import allreduce_tree
        from wormhole_tpu.ops.metrics import auc_from_hist
        bins, lo, hi = 512, -8.0, 8.0
        pos = np.zeros(bins)
        neg = np.zeros(bins)
        for margins, labels, weights in pooled:
            b = (np.clip((margins - lo) / (hi - lo), 0, 1)
                 * (bins - 1)).astype(np.int64)
            np.add.at(pos, b, (labels > 0.5) * weights)
            np.add.at(neg, b, (labels <= 0.5) * weights)
        z = self.cfg.msg_compression
        # one tree, one exchange — and each leaf keeps its own
        # error-feedback residual slot at the site
        pos, neg = self._ctl(
            # transport: engine — control exchange on the drain thread
            lambda: allreduce_tree((pos, neg), self.rt.mesh, "sum",
                                   compress=z, site="async_sgd/auc_hist"))
        return auc_from_hist(np.asarray(pos), np.asarray(neg))

    def _write_preds(self, pooled: list, out_path: str) -> None:
        from wormhole_tpu.data.stream import open_stream
        margins = (np.concatenate([p[0] for p in pooled])
                   if pooled else np.zeros(0, np.float32))
        if self.cfg.loss.value == "logit":
            preds = 1.0 / (1.0 + np.exp(-margins))
        else:
            preds = margins
        with open_stream(out_path, "w") as f:
            for p in preds:
                f.write(f"{p:.6g}\n")
        log.info("wrote %d predictions to %s", len(preds), out_path)

    def _ckpt_ok(self) -> bool:
        """Checkpointing requires fully host-addressable state: parameter
        tables sharded ACROSS processes can't be serialized by a rank-0
        writer (Checkpointer contract). Skip loudly rather than crash."""
        if not hasattr(self.store, "state_pytree"):
            if not self._warned_ckpt:
                self._warned_ckpt = True
                log.warning(
                    "checkpointing skipped: store %s has no state_pytree",
                    type(self.store).__name__)
            return False
        leaves = jax.tree.leaves(self.store.state_pytree())
        ok = all(getattr(x, "is_fully_addressable", True) for x in leaves)
        if not ok and not self._warned_ckpt:
            self._warned_ckpt = True
            log.warning(
                "checkpointing skipped: store state is sharded across "
                "processes (not rank-0 addressable); use per-host model "
                "export (model_out) instead")
        return ok

    def _run_eval(self, pattern: str):
        """Full eval pass; returns (Progress, pooled AUC over the whole
        pass). The per-minibatch mean AUC stays in Progress for display; the
        pooled number is the unbiased pass-level statistic."""
        from wormhole_tpu.ops.metrics import auc_np
        self.obs.set_phase("eval")
        pool = WorkloadPool()
        pool.add(pattern, self.cfg.num_parts_per_file, VAL)
        total = Progress()
        pooled: list = []
        while True:
            wl = pool.get("eval")
            if wl is None:
                break
            total.merge(self.process(wl.file, wl.part, wl.nparts, VAL,
                                     pooled=pooled))
            pool.finish(wl.id)
        if pooled:
            margins = np.concatenate([p[0] for p in pooled])
            labels = np.concatenate([p[1] for p in pooled])
            weights = np.concatenate([p[2] for p in pooled])
            pass_auc = auc_np(labels, margins, weights)
        else:
            pass_auc = 0.5
        return total, pass_auc

    def predict(self, pattern: str, out_path: str) -> None:
        """TEST workload (reference workload.proto:12-16 TEST type): stream
        the test data, write one prediction per real row to ``pred_out`` —
        σ(margin) for logit loss (linear.h MarginToPred), the raw margin
        otherwise."""
        if not out_path:
            raise ValueError("test_data set but pred_out empty")
        self.obs.set_phase("predict")
        if self.cfg.serve_predict and hasattr(self.store,
                                              "build_serve_margin"):
            from wormhole_tpu.serve import ForwardStep
            self._predict_forward = ForwardStep.from_store(self.store)
        pool = WorkloadPool()
        pool.add(pattern, self.cfg.num_parts_per_file, TEST)
        pooled: list = []
        try:
            while True:
                wl = pool.get("predict")
                if wl is None:
                    break
                self.process(wl.file, wl.part, wl.nparts, TEST,
                             pooled=pooled)
                pool.finish(wl.id)
        finally:
            self._predict_forward = None
        self._write_preds(pooled, out_path)

    # -- observability ------------------------------------------------------

    def _key_fold(self) -> str:
        """Key->bucket scheme for this run's data_format (recorded in /
        checked against saved models; the crec family folds differently
        from the text formats — see data/hashing.py)."""
        # text_dense folds on device (mix32) only single-process;
        # run_multihost routes text through the sparse localize path
        # (splitmix64) — the saved fold tag must follow the path that ran.
        # The online tile encoder folds on host with the same mix32
        # (hashing.fold_keys32), so any stream it admits keeps that tag.
        return ("mix32" if self.cfg.data_format in ("crec", "crec2")
                or (self._text_dense() and jax.process_count() == 1)
                or self._tile_online()
                else "splitmix64")

    def _store_io(self, op: str, path: str):
        """save/load the model with the key-fold tag — part of the store
        protocol (ShardedStore enforces it; FM/wide&deep accept it)."""
        if op == "save":
            self.store.save_model(path, self.rt.rank,
                                  key_fold=self._key_fold())
        else:
            self.store.load_model(path,
                                  expect_key_fold=self._key_fold())

    def _display(self, local: Progress) -> None:
        # heartbeat BEFORE the rank gate: every host reports its own
        # liveness/throughput, that is the point of straggler detection
        if self.obs.tick_due():
            snap = Progress(self.progress.fvec + local.fvec,
                            self.progress.ivec + local.ivec)
            self.obs.heartbeat_tick(
                step=snap.count, num_ex=snap.num_ex,
                feed_stall=self.feed_stats["feed_stall"])
        if self.rt.rank != 0:
            return
        self.reporter.report(local)

    def _emit_row(self, local: Progress) -> None:
        snap = Progress(self.progress.fvec + local.fvec,
                        self.progress.ivec + local.ivec)
        # nnz from the last pass boundary (ModelMonitor): a live
        # nnz_weight() would force a full-model sync and drain the
        # dispatch pipeline every disp_itv
        snap.nnz_w = self.model_monitor.prog.nnz_w
        print(snap.print_row(time.time() - self.start_time,
                             self._prev_num_ex))
        self._prev_num_ex = snap.num_ex

    def _converged(self, data_pass: int, pass_prog: Progress,
                   prev_objv_ex) -> bool:
        """Early stop (Config.epsilon, config.proto convergence tolerance):
        a pass that improves per-example objv by less than epsilon
        (relatively) ends training."""
        eps = self.cfg.epsilon
        if not eps or prev_objv_ex is None or pass_prog.num_ex == 0:
            return False
        cur = pass_prog.objv / max(pass_prog.num_ex, 1)
        rel = (prev_objv_ex - cur) / max(abs(prev_objv_ex), 1e-12)
        if rel < eps:
            log.info("converged at pass %d: relative objv improvement "
                     "%.2e < epsilon %.2e", data_pass, rel, eps)
            return True
        return False

    def _check_divergence(self, prog: Progress) -> None:
        """Kill switch on the *freshest* workload part (cumulative averages
        would dilute late divergence); NaN always counts as diverged.

        On cached-replay crec2 parts the deferred metric window means a
        part's Progress can include rows credited up to ~2 windows late,
        so detection lags by that much — delayed, never lost (totals stay
        exact; the pass-end flush_metrics() re-checks the tail)."""
        cfg = self.cfg
        per_ex = prog.objv / max(prog.num_ex, 1)
        if np.isnan(per_ex):
            raise DivergedError("objv is NaN")
        if cfg.max_objv and per_ex > cfg.max_objv:
            raise DivergedError(
                f"objv {per_ex:.4f} > max_objv {cfg.max_objv} "
                f"(async_sgd.h:316-319 kill switch)")


def app_from_argv(argv: Optional[List[str]] = None) -> AsyncSGD:
    """The CLI's construction half: ``[conf] key=val ...`` -> the app.
    Split from main() so chip_smoke.py can drive the same path and
    still look at the store afterwards."""
    import sys
    from wormhole_tpu.utils.config import load_config
    args = list(sys.argv[1:] if argv is None else argv)
    conf = args.pop(0) if args and "=" not in args[0] else None
    return AsyncSGD(load_config(conf, args))


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: ``python -m wormhole_tpu.learners.async_sgd conf key=val ...``"""
    app_from_argv(argv).run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
