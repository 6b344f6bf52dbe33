"""Host-resident cold tier with an on-device hot working set.

``PagedStore`` wraps a table-backed store (ShardedStore / FMStore /
WideDeepStore built at ``hot_buckets`` rows — the ``with_num_buckets``
twin) and keeps the FULL ``(nb_total, val_len)`` bucket space in host
RAM. Batches address global bucket ids; the pager (:mod:`.pager`) maps
them onto hot slots and this module moves the rows:

* **page-in (H2D)** — a missed bucket's cold row ships to its hot slot.
  *Fresh* fills ride the ``DeviceFeed`` transfer ring (staged on the
  transfer thread, overlapping the device step); *late* fills — buckets
  evicted within the pipeline's lookahead window — are read at apply
  time, after writeback resolution (see pager.py for the race this
  closes). Both land under the ``page:h2d`` span.
* **page-out (D2H)** — LFU victims gather into a device buffer whose
  device→host copy starts asynchronously (``copy_to_host_async``) and
  resolves one plan later, so the writeback overlaps the step that
  follows the eviction. Spans: ``page:evict`` (gather + dispatch),
  ``page:d2h`` (the resolving read).

The arithmetic is untouched: batches are remapped (global bucket id →
hot slot id) on the prep workers and fed to the wrapped store's own
jitted step, so a paged run is **bitwise identical** to the same stream
through a full-size table — the gather/scatter sees the same row values
at remapped indices (the parity the tests pin). Gather/scatter index
vectors pad to power-of-two chunks (``page_chunk`` floor) so paging
compiles O(log) programs, not one per miss count; padding duplicates
index 0 with its own row, which ``.at[].set`` resolves to the identical
value.

All paging device ops run on the consumer thread in stream order; the
transfer thread only ``device_put``s immutable cold rows. Paging H2D
goes through a dedicated ``DeviceFeed.prepare`` entry so it shares the
ring's stage accounting and trace spans instead of growing a second
transfer path.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Any, Iterable, Optional, Tuple

import numpy as np

from wormhole_tpu.bigmodel.pager import BucketPager, PagePlan, \
    late_window_for
from wormhole_tpu.obs import trace

__all__ = ["PagedStore"]


def _jax():
    import jax
    return jax


def _pad_len(n: int, chunk: int) -> int:
    """Smallest power-of-two multiple of ``chunk`` holding ``n`` rows —
    the fixed-shape quantum that bounds paging recompiles."""
    p = max(int(chunk), 1)
    while p < n:
        p *= 2
    return p


def _pad_pair(idx: np.ndarray, rows: np.ndarray,
              chunk: int) -> Tuple[np.ndarray, np.ndarray]:
    n = idx.shape[0]
    p = _pad_len(n, chunk)
    if p == n:
        return idx, rows
    idx_p = np.concatenate([idx, np.repeat(idx[:1], p - n)])
    rows_p = np.concatenate([rows, np.repeat(rows[:1], p - n, axis=0)])
    return idx_p, rows_p


class PagedStore:
    """Two-tier bucket table: ``hot`` (a device-resident store at
    ``hot_buckets`` rows) backed by a host cold table at ``nb_total``
    rows. See the module docstring for the data motion contract."""

    def __init__(self, hot_store, nb_total: int, *,
                 cold_init: Optional[np.ndarray] = None,
                 late_window: int = 64, page_chunk: int = 64) -> None:
        self.hot = hot_store
        self.nb_total = int(nb_total)
        self.hot_buckets = int(hot_store.cfg.num_buckets)
        if self.nb_total < self.hot_buckets:
            raise ValueError(f"nb_total {nb_total} smaller than the hot "
                             f"tier {self.hot_buckets}")
        self.page_chunk = int(page_chunk)
        self._row_bytes = (int(np.prod(hot_store.slots.shape[1:]))
                           * hot_store.slots.dtype.itemsize)
        if cold_init is None:
            handle = getattr(hot_store, "handle", None)
            if handle is None:
                raise ValueError(
                    "store has no .handle to build the cold tier from; "
                    "pass cold_init (e.g. np.asarray of a full-size "
                    "with_num_buckets twin's slots)")
            cold_init = np.asarray(handle.init(self.nb_total)).astype(
                np.asarray(hot_store.slots[:1]).dtype)
        cold_init = np.asarray(cold_init)
        if cold_init.shape[0] != self.nb_total:
            raise ValueError(f"cold_init has {cold_init.shape[0]} rows, "
                             f"want nb_total={self.nb_total}")
        self.cold = np.array(cold_init)  # owner-thread: consumer
        self.pager = BucketPager(self.nb_total, self.hot_buckets,
                                 late_window=late_window)
        # previous plan's async writeback: (victim_buckets, device rows,
        # real row count); resolved at the next apply_plan / flush
        self._pending = None  # owner-thread: consumer
        self._lock = threading.Lock()
        # paging byte counters: transfer thread adds H2D stage bytes,
        # the consumer adds late-fill/writeback bytes and stats() reads
        self._bytes_h2d = 0  # guarded-by: _lock
        self._bytes_d2h = 0  # guarded-by: _lock
        # dedicated transfer entry for paging H2D: DeviceFeed.prepare
        # gives the page rows the ring's stage accounting + spans
        from wormhole_tpu.data.pipeline import DeviceFeed
        self._ring = DeviceFeed((), prep=None, workers=0, name="page")
        self._gather = None
        self._scatter = None

    @classmethod
    def from_config(cls, cfg, hot_store, *,
                    cold_init: Optional[np.ndarray] = None
                    ) -> "PagedStore":
        """Wire the run Config's bigmodel knobs: ``hot_store`` is the
        ``with_num_buckets(cfg.hot_buckets)`` twin; the cold tier spans
        ``cfg.num_buckets``; the late-fill window follows the pipeline
        geometry (pipeline_workers/pipeline_ring) plus the
        ``page_prefetch`` slack; ``page_chunk`` sets the pad quantum."""
        window = late_window_for(getattr(cfg, "pipeline_workers", 2),
                                 getattr(cfg, "pipeline_ring", 2),
                                 getattr(cfg, "page_prefetch", 8))
        return cls(hot_store, cfg.num_buckets, cold_init=cold_init,
                   late_window=window,
                   page_chunk=getattr(cfg, "page_chunk", 64))

    # -- jitted tier-move programs (built lazily: jax import stays off
    #    the constructor for host-only planning tests) ------------------

    def _ops(self):
        if self._gather is None:
            jax = _jax()

            @jax.jit
            def gather(slots, idx):
                return slots[idx]

            @partial(jax.jit, donate_argnums=(0,))
            def scatter(slots, idx, rows):
                return slots.at[idx].set(rows.astype(slots.dtype))

            self._gather, self._scatter = gather, scatter
        return self._gather, self._scatter

    def _table(self):
        """The hot table as the ``(rows, slots)`` array the row moves
        index. A store that keeps planes (learners/table.py) crosses to
        it here, counted under ``table_cross``, and stays stacked."""
        stacked = getattr(self.hot, "_stacked", None)
        return stacked() if stacked is not None else self.hot.slots

    # -- tier moves (consumer thread, stream order) ---------------------

    def _resolve_pending(self) -> None:  # owner-thread: consumer
        if self._pending is None:
            return
        buckets, rows_dev, n = self._pending
        self._pending = None
        with trace.span("page:d2h", cat="page"):
            # the copy was started async one plan ago, so this read
            # usually completes without blocking the device
            # host-sync: writeback must land in the cold tier before
            # any later fill re-reads these buckets
            rows = np.asarray(rows_dev)
        self.cold[buckets] = rows[:n]
        with self._lock:
            self._bytes_d2h += n * self._row_bytes

    def apply_plan(self, plan: PagePlan) -> None:  # owner-thread: consumer
        """Execute one plan's tier moves against the hot table. Must be
        called on the consumer thread, once per plan, in stream order,
        BEFORE the step that consumes the remapped batch."""
        gather, scatter = self._ops()
        self._resolve_pending()
        late = plan.late
        n_late = int(late.sum())
        if n_late:
            late_rows = self.cold[plan.miss_buckets[late]]
        if plan.victim_slots.size:
            with trace.span("page:evict", cat="page"):
                idx_p, _ = _pad_pair(plan.victim_slots,
                                     np.empty((plan.victim_slots.size, 0)),
                                     self.page_chunk)
                rows_dev = gather(self._table(), idx_p)
                rows_dev.copy_to_host_async()
            self._pending = (plan.victim_buckets, rows_dev,
                             int(plan.victim_slots.size))
        if plan.staged_rows is not None:
            idx_d, rows_d = plan.staged_rows
            self.hot.slots = scatter(self._table(), idx_d, rows_d)
        if n_late:
            idx_p, rows_p = _pad_pair(plan.miss_slots[late], late_rows,
                                      self.page_chunk)
            dev = self._ring.prepare((idx_p, rows_p),
                                     put_label="page:h2d")
            self.hot.slots = scatter(self._table(), dev[0], dev[1])
            with self._lock:
                self._bytes_h2d += n_late * self._row_bytes

    def stage_fresh(self, plan: PagePlan) -> None:
        """Ship a plan's fresh page-in rows to the device through the
        paging ring entry (``page:h2d``). Runs on the feed's transfer
        thread — safe because fresh buckets' cold rows are immutable
        inside the pipeline window (pager.py) — or inline on the
        consumer in the serial path."""
        fresh = plan.fresh
        n = int(fresh.sum())
        if not n:
            return
        idx_p, rows_p = _pad_pair(plan.miss_slots[fresh],
                                  self.cold[plan.miss_buckets[fresh]],
                                  self.page_chunk)
        plan.staged_rows = self._ring.prepare((idx_p, rows_p),
                                              put_label="page:h2d")
        with self._lock:
            self._bytes_h2d += n * self._row_bytes

    def flush(self) -> np.ndarray:  # owner-thread: consumer
        """Resolve the pending writeback and copy every occupied hot
        slot back to the cold tier; returns the cold table — after this,
        ``cold`` equals the full-size table a non-paged run would hold
        (the parity oracle surface)."""
        gather, _ = self._ops()
        self._resolve_pending()
        occ = np.flatnonzero(self.pager.bucket_of >= 0)
        if occ.size:
            buckets = self.pager.bucket_of[occ]
            idx_p, _ = _pad_pair(occ, np.empty((occ.size, 0)),
                                 self.page_chunk)
            with trace.span("page:d2h", cat="page"):
                # host-sync: flush is the stream-end barrier — cold
                # must hold the final rows before readers touch it
                rows = np.asarray(gather(self._table(), idx_p))
            self.cold[buckets] = rows[:occ.size]
            with self._lock:
                self._bytes_d2h += occ.size * self._row_bytes
        return self.cold

    # -- the feed: plan + remap + stage through the DeviceFeed ring -----

    def _remap(self, batch, plan: PagePlan):
        """Global bucket ids -> hot slot ids on a host SparseBatch.
        Padded keys (key_mask 0) map to slot 0 — their deltas are masked
        to zero inside the step, same as bucket-0 aliasing in the
        full-size path."""
        keys = np.asarray(batch.uniq_keys)
        mask = np.asarray(batch.key_mask) > 0
        slots = np.zeros(keys.shape, np.int32)
        slots[mask] = plan.slots[
            np.searchsorted(plan.uniq, keys[mask].astype(np.int64))]
        return dataclasses.replace(batch, uniq_keys=slots)

    def feed(self, source: Iterable[Any], *, workers: int = 2,
             ring_depth: int = 2):
        """Wrap a host-SparseBatch stream in a DeviceFeed that plans
        residency on the dispatcher, remaps keys on the prep workers,
        and stages fresh page rows + the batch from the transfer thread.
        Yields ``(plan, device_batch)`` pairs; the consumer must call
        :meth:`apply_plan` on each plan before stepping the batch."""
        need = late_window_for(workers, ring_depth)
        if self.pager.late_window < need:
            raise ValueError(
                f"late_window {self.pager.late_window} below the "
                f"pipeline lookahead bound {need} for workers={workers} "
                f"ring_depth={ring_depth}; raise late_window (the "
                "page_prefetch knob) or shrink the pipeline")
        from wormhole_tpu.data.pipeline import DeviceFeed

        def seq_ctx(batch):
            keys = np.asarray(batch.uniq_keys)
            mask = np.asarray(batch.key_mask) > 0
            return self.pager.plan(keys[mask].astype(np.int64))

        def prep(batch, plan):
            return plan, self._remap(batch, plan)

        def transfer(payload):
            plan, hb = payload
            self.stage_fresh(plan)
            return plan, _jax().device_put(hb)

        return DeviceFeed(source, prep, workers=workers,
                          ring_depth=ring_depth, seq_ctx=seq_ctx,
                          transfer=transfer, name="bigmodel")

    def train_sparse(self, source: Iterable[Any], tau: float = 0.0, *,
                     workers: int = 2, ring_depth: int = 2) -> int:
        """Drive a host-batch stream end to end: feed → apply_plan →
        hot train_step, in stream order. Returns the batch count. The
        convenience loop bench.py and the determinism tests share."""
        n = 0
        for plan, batch in self.feed(source, workers=workers,
                                     ring_depth=ring_depth):
            self.apply_plan(plan)
            self.hot.train_step(batch, tau)
            n += 1
        return n

    # -- accounting -----------------------------------------------------

    def stats(self) -> dict:
        out = self.pager.stats()
        with self._lock:
            out["bytes_h2d"] = self._bytes_h2d
            out["bytes_d2h"] = self._bytes_d2h
        out.update(self._ring.stats())
        return out

    def to_registry(self, reg=None) -> None:
        """Publish paging counters (``page/*``) through the metrics
        registry — bench reads them back as registry deltas."""
        if reg is None:
            from wormhole_tpu.obs.metrics import default_registry
            reg = default_registry()
        s = self.stats()
        for k in ("bytes_h2d", "bytes_d2h", "pages_in", "pages_out",
                  "late_fills", "hits", "misses"):
            reg.counter(f"page/{k}",
                        help=f"bigmodel paging: cumulative {k}"
                        ).inc(float(s[k]))
        reg.gauge("page/hit_rate",
                  help="bigmodel paging: hot-tier hit rate "
                       "(hits / (hits+misses))").value = s["hit_rate"]
