"""Staged host→device ingest pipeline (parse ∥ pad ∥ transfer).

The reference keeps a dedicated ``ThreadedParser`` behind every minibatch
iterator (``learn/linear/base/minibatch_iter.h:50``) so text parsing
overlaps the SGD step. Our block parsers already prefetch on a thread
(``MinibatchIter``/``PackedFeed``), but everything downstream of the parse
— localization, the CSR→padded-dense scatter, ``device_put`` — ran
serially on the consumer thread, in lockstep with the device step.

``DeviceFeed`` generalizes the prefetch idea to the whole feed path:

    source ──► dispatcher ──► work queue ──► prep workers (pool)
                   │                              │
               seq_ctx()                    results, by seq
             (sequential,                         │
              in order)                           ▼
                                     transfer thread (reorders to
                                     stream order, optional collate,
                                     device_put) ──► ring ──► consumer

* the **dispatcher** iterates ``source`` and runs ``seq_ctx(item)``
  sequentially in stream order — shape-bucket state (monotone max_nnz
  growth) lives here, so every batch sees exactly the bucket value the
  serial path would have given it, no matter which worker pads it;
* ``workers`` **prep workers** run ``prep(item, ctx)`` concurrently
  (localize + pad, or block read, or text chunk assembly — anything
  thread-safe and stateless);
* the **transfer thread** restores stream order by sequence number,
  optionally folds results through a sequential ``collate`` (stateful
  re-blocking, e.g. text chunks → fixed-row blocks), runs ``transfer``
  (``jax.device_put`` by default) and keeps a ``ring_depth``-deep ring
  of device-resident batches ahead of the consumer.

Contracts preserved from the serial path:

* **deterministic order** — batches arrive exactly as the serial path
  would produce them;
* **exception propagation** — an error in any stage surfaces at the
  consumer, after every batch that precedes it in stream order;
* **clean shutdown** — a consumer that abandons the iterator mid-stream
  (GC of the generator) stops every thread; all blocking operations are
  timed polls against a stop event, the idiom of ``MinibatchIter``;
* ``workers=0`` — run every stage inline on the consumer thread (the
  serial fallback; also the parity oracle for tests).

Per-stage busy/stall seconds and ring occupancy are accumulated under a
lock and surfaced through ``stats()`` / ``drain_stats(timer, prefix)``
so the bench can report where feed time goes. Every accounted interval
is also a trace span (``<feed>:<stage>``, ``<feed>:<stage>_stall``),
opened and closed around the work on the thread that does it
(``_stage``), so a profiler capture shows the dispatcher, the prep pool,
the transfer thread and the consumer on their own lines beside the
device's ops.
"""

from __future__ import annotations

import queue
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Iterable, Optional

from wormhole_tpu.obs import trace

__all__ = ["DeviceFeed", "group_blocks"]

_END = object()
_EMPTY = object()       # a timed ring get that found nothing


def group_blocks(source: Iterable[Any], size: int, *,
                 clock: Callable[[], float] = time.monotonic):
    """Group consecutive ``source`` items into runs of ``size``.

    Yields ``([items], skew_s)`` in stream order; the final group may be
    short (the caller pads it). ``skew_s`` is the arrival-time spread
    between the group's first and last member on this thread — the
    per-group straggler signal the mesh dispatch telemetry reports (a
    slow member shows up as the whole group's wait)."""
    group: list = []
    t0 = 0.0
    for item in source:
        now = clock()
        if not group:
            t0 = now
        group.append(item)
        if len(group) == size:
            yield group, now - t0
            group = []
    if group:
        yield group, clock() - t0


class _StageError:
    """An exception captured in a pipeline stage, delivered to the
    consumer in sequence position (so batches that precede the failure
    still arrive, then the error raises — same as the serial path)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException) -> None:
        self.exc = exc


class DeviceFeed:
    """Chain source → prep workers → in-order transfer → device ring.

    Parameters
    ----------
    source:  iterable of raw items (blocks, chunks, indices…). Iterated
             on the dispatcher thread, in order.
    prep:    ``prep(item, ctx) -> result``; runs on the worker pool, so
             it must be thread-safe and must not mutate shared state.
             ``None`` passes items through.
    workers: worker-pool size; ``0`` runs the whole chain inline
             (serial fallback — no threads at all).
    ring_depth: device-resident batches kept ahead of the consumer.
    seq_ctx: ``seq_ctx(item) -> ctx``; runs on the dispatcher thread
             sequentially IN STREAM ORDER before the item is handed to
             a worker — the only safe place for order-dependent state
             like monotone shape buckets.
    collate: ``collate(result) -> iterable of payloads``; runs on the
             transfer thread sequentially in stream order (stateful
             re-blocking allowed). Called once more with ``None`` at
             end of stream to flush a buffered tail. A call's payloads
             are taken whole before the first is transferred, so the
             ``collate`` stage's seconds are the re-blocking's.
    transfer: ``transfer(payload) -> device item``; defaults to
             ``jax.device_put``.
    bytes_read: callable forwarded by :meth:`bytes_read` (accounting
             delegation to the underlying reader).
    on_close: called exactly once when iteration ends for any reason
             (exhaustion, error, abandonment) — close per-thread file
             handles here.
    prep_label: display name for the prep stage in trace spans and the
             ``drain_stats`` timer merge (default: ``prep`` spans, the
             historical ``pad`` timer key). The online tile-encode feed
             passes ``"encode"`` so its worker stage shows up as what it
             is instead of as padding.
    """

    def __init__(self, source: Iterable[Any],
                 prep: Optional[Callable[[Any, Any], Any]] = None,
                 *, workers: int = 2, ring_depth: int = 2,
                 seq_ctx: Optional[Callable[[Any], Any]] = None,
                 collate: Optional[Callable[[Any], Iterable[Any]]] = None,
                 transfer: Optional[Callable[[Any], Any]] = None,
                 bytes_read: Optional[Callable[[], int]] = None,
                 on_close: Optional[Callable[[], None]] = None,
                 name: str = "feed",
                 prep_label: Optional[str] = None) -> None:
        if ring_depth < 1:
            raise ValueError("ring_depth must be >= 1")
        self.source = source
        self.prep = prep
        self.workers = max(int(workers), 0)
        self.ring_depth = ring_depth
        self.seq_ctx = seq_ctx
        self.collate = collate
        self._transfer = transfer
        self._bytes_read = bytes_read
        self._on_close = on_close
        self.name = name
        self.prep_label = prep_label
        self._lock = threading.Lock()
        # Stage accumulators are written from the dispatcher, prep-pool,
        # transfer and consumer threads; every read-modify-write goes
        # through _stage() or an explicit `with self._lock` block.
        self._busy = {"parse": 0.0, "prep": 0.0, "collate": 0.0,  # guarded-by: _lock
                      "put": 0.0}
        self._stall = {"parse": 0.0, "prep": 0.0, "put": 0.0,  # guarded-by: _lock
                       "consume": 0.0}
        self._batches = 0  # guarded-by: _lock
        self._ring_max = 0  # guarded-by: _lock
        # (stall?, key, label) -> span name, composed once a feed and not
        # once an interval; racing writers store the same string
        self._span_names: dict = {}
        self._threads: list = []

    # -- stats ---------------------------------------------------------------

    @contextmanager
    def _stage(self, table: dict, key: str, label: Optional[str] = None):
        """One accounted interval of one stage: a trace span around the
        body on the thread that does the work, its seconds added to
        ``table[key]`` under the lock when it ends."""
        stall = table is self._stall
        name = self._span_names.get((stall, key, label))
        if name is None:
            name = label or (self.prep_label
                             if key == "prep" and self.prep_label else key)
            # a label carrying its own namespace (e.g. "page:h2d") IS
            # the span name — it resolves through SPAN_TABLE directly
            # instead of the <feed>:<stage> rule
            if ":" not in name:
                name = f"{self.name}:{name}{'_stall' if stall else ''}"
            self._span_names[(stall, key, label)] = name
        t0 = time.monotonic()
        try:
            with trace.span(name, cat="feed"):
                yield
        finally:
            dt = time.monotonic() - t0
            with self._lock:
                table[key] = table.get(key, 0.0) + dt

    def stats(self) -> dict:
        """Snapshot: per-stage busy/stall seconds (worker seconds sum
        over the pool, so busy can exceed wall time; ``collate`` stays
        0 without a collate), batches delivered, and the deepest ring
        occupancy observed."""
        with self._lock:
            out = {f"{k}": v for k, v in self._busy.items()}
            out.update({f"{k}_stall": v for k, v in self._stall.items()})
            out["batches"] = self._batches
            out["ring_max"] = self._ring_max
            return out

    def drain_stats(self, timer=None, prefix: str = "") -> dict:
        """Return the stats snapshot, reset the accumulators, and (when
        ``timer`` is given) merge the stage seconds into it as
        ``{prefix}parse/pad/put`` + ``{prefix}*_stall`` entries
        (``{prefix}collate`` too where the feed has a collate)."""
        with self._lock:
            snap = {k: v for k, v in self._busy.items()}
            snap.update({f"{k}_stall": v for k, v in self._stall.items()})
            snap["batches"] = self._batches
            snap["ring_max"] = self._ring_max
            for k in self._busy:
                self._busy[k] = 0.0
            for k in self._stall:
                self._stall[k] = 0.0
            self._batches = 0
            self._ring_max = 0
        if timer is not None:
            n = max(snap["batches"], 1)
            lbl = self.prep_label or "pad"
            timer.add(prefix + "parse", snap["parse"], n)
            timer.add(prefix + lbl, snap["prep"], n)
            if self.collate:
                timer.add(prefix + "collate", snap["collate"], n)
            timer.add(prefix + "put", snap["put"], n)
            timer.add(prefix + "feed_stall", snap["consume_stall"], n)
            timer.add(prefix + f"{lbl}_stall", snap["prep_stall"], n)
            timer.add(prefix + "put_stall", snap["put_stall"], n)
        return snap

    def bytes_read(self) -> int:
        return self._bytes_read() if self._bytes_read is not None else 0

    # -- iteration -----------------------------------------------------------

    def __iter__(self):
        if self.workers == 0:
            return self._iter_serial()
        return self._iter_pipelined()

    def _default_transfer(self):
        if self._transfer is not None:
            return self._transfer
        import jax
        return jax.device_put

    def prepare(self, item: Any, ctx: Any = None, *,
                prep_label: Optional[str] = None,
                put_label: Optional[str] = None):
        """Run ONE item through prep + transfer inline and return the
        device-resident result — the pad/transfer machinery as a
        callable instead of a stream. The serving front-end drives the
        pipeline in reverse with this: requests arrive *from* callers
        rather than being pulled from a source, so admission owns the
        loop and hands each flush group here for the same prep/put
        accounting (and trace spans) a streaming feed gets. No collate,
        no on_close: one item in, one device item out.

        ``prep_label``/``put_label`` rename the stage spans for callers
        whose items are not ingest-shaped — the bigmodel pager routes
        its page-row H2D transfers here with ``put_label="page:h2d"``
        so paging reuses this one transfer path (stage accounting,
        spans, batch count) instead of growing a second one."""
        transfer = self._default_transfer()
        with self._stage(self._busy, "prep", prep_label):
            res = self.prep(item, ctx) if self.prep else item
        with self._stage(self._busy, "put", put_label):
            out = transfer(res)
        with self._lock:
            self._batches += 1
        return out

    def _close(self) -> None:
        """``on_close``, on the consumer's thread, under a
        ``<feed>:close`` span (a mapped source unmaps here)."""
        if self._on_close is not None:
            with trace.span(f"{self.name}:close", cat="feed"):
                self._on_close()

    def _next_item(self, it):
        """The ``parse`` stage: the source's next item and its
        ``seq_ctx``, as ``(item, ctx)``; ``(_END, None)`` at the end."""
        with self._stage(self._busy, "parse"):
            try:
                item = next(it)
            except StopIteration:
                return _END, None
            return item, (self.seq_ctx(item) if self.seq_ctx else None)

    def _collated(self, res, end: bool = False) -> Iterable[Any]:
        """The ``collate`` stage: the payloads ``res`` folds into (at
        the stream's ``end``, the flushed tail), taken whole inside the
        stage."""
        if not self.collate:
            return () if end else (res,)
        with self._stage(self._busy, "collate"):
            return list(self.collate(None if end else res))

    def _iter_serial(self):
        """Inline fallback: every stage on the consumer thread, same
        order/exception semantics, no threads (``pipeline_workers=0``)."""
        transfer = self._default_transfer()
        try:
            it = iter(self.source)
            while True:
                item, ctx = self._next_item(it)
                end = item is _END      # then: the collate's tail
                if not end:
                    with self._stage(self._busy, "prep"):
                        item = self.prep(item, ctx) if self.prep else item
                for payload in self._collated(item, end):
                    with self._stage(self._busy, "put"):
                        out = transfer(payload)
                    with self._lock:
                        self._batches += 1
                    yield out
                if end:
                    break
        finally:
            self._close()

    def _iter_pipelined(self):
        transfer = self._default_transfer()
        stop = threading.Event()
        work_q: "queue.Queue" = queue.Queue(maxsize=max(2 * self.workers, 2))
        ring: "queue.Queue" = queue.Queue(maxsize=self.ring_depth)
        done: dict = {}              # seq -> result | _StageError
        cond = threading.Condition()
        total = [None]               # [stream length] once known

        def put_or_stop(q: "queue.Queue", item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def dispatcher() -> None:
            seq = 0
            try:
                it = iter(self.source)
                while not stop.is_set():
                    item, ctx = self._next_item(it)
                    if item is _END:
                        break
                    with self._stage(self._stall, "parse"):
                        ok = put_or_stop(work_q, (seq, item, ctx))
                    if not ok:
                        return
                    seq += 1
            except BaseException as e:
                with cond:
                    done[seq] = _StageError(e)
                    total[0] = seq + 1
                    cond.notify_all()
            else:
                with cond:
                    total[0] = seq
                    cond.notify_all()
            finally:
                for _ in range(self.workers):
                    if not put_or_stop(work_q, _END):
                        break

        def worker() -> None:
            while not stop.is_set():
                with self._stage(self._stall, "prep"):
                    try:
                        task = work_q.get(timeout=0.2)
                    except queue.Empty:
                        continue
                if task is _END:
                    return
                seq, item, ctx = task
                with self._stage(self._busy, "prep"):
                    try:
                        res = self.prep(item, ctx) if self.prep else item
                    except BaseException as e:
                        res = _StageError(e)
                with cond:
                    done[seq] = res
                    cond.notify_all()

        def emit(payload) -> bool:
            """device_put + ring put; False when the consumer is gone."""
            try:
                with self._stage(self._busy, "put"):
                    dev = transfer(payload)
            except BaseException as e:
                put_or_stop(ring, _StageError(e))
                return False
            if not put_or_stop(ring, dev):
                return False
            with self._lock:
                self._ring_max = max(self._ring_max, ring.qsize())
            if trace.enabled():
                # counter track: ring depth over time renders as a line
                # chart next to the stage spans (empty ring under a
                # consume_stall = starved feed, full = device-bound)
                trace.counter(f"{self.name}:ring", ring.qsize(),
                              cat="feed")
            return True

        def transferrer() -> None:
            nxt = 0
            while not stop.is_set():
                with self._stage(self._stall, "put"), cond:
                    while nxt not in done and \
                            (total[0] is None or nxt < total[0]):
                        if stop.is_set():
                            return
                        cond.wait(timeout=0.2)
                    end = total[0] is not None and nxt >= total[0]
                    res = None if end else done.pop(nxt)
                nxt += 1
                if isinstance(res, _StageError):
                    put_or_stop(ring, res)
                    return
                try:
                    # at the end: the collate's tail
                    payloads = self._collated(res, end)
                except BaseException as e:
                    put_or_stop(ring, _StageError(e))
                    return
                for payload in payloads:
                    if not emit(payload):
                        return
                if end:
                    put_or_stop(ring, _END)
                    return

        threads = [threading.Thread(target=dispatcher, daemon=True,
                                    name=f"{self.name}-dispatch")]
        threads += [threading.Thread(target=worker, daemon=True,
                                     name=f"{self.name}-prep{i}")
                    for i in range(self.workers)]
        xfer = threading.Thread(target=transferrer, daemon=True,
                                name=f"{self.name}-xfer")
        threads.append(xfer)
        self._threads = threads
        for t in threads:
            t.start()
        try:
            while True:
                with self._stage(self._stall, "consume"):
                    try:
                        item = ring.get(timeout=0.5)
                    except queue.Empty:
                        item = _EMPTY
                if item is _EMPTY:
                    if not xfer.is_alive():
                        raise RuntimeError(
                            f"{self.name}: transfer thread died without "
                            "delivering end-of-stream")
                    continue
                if item is _END:
                    break
                if isinstance(item, _StageError):
                    raise item.exc
                with self._lock:
                    self._batches += 1
                yield item
        finally:
            stop.set()
            self._close()
