"""DeviceFeed contracts: ordering, exception propagation, clean shutdown,
accounting — plus end-to-end parity of the pipelined vs serial ingest
paths (AsyncSGD sparse batches, PackedFeed crec blocks, TextCRecFeed).

The serial (``workers=0``) path is the parity oracle everywhere: the
pipeline must be an invisible optimization.
"""

import gc
import threading
import time

import numpy as np
import pytest

from wormhole_tpu.data.pipeline import DeviceFeed

NB = 1 << 12


def _ident(x):
    return x


def _jittered_prep(item, _ctx):
    # deterministic per-item jitter so worker completion order scrambles
    time.sleep((item * 7 % 5) / 1000.0)
    return item * 10


def _collect(feed):
    return list(feed)


# -- ordering / determinism --------------------------------------------------

def test_ordering_matches_serial():
    serial = _collect(DeviceFeed(range(40), _jittered_prep, workers=0,
                                 transfer=_ident))
    piped = _collect(DeviceFeed(range(40), _jittered_prep, workers=4,
                                transfer=_ident))
    assert piped == serial == [i * 10 for i in range(40)]


def test_seq_ctx_runs_in_stream_order():
    # order-dependent ctx (running max) must see items in stream order
    # even though prep completion order scrambles across the pool
    items = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9]

    def make_feed(workers):
        state = {"mx": 0}

        def ctx(item):
            state["mx"] = max(state["mx"], item)
            return state["mx"]

        return DeviceFeed(items, lambda it, c: (it, c), workers=workers,
                          seq_ctx=ctx, transfer=_ident)

    oracle, run = [], 0
    for it in items:
        run = max(run, it)
        oracle.append((it, run))
    assert _collect(make_feed(0)) == oracle
    assert _collect(make_feed(3)) == oracle


@pytest.mark.parametrize("workers", [0, 3])
def test_collate_reblocks_and_flushes_tail(workers):
    # 10 items of 3 ints re-blocked into chunks of 4: collate is stateful
    # and sequential; the None call must flush the 2-int tail
    def make_fold():
        buf = []

        def fold(res):
            if res is None:
                out, buf[:] = [tuple(buf)] if buf else [], []
                return out
            buf.extend(res)
            out = []
            while len(buf) >= 4:
                out.append(tuple(buf[:4]))
                del buf[:4]
            return out

        return fold

    items = [[3 * i + j for j in range(3)] for i in range(10)]
    flat = [v for it in items for v in it]
    expect = [tuple(flat[i:i + 4]) for i in range(0, 30, 4)]
    got = _collect(DeviceFeed(items, workers=workers, collate=make_fold(),
                              transfer=_ident))
    assert got == expect


# -- exception propagation ---------------------------------------------------

def _bad_source():
    yield from range(5)
    raise ValueError("source boom")


@pytest.mark.parametrize("workers", [0, 3])
def test_exception_from_source_after_prefix(workers):
    feed = DeviceFeed(_bad_source(), _jittered_prep, workers=workers,
                      transfer=_ident)
    got = []
    with pytest.raises(ValueError, match="source boom"):
        for x in feed:
            got.append(x)
    # every batch preceding the failure still arrives, in order
    assert got == [i * 10 for i in range(5)]


@pytest.mark.parametrize("workers", [0, 3])
def test_exception_from_prep(workers):
    def prep(item, _ctx):
        if item == 7:
            raise RuntimeError("prep boom")
        return item

    got = []
    with pytest.raises(RuntimeError, match="prep boom"):
        for x in DeviceFeed(range(12), prep, workers=workers,
                            transfer=_ident):
            got.append(x)
    assert got == list(range(7))


@pytest.mark.parametrize("workers", [0, 2])
def test_exception_from_collate(workers):
    def collate(res):
        if res == 4:
            raise KeyError("collate boom")
        return () if res is None else (res,)

    got = []
    with pytest.raises(KeyError, match="collate boom"):
        for x in DeviceFeed(range(8), workers=workers, collate=collate,
                            transfer=_ident):
            got.append(x)
    assert got == list(range(4))


@pytest.mark.parametrize("workers", [0, 2])
def test_exception_from_transfer(workers):
    def transfer(payload):
        if payload == 3:
            raise OSError("transfer boom")
        return payload

    got = []
    with pytest.raises(OSError, match="transfer boom"):
        for x in DeviceFeed(range(8), workers=workers, transfer=transfer):
            got.append(x)
    assert got == list(range(3))


# -- shutdown ----------------------------------------------------------------

def _threads_dead(feed, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not any(t.is_alive() for t in feed._threads):
            return True
        time.sleep(0.05)
    return False


def test_early_abandon_stops_threads_and_closes():
    closed = []
    feed = DeviceFeed(range(1000),
                      lambda it, _c: (time.sleep(0.002), it)[1],
                      workers=3, transfer=_ident,
                      on_close=lambda: closed.append(1))
    it = iter(feed)
    assert next(it) == 0 and next(it) == 1
    # consumer walks away mid-stream: generator GC must stop every thread
    del it
    gc.collect()
    assert _threads_dead(feed), [t.name for t in feed._threads
                                 if t.is_alive()]
    assert closed == [1]


def test_exhaustion_stops_threads_and_closes_once():
    closed = []
    feed = DeviceFeed(range(20), workers=2, transfer=_ident,
                      on_close=lambda: closed.append(1))
    assert _collect(feed) == list(range(20))
    assert _threads_dead(feed)
    assert closed == [1]


def test_workers0_spawns_no_threads():
    before = threading.active_count()
    feed = DeviceFeed(range(10), workers=0, transfer=_ident)
    assert _collect(feed) == list(range(10))
    assert feed._threads == []
    assert threading.active_count() == before


# -- accounting --------------------------------------------------------------

@pytest.mark.parametrize("workers", [0, 2])
def test_bytes_read_delegates(workers):
    box = {"n": 0}

    def prep(item, _ctx):
        box["n"] += 8
        return item

    feed = DeviceFeed(range(6), prep, workers=workers, transfer=_ident,
                      bytes_read=lambda: box["n"])
    _collect(feed)
    assert feed.bytes_read() == 48


def test_stats_drain_resets_and_feeds_timer():
    from wormhole_tpu.utils.timer import Timer
    feed = DeviceFeed(range(12), _jittered_prep, workers=2,
                      transfer=_ident)
    assert len(_collect(feed)) == 12
    snap = feed.stats()
    assert snap["batches"] == 12 and snap["prep"] > 0.0
    timer = Timer()
    feed.drain_stats(timer, "x_")
    for key in ("x_parse", "x_pad", "x_put", "x_feed_stall",
                "x_pad_stall", "x_put_stall"):
        assert key in timer.totals
    drained = feed.stats()
    assert drained["batches"] == 0 and drained["prep"] == 0.0


# -- stage spans (the ring's view of the feed's stages) ------------------------

def _ring_spans(feed_factory):
    """Every complete event the ring holds after one pass of the feed:
    (name, tid, start_us, end_us)."""
    from wormhole_tpu.obs import trace
    trace.enable()
    try:
        out = _collect(feed_factory())
        evs = [(e["name"], e["tid"], e["ts"], e["ts"] + e["dur"])
               for e in trace.events() if e["ph"] == "X"]
        assert all(e.get("cat") == "feed" for e in trace.events())
    finally:
        trace.disable()
    return out, evs


def test_stage_spans_names_and_threads_pipelined():
    """The spans ``_acc`` composed, composed by ``_stage`` now:
    ``<feed>:<stage>`` for work, ``<feed>:<stage>_stall`` for waits, the
    prep label in place of ``prep``, each on the thread that did it."""
    def fold(res):
        return [] if res is None else [res]

    got, evs = _ring_spans(lambda: DeviceFeed(
        range(8), _jittered_prep, workers=2, transfer=_ident,
        collate=fold, name="f", prep_label="encode"))
    assert got == [i * 10 for i in range(8)]
    names = {n for n, *_ in evs}
    assert names == {"f:parse", "f:parse_stall", "f:encode",
                     "f:encode_stall", "f:collate", "f:put", "f:put_stall",
                     "f:consume_stall"}
    tids = {}
    for n, tid, *_ in evs:
        tids.setdefault(n, set()).add(tid)
    me = threading.get_ident()
    assert tids["f:consume_stall"] == {me}
    assert len(tids["f:parse"]) == 1 and tids["f:parse"] != {me}
    assert tids["f:parse_stall"] == tids["f:parse"]
    assert tids["f:put"] == tids["f:collate"] == tids["f:put_stall"]
    assert len(tids["f:put"]) == 1
    assert tids["f:encode"] == tids["f:encode_stall"]
    assert not tids["f:encode"] & (tids["f:put"] | tids["f:parse"] | {me})
    # one busy span an item a stage (parse has the end-of-stream probe;
    # collate the flush of the tail)
    count = lambda n: sum(1 for m, *_ in evs if m == n)   # noqa: E731
    assert count("f:encode") == 8 and count("f:put") == 8
    assert count("f:parse") == 9 and count("f:collate") == 9


def test_stage_spans_do_not_overlap_on_a_thread_and_sum_to_the_stats():
    feed = DeviceFeed(range(12), _jittered_prep, workers=2,
                      transfer=_ident, name="f")
    _got, evs = _ring_spans(lambda: feed)
    by_tid = {}
    for n, tid, s, e in evs:
        by_tid.setdefault(tid, []).append((s, e, n))
    for spans in by_tid.values():      # stages are sequential on a thread
        spans.sort()
        for (s0, e0, n0), (s1, _e1, n1) in zip(spans, spans[1:]):
            assert e0 <= s1 + 1e-3, (n0, n1)
    snap = feed.stats()
    for stage, key in (("f:prep", "prep"), ("f:put", "put"),
                       ("f:consume_stall", "consume_stall"),
                       ("f:prep_stall", "prep_stall")):
        total = sum(e - s for n, _t, s, e in evs if n == stage) / 1e6
        assert snap[key] == pytest.approx(total, rel=0.05, abs=2e-3), stage
    assert snap["collate"] == 0.0      # no collate, no stage, no span
    assert not any(n == "f:collate" for n, *_ in evs)


def test_stage_spans_serial_and_prepare_labels():
    """workers=0: every stage's span on the consumer's thread; and
    ``prepare``'s labels: a label with its own namespace IS the span."""
    def fold(res):
        return [] if res is None else [res]

    _got, evs = _ring_spans(lambda: DeviceFeed(
        range(4), workers=0, transfer=_ident, collate=fold, name="s"))
    assert {n for n, *_ in evs} == {"s:parse", "s:prep", "s:collate",
                                    "s:put"}
    assert {tid for _n, tid, *_ in evs} == {threading.get_ident()}
    from wormhole_tpu.obs import trace
    feed = DeviceFeed((), lambda it, c: it + 1, workers=0,
                      transfer=_ident, name="pager")
    trace.enable()
    try:
        assert feed.prepare(1) == 2
        assert feed.prepare(2, put_label="page:h2d") == 3
        names = [e["name"] for e in trace.events()]
    finally:
        trace.disable()
    assert names == ["pager:prep", "pager:put", "pager:prep", "page:h2d"]
    assert feed.stats()["batches"] == 2


def test_on_close_runs_under_a_close_span():
    closed = []
    _got, evs = _ring_spans(lambda: DeviceFeed(
        range(3), workers=1, transfer=_ident, name="f",
        on_close=lambda: closed.append(1)))
    assert closed == [1]
    assert [tid for n, tid, *_ in evs if n == "f:close"] == [
        threading.get_ident()]


# -- double buffering (acceptance: ≥2 batches device-resident) ---------------

def test_ring_holds_two_device_batches_while_consumer_mid_step():
    import jax
    arrs = [np.full((64, 8), i, np.float32) for i in range(12)]
    feed = DeviceFeed(arrs, workers=2, ring_depth=2)  # default device_put
    seen_depth = 0
    for i, dev in enumerate(feed):
        assert isinstance(dev, jax.Array)
        np.testing.assert_array_equal(np.asarray(dev), arrs[i])
        # emulate a compute step; the transfer thread refills the ring
        # behind our back while we are mid-step
        time.sleep(0.03)
        seen_depth = max(seen_depth, feed.stats()["ring_max"])
    assert seen_depth >= 2, f"ring never double-buffered ({seen_depth})"


# -- end-to-end parity: the real feeds ---------------------------------------

def _write_libsvm(path, rng, n=240, f=64):
    lines = []
    for _ in range(n):
        nnz = rng.integers(3, 14)
        ids = np.sort(rng.choice(f, size=nnz, replace=False))
        feats = " ".join(f"{j}:{rng.standard_normal():.4f}" for j in ids)
        lines.append(f"{int(rng.random() < 0.5)} {feats}")
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def _leaves(batch):
    import jax
    return jax.tree_util.tree_leaves(batch)


def test_async_sgd_batches_parity(rng, tmp_path):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime
    from wormhole_tpu.utils.config import Config
    path = str(tmp_path / "t.libsvm")
    _write_libsvm(path, rng)

    def batches(workers):
        app = AsyncSGD(Config(train_data=path, minibatch=64,
                              num_buckets=NB, disp_itv=1e9,
                              pipeline_workers=workers),
                       MeshRuntime.create())
        return list(app._batches(path, 0, 1))

    ser, par = batches(0), batches(3)
    assert len(ser) == len(par) > 1
    for a, b in zip(ser, par):
        assert getattr(a, "num_real", None) == getattr(b, "num_real", None)
        for la, lb in zip(_leaves(a), _leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_packed_feed_parity_and_bytes_read(rng, tmp_path):
    from wormhole_tpu.data.crec import CRecWriter, PackedFeed, SENTINEL_KEY
    path = str(tmp_path / "t.crec")
    rows, nnz = 200, 6
    keys = rng.integers(1, 1 << 31, size=(rows, nnz), dtype=np.uint32)
    keys[rng.random((rows, nnz)) < 0.1] = SENTINEL_KEY
    labels = (rng.random(rows) < 0.4).astype(np.uint8)
    with CRecWriter(path, nnz=nnz, block_rows=32) as w:
        w.append(keys, labels)

    def run(workers):
        feed = PackedFeed(path, workers=workers, device_put=_ident)
        out = [(np.asarray(h).tobytes(), r) for _dev, h, r in feed]
        return out, feed.bytes_read

    ser, ser_bytes = run(0)
    par, par_bytes = run(2)
    assert par == ser and len(ser) == -(-rows // 32)
    assert ser_bytes == par_bytes > 0


def test_text_crec_feed_parity(rng, tmp_path):
    from wormhole_tpu.data.crec import TextCRecFeed
    lines = []
    for _ in range(120):
        ints = "\t".join(str(rng.integers(0, 1000)) if rng.random() > 0.2
                         else "" for _ in range(13))
        cats = "\t".join(f"{rng.integers(0, 1 << 32):08x}"
                         if rng.random() > 0.2 else "" for _ in range(26))
        lines.append(f"{int(rng.random() < 0.3)}\t{ints}\t{cats}")
    src = tmp_path / "c.txt"
    src.write_text("\n".join(lines) + "\n")

    def run(workers):
        feed = TextCRecFeed(str(src), text_fmt="criteo", nnz=39,
                            block_rows=32, device_put=_ident,
                            workers=workers)
        return [(np.asarray(h).tobytes(), r) for _dev, h, r in feed]

    assert run(2) == run(0)


# -- satellite regressions ---------------------------------------------------

def test_upload_buffer_reclose_retries():
    """A failed upload must keep the bytes and retry on the next close()
    — not silently no-op (the retry-by-reclose contract)."""
    from wormhole_tpu.data.stream import UploadOnCloseBuffer
    attempts = []

    def flaky(body):
        attempts.append(body)
        if len(attempts) < 3:
            raise OSError("503")

    buf = UploadOnCloseBuffer(flaky)
    buf.write(b"payload")
    for _ in range(2):
        with pytest.raises(OSError):
            buf.close()
        assert not buf.closed          # bytes retained for the retry
    buf.close()                        # third attempt lands
    assert buf.closed and attempts == [b"payload"] * 3


def test_upload_buffer_gc_after_failure_never_publishes():
    from wormhole_tpu.data.stream import UploadOnCloseBuffer
    attempts = []

    def always_fail(body):
        attempts.append(body)
        raise OSError("down")

    buf = UploadOnCloseBuffer(always_fail)
    buf.write(b"junk")
    with pytest.raises(OSError):
        buf.close()
    del buf
    gc.collect()
    assert attempts == [b"junk"]       # the destructor made no 2nd attempt


def test_gbdt_stale_cache_sweep(tmp_path, monkeypatch):
    import os
    import tempfile
    from wormhole_tpu.models.gbdt import _sweep_stale_caches
    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    tag, uid = "ab" * 6, os.getuid()
    dead = tmp_path / f"wh_gbdt_{tag}_u{uid}_p999999.part0of1.binned.cache"
    own = tmp_path / (f"wh_gbdt_{tag}_u{uid}_p{os.getpid()}"
                      ".part0of1.binned.cache")
    other = tmp_path / f"wh_gbdt_{'cd' * 6}_u{uid}_p999998.part0of1.binned.cache"
    for p in (dead, own, other):
        p.write_bytes(b"x")
    _sweep_stale_caches(tag)
    assert not dead.exists()           # dead owner: swept
    assert own.exists()                # our own live cache: kept
    assert other.exists()              # different dataset tag: untouched


def test_gbdt_sketch_sample_is_shuffled_and_deterministic():
    from wormhole_tpu.models.gbdt import (_entry_quantile_cuts,
                                          _global_sparse_sketch)
    from wormhole_tpu.parallel.mesh import MeshRuntime
    rt = MeshRuntime.create()
    rng = np.random.default_rng(7)
    n = 50_000
    ef = np.zeros(n, np.int64)
    ev = np.sort(rng.standard_normal(n).astype(np.float32))  # value-sorted
    ids_a, cuts_a = _global_sparse_sketch(ef, ev, 16, rt,
                                          sample_cap=2000)
    ids_b, cuts_b = _global_sparse_sketch(ef, ev, 16, rt,
                                          sample_cap=2000)
    np.testing.assert_array_equal(cuts_a, cuts_b)  # fixed seed: stable
    # the shuffled sample's cuts must track the full-data quantiles
    full = _entry_quantile_cuts(ef.copy(), ev, 1, 16)
    np.testing.assert_allclose(cuts_a, full, atol=0.08)
