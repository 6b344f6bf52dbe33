"""Unified telemetry (wormhole_tpu/obs/): span tracing, metrics
registry, heartbeat/straggler detection, and their learner/launcher/
bench integration points.

Pins the PR-3 contracts: trace files are Perfetto-loadable Chrome
trace-event JSON with thread attribution; registry merge across
simulated hosts equals serial totals; heartbeat files parse and flag
stragglers; and with every knob off, nothing records and nothing is
written."""

import io
import json
import os
import re
import threading
import time

import pytest

from wormhole_tpu import obs
from wormhole_tpu.obs import trace
from wormhole_tpu.obs.metrics import Registry, merge_snapshots
from wormhole_tpu.obs.heartbeat import (HeartbeatWriter, HeartbeatMonitor,
                                        StragglerDetector, read_heartbeats,
                                        heartbeat_path)


@pytest.fixture(autouse=True)
def _trace_off():
    """The trace recorder is module-global state; leave it off."""
    trace.disable()
    yield
    trace.disable()


def _span(name, dur=0.0, cat=""):
    """One span of about ``dur`` seconds, opened and closed here."""
    with trace.span(name, cat=cat):
        if dur:
            time.sleep(dur)


# -- span tracing ------------------------------------------------------------

def test_trace_disabled_records_nothing(tmp_path):
    assert not trace.enabled()
    _span("x", 0.001)
    with trace.span("y"):
        pass
    trace.counter("c", 1.0)
    assert trace.events() == []
    assert trace.flush(str(tmp_path / "no.json")) is None
    assert list(tmp_path.iterdir()) == []


def test_trace_json_schema_and_thread_attribution(tmp_path):
    path = str(tmp_path / "run.trace.json")
    trace.enable(path)

    with trace.span("main:work", cat="app"):
        time.sleep(0.001)
    trace.counter("ring", 3)

    def worker():
        _span("worker:stage", 0.002, cat="feed")

    t = threading.Thread(target=worker, name="prep0")
    t.start()
    t.join()

    assert trace.flush() == path
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]

    complete = [e for e in evs if e["ph"] == "X"]
    names = {e["name"] for e in complete}
    assert {"main:work", "worker:stage"} <= names
    for e in complete:
        # the Chrome trace-event complete-span schema Perfetto needs
        assert {"ph", "name", "pid", "tid", "ts", "dur"} <= set(e)
        assert e["dur"] >= 0

    # distinct threads -> distinct tids, both named via M-events
    tids = {e["name"]: e["tid"] for e in complete}
    assert tids["main:work"] != tids["worker:stage"]
    meta = [e for e in evs if e["ph"] == "M"]
    tnames = {e["args"]["name"] for e in meta
              if e["name"] == "thread_name"}
    assert "prep0" in tnames
    assert any(e["name"] == "process_name" for e in meta)

    assert any(e["ph"] == "C" and e["args"]["value"] == 3.0 for e in evs)


def test_trace_ring_is_bounded():
    trace.enable(ring=16)
    for i in range(100):
        _span(f"s{i}")
    evs = trace.events()
    assert len(evs) == 16
    assert evs[-1]["name"] == "s99"   # freshest window survives


def test_trace_summary_aggregates():
    trace.enable()
    for _ in range(3):
        _span("a", 0.010)
    _span("b", 0.005)
    s = trace.summary()
    assert s["a"]["count"] == 3
    # a sleep never returns early; a loaded host returns late
    assert 0.030 <= s["a"]["total_s"] < 0.5
    assert s["a"]["total_s"] == pytest.approx(
        sum(e["dur"] for e in trace.events() if e["name"] == "a") / 1e6,
        abs=1e-5)
    assert s["b"]["count"] == 1


def test_timer_scope_emits_spans():
    from wormhole_tpu.utils.timer import Timer
    trace.enable()
    tm = Timer()
    with tm.scope("dispatch"):
        time.sleep(0.001)
    names = {e["name"] for e in trace.events()}
    assert "dispatch" in names
    # and the timer still accumulated normally
    assert tm.totals["dispatch"] > 0


def test_device_feed_stage_spans_with_thread_tracks():
    from wormhole_tpu.data.pipeline import DeviceFeed
    trace.enable()
    feed = DeviceFeed(range(16), lambda it, c: it, workers=2,
                      transfer=lambda x: x, name="feed")
    assert list(feed) == list(range(16))
    evs = [e for e in trace.events() if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert "feed:parse" in names and "feed:prep" in names \
        and "feed:put" in names
    # pool work is attributed to worker threads, not the consumer
    tids = {e["name"]: set() for e in evs}
    for e in evs:
        tids[e["name"]].add(e["tid"])
    assert tids["feed:prep"] != tids["feed:parse"]


def test_timer_scope_ring_output_names_cats_and_nesting():
    """The ring's view of Timer.scope is what it was before the spans
    also went to the profiler: one complete event a scope under the
    scope's own name, category ``timer``, on the thread that ran it,
    an inner span inside its outer scope."""
    from wormhole_tpu.utils.timer import Timer
    trace.enable()
    tm = Timer()
    with tm.scope("wait"):
        time.sleep(0.001)
        with trace.span("pass:drain", cat="pass"):
            time.sleep(0.001)
        time.sleep(0.001)
    with tm.scope("eval_dispatch"):
        pass
    evs = trace.events()
    # a span is recorded when it closes: inner first
    assert [(e["name"], e.get("cat"), e["ph"]) for e in evs] == [
        ("pass:drain", "pass", "X"), ("wait", "timer", "X"),
        ("eval_dispatch", "timer", "X")]
    inner, outer, _ = evs
    assert outer["ts"] < inner["ts"]
    assert inner["ts"] + inner["dur"] < outer["ts"] + outer["dur"]
    assert len({e["tid"] for e in evs}) == 1
    assert tm.counts == {"wait": 1, "eval_dispatch": 1}
    assert tm.totals["wait"] * 1e6 == pytest.approx(outer["dur"], rel=0.05)


def test_span_args_are_snapshotted_at_close_and_exceptions_pass():
    trace.enable()
    args = {}
    with trace.span("checkpoint:save", cat="checkpoint", args=args):
        args["bytes"] = 12
    with pytest.raises(KeyError):
        with trace.span("checkpoint:load"):
            raise KeyError("boom")
    evs = trace.events()
    assert evs[0]["args"] == {"bytes": 12}
    assert [e["name"] for e in evs] == ["checkpoint:save",
                                        "checkpoint:load"]


def test_obs_imports_and_spans_without_jax():
    """``obs`` stays importable without jax: the profiler sink is bound
    through ``sys.modules`` only once something else imported jax. No
    jax, no sink; the ring works either way."""
    import subprocess
    import sys
    prog = (
        "import sys\n"
        "from wormhole_tpu.obs import trace\n"
        "assert 'jax' not in sys.modules\n"
        "with trace.span('a'): pass\n"
        "assert trace._ANNOTATION is None\n"
        "trace.enable()\n"
        "with trace.span('b'): pass\n"
        "assert [e['name'] for e in trace.events()] == ['b']\n"
        "assert 'jax' not in sys.modules\n"
        "import jax\n"
        "with trace.span('c'): pass\n"
        "assert trace._ANNOTATION is jax.profiler.TraceAnnotation\n"
        "print('OK')\n")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0 and "OK" in r.stdout, r.stdout + r.stderr


def test_spans_reach_a_profiler_session_with_the_ring_off(tmp_path):
    """With the ring off a span is still in a ``jax.profiler`` capture,
    a thread's spans on that thread's own line, on the session's clock
    (ns from its start, not the epoch)."""
    import glob
    import jax
    assert not trace.enabled()

    def worker():
        with trace.span("feed:prep", cat="feed"):
            time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("pass:open", cat="pass"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    finally:
        jax.profiler.stop_trace()
    assert trace.events() == []
    xplane, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    where = {}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in ("pass:open", "feed:prep"):
                    where[ev.name] = (plane.name, i, ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
    assert set(where) == {"pass:open", "feed:prep"}
    (p0, l0, s0, e0), (p1, l1, s1, e1) = where["pass:open"], where["feed:prep"]
    assert p0 == p1 == "/host:CPU" and l0 != l1
    assert 0 <= s0 < s1 < e1 < e0 < 600e9


def test_collective_span_single_process():
    import numpy as np
    from wormhole_tpu.parallel.collectives import allreduce_tree
    trace.enable()
    out = allreduce_tree(np.ones(4), None, "sum")
    assert (out == np.ones(4)).all()
    assert "collective:allreduce_sum" in {e["name"]
                                          for e in trace.events()}


# -- metrics registry --------------------------------------------------------

def _load_host(reg, scale):
    reg.counter("steps").inc(10 * scale)
    reg.gauge("nnz", agg="sum").set(100.0 * scale)
    reg.gauge("ring_max", agg="max").set(float(scale))
    reg.gauge("t_min", agg="min").set(float(scale))
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        for _ in range(scale):
            h.observe(v)


def test_merge_across_hosts_equals_serial():
    hosts = []
    for scale in (1, 2, 3):
        r = Registry()
        _load_host(r, scale)
        hosts.append(r)
    merged = merge_snapshots([r.snapshot() for r in hosts])

    serial = Registry()
    _load_host(serial, 1 + 2 + 3)

    assert merged.get("steps").value == serial.get("steps").value
    assert merged.get("nnz").value == serial.get("nnz").value
    assert merged.get("ring_max").value == 3.0
    assert merged.get("t_min").value == 1.0
    assert merged.get("lat").bins == serial.get("lat").bins
    assert merged.get("lat").count == serial.get("lat").count
    assert merged.get("lat").sum == pytest.approx(serial.get("lat").sum)


def test_registry_redeclare_and_kind_guard():
    r = Registry()
    c = r.counter("x")
    assert r.counter("x") is c            # same name+kind: same object
    with pytest.raises(ValueError):
        r.gauge("x")                      # kind collision fails loud
    with pytest.raises(ValueError):
        c.inc(-1)                         # counters only go up


def test_registry_allreduce_single_process_identity():
    r = Registry()
    _load_host(r, 2)
    before = r.snapshot()
    r.allreduce(None)                     # process_count == 1: identity
    assert r.snapshot() == before


def test_prometheus_text_format():
    r = Registry()
    r.counter("steps", help="device steps").inc(5)
    h = r.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = r.prometheus_text(labels={"host": "2"})
    assert "# TYPE steps counter" in text
    assert 'steps{host="2"} 5.0' in text
    assert "# HELP steps device steps" in text
    # cumulative le buckets + the +Inf bucket equal to count
    assert 'lat_bucket{host="2",le="0.1"} 1' in text
    assert 'lat_bucket{host="2",le="1.0"} 2' in text
    assert 'lat_bucket{host="2",le="+Inf"} 3' in text
    assert 'lat_count{host="2"} 3' in text


def test_adapters_timer_progress_feed():
    from wormhole_tpu.utils.timer import Timer
    from wormhole_tpu.utils.progress import Progress
    r = Registry()
    tm = Timer()
    with tm.scope("dispatch"):
        pass
    r.from_timer(tm)
    assert r.get("timer_dispatch_calls").value == 1.0
    assert r.get("timer_dispatch_seconds").value >= 0.0

    p = Progress()
    p.num_ex = 123
    p.feed_stall = 4.5
    r.from_progress(p)
    assert r.get("progress_num_ex").value == 123.0
    assert r.get("progress_feed_stall").value == 4.5
    assert r.get("progress_num_ex").agg == "sum"

    r.ingest_feed({"parse": 1.0, "batches": 7, "ring_max": 2})
    r.ingest_feed({"parse": 0.5, "batches": 3, "ring_max": 1})
    assert r.get("feed_parse_seconds").value == 1.5
    assert r.get("feed_batches").value == 10.0
    assert r.get("feed_ring_max").value == 2.0


def test_registry_record_flat_dict():
    r = Registry()
    r.counter("steps").inc(2)
    r.histogram("lat", buckets=(1.0,)).observe(0.5)
    rec = r.record(rank=3, step=10)
    assert rec["rank"] == 3 and rec["step"] == 10
    assert rec["steps"] == 2.0
    assert rec["lat_count"] == 1 and "ts" in rec
    json.dumps(rec)   # JSON-lines-able


# -- heartbeats & stragglers -------------------------------------------------

def test_heartbeat_write_read_roundtrip(tmp_path):
    hb = HeartbeatWriter(str(tmp_path), rank=2, interval=30.0)
    assert hb.beat(step=1, num_ex=100)            # first beat: immediate
    assert not hb.beat(step=2, num_ex=200)        # rate-limited
    assert hb.beat(step=3, num_ex=300, force=True)
    hb.close(step=3, num_ex=300)

    by_rank = read_heartbeats(str(tmp_path))
    recs = by_rank[2]
    assert len(recs) == 3
    assert [r["seq"] for r in recs] == [0, 1, 2]
    assert all(r["rank"] == 2 for r in recs)
    assert recs[-1]["final"] is True
    assert recs[1]["ex_per_sec"] > 0              # delta-based rate


def test_heartbeat_torn_line_skipped(tmp_path):
    p = heartbeat_path(str(tmp_path), 0)
    with open(p, "w") as f:
        f.write(json.dumps({"rank": 0, "seq": 0, "ex_per_sec": 5.0})
                + "\n")
        f.write('{"rank": 0, "seq": 1, "ex_per')   # writer mid-append
    assert len(read_heartbeats(str(tmp_path))[0]) == 1


def test_heartbeat_unwritable_never_raises(tmp_path):
    hb = HeartbeatWriter(str(tmp_path), rank=0)
    # occupy the writer's path with a directory (chmod tricks don't
    # work under root): open(path, "a") raises OSError
    os.mkdir(hb.path)
    assert hb.beat(step=1, num_ex=1) is False       # dead, not raising
    assert hb.beat(step=2, num_ex=2) is False


def _hb_files(tmp_path, rates):
    for rank, rate in rates.items():
        with open(heartbeat_path(str(tmp_path), rank), "w") as f:
            f.write(json.dumps({"rank": rank, "seq": 0,
                                "ex_per_sec": rate}) + "\n")


def test_straggler_detection(tmp_path):
    _hb_files(tmp_path, {0: 100.0, 1: 110.0, 2: 10.0, 3: 95.0})
    flags = StragglerDetector(factor=3.0).check(
        read_heartbeats(str(tmp_path)))
    assert [f["rank"] for f in flags] == [2]
    assert flags[0]["ex_per_sec"] == 10.0
    assert flags[0]["floor"] < flags[0]["median"]
    # nobody below median/factor -> no flags
    _hb_files(tmp_path, {0: 100.0, 1: 110.0, 2: 90.0, 3: 95.0})
    assert StragglerDetector(factor=3.0).check(
        read_heartbeats(str(tmp_path))) == []


def test_monitor_warns_once_per_rank(tmp_path):
    _hb_files(tmp_path, {0: 100.0, 1: 100.0, 2: 1.0})
    warnings = []
    mon = HeartbeatMonitor(str(tmp_path), factor=3.0,
                           sink=warnings.append, rewarn_after=3600.0)
    assert [f["rank"] for f in mon.scan_once()] == [2]
    mon.scan_once()                       # same straggler: rate-limited
    assert len(warnings) == 1
    assert "straggler: w2" in warnings[0]


# -- the Obs hub -------------------------------------------------------------

def _cfg(**kw):
    from wormhole_tpu.utils.config import Config
    return Config(**kw)


def test_obs_disabled_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.METRICS_EXPORT_ENV, raising=False)
    monkeypatch.chdir(tmp_path)
    hub = obs.setup(_cfg(), rank=0, registry=Registry())
    assert not hub.active
    assert not trace.enabled()
    hub.heartbeat_tick(step=1, num_ex=10)
    hub.finalize(step=1, num_ex=10, timer=None, progress=None)
    assert list(tmp_path.iterdir()) == []


def test_obs_enabled_end_to_end(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.METRICS_EXPORT_ENV, raising=False)
    from wormhole_tpu.utils.timer import Timer
    trace_path = str(tmp_path / "t.json")
    export = str(tmp_path / "telemetry")
    hub = obs.setup(_cfg(trace_path=trace_path, metrics_export=export,
                         heartbeat_itv=0.0),
                    rank=0, registry=Registry())
    assert hub.active and trace.enabled()

    tm = Timer()
    with tm.scope("dispatch"):
        pass
    hub.heartbeat_tick(step=1, num_ex=100)
    hub.finalize(step=2, num_ex=200, timer=tm, progress=None)

    # all three artifact kinds exist and parse
    doc = json.loads(open(trace_path).read())
    assert any(e["name"] == "dispatch" for e in doc["traceEvents"])
    recs = read_heartbeats(export)[0]
    assert recs[-1]["final"] is True
    prom = open(os.path.join(export, "host0.prom")).read()
    assert 'timer_dispatch_calls{host="0"} 1.0' in prom


def test_obs_env_fallback_and_rank_path(tmp_path, monkeypatch):
    export = str(tmp_path / "hb")
    monkeypatch.setenv(obs.METRICS_EXPORT_ENV, export)
    hub = obs.setup(_cfg(trace_path=str(tmp_path / "t.json")), rank=3,
                    registry=Registry())
    assert hub.export_dir == export       # launcher env fallback
    assert hub.trace_path.endswith("t.r3.json")   # per-rank trace file
    hub.heartbeat_tick(step=1, num_ex=1)
    assert os.path.exists(heartbeat_path(export, 3))


# -- satellite integrations --------------------------------------------------

def test_progress_slot_overflow_raises_with_names():
    from wormhole_tpu.utils import progress as P
    assert P.Progress.names() == (tuple(P._F_SLOTS), tuple(P._I_SLOTS))
    orig = list(P._F_SLOTS)
    try:
        P._F_SLOTS[:] = [f"s{i}" for i in range(11)]
        with pytest.raises(ValueError, match="s10"):
            P._check_slots()
        P._F_SLOTS[:] = ["a", "b", "a"]
        with pytest.raises(ValueError, match="duplicate"):
            P._check_slots()
    finally:
        P._F_SLOTS[:] = orig


def test_time_reporter_first_delay():
    from wormhole_tpu.utils.progress import TimeReporter
    fired = []
    immediate = TimeReporter(fired.append, interval=60.0)
    assert immediate.due()                # default: t=0 row fires
    delayed = TimeReporter(fired.append, interval=60.0, first_delay=True)
    assert not delayed.due()              # heartbeat-style: waits


def test_pump_lines_rank_prefix():
    from wormhole_tpu.parallel.launcher import _pump_lines
    sink = io.BytesIO()
    sink.flush = lambda: None
    _pump_lines(io.BytesIO(b"hello\nworld\n"), sink, threading.Lock(),
                tag=b"[w3] ")
    assert sink.getvalue() == b"[w3] hello\n[w3] world\n"
    # no tag: verbatim relay (sim mode, single child)
    sink2 = io.BytesIO()
    sink2.flush = lambda: None
    _pump_lines(io.BytesIO(b"x\n"), sink2, threading.Lock())
    assert sink2.getvalue() == b"x\n"


def test_bench_phase_telemetry(monkeypatch):
    import bench
    monkeypatch.delenv(obs.METRICS_EXPORT_ENV, raising=False)
    trace.enable()
    _span("feed:parse", 0.03)
    _span("feed:consume_stall", 0.01)
    rec = bench._phase_telemetry()
    assert rec["spans"]["feed:parse"]["count"] == 1
    # the spans are as long as the sleeps came out: hold the telemetry
    # to what was recorded
    parse = rec["spans"]["feed:parse"]["total_s"]
    stall = rec["spans"]["feed:consume_stall"]["total_s"]
    assert parse >= 0.03 and stall >= 0.01
    assert rec["stall_sec"] == pytest.approx(stall, abs=1e-3)
    assert rec["stall_frac"] == pytest.approx(stall / (parse + stall),
                                              abs=1e-3)
    assert "straggler_flags" not in rec   # no heartbeat dir configured


def test_bench_summarize_telemetry_passthrough():
    import bench
    tele = {"e2e": {"spans": {}, "stall_sec": 0.0, "stall_frac": 0.0}}
    out = bench._summarize({}, {}, [], [], "cpu", None, None, 840.0,
                           1.0, tele)
    assert out["extra"]["telemetry"] is tele
    out2 = bench._summarize({}, {}, [], [], "cpu", None, None, 840.0,
                            1.0, {})
    assert "telemetry" not in out2["extra"]


# -- trace drop accounting (PR-6) --------------------------------------------

def test_trace_drop_counter_and_flush_metadata(tmp_path):
    path = str(tmp_path / "d.json")
    trace.enable(path, ring=16)
    for i in range(100):
        _span(f"s{i}")
    assert trace.dropped() == 84          # 100 recorded, 16 retained
    trace.reset()                         # phase reset keeps the tally
    assert trace.dropped() == 84
    _span("tail")
    assert trace.flush() == path
    doc = json.loads(open(path).read())
    assert doc["metadata"]["dropped_spans"] == 84
    assert "mono_t0" in doc["metadata"] and "wall_t0" in doc["metadata"]
    trace.enable(ring=16)                 # reconfigure: fresh tally
    assert trace.dropped() == 0


def test_trace_no_drops_when_ring_fits():
    trace.enable(ring=64)
    for i in range(10):
        _span(f"s{i}")
    assert trace.dropped() == 0


# -- Prometheus exposition strictness (PR-6) ---------------------------------

def test_prometheus_help_type_for_every_family():
    """A strict scraper requires # HELP and # TYPE per family, in order,
    and escaped HELP/label values. Parse the dump like one would."""
    r = Registry()
    r.counter("steps", help="device steps").inc(5)
    r.gauge("undocumented_gauge").set(1.0)      # no help: falls back
    r.counter("weird/name", help='line\none "q" \\ back').inc(1)
    r.histogram("lat", buckets=(0.1,)).observe(0.05)
    text = r.prometheus_text(labels={"host": 'a"b\\c'})

    families = {}
    cur = None
    sample = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.eE+infa]+)$')
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            name = line.split()[2]
            assert name not in families, f"duplicate HELP for {name}"
            families[name] = {"help": True, "type": False, "samples": 0}
            cur = name
        elif line.startswith("# TYPE "):
            name = line.split()[2]
            assert name == cur, "TYPE must follow its family's HELP"
            assert families[name]["help"] and not families[name]["type"]
            families[name]["type"] = True
        else:
            m = sample.match(line)
            assert m, f"unparseable sample line: {line!r}"
            base = m.group(1)
            for suffix in ("_bucket", "_sum", "_count"):
                if base.endswith(suffix) and base[:-len(suffix)] in families:
                    base = base[:-len(suffix)]
                    break
            assert base == cur, f"sample {line!r} outside its family"
            families[base]["samples"] += 1
    assert all(f["type"] and f["samples"] for f in families.values())
    # escaping: HELP newline + label value quote/backslash
    assert r'line\none "q" \\ back' in text
    assert 'host="a\\"b\\\\c"' in text
    # the sanitized family name, not the raw slash name
    assert "# TYPE weird_name counter" in text


def test_declared_repo_metrics_have_help():
    """The metric families the repo itself declares with help= render a
    non-trivial HELP line (not the name fallback)."""
    from wormhole_tpu.obs.metrics import (encode_counters,
                                          encode_native_counter)
    r = Registry()
    encode_counters(r)
    encode_native_counter(r)
    text = r.prometheus_text()
    assert "# HELP feed_encode_stall seconds the stream waited" in text
    assert "# TYPE feed_encode_stall counter" in text
    assert "# HELP feed_encode_native_blocks online-encoded blocks" in text
    assert "# TYPE feed_encode_native_blocks counter" in text


# -- monitor incidents: dedup, recovery, relapse (PR-6) ----------------------

def test_monitor_recovery_and_new_incident(tmp_path):
    warnings = []
    mon = HeartbeatMonitor(str(tmp_path), factor=3.0,
                           sink=warnings.append, rewarn_after=3600.0)
    _hb_files(tmp_path, {0: 100.0, 1: 100.0, 2: 1.0})
    mon.scan_once()
    mon.scan_once()
    assert len(warnings) == 1 and "incident #1" in warnings[0]
    # rank 2 climbs back above the floor -> one recovery line
    _hb_files(tmp_path, {0: 100.0, 1: 100.0, 2: 95.0})
    assert mon.scan_once() == []
    assert len(warnings) == 2
    assert "recovered: w2" in warnings[1]
    assert "back above floor" in warnings[1]
    # relapse -> a FRESH warning, incident #2
    _hb_files(tmp_path, {0: 100.0, 1: 100.0, 2: 2.0})
    mon.scan_once()
    mon.scan_once()
    assert len(warnings) == 3
    assert "straggler: w2" in warnings[2] and "incident #2" in warnings[2]


def test_monitor_recovery_on_final_heartbeat(tmp_path):
    warnings = []
    mon = HeartbeatMonitor(str(tmp_path), factor=3.0,
                           sink=warnings.append, rewarn_after=3600.0)
    _hb_files(tmp_path, {0: 100.0, 1: 100.0, 2: 1.0})
    mon.scan_once()
    # the straggler finishes: its final record closes the incident as
    # "finished", not as a bogus rate
    with open(heartbeat_path(str(tmp_path), 2), "a") as f:
        f.write(json.dumps({"rank": 2, "seq": 1, "ex_per_sec": 0.0,
                            "final": True}) + "\n")
    mon.scan_once()
    assert len(warnings) == 2
    assert "recovered: w2 finished" in warnings[1]


def test_monitor_rewarn_after_elapses(tmp_path):
    _hb_files(tmp_path, {0: 100.0, 1: 100.0, 2: 1.0})
    warnings = []
    mon = HeartbeatMonitor(str(tmp_path), factor=3.0,
                           sink=warnings.append, rewarn_after=0.0)
    mon.scan_once()
    mon.scan_once()                   # rewarn_after=0: re-warn each scan
    assert len(warnings) == 2
    assert "still at" in warnings[1] and "incident #1" in warnings[1]


# -- straggler detection under clock jitter (PR-6) ---------------------------

def _hb_files_jittered(tmp_path, rows):
    """rows: rank -> (ex_per_sec, wall_skew_s). Each rank's wall clock
    (ts) disagrees by its skew while mono stays honest — NTP jitter."""
    now = time.time()
    mono = time.monotonic()
    for rank, (rate, skew) in rows.items():
        with open(heartbeat_path(str(tmp_path), rank), "w") as f:
            for seq in range(3):
                f.write(json.dumps({
                    "ts": round(now + skew + seq, 3),
                    "mono": round(mono + seq, 4),
                    "rank": rank, "seq": seq,
                    "ex_per_sec": rate}) + "\n")


def test_straggler_detection_ignores_clock_jitter(tmp_path):
    # equal rates, wildly skewed wall clocks: nobody is flagged —
    # detection reads per-rank delta rates, never cross-rank timestamps
    _hb_files_jittered(tmp_path, {0: (100.0, 0.0), 1: (100.0, -7.5),
                                  2: (100.0, 42.0), 3: (101.0, 3.3)})
    assert StragglerDetector(factor=3.0).check(
        read_heartbeats(str(tmp_path))) == []
    # a real straggler is flagged regardless of its clock skew
    _hb_files_jittered(tmp_path, {0: (100.0, 0.0), 1: (100.0, -7.5),
                                  2: (5.0, 42.0), 3: (101.0, 3.3)})
    flags = StragglerDetector(factor=3.0).check(
        read_heartbeats(str(tmp_path)))
    assert [f["rank"] for f in flags] == [2]


# -- the step ledger (PR-6 tentpole) -----------------------------------------

def _ev(name, ts_us, dur_us, tid=1, cat=""):
    ev = {"ph": "X", "name": name, "pid": 0, "tid": tid,
          "ts": float(ts_us), "dur": float(dur_us)}
    if cat:
        ev["cat"] = cat
    return ev


def test_ledger_buckets_sum_to_wall():
    from wormhole_tpu.obs import ledger
    # 1.0 s wall: parse 0.2, encode 0.1, put 0.1, dispatch 0.05,
    # wait 0.35, read 0.05 -> 0.85 attributed, 0.15 unattributed
    evs = [_ev("parse", 0, 200_000), _ev("encode", 200_000, 100_000),
           _ev("put", 300_000, 100_000), _ev("dispatch", 400_000, 50_000),
           _ev("wait", 450_000, 350_000), _ev("read", 800_000, 50_000)]
    led = ledger.build(evs, wall_s=1.0, tid=1)
    b = led["buckets_s"]
    assert b["host_prep"] == pytest.approx(0.2)
    assert b["encode"] == pytest.approx(0.1)
    assert b["h2d_transfer"] == pytest.approx(0.1)
    assert b["device_compute"] == pytest.approx(0.4)
    assert b["metrics_readback"] == pytest.approx(0.05)
    assert led["unattributed_s"] == pytest.approx(0.15)
    # the acceptance identity: buckets + unattributed == wall, exactly
    assert sum(b.values()) + led["unattributed_s"] == \
        pytest.approx(led["wall_s"], rel=1e-6)
    assert led["frac"]["unattributed"] == pytest.approx(0.15, abs=1e-3)
    assert sum(led["frac"].values()) == pytest.approx(1.0, abs=0.01)
    assert led["device_frac"] == pytest.approx(0.4)
    # a host-span share only: no utilization is derived from it
    assert "est_mxu_util" not in led


def test_ledger_nested_spans_self_time():
    from wormhole_tpu.obs import ledger
    # collective:allreduce_sum (40ms) nested inside
    # collective:metrics_window (100ms): naive summing would count
    # 140ms; self-time charges 40 to collective_wait, 60 to readback
    evs = [_ev("collective:metrics_window", 0, 100_000),
           _ev("collective:allreduce_sum", 30_000, 40_000)]
    led = ledger.build(evs, wall_s=0.1, tid=1)
    assert led["buckets_s"]["collective_wait"] == pytest.approx(0.04)
    assert led["buckets_s"]["metrics_readback"] == pytest.approx(0.06)
    assert led["unattributed_s"] == pytest.approx(0.0, abs=1e-6)


def test_ledger_other_thread_spans_ignored():
    from wormhole_tpu.obs import ledger
    # worker-thread feed spans overlap the consumer's wall clock; only
    # the step loop's thread is attributed
    evs = [_ev("wait", 0, 500_000, tid=1),
           _ev("feed:parse", 0, 400_000, tid=2),
           _ev("feed:put", 400_000, 100_000, tid=2)]
    led = ledger.build(evs, wall_s=0.5, tid=1)
    assert led["buckets_s"]["device_compute"] == pytest.approx(0.5)
    assert led["buckets_s"]["host_prep"] == 0.0
    assert led["spans_attributed"] == 1


def test_ledger_negative_unattributed_visible():
    from wormhole_tpu.obs import ledger
    # spans longer than the claimed wall (mis-nesting / clock noise)
    # surface as a NEGATIVE remainder, never clamped away
    evs = [_ev("wait", 0, 500_000)]
    led = ledger.build(evs, wall_s=0.3, tid=1)
    assert led["unattributed_s"] == pytest.approx(-0.2)
    assert led["frac"]["unattributed"] < 0


def test_ledger_span_bucket_rules():
    from wormhole_tpu.obs.ledger import span_bucket
    assert span_bucket("dispatch") == "device_compute"
    assert span_bucket("eval_dispatch") == "device_compute"
    assert span_bucket("collective:allreduce_max") == "collective_wait"
    assert span_bucket("collective:metrics_window") == "metrics_readback"
    assert span_bucket("checkpoint:shard_save") == "other"
    assert span_bucket("crec:put_stall") == "residual_stall"
    assert span_bucket("myfeed:encode") == "encode"
    assert span_bucket("myfeed:put") == "h2d_transfer"
    assert span_bucket("nonsense") is None


def test_ledger_from_live_trace_within_five_percent():
    """End to end through the real recorder: sleep-backed spans covering
    a measured wall window; buckets + unattributed land within 5% of it
    (the ISSUE acceptance bound — pure measurement noise)."""
    from wormhole_tpu.obs import ledger
    trace.enable()
    t_start = time.monotonic()
    with trace.span("parse"):
        time.sleep(0.02)
    with trace.span("dispatch"):
        time.sleep(0.03)
    with trace.span("wait"):
        time.sleep(0.05)
    wall = time.monotonic() - t_start
    led = ledger.build(trace.events(), wall_s=wall)
    total = sum(led["buckets_s"].values()) + led["unattributed_s"]
    # identity up to the record's 6-decimal rounding
    assert total == pytest.approx(wall, abs=1e-5)
    assert led["unattributed_s"] <= 0.05 * wall + 0.005
    assert led["buckets_s"]["device_compute"] == pytest.approx(
        0.08, abs=0.02)


def test_ledger_to_registry_exports_gauges():
    from wormhole_tpu.obs import ledger
    led = ledger.build([_ev("wait", 0, 100_000)], wall_s=0.2, tid=1)
    r = Registry()
    ledger.to_registry(led, r)
    assert r.get("ledger/device_compute_seconds").value == \
        pytest.approx(0.1)
    assert r.get("ledger/unattributed_seconds").value == \
        pytest.approx(0.1)
    assert r.get("ledger/wall_seconds").value == pytest.approx(0.2)
    assert r.get("ledger/device_compute_seconds").agg == "sum"
    assert r.get("ledger/device_frac").value == pytest.approx(0.5)
    assert r.get("ledger/est_mxu_util") is None
    # help strings present -> strict Prometheus HELP lines
    assert "step ledger" in r.get("ledger/wall_seconds").help


def test_disabled_instrumentation_is_cheap():
    """The off-path contract: with the ring off and no profiler session
    open, a span is the profiler's own no-op annotation and one
    module-global bool check (about half a microsecond). The fastest of
    five rounds of 40k must stay far under any per-batch budget: under
    5 us a span, a bound generous enough for a loaded CI box (a round
    that lost its core to another process does not count)."""
    import jax  # noqa: F401  (the profiler sink binds through it)
    assert not trace.enabled()
    rounds = []
    for _ in range(5):
        t0 = time.monotonic()
        for _ in range(40_000):
            with trace.span("x"):
                pass
        rounds.append(time.monotonic() - t0)
    assert trace.events() == []
    assert trace._ANNOTATION is jax.profiler.TraceAnnotation
    best = min(rounds) / 40_000
    assert best < 5e-6, (f"{best * 1e6:.2f} us a span in the best of "
                         f"{[round(r, 3) for r in rounds]} s")


def test_obs_finalize_exports_ledger_and_drop_counter(tmp_path,
                                                     monkeypatch):
    monkeypatch.delenv(obs.METRICS_EXPORT_ENV, raising=False)
    monkeypatch.delenv(obs.TRACE_EXPORT_ENV, raising=False)
    export = str(tmp_path / "tele")
    reg = Registry()
    hub = obs.setup(_cfg(trace_path=str(tmp_path / "t.json"),
                         metrics_export=export, heartbeat_itv=0.0),
                    rank=0, registry=reg)
    with trace.span("dispatch"):
        time.sleep(0.002)
    hub.finalize(step=1, num_ex=10, wall_s=0.05)
    assert reg.get("ledger/wall_seconds").value == pytest.approx(0.05)
    assert reg.get("ledger/device_compute_seconds").value > 0
    assert reg.get("trace/dropped_spans").value == 0.0
    prom = open(os.path.join(export, "host0.prom")).read()
    assert "# TYPE ledger_device_compute_seconds gauge" in prom
    assert "# HELP ledger_device_compute_seconds step ledger" in prom


def test_obs_trace_env_fallback(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.METRICS_EXPORT_ENV, raising=False)
    trace_dir = str(tmp_path / "traces")
    os.makedirs(trace_dir)
    monkeypatch.setenv(obs.TRACE_EXPORT_ENV, trace_dir)
    hub = obs.setup(_cfg(), rank=1, registry=Registry())
    # launch_mp --trace-dir: rank files land under the exported dir
    assert hub.trace_path == os.path.join(trace_dir, "trace.r1.json")
    assert trace.enabled()


def test_bench_phase_telemetry_ledger_block(monkeypatch):
    import bench
    monkeypatch.delenv(obs.METRICS_EXPORT_ENV, raising=False)
    trace.enable()
    _span("dispatch", 0.03)
    _span("wait", 0.05)
    rec = bench._phase_telemetry(wall_s=1.0)
    led = rec["ledger"]
    assert led["wall_s"] == pytest.approx(1.0)
    # both spans land in one bucket, as long as the sleeps came out
    busy = sum(rec["spans"][k]["total_s"] for k in ("dispatch", "wait"))
    assert busy >= 0.08
    assert led["buckets_s"]["device_compute"] == pytest.approx(busy,
                                                               abs=1e-4)
    assert led["unattributed_s"] == pytest.approx(1.0 - busy, abs=1e-4)
    assert rec["dropped_spans"] == 0
