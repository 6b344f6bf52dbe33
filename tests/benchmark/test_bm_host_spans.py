"""``benchmark/host_spans.py`` on hand-made profiles, the readers of PR 41's
metrics, and the text feed's new counters in a CPU run of the text cell.

A fake profile is what ``jax.profiler.ProfileData`` gives, duck-typed:
planes of lines of events with a name, a start and a duration in ns."""

import json
import types

import pytest

import bm_helpers
from benchmark import host_spans as hs
from benchmark.readers import (collate_ms_per_block, idle_head_share,
                               idle_in_flight_share, idle_starved_share,
                               idle_tail_share, idle_unnamed_share,
                               pass_head_ms, text_read_ms_per_block)
from wormhole_tpu.obs import ledger

BENCH = bm_helpers.load("BENCHMARK.json")
NEW_SPAN_METRICS = ("idle_head_share.stream", "idle_in_flight_share.stream",
                    "idle_starved_share.stream", "idle_tail_share.stream",
                    "idle_unnamed_share.stream", "pass_head_ms.stream")
NEW_COUNTER_METRICS = ("text_read_ms_per_block.stream",
                       "collate_ms_per_block.stream")
TEXT_CELL = "criteo_ftrl_text.stream_text_uniform"
CLICK_CELL = "criteo_ftrl_clicklog.stream_text_fields"


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def profile(loop_events, device_ops, workers=(), host_noise=(), steps=()):
    """One host plane (the loop's line, a line a worker) and a device plane
    a list of (start, dur) ops; ``steps``: the (start, dur) of the step
    program's executions on every device's modules line."""
    host = [line("python3", list(loop_events) + list(host_noise))]
    host += [line("python3", list(w)) for w in workers]
    planes = [types.SimpleNamespace(name="/host:CPU", lines=host)]
    modules = [ev("jit_convert_element_type(153)", 161, 1)] + [
        ev("jit_step(7204656513395253915)", s, d) for s, d in steps]
    for i, ops in enumerate(device_ops):
        planes.append(types.SimpleNamespace(
            name=f"/device:TPU:{i}",
            lines=[line("XLA Ops", [ev("%fusion = f32[8] fusion()", s, d)
                                    for s, d in ops]),
                   line("XLA Modules", list(modules))]))
    return types.SimpleNamespace(planes=planes)


# one pass of two steps in a window of 1000 ns
LOOP = [ev("bench_pass", 0, 1000), ev("pass:open", 10, 40),
        ev("feed:consume_stall", 50, 100), ev("wait", 150, 10),
        ev("dispatch", 160, 40), ev("tilemm:fused_step", 165, 30),
        ev("feed:consume_stall", 200, 100), ev("wait", 300, 1),
        ev("dispatch", 301, 99), ev("wait", 400, 300),
        ev("pass:drain", 500, 200),
        ev("collective:metrics_window", 520, 100),
        ev("pass:close", 700, 50), ev("pass:flush", 760, 40)]
OPS = [(170, 60), (330, 300)]          # busy 170-230 and 330-630


def ns(table, key):
    return {k: round(v * 1e9, 6) for k, v in table[key].items()}


def classes(head, starved, tail, unnamed, in_flight=0.0):
    return {"head": head, "in_flight": in_flight, "starved": starved,
            "tail": tail, "unnamed": unnamed}


def test_the_classes_sum_to_the_idle_total():
    t = hs.attribute(profile(LOOP, [OPS]))
    assert t["window_s"] == pytest.approx(1000e-9)
    assert t["idle_s"] == pytest.approx(640e-9)          # 170 + 100 + 370
    assert sum(t["classes"].values()) == pytest.approx(t["idle_s"])
    # head: pass:open's start (10) to the first dispatch's (160), all idle;
    # starved: the second consume_stall's idle part (230-300); unnamed: the
    # device idle under dispatch (160-170, 301-330) and wait (300-301);
    # without a modules line nothing is known to be in flight
    assert ns(t, "classes") == classes(150.0, 70.0, 380.0, 40.0)
    assert t["pass_heads_ms"] == [pytest.approx(150e-6)]


def test_a_gap_astride_two_spans_is_split_at_the_boundary():
    t = hs.attribute(profile(LOOP, [OPS]))
    by = {k: round(v * 1e9, 6) for k, v in t["by_span"].items()}
    # the gap 230-330 lies under a stall, a wait and a dispatch
    assert by[("starved", "feed:consume_stall")] == 70.0
    assert by[("unnamed", "wait")] == 1.0
    # the gap 630-1000 is cut at every edge of the pass's tail
    assert by[("tail", "pass:close")] == 50.0
    assert by[("tail", "pass:flush")] == 40.0
    # before the pass's first span, between close and flush, after flush
    assert by[("tail", hs.NO_SPAN)] == 10.0 + 10.0 + 200.0


def test_innermost_wins_and_an_ancestor_decides_the_class():
    t = hs.attribute(profile(LOOP, [OPS]))
    by = {k: round(v * 1e9, 6) for k, v in t["by_span"].items()}
    # 630-700 is under wait > pass:drain: named by the innermost of them,
    # and a tail because pass:drain is open (wait alone would be unnamed)
    assert by[("tail", "pass:drain")] == 70.0
    assert ("unnamed", "pass:drain") not in by
    # 160-170 is under dispatch and, from 165, under the step's own span
    assert by[("unnamed", "dispatch")] == 5.0 + 29.0
    assert by[("unnamed", "tilemm:fused_step")] == 5.0
    # inside the head the first consume_stall is head, not starved
    assert by[("head", "feed:consume_stall")] == 100.0
    assert by[("head", "pass:open")] == 40.0
    # a drain in mid pass (under wait, no pass:drain) stays unnamed
    mid = [e for e in LOOP if e.name != "pass:drain"]
    t2 = hs.attribute(profile(mid, [OPS]))
    assert t2["classes"]["unnamed"] == pytest.approx(110e-9)   # + 630-700


def test_a_profile_without_the_programs_spans_reads_none():
    parent = [e for e in LOOP if e.name in ("bench_pass",)]
    assert hs.attribute(profile(parent, [OPS])) is None
    # the harness's span alone is not enough, nor are JAX's own events
    noise = [ev("PjitFunction(step)", 160, 30), ev("ParseArguments", 161, 1)]
    assert hs.attribute(profile(parent + noise, [OPS])) is None
    # no bench_pass: no window, nothing to read
    assert hs.attribute(profile(LOOP[1:], [OPS])) is None
    # no device plane (a CPU run)
    assert hs.attribute(profile(LOOP, [])) is None


def test_jaxs_own_events_on_the_loops_line_are_not_spans():
    noise = [ev("PjitFunction(step)", 160, 30), ev("ParseArguments", 161, 1),
             ev("PjRtCApiLoadedExecutable::Execute", 162, 20)]
    assert hs.attribute(profile(LOOP, [OPS], host_noise=noise)) == \
        hs.attribute(profile(LOOP, [OPS]))
    assert not hs.is_program_span("PjitFunction(step)")
    for name in ("wait", "eval_dispatch", "pass:open", "mesh:dispatch",
                 "crec2-feed:prep", "tile-encode:consume_stall",
                 "encode:tile", "meshfeed:stack_stall", "crec-feed:close"):
        assert hs.is_program_span(name), name


@pytest.mark.parametrize("name", sorted(ledger.SPAN_TABLE))
def test_every_declared_span_is_a_program_span(name):
    """One vocabulary: what ``obs/ledger.py`` declares (and the spans lint
    holds every site to) is what the attribution sees on the loop's line, so
    a span in a namespace new to this file cannot fall to ``(no span)``."""
    name = name.replace("*", "part0")           # a prefix pattern's instance
    assert hs.is_program_span(name)
    assert hs.is_program_span("eval_" + name)
    # and a worker's stage is one the head's table knows, or none
    assert hs.stage_of(name) in hs.STAGES + (None,)


def test_a_span_outside_the_old_namespaces_is_named():
    """``checkpoint:*`` on the loop's thread: the idle under it is named by
    it (unnamed, inside a pass), not by ``(no span)``."""
    loop = LOOP + [ev("checkpoint:save", 232, 60)]
    by = {k: round(v * 1e9, 6)
          for k, v in hs.attribute(profile(loop, [OPS]))["by_span"].items()}
    assert by[("unnamed", "checkpoint:save")] == 60.0
    assert by[("starved", "feed:consume_stall")] == 10.0


def test_two_device_planes_average():
    # the second chip is busy through the first chip's gap 230-330
    other = [(170, 160), (330, 300)]                 # busy 170-630
    t = hs.attribute(profile(LOOP, [OPS, other]))
    assert t["planes"] == 2
    assert t["idle_s"] == pytest.approx(0.5 * (640e-9 + 540e-9))
    assert ns(t, "classes") == classes(150.0, 35.0, 380.0, 25.0)
    # a plane without ops in the window is no chip of the cell
    idle_chip = types.SimpleNamespace(
        name="/device:TPU:3", lines=[line("XLA Ops", [])])
    p = profile(LOOP, [OPS])
    p.planes.append(idle_chip)
    assert hs.attribute(p)["planes"] == 1


def test_the_fence_between_two_passes_is_tail_and_heads_are_per_pass():
    second = [ev(e.name, e.start_ns + 1000, e.duration_ns) for e in LOOP]
    ops = OPS + [(s + 1000, d) for s, d in OPS]
    t = hs.attribute(profile(LOOP + second, [ops]))
    assert t["window_s"] == pytest.approx(2000e-9)
    assert ns(t, "classes") == classes(300.0, 140.0, 760.0, 80.0)
    assert t["pass_heads_ms"] == [pytest.approx(150e-6)] * 2
    # a pass that dispatched nothing has a head as long as its pass:open
    empty = [ev("bench_pass", 0, 100), ev("pass:open", 10, 20),
             ev("pass:close", 40, 10)]
    t = hs.attribute(profile(empty, [[(95, 5)]]))
    assert ns(t, "classes") == classes(20.0, 0.0, 75.0, 0.0)
    assert t["pass_heads_ms"] == [] and t["first_step_ms"] == []


def test_the_first_step_in_flight_is_a_class_of_its_own():
    """The first step is dispatched at 160 and its program starts on the
    device at 170 (its block was still crossing): those 10 ns of idle are
    ``in_flight``, under whatever span the loop is in; the head stays the
    host spans' (what ``pass_head_ms`` times). Later steps have no flight."""
    t = hs.attribute(profile(LOOP, [OPS], steps=[(170, 60), (330, 300)]))
    assert ns(t, "classes") == classes(150.0, 70.0, 380.0, 30.0,
                                       in_flight=10.0)
    assert sum(t["classes"].values()) == pytest.approx(t["idle_s"])
    by = {k: round(v * 1e9, 6) for k, v in t["by_span"].items()}
    assert by[("in_flight", "dispatch")] == 5.0
    assert by[("in_flight", "tilemm:fused_step")] == 5.0
    assert by[("unnamed", "dispatch")] == 29.0          # the second step's
    assert t["pass_heads_ms"] == [pytest.approx(150e-6)]    # host spans only
    assert t["first_step_ms"] == [pytest.approx(10e-6)]
    stages = {k: round(v * 1e9, 6) for k, v in t["head_stages"].items() if v}
    assert stages == {"feed_start": 150.0}              # the head's alone
    # the first step starts late, into the pass's second consume_stall: the
    # stall's idle up to there is in flight, not starved
    late = hs.attribute(profile(LOOP, [[(250, 50), (330, 300)]],
                                steps=[(250, 50), (330, 300)]))
    assert ns(late, "classes") == classes(150.0, 0.0, 380.0, 30.0,
                                          in_flight=90.0)
    # a step program that never ran in the pass is no flight's end
    far = hs.attribute(profile(LOOP, [OPS], steps=[(1170, 60)]))
    assert ns(far, "classes") == ns(hs.attribute(profile(LOOP, [OPS])),
                                    "classes")
    # two chips start their first step at different times
    # (the module line is each plane's own; here both read 170)
    both = hs.attribute(profile(LOOP, [OPS, OPS], steps=[(170, 60)]))
    assert ns(both, "classes") == ns(t, "classes")


def test_the_heads_critical_stage_is_the_downstream_most_busy_one():
    workers = [
        # the outer dispatcher: its parse is the inner feed's consume_stall
        # but for its edges, so it is busy 20-30 and 70-80 only
        [ev("tile-encode:parse", 20, 60), ev("crec-feed:consume_stall", 30,
                                             40)],
        # an encode worker, 80-140, its three steps nested
        [ev("tile-encode:encode_stall", 0, 80), ev("tile-encode:encode", 80,
                                                   60),
         ev("encode:unpack", 80, 10), ev("encode:tile", 90, 30),
         ev("encode:list", 120, 20)],
        # the inner prep worker and transfer thread
        [ev("crec-feed:prep", 25, 30)],
        [ev("crec-feed:collate", 50, 15), ev("tile-encode:put", 135, 20)]]
    t = hs.attribute(profile(LOOP, [OPS], workers=workers))
    got = {k: round(v * 1e9, 6) for k, v in t["head_stages"].items() if v}
    # the head is 10-160: put 135-155; encode 80-135; collate 50-65; prep
    # 25-50; parse 20-25 and 70-80; nothing busy 10-20, 65-70, 155-160
    assert got == {"put": 20.0, "encode": 55.0, "collate": 15.0,
                   "prep": 25.0, "parse": 15.0, "feed_start": 20.0}
    assert sum(got.values()) == pytest.approx(1e9 * t["classes"]["head"])
    # the table by hand, on the same profile in ms instead of ns
    def ms(events):
        return [ev(e.name, e.start_ns * 1e6, e.duration_ns * 1e6)
                for e in events]
    text = hs.describe(hs.attribute(profile(
        ms(LOOP), [[(s * 1e6, d * 1e6) for s, d in OPS]],
        workers=[ms(w) for w in workers])))
    for needle in ("window 1.000 s, 1 device plane(s), idle 0.640 s (64.00%)",
                   "head        0.150 s   15.00%",
                   "0.100 s  under feed:consume_stall",
                   "1 passes: mean 150.0 ms", "0.055 s  encode",
                   "0.020 s  feed_start"):
        assert needle in text, (needle, text)
    assert hs.describe(None).startswith("no bench_pass")


# -- the readers -------------------------------------------------------------


def _reading(trace=True, timers=None):
    return {"trace": {"window_s": 1.0, "busy_s": 0.5, "steps": 2}
            if trace else None,
            "config": {"name": "no_such_config"},
            "traffic": {"name": "no_such_mix"},
            "window": {"timers": timers or {}, "blocks": 4,
                       "window_s": 2.0}}


@pytest.mark.parametrize("metric", NEW_SPAN_METRICS + NEW_COUNTER_METRICS)
def test_new_metrics_are_catalogued_for_their_cells(metric):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == metric]
    spec = bm_helpers.load(f"benchmark/metrics/{metric}.json")
    stream = [w["name"] for w in BENCH["workloads"]
              if bm_helpers.load(f"benchmark/traffic/{w['traffic']}.json")
              ["regime"] == "stream"]
    assert len(stream) == 4
    # the counters: the click-log cell alone (the module's last test)
    want = [CLICK_CELL] if metric in NEW_COUNTER_METRICS else stream
    assert sorted(entry["workloads"]) == sorted(want)
    assert entry["better"] == "lower" and entry["moves"] == "stream_ex_per_s"
    # the span metrics are read out of the device's trace file, and a CPU
    # run must leave them out: `device_trace`, as the CPU tests know it
    assert spec["source"] == ("program_counter"
                              if metric in NEW_COUNTER_METRICS
                              else "device_trace")
    # appended: the accepted entries keep their places
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(metric) >= names.index("overflow_ms_per_step.stream")


@pytest.mark.parametrize("reader", [idle_head_share, idle_in_flight_share,
                                    idle_starved_share, idle_tail_share,
                                    idle_unnamed_share, pass_head_ms])
def test_span_readers_read_nothing_without_a_trace(reader):
    assert reader.read(_reading(trace=False)) is None    # untraced, or CPU
    assert reader.read(_reading()) is None               # the trace is gone


def test_span_readers_read_the_runs_table(monkeypatch):
    t = hs.attribute(profile(LOOP, [OPS], steps=[(170, 60), (330, 300)]))
    monkeypatch.setattr(hs, "table", lambda r: t)
    r = _reading()
    shares = [reader.read(r) for reader in (
        idle_head_share, idle_in_flight_share, idle_starved_share,
        idle_tail_share, idle_unnamed_share)]
    assert shares == pytest.approx([15.0, 1.0, 7.0, 38.0, 3.0])
    assert sum(shares) == pytest.approx(100.0 * t["idle_s"] / t["window_s"])
    assert pass_head_ms.read(r) == pytest.approx(150e-6)
    monkeypatch.setattr(hs, "table", lambda r: None)     # a parent commit
    assert idle_head_share.read(r) is None
    assert pass_head_ms.read(r) is None


def test_counter_readers_read_the_timer():
    timers = {"text_read": 0.08, "collate": 0.04}
    r = _reading(timers=timers)
    assert text_read_ms_per_block.read(r) == pytest.approx(20.0)
    assert collate_ms_per_block.read(r) == pytest.approx(10.0)
    cpu = _reading(trace=False, timers=timers)           # counters count
    assert text_read_ms_per_block.read(cpu) == pytest.approx(20.0)
    parent = _reading(timers={"read": 0.2})              # no such counter
    assert text_read_ms_per_block.read(parent) is None
    assert collate_ms_per_block.read(parent) is None


def test_the_text_cell_fills_the_new_counters_on_the_cpu(tmp_path):
    """A CPU run of the text cell (``test_bm_formats.py``'s pattern): the
    Timer's line holds the two new keys, and the result line holds none of
    PR 41's metrics: the span metrics need a device trace, and the counter
    metrics list the click-log cell alone, whose CPU run
    (``test_bm_clicklog.py``) reports every metric that is no device's."""
    config_patch, _ = bm_helpers.tiny_patches(*TEXT_CELL.split("."))
    r, result = bm_helpers.run_tiny(TEXT_CELL, tmp_path,
                                    patches=(config_patch, {"blocks": 4}),
                                    trace=True, seed=2**31 + 41)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True and result["failed"] == 0
    line_, = [ln for ln in r.stdout.splitlines()
              if ln.startswith("[bench] timers in the window (s): ")]
    timers = json.loads(line_[line_.index("{"):])
    for key in ("text_read", "collate", "encode", "read"):
        assert timers.get(key, 0.0) > 0.0, (key, timers)
    # the reader's seconds are the inner dispatcher's, a part of `read`'s
    # inner feed; the removed counters stay out of the Timer
    assert not {"encode_unpack", "text_bytes"} & set(timers)
    assert not set(result["metrics"]) & set(NEW_SPAN_METRICS
                                            + NEW_COUNTER_METRICS)
    assert "device metrics: not measured" in r.stdout
