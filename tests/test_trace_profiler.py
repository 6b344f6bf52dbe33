"""The program's spans in a ``jax.profiler`` capture (obs/trace.py's first
sink): a real ``AsyncSGD`` pass over Criteo text under ``tile_online``,
traced on the CPU backend inside a ``bench_pass`` annotation as the
benchmark's traced window is. The ``.xplane.pb`` must hold the pass
loop's spans on ``bench_pass``'s own line and the feed threads' on
lines of their own, all on the session's clock and nested as the code
nests them: this is what ``benchmark/host_spans.py`` names the device's
idle gaps by.
"""

import glob
import os

import jax
import numpy as np
import pytest

from wormhole_tpu.obs import trace

PASSES = 2


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """(lines, app): every host line of the capture as a list of
    (name, start_ns, end_ns), and the app that ran."""
    from test_tile_online import make_app
    tmp = tmp_path_factory.mktemp("xplane")
    rng = np.random.default_rng(3)
    n = 3000
    path = tmp / "t.criteo"
    with open(path, "w") as f:
        for i in range(n):
            ints = "\t".join(str(rng.integers(0, 100)) for _ in range(13))
            cats = "\t".join(f"{rng.integers(0, 1 << 32):x}"
                             for _ in range(26))
            f.write(f"{i % 2}\t{ints}\t{cats}\n")
    app = make_app(path, "criteo", tile_online="on", pipeline_workers=2,
                   max_data_pass=1, text_block_rows=512, max_delay=2)
    assert not trace.enabled()           # the ring is off: no flag is set
    app.process(str(path), 0, 1)         # compile outside the capture
    app.flush_metrics()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1           # the benchmark's own options
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    try:
        for _ in range(PASSES):
            with jax.profiler.TraceAnnotation("bench_pass"):
                app.process(str(path), 0, 1)
                app.flush_metrics()
                jax.block_until_ready(app.store.slots)
    finally:
        jax.profiler.stop_trace()
    xplane, = glob.glob(os.path.join(str(tmp), "trace", "plugins",
                                     "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(xplane)
    lines = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(ev.name, float(ev.start_ns),
                    float(ev.start_ns + ev.duration_ns))
                   for ev in line.events]
            if evs:
                lines.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return lines, app


def _named(line, name):
    return [(s, e) for n, s, e in line if n == name]


def _inside(inner, outer):
    return any(s0 <= inner[0] and inner[1] <= e0 for s0, e0 in outer)


def test_the_pass_loops_spans_are_on_bench_pass_line(captured):
    lines, _app = captured
    loop, = [ln for ln in lines if _named(ln, "bench_pass")]
    passes = _named(loop, "bench_pass")
    assert len(passes) == PASSES
    for name in ("pass:open", "pass:drain", "pass:close", "pass:flush"):
        spans = _named(loop, name)
        assert len(spans) == PASSES, name
        assert all(_inside(sp, passes) for sp in spans), name
    for name in ("dispatch", "wait", "tile-encode:consume_stall"):
        spans = _named(loop, name)
        assert len(spans) >= PASSES, name
        assert all(_inside(sp, passes) for sp in spans), name
    # no feed thread's work on the loop's line
    assert not _named(loop, "encode:tile")
    assert not _named(loop, "crec-feed:collate")


def test_the_loops_spans_nest_and_order_as_the_code_does(captured):
    lines, _app = captured
    loop, = [ln for ln in lines if _named(ln, "bench_pass")]
    waits = _named(loop, "wait")
    for drain in _named(loop, "pass:drain"):
        assert _inside(drain, waits)            # in the wait that holds it
    for window in _named(loop, "collective:metrics_window"):
        assert _inside(window, _named(loop, "pass:drain")
                       + _named(loop, "pass:flush"))
    for (os_, oe), (ds, de), (cs, ce), (fs, fe), (ps, pe) in zip(
            _named(loop, "pass:open"), _named(loop, "pass:drain"),
            _named(loop, "pass:close"), _named(loop, "pass:flush"),
            _named(loop, "bench_pass")):
        first = min(s for s, _e in _named(loop, "dispatch") if s >= os_)
        assert ps <= os_ < oe <= first < ds < de <= cs < ce <= fs < fe <= pe
    # the fused step's own span is inside its dispatch
    for step in _named(loop, "tilemm:fused_step"):
        assert _inside(step, _named(loop, "dispatch"))


def test_the_feeds_spans_are_on_lines_of_their_own(captured):
    lines, _app = captured
    others = [ln for ln in lines if not _named(ln, "bench_pass")]
    for name in ("crec-feed:prep", "crec-feed:collate", "encode:tile",
                 "tile-encode:put", "crec-feed:parse",
                 "tile-encode:encode", "encode:unpack", "encode:list"):
        assert any(_named(ln, name) for ln in others), name
    # the inner transfer thread, the encode pool and the outer transfer
    # thread are three threads at least
    carrying = [ln for ln in others if any(
        _named(ln, n) for n in ("crec-feed:prep", "crec-feed:collate",
                                "encode:tile", "tile-encode:put"))]
    assert len(carrying) >= 3
    for ln in others:
        encodes = _named(ln, "tile-encode:encode")
        steps = [_named(ln, n) for n in ("encode:unpack", "encode:tile",
                                         "encode:list")]
        for part in steps:
            assert all(_inside(sp, encodes) for sp in part)
        for (us, ue), (ts, te), (ls, le) in zip(*steps):
            assert us < ue <= ts < te <= ls < le   # one after the other
        # a collate lies between the transfer thread's wait and its put
        for cs, ce in _named(ln, "crec-feed:collate"):
            assert not _inside((cs, ce), _named(ln, "crec-feed:put_stall"))
            assert not _inside((cs, ce), _named(ln, "crec-feed:put"))


def test_every_span_is_inside_the_sessions_clock(captured):
    lines, _app = captured
    loop, = [ln for ln in lines if _named(ln, "bench_pass")]
    lo = min(s for s, _e in _named(loop, "bench_pass"))
    hi = max(e for _s, e in _named(loop, "bench_pass"))
    assert 0 <= lo < hi < 600e9        # ns since the session began
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import host_spans
    seen = 0
    for ln in lines:
        for name, s, e in ln:
            if host_spans.is_program_span(name):
                seen += 1
                # a feed thread outlives the loop's last span by its
                # shutdown polls: a second covers them
                assert lo - 1e9 <= s <= e <= hi + 1e9, name
    assert seen > 50


def test_the_counters_of_the_text_feeds_serial_stages_are_filled(captured):
    _lines, app = captured
    t = app.timer.totals
    for key in ("text_read", "collate", "encode"):
        assert t[key] > 0.0, key
    # the reader's seconds are the inner dispatcher's `parse`, which the
    # Timer did not keep before; what has no reader is not counted
    assert "encode_unpack" not in t and "text_bytes" not in t
