"""The device's idle gaps, named by what the program's pass loop was doing.

Since PR 41 every span of the program (``wormhole_tpu/obs/trace.py``) is a
``jax.profiler.TraceAnnotation``, so a ``--trace 1`` run's ``.xplane.pb``
holds them on the clock of the device's ops: the pass loop's on the host
line that carries the harness's ``bench_pass``, each feed thread's on a line
of its own. This module takes the device's idle gaps exactly as
``trace_reduce.reduce_trace`` does (same window: first ``bench_pass`` start
to last end; same lines: the op line of every device plane with ops in the
window; averaged over those planes) and gives every instant of every gap to
one of five classes by the spans open on the loop's thread at that instant:

    head      from a ``pass:open``'s start to that pass's first ``dispatch``
              (or ``mesh:dispatch``) start: the pass's set-up and the new
              feed's first block, by the host's spans alone
    in_flight from that first dispatch's start until the device starts the
              pass's first step program (the device plane's ``XLA Modules``
              line says when): the step is dispatched and its block's
              transfer still on its way (the put returns at once, so no
              host span covers it), or a launch that came late
    starved   under a ``<feed>:consume_stall`` after that: the loop
              waiting on the feed
    tail      under ``pass:drain``, ``pass:close``, ``pass:flush`` or the
              feed's ``<feed>:close``; or under no program span, after a
              pass's last one and before the next ``pass:open`` (the
              harness's fence, and whatever lies between two passes)
    unnamed   the rest: under no program span inside a pass, or under
              ``wait``/``dispatch``, where the loop believes the device busy

The five sum to the idle total by construction. A profile without the
program's spans (a parent commit) or without ``bench_pass`` reads ``None``.
Which host events are the program's spans is ``obs/ledger.py``'s to say
(``span_bucket``: the table the spans lint holds every span to).

It takes a duck-typed profile (``.planes`` of ``.name``/``.lines``, lines of
``.name``/``.events``, events of ``.name``/``.start_ns``/``.duration_ns``:
what ``jax.profiler.ProfileData`` gives), so tests hand it fakes.

``python3 benchmark/host_spans.py <file.xplane.pb>`` prints the whole table
by hand: idle seconds by class and by span name, the head of a pass in ms,
and for the head's idle the critical feed stage at each instant, by "the
downstream-most busy stage wins": a ``put``, then ``stack``, ``encode``,
``collate``, ``prep``, the reader's ``parse``, else ``feed_start`` (no stage
busy: threads starting, a file being mapped).
"""

from __future__ import annotations

import bisect
import functools
import os
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from benchmark import trace_reduce       # noqa: E402
from wormhole_tpu.obs.ledger import span_bucket       # noqa: E402

CLASSES = ("head", "in_flight", "starved", "tail", "unnamed")
PASS_OPEN = "pass:open"
DISPATCH = ("dispatch", "mesh:dispatch")
TAIL_SPANS = ("pass:drain", "pass:close", "pass:flush")
NO_SPAN = "(no span)"
# the feed's stages, downstream first: which one the device waits on
STAGES = ("put", "stack", "encode", "collate", "prep", "parse")


def is_program_span(name: str) -> bool:
    """Is this host event one of the program's spans? (The profiler puts
    JAX's own events on the same lines.) The program's own table says."""
    return span_bucket(name) is not None


def stage_of(name: str):
    """The feed stage a worker thread's span is busy in, or None (a stall
    is no work, and a span of the loop's is no stage)."""
    if name.startswith("encode:"):
        return "encode"
    stage = name.rsplit(":", 1)[-1]
    if ":" not in name or stage.endswith("_stall"):
        return None
    stage = "prep" if stage == "pad" else stage
    return stage if stage in STAGES else None


def _span_events(line) -> list:
    """(start_ns, end_ns, name) of a host line's program spans, outermost
    first where two start together."""
    out = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
           for ev in line.events if is_program_span(ev.name)]
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def host_lines(profile):
    """(the loop's spans, [each other host line's spans]): the loop's line
    is the one that carries ``bench_pass``."""
    loop, others = None, []
    for plane in profile.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if any(ev.name == trace_reduce.PASS_SPAN for ev in line.events):
                loop = _span_events(line)
            else:
                spans = _span_events(line)
                if spans:
                    others.append(spans)
    return loop, others


def device_planes(profile, lo: float, hi: float,
                  step_program: str = "step") -> list:
    """(idle gaps, step starts) of every device plane that has ops inside
    [lo, hi]: the idle (start_ns, end_ns) gaps of its op line there, and the
    sorted starts of its step program's executions (the modules line's
    events that name ``step_program``, as ``reduce_trace`` finds them)."""
    out = []
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        busy, steps = [], []
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                busy = [(max(s, lo), min(e, hi))
                        for _n, s, e in trace_reduce._events(line)
                        if e > lo and s < hi]
            elif line.name == trace_reduce.MODULES_LINE:
                steps = sorted(float(ev.start_ns) for ev in line.events
                               if step_program in ev.name)
        if busy:
            out.append((trace_reduce.gaps(busy, lo, hi), steps))
    return out


def _sweep(spans: list, lo: float, hi: float, cuts=()):
    """Cut [lo, hi] at every span edge of one line (and at ``cuts``):
    (start, end, the names of the spans open there, outermost first) pieces,
    in order, covering the window."""
    edges = {lo, hi}
    edges.update(t for t in cuts if lo < t < hi)
    for s, e, _n in spans:
        edges.update(t for t in (s, e) if lo < t < hi)
    cuts = sorted(edges)
    stack, k = [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= a:
            stack.append(spans[k])
            k += 1
        stack = [sp for sp in stack if sp[1] > a]
        yield a, b, [n for _s, _e, n in stack]


def loop_segments(spans: list, lo: float, hi: float, heads: list) -> list:
    """The window cut at the span edges of the loop's line: (start, end,
    class, innermost span name) segments. ``heads``: :func:`pass_heads` on
    one device."""
    starts = [s for s, _e, _n in spans]
    out, h = [], 0
    for a, b, names in _sweep(spans, lo, hi,
                              [t for _s, first, began in heads
                               for t in (first, began)]):
        while h < len(heads) and heads[h][2] <= a:
            h += 1
        inner = names[-1] if names else NO_SPAN
        if h < len(heads) and heads[h][0] <= a:
            cls = "head" if a < heads[h][1] else "in_flight"
        elif any(n in TAIL_SPANS or n.endswith(":close") for n in names):
            cls = "tail"
        elif inner.endswith(":consume_stall"):
            cls = "starved"
        elif not names:
            # between a pass's last span and the next pass:open (or the
            # window's end; or before the window's first span): tail
            j = bisect.bisect_left(starts, b)
            cls = ("tail" if j in (0, len(spans)) or spans[j][2] in
                   (PASS_OPEN,) + TAIL_SPANS else "unnamed")
        else:
            cls = "unnamed"
        out.append((a, b, cls, inner))
    return out


def stage_busy(lines: list, lo: float, hi: float) -> dict:
    """{stage: the union of the intervals in which some feed thread's
    INNERMOST span was that stage's work}: a stage that waits inside (the
    outer feed's ``parse`` is mostly the inner feed's ``consume_stall``) is
    not busy while it waits."""
    busy = {st: [] for st in STAGES}
    for spans in lines:
        for a, b, names in _sweep(spans, lo, hi):
            st = stage_of(names[-1]) if names else None
            if st is not None:
                busy[st].append((a, b))
    return {st: _union(iv) for st, iv in busy.items()}


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _split(pieces: list, cover: list):
    """``pieces`` (disjoint, sorted) cut by ``cover`` (a union): (the parts
    inside it, the parts outside)."""
    inside, outside = [], []
    for s, e in pieces:
        edge = s
        for cs, ce in _clip(cover, s, e):
            if cs > edge:
                outside.append((edge, cs))
            inside.append((cs, ce))
            edge = ce
        if edge < e:
            outside.append((edge, e))
    return inside, outside


def attribute(profile) -> dict:
    """See the module docstring. Returns ``None`` without ``bench_pass`` or
    without the program's ``pass:open``; else seconds, averaged over the
    device planes: ``window_s``, ``idle_s``, ``classes`` {class: s},
    ``by_span`` {(class, innermost span): s}, ``head_stages`` {stage: s} (of
    the head's idle); ``planes``; and a pass that dispatched, in ms:
    ``pass_heads_ms`` (``pass:open``'s start to the first dispatch's) and
    ``first_step_ms`` (from there to the first step's start on the first
    device)."""
    window = trace_reduce.pass_spans(profile)
    loop, others = host_lines(profile)
    if not window or not loop or not any(n == PASS_OPEN
                                         for _s, _e, n in loop):
        return None
    lo, hi = window[0][0], window[-1][1]
    per_plane = device_planes(profile, lo, hi)
    if not per_plane:
        return None
    busy = stage_busy(others, lo, hi)
    classes, by_span = defaultdict(float), defaultdict(float)
    stages = defaultdict(float)
    for plane_gaps, steps in per_plane:
        segments = loop_segments(loop, lo, hi, pass_heads(loop, steps))
        k = 0
        for gs, ge in plane_gaps:
            while segments[k][1] <= gs:
                k += 1
            j = k
            while j < len(segments) and segments[j][0] < ge:
                a, b, cls, inner = segments[j]
                a, b = max(a, gs), min(b, ge)
                classes[cls] += b - a
                by_span[(cls, inner)] += b - a
                if cls == "head":
                    left = [(a, b)]
                    for st in STAGES:
                        got, left = _split(left, busy[st])
                        stages[st] += sum(e - s for s, e in got)
                    stages["feed_start"] += sum(e - s for s, e in left)
                j += 1
    n = len(per_plane)
    dispatched = {s for s, _e, name in loop if name in DISPATCH}
    heads = [(s, first, began) for s, first, began
             in pass_heads(loop, per_plane[0][1])
             if first in dispatched and lo <= s < hi]
    return {"window_s": (hi - lo) * 1e-9, "planes": n,
            "idle_s": sum(classes.values()) * 1e-9 / n,
            "classes": {c: classes[c] * 1e-9 / n for c in CLASSES},
            "by_span": {k: v * 1e-9 / n for k, v in by_span.items()},
            "head_stages": {k: v * 1e-9 / n for k, v in stages.items()},
            "pass_heads_ms": [1e-6 * (first - s) for s, first, _b in heads],
            "first_step_ms": [1e-6 * (began - first)
                              for _s, first, began in heads]}


def pass_heads(loop: list, steps=()) -> list:
    """(pass:open start, the head's end, the flight's end) a pass. The head
    ends at the start of the pass's first dispatch (one that starts before
    the next ``pass:open``), else (a pass that dispatched nothing) at the
    ``pass:open``'s end. What is in flight ends where the device starts the
    first step program at or after that dispatch (``steps``: its sorted
    starts on one device), inside the pass and before the pass's own tail;
    else (no modules line, no dispatch) where the head does: nothing."""
    opens = [(s, e) for s, e, n in loop if n == PASS_OPEN]
    firsts = [s for s, _e, n in loop if n in DISPATCH]
    drains = [s for s, _e, n in loop if n in TAIL_SPANS]
    out = []
    for i, (s, e) in enumerate(opens):
        nxt = opens[i + 1][0] if i + 1 < len(opens) else float("inf")
        j = bisect.bisect_left(firsts, s)
        if j == len(firsts) or firsts[j] >= nxt:
            out.append((s, e, e))
            continue
        d = bisect.bisect_left(drains, firsts[j])
        if d < len(drains):
            nxt = min(nxt, drains[d])
        k = bisect.bisect_left(steps, firsts[j])
        began = steps[k] if k < len(steps) and steps[k] < nxt else firsts[j]
        out.append((s, firsts[j], began))
    return out


# -- what the metric readers call -----------------------------------------


@functools.lru_cache(maxsize=1)          # six metrics read one run's trace
def table_of(xplane: str):
    return attribute(trace_reduce.load(xplane))


def table(r: dict):
    """The run's table, from the ``.xplane.pb`` that ``run.py`` keeps under
    ``.cache/<cell>/trace`` until the metrics are read; ``None`` for a run
    without a device trace (an untraced run, a CPU run), a trace that is
    gone, or a program without the spans."""
    if not r.get("trace"):
        return None
    cell = f"{r['config']['name']}.{r['traffic']['name']}"
    try:
        xplane = trace_reduce.find_xplane(
            os.path.join(HERE, ".cache", cell, "trace"))
    except FileNotFoundError:
        return None
    return table_of(xplane)


def idle_share(r: dict, cls: str):
    """The class's idle seconds over the traced window, in percent."""
    t = table(r)
    if t is None or t["window_s"] <= 0.0:
        return None
    return 100.0 * t["classes"][cls] / t["window_s"]


def describe(t: dict) -> str:
    if t is None:
        return "no bench_pass, no pass:open or no device ops: nothing to read"
    w = t["window_s"]
    rows = [f"window {w:.3f} s, {t['planes']} device plane(s), idle "
            f"{t['idle_s']:.3f} s ({100 * t['idle_s'] / w:.2f}%)"]
    for cls in CLASSES:
        s = t["classes"][cls]
        rows.append(f"  {cls:8s} {s:8.3f} s  {100 * s / w:6.2f}%")
        for (c, name), v in sorted(t["by_span"].items(),
                                   key=lambda kv: -kv[1]):
            if c == cls and v >= 5e-4:
                rows.append(f"      {v:8.3f} s  under {name}")
    heads = t["pass_heads_ms"]
    if heads:
        rows.append(f"pass head (pass:open to first dispatch), {len(heads)} "
                    f"passes: mean {sum(heads) / len(heads):.1f} ms, least "
                    f"{min(heads):.1f}, most {max(heads):.1f}")
        late = t["first_step_ms"]
        rows.append("first dispatch to the device's first step: mean "
                    f"{sum(late) / len(late):.1f} ms, least {min(late):.1f},"
                    f" most {max(late):.1f}")
    rows.append("the head's idle by critical feed stage (downstream-most "
                "busy stage wins):")
    for st in STAGES + ("feed_start",):
        v = t["head_stages"].get(st, 0.0)
        if v >= 5e-4:
            rows.append(f"      {v:8.3f} s  {st}")
    return "\n".join(rows)


if __name__ == "__main__":
    print(describe(attribute(trace_reduce.load(sys.argv[1]))))
