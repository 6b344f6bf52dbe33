"""From a profiler trace (``.xplane.pb``) to numbers: the yardstick's reducer.

Read with nothing but JAX (``jax.profiler.ProfileData``). A TPU device plane
(``/device:TPU:<n>``) has a line of XLA ops (one event an executed HLO op,
with start and duration in ns) and a line of XLA modules (one event an
executed program). The host plane has one line a thread; the harness writes
a ``bench_pass`` ``TraceAnnotation`` around every pass, which is how an idle
gap is named ``inside_a_pass`` or ``between_passes``.

``reduce_trace`` returns, for the chips used (averaged where a time):

    window_s      the traced window (first bench_pass start to last end; the
                  span of device events when no pass was annotated)
    busy_s        union of the intervals in which an op ran on the device
    step_s        device time of every op inside the step program's events
    kernel_s      of those, the Mosaic custom calls (the Pallas kernels)
    steps         executions of the step program
    device_ops    [[name, seconds], ...] the ten ops that took most time
    idle_gaps     [["inside_a_pass", s], ["between_passes", s]]

``python3 benchmark/trace_reduce.py <file.xplane.pb>`` describes a trace by
hand: planes, lines, the commonest events.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from collections import Counter, defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PASS_SPAN = "bench_pass"
KERNEL_MARK = "tpu_custom_call"     # custom_call_target of a Mosaic kernel
_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\b([a-z][a-z\-]*)\(")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line) -> list:
    """(name, start_ns, end_ns) of a line's events, by start."""
    out = [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
           for ev in line.events]
    out.sort(key=lambda e: e[1])
    return out


def union_seconds(intervals: list) -> float:
    """Total length of the union of (start_ns, end_ns) intervals, in s."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def gaps(intervals: list, lo: float, hi: float) -> list:
    """The idle (start_ns, end_ns) gaps of the union inside [lo, hi]."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
    if edge < hi:
        out.append((edge, hi))
    return out


def is_kernel(name: str) -> bool:
    """Is this op event a Pallas (Mosaic) kernel?"""
    return KERNEL_MARK in name


def short_name(name: str, width: int = 96) -> str:
    """An op event's name is its whole HLO text; keep the op's own name, its
    kind and the start of its result shape: ``%fusion.34 fusion f32[...]``."""
    m = _HLO.match(name)
    if not m:
        return name[:width]
    lhs, shape, kind = m.groups()
    if is_kernel(name):
        kind = "tpu_custom_call"
    return f"{lhs} {kind} {shape.strip()}"[:width]


def _inside(mid: float, spans: list) -> bool:
    """Is ``mid`` inside one of the sorted, disjoint (start, end) spans?"""
    i = bisect.bisect_right(spans, (mid, float("inf"))) - 1
    return i >= 0 and mid <= spans[i][1]


def pass_spans(profile) -> list:
    """(start_ns, end_ns) of the harness's bench_pass annotations."""
    spans = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == PASS_SPAN:
                    spans.append((float(ev.start_ns),
                                  float(ev.start_ns + ev.duration_ns)))
    return sorted(spans)


def reduce_trace(profile, step_program: str = "step", chips: int = 1,
                 top: int = 10) -> dict:
    """See the module docstring. ``step_program``: the substring that names
    the step program's events on the modules line (``jit_step``)."""
    spans = pass_spans(profile)
    per_chip = []
    op_time: Counter = Counter()
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = _events(line)
            elif line.name == MODULES_LINE:
                modules = _events(line)
        if not ops:
            continue
        steps = sorted((s, e) for name, s, e in modules
                       if step_program in name)
        lo = spans[0][0] if spans else ops[0][1]
        hi = spans[-1][1] if spans else max(e for _n, _s, e in ops)
        inside = [(n, s, e) for n, s, e in ops if e > lo and s < hi]
        intervals = [(max(s, lo), min(e, hi)) for _n, s, e in inside]
        step_s = kernel_s = 0.0
        for name, s, e in inside:
            op_time[name] += (e - s) * 1e-9
            if not steps or _inside(0.5 * (s + e), steps):
                step_s += (e - s) * 1e-9
                if is_kernel(name):
                    kernel_s += (e - s) * 1e-9
        gap = defaultdict(float)
        for s, e in gaps(intervals, lo, hi):
            where = ("inside_a_pass" if _inside(0.5 * (s + e), spans)
                     else "between_passes")
            gap[where] += (e - s) * 1e-9
        per_chip.append({
            "window_s": (hi - lo) * 1e-9,
            "busy_s": union_seconds(intervals),
            "step_s": step_s, "kernel_s": kernel_s,
            "steps": len([1 for s, e in steps if e > lo and s < hi]),
            "gaps": gap})
    if not per_chip:
        return {}
    n = len(per_chip)
    mean = lambda key: sum(c[key] for c in per_chip) / n   # noqa: E731
    return {
        "window_s": mean("window_s"), "busy_s": mean("busy_s"),
        "step_s": mean("step_s"), "kernel_s": mean("kernel_s"),
        "steps": max(c["steps"] for c in per_chip),
        "device_ops": [[short_name(name), secs / n]
                       for name, secs in op_time.most_common(top)],
        "idle_gaps": [[k, sum(c["gaps"].get(k, 0.0) for c in per_chip) / n]
                      for k in ("inside_a_pass", "between_passes")],
    }


def describe(profile, most: int = 12) -> str:
    rows = []
    for plane in profile.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  line {line.name!r}: {len(evs)} events")
            seen = Counter()
            for ev in evs:
                seen[ev.name] += ev.duration_ns
            for name, ns in seen.most_common(most):
                rows.append(f"    {ns * 1e-6:10.3f} ms  {name[:140]}")
            for ev in evs[:1]:
                try:
                    stats = {k: str(v)[:80] for k, v in ev.stats}
                except Exception as e:          # noqa: BLE001
                    stats = f"(no stats: {e!r})"
                rows.append(f"    first event stats: {stats}")
    return "\n".join(rows)


if __name__ == "__main__":
    print(describe(load(sys.argv[1])))
