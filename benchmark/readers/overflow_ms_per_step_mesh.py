"""Device time a step of the mesh step's COO overflow path, in ms, a chip's
mean over the device planes: the ops that the profiler files under the
program's nested jits ``mesh_ovf_gather`` (every chip masks its DATA member's
whole list to its MODEL shard's key range, gathers ``w`` at the owned pairs'
buckets and adds it onto their rows, before the margins' psum) and
``mesh_ovf_scatter`` (the duals gathered at the pairs' rows and added into the
shard's gradient, before the gradient's psum). Jits of their own inside the
``shard_map`` body, so the trace keeps their path as an op's ``tf_op``
(``tower_ms_per_step.scoped_ops`` reads it from the file, a device plane at a
time). Times come from the same events and the same window as
``trace_reduce.reduce_trace``: the op line of every device plane with ops,
first ``bench_pass`` start to last end, averaged over those planes.

A program without these jits (a commit before PR 43: bare named scopes, which
the profiler loses), a run without a trace, or a trace that is gone: nothing
to read, ``None``.
"""

from __future__ import annotations

import os

from benchmark import trace_reduce
from benchmark.readers import tower_ms_per_step as scoped

SCOPES = ("mesh_ovf_gather", "mesh_ovf_scatter")


def seconds_a_chip(xplane: str):
    """Seconds of the traced window in the scoped ops, the mean over the
    device planes that have any op in it; None where no plane has a scoped
    op."""
    profile = trace_reduce.load(xplane)
    spans = trace_reduce.pass_spans(profile)
    per_plane, found = [], False
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                ops = trace_reduce._events(line)
        if not ops:
            continue
        names = {name for name, scope in
                 scoped.scoped_ops(xplane, plane.name).items()
                 if any(w in scope for w in SCOPES)}
        found = found or bool(names)
        lo = spans[0][0] if spans else ops[0][1]
        hi = spans[-1][1] if spans else max(e for _n, _s, e in ops)
        per_plane.append(sum((e - s) * 1e-9 for name, s, e in ops
                             if name in names and e > lo and s < hi))
    if not found:
        return None
    return sum(per_plane) / len(per_plane)


def read(r: dict):
    tr = r.get("trace")
    if not tr or not tr["steps"]:
        return None
    cell = f"{r['config']['name']}.{r['traffic']['name']}"
    try:
        xplane = trace_reduce.find_xplane(
            os.path.join(scoped.BENCHMARK_DIR, ".cache", cell, "trace"))
    except FileNotFoundError:
        return None
    secs = seconds_a_chip(xplane)
    return 1e3 * secs / tr["steps"] if secs else None
