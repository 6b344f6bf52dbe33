"""Device time of wide&deep's dense tower per step, in ms: the ops of the
step program that the profiler files under the program's tower scopes
(``wd_tower_forward``, ``wd_tower_backward``), forward and backward: the
tower's matmuls with the element-wise work XLA fuses into them. In PR 34's
traces that is all of the tower: the bias add is in the matmul's fusion, the
ReLU and the operands' rounding are the next matmul's producers, the ReLU's
mask and the bias gradient's sum ride the backward fusions, and the weight
gradients' fusions carry the dense AdaGrad update besides; no op with the
block's 98,304 rows between the pull and the push kernels lacks the scope
(``chiprun_out/pr34_*_wd.xplane.pb``, listed op by op in PERF.md section 5).

The reduced trace keeps the ten longest ops, and an op's event carries its
HLO text without the scope it was traced under, so this reader opens the
run's ``.xplane.pb`` itself. The profiler keeps the scope path as the
``tf_op`` stat of the op's event METADATA, for an op inside a nested jit
(``jit(step)/wd_tower_forward/jvp(jit(wd_tower))/dot_general``; a bare named
scope's ops lose theirs, which is why the program makes the tower a jit of
its own). ``jax.profiler.ProfileData`` does not show metadata stats;
``scoped_ops`` reads them from the file with a decoder of the few protobuf
fields it needs (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
.stat_metadata = 5; XEventMetadata.name = 2, .display_name = 4, .stats = 5;
XStat.metadata_id = 1, .str_value = 5, .ref_value = 7; XStatMetadata.name =
2). Times come from the same events and the same window as
``trace_reduce.reduce_trace``: chip 0's op line, first ``bench_pass`` start to
last end.

A program without these scopes (a parent commit, another configuration), a
run without a trace, or a trace that is gone: nothing to read, ``None``.
"""

from __future__ import annotations

import functools
import os

from benchmark import trace_reduce

SCOPES = ("wd_tower_forward", "wd_tower_backward")
DEVICE_PLANE = "/device:TPU:0"
BENCHMARK_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint
    or a fixed field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, value


def _map_entry(buf) -> tuple:
    key, value = 0, b""
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def scoped_ops(path: str, plane_name: str = DEVICE_PLANE) -> dict:
    """{an op event's name: the scope path it was traced under} for the
    ops of one device plane of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and bytes(v).decode() == plane_name
                   for f, v in parts):
            continue
        stat_names = {}
        for f, v in parts:
            if f == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode("utf-8", "replace")
                     for g, x in _fields(meta) if g == 2), "")
        out = {}
        for f, v in parts:
            if f != 4:
                continue
            names, scope = [], None
            for g, x in _fields(_map_entry(v)[1]):
                if g in (2, 4):
                    names.append(bytes(x).decode("utf-8", "replace"))
                elif g == 5:
                    stat = dict(_fields(x))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    scope = (bytes(stat[5]).decode("utf-8", "replace")
                             if 5 in stat else stat_names.get(stat.get(7)))
            if scope:
                out.update((name, scope) for name in names)
        return out
    return {}


def seconds_of(profile, names: set) -> float:
    """Device seconds, inside the traced window, of chip 0's op events
    whose name is one of ``names``."""
    spans = trace_reduce.pass_spans(profile)
    total = 0.0
    for plane in profile.planes:
        if plane.name != DEVICE_PLANE:
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            ops = trace_reduce._events(line)
            if not ops:
                continue
            lo = spans[0][0] if spans else ops[0][1]
            hi = spans[-1][1] if spans else max(e for _n, _s, e in ops)
            total += sum((e - s) * 1e-9 for name, s, e in ops
                         if name in names and e > lo and s < hi)
    return total


@functools.lru_cache(maxsize=1)      # two metrics read one run's trace
def tower_seconds(xplane: str):
    """Seconds of the window in the tower's ops, or None without them."""
    tower = {name for name, scope in scoped_ops(xplane).items()
             if any(w in scope for w in SCOPES)}
    if not tower:
        return None
    return seconds_of(trace_reduce.load(xplane), tower) or None


def seconds_per_step(r: dict):
    tr = r.get("trace")
    if not tr or not tr["steps"]:
        return None
    # where run.py keeps the run's trace until the metrics are read
    cell = f"{r['config']['name']}.{r['traffic']['name']}"
    try:
        xplane = trace_reduce.find_xplane(
            os.path.join(BENCHMARK_DIR, ".cache", cell, "trace"))
    except FileNotFoundError:
        return None
    secs = tower_seconds(xplane)
    return None if secs is None else secs / tr["steps"]


def read(r: dict):
    secs = seconds_per_step(r)
    return None if secs is None else 1e3 * secs
