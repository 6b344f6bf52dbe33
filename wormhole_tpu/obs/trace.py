"""Span tracing: one span primitive, two sinks.

Dapper-style spans (Sigelman et al., 2010) over the hot paths this repo
already times — the pass loop's open/dispatch/wait/drain/close,
DeviceFeed stages, collective boundaries, GBDT histogram kernels and
chunk reads, checkpoint save/load. :func:`span` is the one primitive;
a span is opened and closed where the work happens, on the thread that
does it, and lands in:

* **the profiler** (``jax.profiler.TraceAnnotation``), always: with no
  profiler session open that is the profiler's own no-op; inside one
  (``jax.profiler.start_trace``, a TensorBoard capture, the benchmark's
  ``--trace 1``) the span is in the same ``.xplane.pb`` as the device's
  ops, on the same clock, on its thread's own line of ``/host:CPU``.
  The class is bound lazily through ``sys.modules`` (as :func:`_rank`
  finds jax), so ``obs`` imports without jax: no jax, no such sink;
* **the ring**, behind :func:`enable`: a bounded recorder stamped with
  ``time.monotonic()`` and flushed as Chrome trace-event JSON that
  loads in Perfetto (ui.perfetto.dev) or chrome://tracing. Host only:
  it shares no clock with a device trace (``obs/merge.py`` aligns the
  ranks' files with each other on the wall clock).

Design constraints of the ring, in order:

1. **Near-zero cost when off.** The ring is off by default; every
   record call starts with one module-global bool check and returns. The
   instrumented paths (``Timer.scope``, DeviceFeed stages) are
   per-*batch*, not per-row, so even enabled tracing is noise next to a
   device step.
2. **Bounded memory.** Events land in a ``deque(maxlen=ring)`` — a long
   run keeps the freshest window instead of growing without bound
   (the dist_monitor.h rate-limit philosophy applied to traces).
3. **Thread attribution.** Events carry the recording thread's id and
   the first event per thread registers its name, so the pipeline's
   dispatcher / prep workers / transfer thread / consumer render as
   separate Perfetto tracks and stage overlap is visible.

Events are stored as tuples and formatted only at :func:`flush`; the
record path does no dict building, no JSON, no I/O.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional

__all__ = ["enable", "disable", "enabled", "configure", "span",
           "counter", "events", "summary", "reset", "dropped", "flush",
           "write_trace"]

# module-global fast path: `if not _ENABLED: return` is the entire cost
# of every record call while tracing is off
_ENABLED = False
_RING: "deque" = deque(maxlen=1)
_PATH: Optional[str] = None
_PID = 0
_T0 = 0.0                      # monotonic base; ts are relative to it
_WALL_T0 = 0.0                 # wall clock at _T0 (merge.py alignment)
_DROPPED = 0                   # ring evictions since configure()
_TID_NAMES: dict = {}          # tid -> thread name (first event wins)

# event tuples: (ph, name, cat, ts_us, dur_us, tid, arg)
_PH_COMPLETE = "X"
_PH_COUNTER = "C"

# jax.profiler.TraceAnnotation, once jax is there to take it from
_ANNOTATION = None


def _bind_annotation():
    """The profiler's span class, from a jax that something else has
    already imported (never imported here). None without one."""
    global _ANNOTATION
    j = sys.modules.get("jax")
    if j is not None:
        try:
            _ANNOTATION = j.profiler.TraceAnnotation
        except AttributeError:
            pass        # a jax still importing: ask again next span
    return _ANNOTATION


def _rank() -> int:
    """Process rank without forcing a jax import: prefer an initialized
    multi-process jax runtime, fall back to the launcher's PROCESS_ID
    env, then 0. A jax that never ran ``distributed.initialize`` reports
    ``process_index() == 0`` in every launch_mp child, so its answer is
    only trusted when the jax world is actually larger than one."""
    j = sys.modules.get("jax")
    if j is not None:
        try:
            if int(j.process_count()) > 1:
                return int(j.process_index())
        except Exception:
            pass
    return int(os.environ.get("PROCESS_ID", "0"))


def configure(trace_path: str = "", ring: int = 1 << 16,
              enabled: Optional[bool] = None,
              pid: Optional[int] = None) -> None:
    """(Re)configure the global recorder. ``trace_path`` non-empty (or
    ``enabled=True`` for a ring-only, no-file session) turns tracing on;
    both empty/False turns it off and drops buffered events. ``pid``
    overrides the recorder's process rank (the Obs hub passes the rank
    it was constructed with — authoritative over the env sniffing)."""
    global _ENABLED, _RING, _PATH, _PID, _T0, _WALL_T0, _DROPPED
    on = bool(trace_path) if enabled is None else enabled
    _PATH = trace_path or None
    if on:
        _RING = deque(maxlen=max(int(ring), 16))
        _TID_NAMES.clear()
        _PID = _rank() if pid is None else int(pid)
        _T0 = time.monotonic()
        _WALL_T0 = time.time()
        _DROPPED = 0
    _ENABLED = on
    if not on:
        _RING = deque(maxlen=1)
        _TID_NAMES.clear()


def enable(trace_path: str = "", ring: int = 1 << 16,
           pid: Optional[int] = None) -> None:
    configure(trace_path, ring, enabled=True, pid=pid)


def disable() -> None:
    configure("", enabled=False)


def enabled() -> bool:
    return _ENABLED


def _record(ph: str, name: str, cat: str, ts: float, dur: float,
            arg=None) -> None:
    global _DROPPED
    t = threading.current_thread()
    tid = t.ident or 0
    if tid not in _TID_NAMES:
        _TID_NAMES[tid] = t.name
    if len(_RING) == _RING.maxlen:
        # the append below silently evicts the oldest event; count it so
        # a truncated trace is detectable (summary counter + flush
        # metadata). Approximate under racing writers — it's a tally,
        # not an index.
        _DROPPED += 1
    # deque.append is atomic under the GIL — no lock on the record path
    _RING.append((ph, name, cat, (ts - _T0) * 1e6, dur * 1e6, tid, arg))


class span:
    """``with trace.span("checkpoint:save"): ...``: the one span
    primitive. It always opens a profiler annotation of the same name
    (see the module docstring) and, while the ring is on, records a
    complete event when it closes. A mutable ``args`` dict may be
    filled *inside* the span (payload sizes known only after encoding);
    the ring snapshots it when the span closes. The profiler takes the
    name alone."""

    __slots__ = ("name", "cat", "args", "_t0", "_ann")

    def __init__(self, name: str, cat: str = "",
                 args: Optional[dict] = None) -> None:
        self.name, self.cat, self.args = name, cat, args

    def __enter__(self) -> None:
        ann = _ANNOTATION or _bind_annotation()
        if ann is not None:
            ann = ann(self.name)
            ann.__enter__()
        self._ann = ann
        self._t0 = time.monotonic() if _ENABLED else None

    def __exit__(self, *exc) -> None:
        t0 = self._t0
        if t0 is not None and _ENABLED:
            _record(_PH_COMPLETE, self.name, self.cat, t0,
                    time.monotonic() - t0,
                    dict(self.args) if self.args else None)
        if self._ann is not None:
            self._ann.__exit__(*exc)


def counter(name: str, value: float, cat: str = "") -> None:
    """Chrome counter-track sample (rendered as a line chart)."""
    if not _ENABLED:
        return
    _record(_PH_COUNTER, name, cat, time.monotonic(), 0.0, float(value))


def events() -> list:
    """Buffered events as trace-event dicts (the flush format)."""
    out = []
    for ph, name, cat, ts, dur, tid, arg in list(_RING):
        ev = {"ph": ph, "name": name, "pid": _PID, "tid": tid,
              "ts": round(ts, 3)}
        if cat:
            ev["cat"] = cat
        if ph == _PH_COMPLETE:
            ev["dur"] = round(dur, 3)
            if arg:
                ev["args"] = arg
        elif ph == _PH_COUNTER:
            ev["args"] = {"value": arg}
        out.append(ev)
    return out


def summary() -> dict:
    """Aggregate buffered complete-spans: name -> {count, total_s}.
    The bench folds this per-phase view into its --out JSON."""
    agg: dict = {}
    for ph, name, _cat, _ts, dur, _tid, _arg in list(_RING):
        if ph != _PH_COMPLETE:
            continue
        row = agg.setdefault(name, {"count": 0, "total_s": 0.0})
        row["count"] += 1
        row["total_s"] += dur / 1e6
    for row in agg.values():
        row["total_s"] = round(row["total_s"], 6)
    return agg


def dropped() -> int:
    """Ring evictions since :func:`configure` — events silently lost to
    the bounded buffer. Cumulative across :func:`reset` (phase resets
    keep the run-level truncation visible)."""
    return _DROPPED


def reset() -> None:
    _RING.clear()


def write_trace(path: str, evs: list) -> str:
    """Write ``evs`` (trace-event dicts, e.g. accumulated :func:`events`
    batches) plus the recorder's thread/process metadata as a Chrome
    trace-event JSON file (atomic tmp+replace). The bench uses this to
    merge per-phase event batches into one viewable file.

    The doc carries a ``metadata`` block (Perfetto ignores unknown
    top-level keys): the recorder's rank, its monotonic/wall time bases
    (obs/merge.py aligns per-rank files on these), and the drop count —
    a nonzero ``dropped_spans`` marks the trace as truncated."""
    evs = list(evs)
    for tid, tname in sorted(_TID_NAMES.items()):
        evs.append({"ph": "M", "name": "thread_name", "pid": _PID,
                    "tid": tid, "args": {"name": tname}})
    evs.append({"ph": "M", "name": "process_name", "pid": _PID,
                "args": {"name": f"wormhole-host{_PID}"}})
    doc = {"traceEvents": evs, "displayTimeUnit": "ms",
           "metadata": {"rank": _PID, "mono_t0": round(_T0, 6),
                        "wall_t0": round(_WALL_T0, 6),
                        "dropped_spans": _DROPPED}}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def flush(path: Optional[str] = None) -> Optional[str]:
    """Write buffered events (plus per-thread name metadata) as Chrome
    trace-event JSON. Returns the path written, or None when tracing is
    off / no destination is configured."""
    dst = path or _PATH
    if not _ENABLED or not dst:
        return None
    return write_trace(dst, events())
