"""Device time a step of the COO overflow path of ``FMStore``'s spill step,
in ms: the ops that the profiler files under the program's scopes
``fm_ovf_pull`` (the listed pairs' w and v gathered plane by plane, their pull
channels formed in float32 and summed onto their rows, before the kernel) and
``fm_ovf_scatter`` (the pairs' dual channels gathered and added into the ten
push planes, after it). Both are jits of their own inside the step, so the
trace keeps their path as an op's ``tf_op``; the scopes are read as
``overflow_ms_per_step``'s reader reads ``ShardedStore``'s
(``tower_ms_per_step.scoped_ops``, ``seconds_of``: the same events, the same
window).

A program without these scopes (a parent commit, another store), a run
without a trace, or a trace that is gone: nothing to read, ``None``.
"""

from __future__ import annotations

import functools
import os

from benchmark import trace_reduce
from benchmark.readers import tower_ms_per_step as scoped

SCOPES = ("fm_ovf_pull", "fm_ovf_scatter")


@functools.lru_cache(maxsize=1)      # three metrics read one run's trace
def _parsed(xplane: str) -> tuple:
    return scoped.scoped_ops(xplane), trace_reduce.load(xplane)


def scope_seconds_per_step(r: dict, scopes: tuple):
    """Device seconds a step of the ops filed under any of ``scopes`` in the
    run's kept trace, or ``None`` where there is nothing to read."""
    tr = r.get("trace")
    if not tr or not tr.get("steps"):
        return None
    cell = f"{r['config']['name']}.{r['traffic']['name']}"
    try:
        xplane = trace_reduce.find_xplane(
            os.path.join(scoped.BENCHMARK_DIR, ".cache", cell, "trace"))
    except FileNotFoundError:
        return None
    scope_of, profile = _parsed(xplane)
    ops = {name for name, scope in scope_of.items()
           if any(w in scope for w in scopes)}
    if not ops:
        return None
    secs = scoped.seconds_of(profile, ops)
    return secs / tr["steps"] if secs else None


def read(r: dict):
    secs = scope_seconds_per_step(r, SCOPES)
    return None if secs is None else 1e3 * secs
