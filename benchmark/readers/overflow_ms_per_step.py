"""Device time a step of the COO overflow path of ``ShardedStore``'s spill
step, in ms: the ops that the profiler files under the program's scopes
``tile_ovf_gather`` (the overflow pairs' weights gathered and summed onto
their rows, before the kernel) and ``tile_ovf_scatter`` (the pairs' duals
gathered and added into the gradient plane, after it). Both are jits of their
own inside the step, so the trace keeps their path as an op's ``tf_op``; the
scopes are read as ``tower_ms_per_step``'s reader reads wide&deep's
(``scoped_ops``, ``seconds_of``: the same events, the same window).

A program without these scopes (a commit before PR 39, another store), a run
without a trace, or a trace that is gone: nothing to read, ``None``.
"""

from __future__ import annotations

import os

from benchmark import trace_reduce
from benchmark.readers import tower_ms_per_step as scoped

SCOPES = ("tile_ovf_gather", "tile_ovf_scatter")


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
    ops = {name for name, scope in scoped.scoped_ops(xplane).items()
           if any(w in scope for w in SCOPES)}
    if not ops:
        return None
    secs = scoped.seconds_of(trace_reduce.load(xplane), ops)
    return 1e3 * secs / tr["steps"] if secs else None
