"""Device time a step of the one XLA pass of ``WideDeepStore``'s split step
over the table, in ms: the ops that the profiler files under the program's
scope ``wd_table_update`` (34 push and 66 state planes in, 66 out onto the
donated state: AdaGrad on the touched buckets, with weight decay on v). A jit
of its own inside the step, read as ``fm_update_ms_per_step`` reads
``FMStore``'s.

A program without the scope (a parent commit, another store), a run without a
trace, or a trace that is gone: nothing to read, ``None``.
"""

from benchmark.readers.fm_overflow_ms_per_step import scope_seconds_per_step

SCOPES = ("wd_table_update",)


def seconds_per_step(r: dict):
    return scope_seconds_per_step(r, SCOPES)


def read(r: dict):
    secs = seconds_per_step(r)
    return None if secs is None else 1e3 * secs
