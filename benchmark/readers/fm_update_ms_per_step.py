"""Device time a step of the one XLA pass of ``FMStore``'s spill step over
the table, in ms: the ops that the profiler files under the program's scope
``fm_table_update`` (ten push and eighteen state planes in, eighteen out onto
the donated state: AdaGrad on the touched buckets). A jit of its own inside
the step, read as ``fm_overflow_ms_per_step`` reads the list's two.

A program without the scope (a parent commit, another store, a block that
took the in-place kernel), a run without a trace, or a trace that is gone:
nothing to read, ``None``.
"""

from benchmark.readers.fm_overflow_ms_per_step import scope_seconds_per_step

SCOPES = ("fm_table_update",)


def seconds_per_step(r: dict):
    return scope_seconds_per_step(r, SCOPES)


def read(r: dict):
    secs = seconds_per_step(r)
    return None if secs is None else 1e3 * secs
