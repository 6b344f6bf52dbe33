"""Device time a step of the COO overflow path of ``WideDeepStore``'s spill
step, in ms: the ops that the profiler files under the program's scopes
``wd_ovf_pull`` (inside ``wd_pull``: the listed buckets' w and v gathered
plane by plane, 33 float32 planes read once a listed bucket and the slots
from those, or a slot a pair where the list brings no distinct buckets, and
summed onto their rows beside the pull kernel's) and ``wd_ovf_scatter`` (inside ``wd_push``: the pairs' 34 dual
channels gathered from their rows and scatter-added into the kernel's
pushes, a slot a pair). Both are jits of their own inside the step, so the
trace keeps their path as an op's ``tf_op``; read as
``fm_overflow_ms_per_step`` reads ``FMStore``'s two (the same events, the
same window).

A program without these scopes (a parent commit, another store), a run
without a trace, or a trace that is gone: nothing to read, ``None``.
"""

from benchmark.readers.fm_overflow_ms_per_step import scope_seconds_per_step

SCOPES = ("wd_ovf_pull", "wd_ovf_scatter")


def seconds_per_step(r: dict):
    return scope_seconds_per_step(r, SCOPES)


def read(r: dict):
    secs = seconds_per_step(r)
    return None if secs is None else 1e3 * secs
