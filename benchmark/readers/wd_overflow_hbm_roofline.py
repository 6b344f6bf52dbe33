"""The share of the HBM peak that the list path of ``WideDeepStore``'s spill
step reaches: the bytes the algorithm needs for a block's listed pairs (the
configuration's ``roofline.list_bytes``: a pair's 33 values read and 34 dual
values written, on the TRUE pair count, ``wd_listed_pairs_per_block``'s, not
on the list's slots) over ``peaks.json``'s ``hbm_bytes_per_s`` of the device
kind, over the list path's device time a step (``wd_overflow_ms_per_step``'s).
A list of single float32 gathers and scatters a slot reads a small
fraction of a percent: the number a hot form of the list has to move. It
cannot pass 100%. A device kind that is not in the table is an error, never a
default; a configuration whose roofline module has no such function, a
program without the scopes or the counters, or a run without a trace has
nothing to read."""

from benchmark.readers import (wd_listed_pairs_per_block,
                               wd_overflow_ms_per_step)
from benchmark.readers.wd_update_hbm_roofline import hbm_share


def read(r: dict):
    pairs = wd_listed_pairs_per_block.read(r)
    if pairs is None:
        return None
    return hbm_share(r, wd_overflow_ms_per_step.seconds_per_step(r),
                     "list_bytes", pairs)
