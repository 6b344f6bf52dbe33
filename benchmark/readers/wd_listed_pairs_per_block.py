"""Pairs a block on the COO overflow lists of the blocks that took
``WideDeepStore``'s spill step: the ``wd_listed_pairs`` count of the
program's Timer (a count, not seconds: the store counts a list's pairs once,
where it crosses to the device, and adds them at every step the block takes)
over its ``wd_spill_blocks``. A program without the counters (a parent
commit, another store) has nothing to read."""


def read(r: dict):
    t = (r.get("window") or {}).get("timers") or {}
    if not t.get("wd_spill_blocks") or "wd_listed_pairs" not in t:
        return None
    return t["wd_listed_pairs"] / t["wd_spill_blocks"]
