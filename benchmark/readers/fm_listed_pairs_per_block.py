"""Pairs a block on the COO overflow lists of the blocks that took
``FMStore``'s spill step: the ``fm_listed_pairs`` count of the program's
Timer (a count, not seconds: the store counts a list's pairs once, where it
crosses to the device, and adds them at every step the block takes) over the
window's blocks. A program without the counter (a parent commit, another
store) has nothing to read."""


def read(r: dict):
    window = r.get("window") or {}
    t, blocks = window.get("timers") or {}, window.get("blocks")
    if not blocks or "fm_listed_pairs" not in t:
        return None
    return t["fm_listed_pairs"] / blocks
