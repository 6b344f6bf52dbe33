"""Pairs a block that the online tile encoder put on the COO overflow list:
the ``online_overflow_pairs`` count of the program's Timer (a count, not
seconds: ``TileOnlineFeed`` adds each block's true overflow count as it ships
the block) over the window's blocks. A program without the counter (a commit
before PR 39) or a feed that encodes nothing online has nothing to read."""


def read(r: dict):
    t, blocks = r["window"]["timers"], r["window"]["blocks"]
    if not blocks or "online_overflow_pairs" not in t:
        return None
    return t["online_overflow_pairs"] / blocks
