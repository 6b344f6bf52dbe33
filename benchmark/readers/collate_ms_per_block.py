"""Busy seconds of the text feed's collate stage over blocks, in ms: the inner
feed's transfer thread re-blocking parsed rows into fixed-row v1 blocks (the
``<feed>:collate`` span), the ``collate`` key of the program's Timer, which
only a text feed fills (``drain_pipe_stats`` through ``_merge_pipe_snap``).
A program without the counter (a parent commit): nothing to read,
``None``. Listed for the click-log cell alone: ``tests/benchmark/
test_bm_formats.py`` pins the uniform text cell's CPU run to the metrics it
had, so that cell's reading is taken by hand from the ``timers`` line until a
``benchmark`` issue lets the cell join (``PERF.md`` section 7.4)."""


def read(r: dict):
    t, blocks = r["window"]["timers"], r["window"]["blocks"]
    if not blocks or t.get("collate", 0.0) <= 0.0:
        return None
    return 1e3 * t["collate"] / blocks
