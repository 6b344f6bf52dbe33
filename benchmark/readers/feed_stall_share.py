"""Seconds the consumer waited on the feed's ring (``consume_stall`` of
``drain_pipe_stats``, which the pass loop merges into its Timer as
``feed_stall``) over window seconds."""


def read(r: dict):
    t = r["window"]["timers"]
    if "feed_stall" not in t:
        return None
    return 100.0 * t["feed_stall"] / r["window"]["window_s"]
