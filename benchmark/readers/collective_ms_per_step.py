"""Device time of the collective operations (all-reduce and its kin) per
step per chip, in ms.

Read from the reduced trace's ``device_ops``: the ten operations that took
most device time in the window, each ``[short name, seconds a chip]``, the
short name being ``%<instruction> <kind> <result shape>``. The reader sums
those whose kind is a collective; one that is not among the ten is not
counted (the margins' all-reduce of 98,304 floats and the metric row's are
such), and where none is among them there is nothing to read. The time is
what the op's event holds on the device's op line: the transfer and the
wait for the slower partner of the ring alike.
"""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(short_name: str) -> bool:
    """``%psum.57 all-reduce f32[...]``: is the second word (the HLO
    kind, ``-start``/``-done`` halves included) a collective?"""
    words = short_name.split()
    return len(words) > 1 and words[1].startswith(COLLECTIVES)


def seconds_per_step(r: dict):
    """Seconds a step a chip in the collectives among the top ops, or None."""
    tr = r.get("trace")
    if not tr or not tr["steps"]:
        return None
    found = [secs for name, secs in tr["device_ops"] if is_collective(name)]
    return sum(found) / tr["steps"] if found else None


def read(r: dict):
    secs = seconds_per_step(r)
    return None if secs is None else 1e3 * secs
