"""Device idle seconds under a ``<feed>:consume_stall`` of the pass loop's
thread after the pass's head and what was in flight behind it (the device
has started the pass's first step),
over the traced window, in percent: the loop waiting on the feed with nothing
on the device (``benchmark/host_spans.py``, class ``starved``). ``None``
without a device trace or without the program's spans (a parent commit, a CPU
run)."""

from benchmark import host_spans


def read(r: dict):
    return host_spans.idle_share(r, "starved")
