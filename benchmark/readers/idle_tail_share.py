"""Device idle seconds in the tail of a pass, over the traced window, in
percent: under ``pass:drain``, ``pass:close``, ``pass:flush`` or the feed's
``<feed>:close``, or under no program span between a pass's last span and the
next ``pass:open`` (the harness's fence): ``benchmark/host_spans.py``, class
``tail``. ``None`` without a device trace or without the program's spans (a
parent commit, a CPU run)."""

from benchmark import host_spans


def read(r: dict):
    return host_spans.idle_share(r, "tail")
