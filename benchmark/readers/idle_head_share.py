"""Device idle seconds in the head of a pass, over the traced window, in
percent: from a ``pass:open``'s start to that pass's first ``dispatch`` (or
``mesh:dispatch``) start on the pass loop's thread, the interval
``pass_head_ms.stream`` times: the pass's set-up and the new feed's first
block. ``benchmark/host_spans.py`` names the device trace's idle gaps by the
program's spans in the same ``.xplane.pb``; its five classes sum to
``device_idle_share``. ``None`` without a device trace or without the
program's spans (a parent commit, a CPU run)."""

from benchmark import host_spans


def read(r: dict):
    return host_spans.idle_share(r, "head")
