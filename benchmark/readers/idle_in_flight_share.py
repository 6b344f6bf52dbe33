"""Device idle seconds between a pass's first ``dispatch`` (or
``mesh:dispatch``) start on the pass loop's thread and the device's start of
the pass's first step program, over the traced window, in percent: the step
is dispatched and its first block's transfer is still in flight (the put
returns at once: no host span covers it), or the launch came late
(``benchmark/host_spans.py``, class ``in_flight``; the device plane's
``XLA Modules`` line says when the step began). ``None`` without a device
trace or without the program's spans (a parent commit, a CPU run)."""

from benchmark import host_spans


def read(r: dict):
    return host_spans.idle_share(r, "in_flight")
