"""Device idle seconds that no span of the program explains, over the traced
window, in percent: under no program span inside a pass, or under
``wait``/``dispatch``, where the loop believes the device busy (launch
latency, or a fault; a pass's first step has a class of its own, ``in_flight``): ``benchmark/host_spans.py``, class
``unnamed``. ``None`` without a device trace or without the program's spans (a
parent commit, a CPU run)."""

from benchmark import host_spans


def read(r: dict):
    return host_spans.idle_share(r, "unnamed")
