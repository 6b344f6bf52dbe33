"""The head of a pass in ms: ``pass:open``'s start to the pass's first
``dispatch`` (or ``mesh:dispatch``) start on the pass loop's thread, the mean
over the traced window's passes, from the program's spans in the run's
``.xplane.pb`` (``benchmark/host_spans.py``). ``None`` without a device trace
or without the spans (a parent commit, a CPU run)."""

from benchmark import host_spans


def read(r: dict):
    t = host_spans.table(r)
    if t is None or not t["pass_heads_ms"]:
        return None
    return sum(t["pass_heads_ms"]) / len(t["pass_heads_ms"])
