"""The collectives' share of their roofline: the least time one chip's
interconnect could take for the ICI bytes the program books for a step
(``ici_gb_per_step``'s count over the published ICI bandwidth of the device
kind, ``benchmark/ici_peaks.json``) over the device time of the step's
collective operations (``collective_ms_per_step``'s). It cannot pass 100%:
above it the bytes are booked too high, the peak too low, or a collective
is missing from the time. A device kind that is not in the table is an
error, never a default."""

import json
import os

from benchmark.readers import collective_ms_per_step, ici_gb_per_step

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "ici_peaks.json")


def ici_bytes_per_s(device_kind: str) -> float:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/ici_peaks.json (has {sorted(table)}): "
                       "add its published ICI bandwidth with its source")
    return float(table[device_kind]["ici_bytes_per_s"])


def read(r: dict):
    took = collective_ms_per_step.seconds_per_step(r)
    booked = ici_gb_per_step.bytes_per_step(r)
    if took is None or booked is None:
        return None
    import jax
    peak = ici_bytes_per_s(jax.devices()[0].device_kind)
    return 100.0 * (booked / peak) / took
