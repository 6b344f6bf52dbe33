"""The table of peaks, keyed by ``device_kind``. A device that is not in it
is an error, never a default."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_of(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json (has {sorted(table)}): add its "
                       "published peaks with their source")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> tuple:
    """The least time the chip could take for ``work`` = {"bytes", "flops"}:
    the larger of bytes over peak bytes/s and flops over peak FLOP/s, and
    which of the two bounds it."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = work["flops"] / peaks["flops_per_s"]
    return max(by_bytes, by_flops), ("bytes" if by_bytes >= by_flops
                                     else "flops")
