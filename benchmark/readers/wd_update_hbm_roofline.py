"""The share of the HBM peak that ``WideDeepStore``'s update pass reaches:
the bytes the pass moves (the configuration's ``roofline.update_pass_bytes``:
34 push and 66 state planes read, 66 written, by their shapes) over
``peaks.json``'s ``hbm_bytes_per_s`` of the device kind, over the pass's
device time a step (``wd_update_ms_per_step``'s). It cannot pass 100%: above
it the bytes are counted too high or an op of the pass is missing from the
time. A device kind that is not in the table is an error, never a default; a
configuration whose roofline module has no such function, a program without
the scope, or a run without a trace has nothing to read."""

import importlib

from benchmark import peaks
from benchmark.readers import wd_update_ms_per_step


def hbm_share(r: dict, took, count: str, *args):
    """100 x the seconds the HBM peak needs for the bytes that the
    configuration's ``roofline.<count>(config, *args)`` counts, over
    ``took`` seconds; ``None`` without ``took`` or without the function."""
    if took is None:
        return None
    config = r["config"]
    roofline = importlib.import_module(
        f"benchmark.configs.{config['name']}.roofline")
    if not hasattr(roofline, count):
        return None
    import jax
    peak = float(peaks.peaks_of(
        jax.devices()[0].device_kind)["hbm_bytes_per_s"])
    return 100.0 * (getattr(roofline, count)(config, *args) / peak) / took


def read(r: dict):
    return hbm_share(r, wd_update_ms_per_step.seconds_per_step(r),
                     "update_pass_bytes")
