"""The dense tower's share of the MXU's peak: the FLOPs the tower needs for
a step (the configuration's ``roofline.tower_flops``: three matmuls a layer
and row, forward and backward) over ``peaks.json``'s ``flops_per_s`` of the
device kind (the published bfloat16 peak, whatever precision the tower
states: a tower that stated float32 would read a smaller share, not another
peak) over the tower's device time a step (``tower_ms_per_step``'s). It
cannot pass 100%: above it the FLOPs are counted too high or a tower op is
missing from the time. A device kind that is not in the table is an error,
never a default; a configuration without a tower has nothing to read."""

import importlib

from benchmark import peaks
from benchmark.readers import tower_ms_per_step


def read(r: dict):
    took = tower_ms_per_step.seconds_per_step(r)
    if took is None:
        return None
    config = r["config"]
    roofline = importlib.import_module(
        f"benchmark.configs.{config['name']}.roofline")
    if not hasattr(roofline, "tower_flops"):
        return None
    import jax
    peak = float(peaks.peaks_of(jax.devices()[0].device_kind)["flops_per_s"])
    flops = roofline.tower_flops(config, int(config["block_rows"]))
    return 100.0 * (flops / peak) / took
