"""What wide&deep needs for one block of ``criteo_wide_deep_clicklog``,
whatever the formulation, and the bytes of the spill step's list and of its
one XLA pass over the table.

``block_work`` and ``tower_flops`` are ``criteo_wide_deep``'s counts (the
kernel pair's embedding side for ``tile_kernel_roofline.replay``, the dense
tower for ``tower_mxu_roofline.replay``). A pair on the block's COO overflow
list is a pair like any other and is counted with them, once.

``update_pass_bytes``: what ``wd_table_update`` moves, by its shapes: the
``dim + 2`` push planes and the ``2 (1 + dim)`` state planes read, the state
planes written, each a float32 plane of ``num_buckets``: 166 planes at dim
32, 11.1 GB at ``2**24``. The pass touches every bucket whatever the block
touched (that is the implementation's cost, and what
``wd_update_hbm_roofline.replay`` holds against the HBM peak: a share of a
peak, so it cannot pass 100%).

``list_bytes``: what the ALGORITHM needs for the listed pairs whatever
implements them: a pair's ``1 + dim`` values (w and v) read and its
``dim + 2`` dual channels (dual, d loss / d pooled, count) written, 4 B each,
counted on the TRUE pair count and not on the list's slots (its padded tail
is the layout's), so that ``wd_overflow_hbm_roofline.replay`` reads the same
work when a later PR changes the list's form. A lower bound of a lower bound:
a form that reads a hot bucket's values once for all its pairs moves less.
"""

from benchmark.configs.criteo_wide_deep.roofline import (  # noqa: F401
    block_work, tower_flops)


def update_pass_bytes(config: dict) -> int:
    state_planes = 2 * (1 + int(config["dim"]))
    push_planes = int(config["dim"]) + 2
    return 4 * int(config["num_buckets"]) * (push_planes + 2 * state_planes)


def list_bytes(config: dict, pairs: float) -> float:
    k = int(config["dim"])
    return 4.0 * pairs * ((1 + k) + (k + 2))
