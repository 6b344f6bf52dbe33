"""What the factorization machine needs for one block of
``criteo_fm_clicklog``, whatever the formulation, and the bytes of the spill
step's one XLA pass over the table.

``block_work`` is ``criteo_fm``'s count at this table size, and it counts what
the ALGORITHM needs whatever implements it: the block's pair words (one u32 a
pair) and labels (one byte a row) read once, and for each distinct bucket the
block touches its state read once and written once (models/fm.py keeps
2 x (1 + dim) f32 a bucket: w, v and their AdaGrad accumulators); 2 FLOPs a
pair and channel forward (channels: w, the dim factors, and sum v**2) and as
many backward. A pair on the block's COO overflow list is a pair like any
other and is counted with them, once (the list's second u32 a pair and its
padded tail are the layout's, not the algorithm's).
``tile_kernel_roofline.replay`` divides this by the Pallas kernel's time.

``update_pass_bytes``: what ``fm_table_update`` moves, by its shapes: the ten
push planes and the 2 x (1 + dim) state planes read, the state planes
written, each a float32 plane of ``num_buckets``: 46 planes at dim 8. The
pass touches every bucket whatever the block touched (that is the
implementation's cost, and what ``fm_update_hbm_roofline.replay`` holds
against the HBM peak: a share of a peak, so it cannot pass 100%).
"""


def block_work(config: dict, pairs: int, rows: int,
               distinct_buckets: int) -> dict:
    state = int(config["state_bytes_per_bucket"])
    channels = int(config["dim"]) + 2
    return {"bytes": 4 * pairs + rows + 2 * state * distinct_buckets,
            "flops": 2 * 2 * pairs * channels}


def update_pass_bytes(config: dict) -> int:
    state_planes = 2 * (1 + int(config["dim"]))
    push_planes = int(config["dim"]) + 2
    return 4 * int(config["num_buckets"]) * (push_planes + 2 * state_planes)
