"""What the factorization machine needs for one block, whatever the
formulation.

bytes: the block's pair words (one u32 a pair) and labels (one byte a row)
read once, and for each distinct bucket the block touches its state read once
and written once (models/fm.py keeps 2 x (1 + dim) f32 a bucket: w, v and
their AdaGrad accumulators). operations: 2 FLOPs a pair and channel forward
(channels: w, the dim factors, and sum v**2) and as many backward.
"""


def block_work(config: dict, pairs: int, rows: int,
               distinct_buckets: int) -> dict:
    state = int(config["state_bytes_per_bucket"])
    channels = int(config["dim"]) + 2
    return {"bytes": 4 * pairs + rows + 2 * state * distinct_buckets,
            "flops": 2 * 2 * pairs * channels}
