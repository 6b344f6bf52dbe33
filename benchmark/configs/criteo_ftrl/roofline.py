"""What FTRL needs for one block, whatever the formulation: the bytes and
operations the ALGORITHM moves, not the ones a kernel happens to sweep.

bytes: the block's pair words (one u32 a pair) and labels (one byte a row)
read once, and for each distinct bucket the block touches its state read once
and written once (3 x f32: w, z, cg). operations: 2 FLOPs a pair forward (the
margin's multiply-add) and as many backward (the gradient's). No later kernel
can need less, so no share of this roofline can pass 100%.
"""


def block_work(config: dict, pairs: int, rows: int,
               distinct_buckets: int) -> dict:
    state = int(config["state_bytes_per_bucket"])
    return {"bytes": 4 * pairs + rows + 2 * state * distinct_buckets,
            "flops": 2 * pairs + 2 * pairs}
