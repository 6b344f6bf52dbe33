"""What FTRL needs for one update of ``criteo_ftrl_ps4``, whatever the
layout: the bytes and operations the ALGORITHM moves, summed over the four
chips, not what a kernel sweeps or a collective ships.

The harness hands a step's pairs and rows (a group: two blocks) and the
distinct buckets it touches. bytes: each pair word (one u32) and each label
(one byte) read once, by the one worker that holds the row; each touched
bucket's state (3 x f32: w, z, cg) read once and written once, by the one
server shard that owns it (the copy a DATA pair's second chip keeps is the
layout's, not the algorithm's). operations: 2 FLOPs a pair forward and as
many backward. Counted as ``criteo_ftrl`` counts a block; there is no new
kernel here, so no share of a kernel's roofline is reported from it.
"""


def block_work(config: dict, pairs: int, rows: int,
               distinct_buckets: int) -> dict:
    state = int(config["state_bytes_per_bucket"])
    return {"bytes": 4 * pairs + rows + 2 * state * distinct_buckets,
            "flops": 2 * pairs + 2 * pairs}
