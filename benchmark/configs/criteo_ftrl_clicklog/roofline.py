"""What FTRL needs for one block of ``criteo_ftrl_clicklog``, whatever the
formulation, and beside it what the HOST needs to turn a block's text into
what the chip is handed.

``block_work`` is ``criteo_ftrl``'s count at this table size: the block's
pair words (one u32 a pair) and labels (one byte a row) read once, and for
each distinct bucket the block touches its state read once and written once
(3 x f32: w, z, cg); 2 FLOPs a pair forward and as many backward. The harness
hands it ``rows x 39`` pairs; this configuration's lines hold 34.8 features
on average, so the count is high by a ninth. No kernel is new here and no
share of a kernel's roofline is reported from it.

``host_work`` (printed on a ``[bench]`` line, read by no metric): the text
bytes a block's lines hold, the pairs hashed (the features a line really
has), the strings CRC'd at most, and the bytes of the online block the feed's
encoder writes and ``put_block`` ships, its overflow list at the room in
force.
"""


def block_work(config: dict, pairs: int, rows: int,
               distinct_buckets: int) -> dict:
    state = int(config["state_bytes_per_bucket"])
    return {"bytes": 4 * pairs + rows + 2 * state * distinct_buckets,
            "flops": 2 * pairs + 2 * pairs}


def host_work(config: dict, work: dict) -> dict:
    rows = int(work["rows_per_block"])
    crc = int(config["schema"]["categorical_fields"])
    return {"text_bytes_in": max(work["text_bytes_per_block"]),
            "pairs_hashed": int(rows * max(work["features_per_row"])),
            "crc32_strings_at_most": rows * crc,
            "overflow_pairs": max(work["overflow_pairs_per_block"]),
            "encoded_bytes_out": int(work["block_bytes"])}
