"""What FTRL needs for one update of ``criteo_ftrl_clicklog_ps4``, whatever
the layout: the bytes and operations the ALGORITHM moves, summed over the four
chips, and beside it what the HOST needs to turn a group's text into what the
chips are handed.

``block_work`` is ``criteo_ftrl_ps4``'s count: the harness hands a step's
pairs and rows (a group: two blocks, ``rows x 39`` pairs; this configuration's
lines hold 34.8 features on average, so the count is high by a ninth) and the
distinct buckets it touches. A pair on a block's COO overflow list is a pair
of the group like any other and is counted with them, once: one u32 read, 2
FLOPs forward and 2 backward (the list's second u32 a pair, its padded tail
and the copy of the whole list that every chip of a DATA member is handed are
the layout's, not the algorithm's). bytes: each pair word and each label (one
byte) read once, by the one worker that holds the row; each touched bucket's
state (3 x f32: w, z, cg) read once and written once, by the one server shard
that owns it. No kernel is new here, so no share of a kernel's roofline is
reported from it.

``host_work`` (printed on a ``[bench]`` line, read by no metric), a BLOCK's,
as ``criteo_ftrl_clicklog``'s: the text bytes a block's lines hold, the pairs
hashed (the features a line really has), the strings CRC'd at most, the pairs
the encoder lists, and the bytes of the online block the feed's encoder writes,
its overflow list at the room in force; a group ships two of these, a chip
its MODEL half of a block's pair words and the whole of its list.
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
