"""The plain reference of ``criteo_ftrl_clicklog_ps4``: float64 numpy
FTRL-proximal over ONE table with no shards, a step a GROUP, from its own
parse of the group's Criteo TEXT and its own check of the overflow list it is
handed.

The deployment splits the table over two key-range servers and the lines of
an update over two workers; what it computes is one FTRL update from all the
rows of a group read at the same weights, and that is all this reference
knows: a step here is the harness's merged group (``check.merge_groups``: two
98,304-line blocks, 196,608 rows), its gradient summed over every row once,
applied once to every touched bucket (sgd_server_handle.h:111-141). Held
against it, a program that loses one worker's gradient, one shard's margin,
one shard's push, or a listed pair at the boundary between two shards, is off
by far more than any limit.

The parser, the CRC, both folds, the list check and the update rule are
``criteo_ftrl_clicklog``'s plain reference, imported and not copied (the same
schema, block format and step; that module imports nothing of the program,
and neither does this one): a repair there is a repair here. A step of that
reference is one update from all the rows of the text it is handed, so a
group's text handed whole IS the group step. What this module adds:

- a step's text arrives as ``(uint8 array, labels)``: the group's blocks'
  bytes concatenated in file order, the form in which the harness can merge a
  group (``formats/criteo_text_clicklog_mesh.py``); plain bytes are taken too;
- ``exact_pairs`` (one ``(buckets, rows)`` a step) is the group's blocks'
  lists, the rows of the second block shifted by the rows of the first
  (``check.merge_exact_pairs``): the pairs the mesh step takes unrounded on
  the shard that owns their bucket, a third of a group's pairs in the cell;
- the list check runs over the group's rows as they stand. A tile is
  ``tile.rows`` consecutive rows OF A BLOCK, so that is right only where a
  block holds a whole number of tile row ranges (98,304 = 12 x 8,192: a tile
  of the second block is then rows ``block_rows + ...`` of the group); the
  constructor refuses a configuration in which it does not.
"""

from __future__ import annotations

import numpy as np

from benchmark.configs.criteo_ftrl_clicklog import reference as _one_chip

parse, buckets_of = _one_chip.parse, _one_chip.buckets_of
check_overflow_list = _one_chip.check_overflow_list


def _text_of(block) -> bytes:
    text = block[0] if isinstance(block, tuple) else block
    return text if isinstance(text, bytes) \
        else np.ascontiguousarray(text, np.uint8).tobytes()


class Reference(_one_chip.Reference):
    def __init__(self, config: dict, blocks: list, seed: int, **precision):
        if int(config["block_rows"]) % int(config["tile"]["rows"]):
            raise ValueError(
                f"a block of {config['block_rows']} rows is no whole number "
                f"of tile row ranges of {config['tile']['rows']}: a group's "
                "second block would start inside a tile")
        super().__init__(config, [_text_of(b) for b in blocks], seed,
                         **precision)
