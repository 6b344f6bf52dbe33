"""Criteo TEXT files of a log with skew and empty columns: ``criteo_text``'s
source (the same files, parts, page-cache pass and check blocks) for a mix
whose blocks put a large share of their pairs on the COO overflow list.

Two things differ from ``formats/criteo_text.py``, which this module
subclasses and does not edit:

- the lines come from ``generators/criteo_clicklog.py`` (empty columns), so a
  block is drawn and rendered with its mask of empties;
- a checked block's overflow pairs are kept whatever their number. The
  program sizes the room of an online block's list to what its encoder counts
  (``data.crec.OverflowRoom``), so there is no width to refuse a block by;
  the list is the program's own encoder's (``encode_tile_pairs``), as long as
  it is. The plain reference does not take it on trust: it checks the list
  against its own parse and the tile geometry that ``config.json`` states.

``info`` is the program's ``online_info`` with ``ovf_cap`` set, once the
checked blocks are encoded, to the room the program's rule gives their
largest list (``overflow_room``): what ``work per block`` prints as
``ovf_cap`` and counts into ``block_bytes``.

A program without that rule (a commit before PR 39) cannot run this format:
``begin`` says so at once, before any file is written.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from benchmark.formats import criteo_text


class Source(criteo_text.Source):
    def begin(self) -> None:
        from wormhole_tpu.data import crec
        if not hasattr(crec, "OverflowRoom"):
            raise RuntimeError(
                "this program gives an online block's overflow list no "
                "more room than ONLINE_OVF_CAP and sends a block past it "
                "through the scatter step: it cannot run a mix whose "
                "blocks overflow by a third of their pairs")
        super().begin()

    def _block(self, i: int) -> tuple:
        gen = importlib.import_module(self.traffic["generator"])
        ints, cats, labels, empty = gen.make_block(
            self.traffic, self.seed, i, self.block_rows)
        return gen.render(ints, cats, labels, empty), labels

    def _overflow_of(self, text: bytes) -> tuple:
        """The (buckets, rows) that the feed's encoder puts on this block's
        COO overflow list, however many: the program's own text assembler
        and tile encoder, once over the block."""
        from wormhole_tpu.data import crec, native
        nnz, info = int(self.config["nnz"]), self.info
        asm = native.get_crec_assembler("criteo", nnz)
        self.parser = "native" if asm else "python"
        asm = asm or crec._python_crec_assembler("criteo", nnz)
        keys, _labels = asm(text)
        _pw, ovf_b, ovf_r = crec.encode_tile_pairs(keys, info.nb, info.spec)
        return ovf_b, ovf_r

    def end(self) -> dict:
        from wormhole_tpu.data import crec
        counts = super().end()
        self.info = dataclasses.replace(self.info, ovf_cap=crec.overflow_room(
            max(counts["overflow_pairs_per_block"])))
        # a column is a tab and what follows it: a line's features are its
        # tabs less its empty columns
        feats = [(text.count(b"\t") - _empty_columns(text))
                 / text.count(b"\n") for text, _labels in self.check_blocks]
        counts["features_per_row"] = [round(min(feats), 3),
                                      round(max(feats), 3)]
        return counts


def _empty_columns(text: bytes) -> int:
    """Columns of ``text`` that hold no character: a tab that follows a tab,
    and a newline that follows a tab."""
    buf = np.frombuffer(text, np.uint8)
    sep = (buf == 9) | (buf == 10)
    return int((sep[1:] & (buf[:-1] == 9)).sum())
