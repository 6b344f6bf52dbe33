"""Criteo TEXT files of a log with skew and empty columns, for a mesh whose
DATA axis reads GROUPS of blocks: ``criteo_text_clicklog``'s source (the same
lines, page-cache pass, overflow lists kept whatever their length and refusal
of a program without an ``OverflowRoom``) where a step is ``group`` blocks
read at the same weights.

Two things differ from ``formats/criteo_text_clicklog.py``, which this module
subclasses and does not edit (nor ``formats/criteo_text.py`` under it, whose
constructor refuses ``group != 1``: a text file had one chip):

- check file ``i`` holds the ``group`` blocks of check step ``i`` (blocks
  ``i * group ..`` of the seed's stream, ``group x block_rows`` lines), and
  ``check_part(i)`` steps it alone: the mesh pass forms one group of it. The
  pass files hold the blocks from ``check.steps x group`` on;
  ``check_blocks`` and ``check_overflow`` hold a block each, ``check.steps x
  group`` of them, as the harness merges them (``check.merge_groups``,
  ``check.merge_exact_pairs``);
- ``reference_blocks()`` hands a block as ``(its text as a uint8 array, its
  labels)``: ``merge_groups`` concatenates a group's first members, so the
  plain reference is handed a group's text as one array of bytes, in file
  order, and parses it itself.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.formats import criteo_text_clicklog


class Source(criteo_text_clicklog.Source):
    def __init__(self, config: dict, traffic: dict, workdir: str, seed: int,
                 group: int):
        # criteo_text.Source refuses ``group != 1`` (a text file had one
        # chip): built as one chip's source, so that whatever its parents'
        # constructors set is set, then told its group, which ``Base`` only
        # keeps
        super().__init__(config, traffic, workdir, seed, 1)
        self.group = group
        if int(traffic["blocks"]) % group:
            raise ValueError(f"{traffic['blocks']} blocks a pass file are "
                             f"no whole number of groups of {group}")

    def write_file(self, k: int) -> None:
        """File ``k`` holds blocks ``check_steps x group + [k * per_file,
        (k + 1) * per_file)`` of the stream that the seed names; the thread
        of file 0 first writes the check files, a group each, and leaves
        their blocks' overflow pairs to a thread beside it (``end`` waits
        for it)."""
        if k == 0:
            for i, path in enumerate(self.check_files):
                with open(path, "wb") as f:
                    for j in range(i * self.group, (i + 1) * self.group):
                        text, labels = self._block(j)
                        f.write(text)
                        self.check_blocks.append((text, labels))
            beside = ThreadPoolExecutor(1)
            self._encoded = beside.submit(
                lambda: [self._overflow_of(text)
                         for text, _labels in self.check_blocks])
            beside.shutdown(wait=False)      # end() waits for the result
        first = self.check_steps * self.group + k * self.per_file
        with open(self.files[k], "wb") as f:
            for i in range(first, first + self.per_file):
                f.write(self._block(i)[0])

    def reference_blocks(self) -> list:
        return [(np.frombuffer(text, np.uint8), labels)
                for text, labels in self.check_blocks]
