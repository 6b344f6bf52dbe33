"""The plain reference of ``criteo_wide_deep_clicklog``: float64 numpy
wide&deep whose pairs take one of two stated precisions, and its own check of
the overflow list it is handed.

The equations, the seeded weights and the stated roundings are
``criteo_wide_deep``'s (``configs/criteo_wide_deep/reference.py``, whose
``Reference`` this one extends: wide term, 32 pooled embedding values, the
ReLU tower forward and backward with both operands of every matmul rounded
as ``precision.tower_operands`` states, logistic loss, AdaGrad on the touched
buckets, with ``l2_v`` on ``v``, and on the tower). What differs is how many
pairs take the OTHER precision: ``exact_pairs`` (one ``(buckets, rows)`` a
step) names the pairs on the block's COO overflow list, which the program's
spill step gathers and scatters in float32 (``precision.overflow_operands``),
so those take their 33 pulled values and their 33 pushed gradients unrounded
(``check.take``): three tenths of a block's pairs in this configuration's
cell, where ``criteo_wide_deep``'s has 3 to 100. Without it every pair is
rounded, which is the reference of another program (the control that the
limits must refuse).

**The list is not taken on trust.** It comes from the program's own encoder,
so a fault there would otherwise move both sides alike. From its own pairs
(the keys folded here, ``benchmark.check.block_pairs``) and the tile geometry
that ``config.json`` states under ``tile`` the reference checks that every
handed pair is a pair of the block, as often as the block has it, and that
every tile's share of the list is exactly its pairs past the cap
(``check_overflow_list``: ``criteo_ftrl_clicklog``'s, imported and not
copied: the same block format), and, what a list of a million pairs can show
and one of a hundred could not, that a tile lists its LAST pairs in row
order and keeps its first (``listed_out_of_order``: no kept pair of a tile
lies on a later row than a listed one). A list that fails is a fault of the
program: every loss the reference then returns is NaN, which fails
``correct``, and the reason is printed on standard error.

**The leaf that sees the list's precision** (``m_list``, under
``state_rel_rms``). A ReLU tower in bfloat16 is rough: two sound programs
differ by 7e-4 in ``v`` after three steps, which is what every listed pair
rounded to bfloat16 moves it by too, so the whole-model numbers cannot tell
the two apart. One channel of the list's 34 does not pass through the
tower's backward: ``dual``, which lands in ``w``. A bucket whose ONE pair in
all the checked blocks is a listed pair of the first block
(``listed_singles``: some 17,000 of them) holds in ``cg_w`` that pair's
``|dual| = sigmoid(-y m)`` as the list path scattered it, nothing added and
never touched again, and ``logit(cg_w) = -y m`` is the margin its row was
scored at. A sound program reads that margin as the reference does to 1e-5
(the tower's forward in float32 sums); a dual rounded to bfloat16 moves it by
2e-3, of a margin of 0.05 to 0.1. The leaf is the one place where a step's
state holds single listed pairs apart, so it says nothing of a fault that
spares them (one in the hot buckets' pairs alone): ``PERF.md`` section 7.

Nothing of the program is imported.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark.check import exact_mask
from benchmark.configs.criteo_ftrl_clicklog.reference import \
    check_overflow_list
from benchmark.configs.criteo_wide_deep import reference as wide_deep
from benchmark.configs.criteo_wide_deep.reference import (  # noqa: F401
    STATED, init_factors, init_tower, tower_sizes)


def listed_out_of_order(buckets: np.ndarray, rows: np.ndarray,
                        listed: np.ndarray, num_buckets: int, tile: dict):
    """None where every tile (``tile["rows"]`` rows by ``tile["buckets"]``
    buckets) keeps its first pairs in row order and lists its last
    (``listed``: the mask of the block's pairs that are on the list), else
    the reason."""
    tiles = -(-num_buckets // int(tile["buckets"]))
    cell = (rows // int(tile["rows"])) * tiles + buckets // int(
        tile["buckets"])
    cells = int(cell.max(initial=-1)) + 1
    last_kept = np.full(cells, -1, np.int64)
    first_listed = np.full(cells, np.iinfo(np.int64).max, np.int64)
    np.maximum.at(last_kept, cell[~listed], rows[~listed])
    np.minimum.at(first_listed, cell[listed], rows[listed])
    bad = np.flatnonzero(first_listed < last_kept)
    if len(bad):
        c = int(bad[0])
        return (f"{len(bad)} tiles list a pair of an earlier row than one "
                f"they keep (first: row block {c // tiles}, bucket tile "
                f"{c % tiles}: row {int(first_listed[c])} listed, row "
                f"{int(last_kept[c])} kept)")
    return None


def listed_singles(pairs: list, listed: list) -> np.ndarray:
    """The sorted buckets that have ONE pair in all of ``pairs`` (a
    ``(buckets, rows)`` a step) together, that pair in the first step and on
    its list (``listed``: a mask a step of the pairs on the list)."""
    every, count = np.unique(np.concatenate([b for b, _r in pairs]),
                             return_counts=True)
    first = np.unique(pairs[0][0][listed[0]])
    return np.intersect1d(first, every[count == 1], assume_unique=True)


# ``listed_singles`` of the last reference that was handed its lists, by
# seed. Which buckets they are is a fact of the data, which the harness
# hands to the reference alone: the program's probe (``system.state``)
# reads ``m_list`` at the same buckets, as it reads the other leaves at the
# sample the harness draws from the reference's ``ids``; and a control that
# is handed no list (every pair rounded) reads it there too
SINGLES = {}


def logit(p: np.ndarray) -> np.ndarray:
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.log(p) - np.log1p(-p)


class Reference(wide_deep.Reference):
    def __init__(self, config: dict, blocks: list, seed: int,
                 operands=None, table=None, tower=STATED,
                 exact_pairs=None):
        super().__init__(config, blocks, seed, operands=operands,
                         table=table, tower=tower)
        self.list_fault = None
        self.singles = None
        if exact_pairs is None:
            known = SINGLES.get(int(seed))
            if known is not None and np.isin(known, self.ids).all():
                self.singles = known
            return
        SINGLES.pop(int(seed), None)
        if len(exact_pairs) != len(self.pairs):
            raise ValueError(f"{len(exact_pairs)} overflow lists for "
                             f"{len(self.pairs)} steps")
        nb, tile = int(config["num_buckets"]), config["tile"]
        for i, ((b, r), listed) in enumerate(zip(self.pairs, exact_pairs)):
            fault = check_overflow_list(b, r, listed, nb, tile)
            if fault is None:
                self.exact[i] = exact_mask(b, r, listed, nb)
                fault = listed_out_of_order(b, r, self.exact[i], nb, tile)
            if fault is not None:
                self.list_fault = f"step {i}: {fault}"
                print("[reference] the overflow list handed for "
                      f"{self.list_fault}: every loss is NaN",
                      file=sys.stderr, flush=True)
                break
        if self.list_fault is None:
            self.singles = SINGLES[int(seed)] = listed_singles(
                self.pairs, self.exact)

    def state(self, buckets: np.ndarray) -> dict:
        """``criteo_wide_deep``'s leaves and ``m_list``: the margins that
        the listed singles' rows were scored at, as their buckets' ``cg_w``
        holds them (a reference that never learned which buckets they are
        has no such leaf)."""
        out = super().state(buckets)
        if self.singles is not None:
            out["m_list"] = logit(self.cg_w[np.searchsorted(self.ids,
                                                            self.singles)])
        return out

    def step(self) -> float:
        """One update from the next block; returns its mean loss (NaN where
        the overflow list handed for a step failed its check)."""
        loss = super().step()
        return float("nan") if self.list_fault else loss
