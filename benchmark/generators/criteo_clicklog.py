"""Click-log lines as a real log has them: ``criteo_text``'s rows (the same
field groups, the same draw of a value id a field, the same 32-bit word a
categorical string), with columns that are EMPTY in a stated share of the
lines, as Criteo's are.

A traffic mix that names this generator states, beside its ``fields``,

    "empty_fields": {"I1": 0.45, "I12": 0.75, "C19": 0.45, ...}

``I1..I13`` are the integer columns and ``C1..C26`` the categorical ones, as
the Criteo data sets name them; the share is the probability that the column
is empty in a line, drawn from the seed, each column and line independently.
An empty column is rendered as nothing between its two tabs
(``base/criteo_parser.h`` skips it: no feature), and is left out of the
planted model's margin, so that the labels are those of the features a line
really has.

``make_block`` is a function of (mix, seed, block index, rows) alone. It draws
the values exactly as ``criteo_text.make_block`` does (same generator state,
same order), then the empties from a stream of their own, then the labels:
a mix without ``empty_fields`` gives ``criteo_text``'s keys.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from benchmark.generators.criteo_text import (_HEX, _NL, _TAB, _ZERO, CATS,
                                              INTS)
from benchmark.generators.fields import (_columns, _draw_values, key_weight,
                                         mix32, nnz_of)

__all__ = ["INTS", "CATS", "empty_shares", "make_block", "nnz_of", "render"]

_U32 = np.uint32
_EMPTY_STREAM = 0xE3B7           # the empties' own generator stream


def empty_shares(traffic: dict) -> np.ndarray:
    """The mix's ``empty_fields`` as one share a column, 39 of them."""
    shares = np.zeros(INTS + CATS)
    for name, share in traffic.get("empty_fields", {}).items():
        kind, number = name[:1], int(name[1:])
        if kind not in "IC" or not 1 <= number <= (INTS if kind == "I"
                                                   else CATS):
            raise ValueError(f"empty_fields names {name!r}: columns are "
                             f"I1..I{INTS} and C1..C{CATS}")
        if not 0.0 <= float(share) < 1.0:
            raise ValueError(f"empty_fields[{name!r}] = {share}: a share "
                             "in [0, 1)")
        shares[number - 1 + (INTS if kind == "C" else 0)] = float(share)
    return shares


def make_block(traffic: dict, seed: int, index: int, rows: int
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Block ``index`` of the stream that ``seed`` names: the integer fields'
    values ``(rows, 13) int64``, the categorical fields' 32-bit strings
    ``(rows, 26) uint32``, the labels ``(rows,) uint8`` and which columns are
    empty ``(rows, 39) bool``."""
    cols = _columns(traffic["fields"])
    if len(cols) != INTS + CATS:
        raise ValueError(f"a Criteo line has {INTS + CATS} fields, the mix "
                         f"{traffic['name']} has {len(cols)}")
    rng = np.random.default_rng([int(seed), int(index)])
    v = _draw_values(rng, rows, cols)
    salt = mix32(np.array([int(seed) & 0xFFFFFFFF], _U32))[0]
    field = mix32((np.arange(len(cols), dtype=_U32) + _U32(1))
                  * _U32(0x27D4EB2F))[None, :]
    words = mix32(mix32(v.astype(_U32)) ^ field ^ salt)
    empty = np.random.default_rng(
        [int(seed), int(index), _EMPTY_STREAM]).random(
            (rows, len(cols))) < empty_shares(traffic)[None, :]
    planted = traffic.get("planted_model", {})
    weight = key_weight(words, float(planted.get("weight_scale", 0.5)))
    margin = np.where(empty, 0.0, weight).sum(axis=1) \
        + float(planted.get("bias", 0.0))
    labels = rng.random(rows) < 1.0 / (1.0 + np.exp(-margin))
    return v[:, :INTS], words[:, INTS:], labels.astype(np.uint8), empty


def render(ints: np.ndarray, cats: np.ndarray, labels: np.ndarray,
           empty: np.ndarray) -> bytes:
    """The block's lines, as ``criteo_text.render`` lays them out (one fixed
    width a line, the unused positions dropped in one boolean take), with an
    empty column's characters dropped too: its tab stays, so the columns
    after it keep their places."""
    rows = len(labels)
    if ints.min(initial=0) < 0:
        raise ValueError("integer fields are value ids: none is negative")
    digits = max(len(str(int(ints.max(initial=0)))), 1)
    width = 1 + INTS * (1 + digits) + CATS * 9 + 1
    out = np.zeros((rows, width), np.uint8)
    out[:, 0] = _ZERO + labels
    cell = out[:, 1:1 + INTS * (1 + digits)].reshape(rows, INTS, 1 + digits)
    rest = ints.astype(np.int64)
    for d in range(digits, 0, -1):
        keep = (rest > 0) | (d == digits)
        cell[:, :, d] = np.where(keep, _ZERO + rest % 10, 0)
        rest = rest // 10
    cell[empty[:, :INTS]] = 0
    cell[:, :, 0] = _TAB
    cell = out[:, 1 + INTS * (1 + digits):-1].reshape(rows, CATS, 9)
    byte = cats.astype(">u4").view(np.uint8).reshape(rows, CATS, 4)
    cell[:, :, 1::2] = _HEX[byte >> 4]
    cell[:, :, 2::2] = _HEX[byte & 15]
    cell[empty[:, INTS:]] = 0
    cell[:, :, 0] = _TAB
    out[:, -1] = _NL
    return out[out != 0].tobytes()
