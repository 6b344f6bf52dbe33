"""The plain reference of ``criteo_ftrl_clicklog``: its own parser of Criteo
TEXT, float64 numpy FTRL-proximal, and its own check of the overflow list it
is handed.

It is handed the checked blocks' TEXT (bytes) and derives every (bucket, row)
pair and every label itself, so that a wrong hash, a dropped field, a shifted
column or an empty column taken for a feature in the program's native parser
fails ``correct``. Nothing of the program is imported and nothing of another
configuration: the parser is a copy of ``criteo_ftrl_text``'s (the same
schema), the CRC, the two folds and the update rule are written out here.

The schema (``base/criteo_parser.h:47-80``; ``README.md`` beside this file):
a line is ``label TAB 13 integers TAB 26 strings``; a line of fewer than 14
columns is no row; AN EMPTY COLUMN IS NO FEATURE; integer column ``i`` with
value ``v`` is id ``v + i * (2**64 // 13 + 1)`` modulo 2**64; a string column
is the CRC-32 (IEEE polynomial, reflected, as zlib computes it) of its bytes
as they stand; the label is 1 above 0.5. An id becomes a bucket in two folds:
the low 32 bits of splitmix64(id), with 0xFFFFFFFF (the pad key) moved to
0xFFFFFFFE, then murmur3's 32-bit finaliser modulo ``num_buckets``.

``operands`` rounds the weights (forward) and the duals (backward) to a
lower-precision type before use, as the tile kernels round them to bfloat16;
``table`` rounds the stored state after each step: the controls of
``correct``. ``exact_pairs`` (one ``(buckets, rows)`` a step) names the pairs
on the online encoder's COO overflow list, which take both unrounded: a third
of a block's pairs in this configuration's cell.

**The list is not taken on trust** (``check_overflow_list``). It comes from
the program's own encoder, so a fault there would otherwise move both sides
alike. From its own parsed pairs and the tile geometry that ``config.json``
states under ``tile`` (a tile is ``rows`` consecutive rows by ``buckets``
consecutive buckets and keeps at most ``cap`` pairs) the reference checks
that every handed pair is a pair of the block (as often as the block has it),
and that every tile's share of the list is exactly its pairs past the cap: so
no tile keeps more than the cap, none sheds a pair it had room for, and list
and kept pairs together are the block's pairs, each once. A list that fails
is a fault of the program: every loss the reference then returns is NaN, which
fails ``correct``, and the reason is printed on standard error.
"""

from __future__ import annotations

import functools
import sys

import numpy as np

from benchmark.check import exact_mask, round_to, take

LEAVES = ("w",)
INTS, COLUMNS = 13, 40
_U32, _U64 = np.uint32, np.uint64
_ITV = (2 ** 64 - 1) // 13 + 1
_TAB, _NL, _MINUS, _ZERO = 9, 10, 45, 48


def _crc_table() -> np.ndarray:
    """The byte table of the reflected CRC-32 (polynomial 0xEDB88320)."""
    c = np.arange(256, dtype=_U32)
    for _ in range(8):
        c = np.where(c & _U32(1), (c >> _U32(1)) ^ _U32(0xEDB88320),
                     c >> _U32(1))
    return c


_CRC = _crc_table()


def crc32(buf: np.ndarray, start: np.ndarray, length: np.ndarray):
    """CRC-32 of ``buf[start : start + length]`` for every string at once:
    one table step a byte position, eight for a Criteo string."""
    c = np.full(len(start), 0xFFFFFFFF, _U32)
    for d in range(int(length.max(initial=0))):
        on = d < length
        byte = buf[np.where(on, start + d, 0)].astype(_U32)
        c = np.where(on, _CRC[(c ^ byte) & _U32(0xFF)] ^ (c >> _U32(8)), c)
    return c ^ _U32(0xFFFFFFFF)


def decimal(buf: np.ndarray, start: np.ndarray, length: np.ndarray):
    """The signed decimal integers at ``buf[start : start + length]``, as
    uint64 modulo 2**64 (a negative value wraps, as the C cast does)."""
    neg = buf[start] == _MINUS
    value = np.zeros(len(start), _U64)
    with np.errstate(over="ignore"):
        for d in range(int(length.max(initial=0))):
            on = (d < length) & ~(neg & (d == 0))
            digit = buf[np.where(on, start + d, 0)].astype(np.int64) - _ZERO
            if np.any(on & ((digit < 0) | (digit > 9))):
                raise ValueError("an integer column holds a non-digit")
            value = np.where(on, value * _U64(10) + digit.astype(_U64),
                             value)
        return np.where(neg, _U64(0) - value, value)


def parse(text: bytes) -> tuple:
    """``(ids uint64, rows int64, labels uint8)``: every feature id of every
    row of ``text``, in line order, and each row's label."""
    if text and not text.endswith(b"\n"):
        text += b"\n"
    buf = np.frombuffer(text, np.uint8)
    sep = np.flatnonzero((buf == _TAB) | (buf == _NL))
    ends_line = buf[sep] == _NL
    line = np.cumsum(ends_line) - ends_line          # the line of each column
    first = np.flatnonzero(np.r_[True, ends_line[:-1]])   # its first column
    column = np.arange(len(sep)) - first[line]
    start = np.r_[0, sep[:-1] + 1]
    length = sep - start
    ncols = np.bincount(line, minlength=len(first))
    is_row = ncols >= INTS + 1                       # fewer: no row
    row_of_line = np.cumsum(is_row) - 1
    keep = is_row[line] & (column < COLUMNS)
    lab = keep & (column == 0)
    labels = _labels(text, buf, start[lab], length[lab])
    feature = keep & (column >= 1) & (length > 0)
    ints = feature & (column <= INTS)
    cats = feature & (column > INTS)
    ids = np.zeros(len(sep), _U64)
    with np.errstate(over="ignore"):
        ids[ints] = decimal(buf, start[ints], length[ints]) \
            + (column[ints] - 1).astype(_U64) * _U64(_ITV)
    ids[cats] = crc32(buf, start[cats], length[cats])
    return (ids[feature], row_of_line[line[feature]],
            labels.astype(np.uint8))


def _labels(text: bytes, buf, start, length) -> np.ndarray:
    """1 where a row's first column reads above 0.5. The log's labels are
    one digit, which needs no float parsed; anything else goes one by one."""
    if np.all(length == 1) and np.all((buf[start] >= _ZERO)
                                      & (buf[start] <= _ZERO + 9)):
        return buf[start] > _ZERO
    return np.array([float(text[s:s + n]) > 0.5
                     for s, n in zip(start, length)], bool)


def splitmix64(x: np.ndarray) -> np.ndarray:
    x = x.astype(_U64, copy=True)
    with np.errstate(over="ignore"):
        x += _U64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)
        return x ^ (x >> _U64(31))


def fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(_U32, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> _U32(16)
        x *= _U32(0x85EBCA6B)
        x ^= x >> _U32(13)
        x *= _U32(0xC2B2AE35)
        return x ^ (x >> _U32(16))


def buckets_of(ids: np.ndarray, num_buckets: int) -> np.ndarray:
    """The two folds: id -> 32-bit key -> bucket."""
    key = splitmix64(ids).astype(_U32)
    key = np.where(key == _U32(0xFFFFFFFF), _U32(0xFFFFFFFE), key)
    return (fmix32(key) % _U32(num_buckets)).astype(np.int64)


def check_overflow_list(buckets: np.ndarray, rows: np.ndarray, listed: tuple,
                        num_buckets: int, tile: dict):
    """None where ``listed`` = (buckets, rows) is a sound overflow list of the
    block whose pairs are ``(buckets, rows)``, else the reason it is not."""
    lb = np.asarray(listed[0], np.int64)
    lr = np.asarray(listed[1], np.int64)
    if lb.shape != lr.shape or lb.ndim != 1:
        return f"buckets {lb.shape} and rows {lr.shape} are no list of pairs"
    nrows = int(rows.max(initial=-1)) + 1
    if len(lb) and (lb.min() < 0 or lb.max() >= num_buckets
                    or lr.min() < 0 or lr.max() >= nrows):
        return "a listed pair lies outside the block's buckets or rows"
    # every listed pair is a pair of the block, as often as the block has it
    have, count = np.unique(rows * num_buckets + buckets, return_counts=True)
    named, quota = np.unique(lr * num_buckets + lb, return_counts=True)
    at = np.minimum(np.searchsorted(have, named), len(have) - 1)
    foreign = have[at] != named if len(have) else np.ones(len(named), bool)
    if foreign.any():
        k = int(named[np.flatnonzero(foreign)[0]])
        return (f"{int(foreign.sum())} listed pairs are no pair of the block "
                f"(first: bucket {k % num_buckets}, row {k // num_buckets})")
    if (quota > count[at]).any():
        k = int(named[np.flatnonzero(quota > count[at])[0]])
        return (f"{int((quota > count[at]).sum())} pairs are listed more "
                f"often than the block has them (first: bucket "
                f"{k % num_buckets}, row {k // num_buckets})")
    # every tile's share of the list is exactly its pairs past the cap
    tiles = -(-num_buckets // int(tile["buckets"]))
    cells = tiles * (-(-nrows // int(tile["rows"])))

    def per_cell(b, r):
        return np.bincount((r // int(tile["rows"])) * tiles
                           + b // int(tile["buckets"]), minlength=cells)

    want = np.maximum(per_cell(buckets, rows) - int(tile["cap"]), 0)
    got = per_cell(lb, lr)
    if (got != want).any():
        c = int(np.flatnonzero(got != want)[0])
        kept = int(per_cell(buckets, rows)[c] - got[c])
        return (f"{int((got != want).sum())} tiles list another number of "
                f"pairs than they hold past the cap of {tile['cap']} (first: "
                f"row block {c // tiles}, bucket tile {c % tiles}: "
                f"{int(got[c])} listed, {int(want[c])} past the cap, "
                f"{kept} kept)")
    return None


@functools.lru_cache(maxsize=2)
def _parsed(texts: tuple, num_buckets: int) -> tuple:
    """Each block's (buckets, rows) pairs and labels, and the sorted buckets
    that any of them touches (kept: the controls read the same blocks)."""
    pairs, labels = [], []
    for text in texts:
        ids, rows, lab = parse(text)
        pairs.append((buckets_of(ids, num_buckets), rows))
        labels.append(lab)
    return pairs, labels, np.unique(np.concatenate([b for b, _ in pairs]))


class Reference:
    def __init__(self, config: dict, blocks: list, seed: int,
                 operands=None, table=None, exact_pairs=None):
        h = config["hyper"]
        self.l1, self.l2 = float(h["lambda1"]), float(h["lambda2"])
        self.alpha, self.beta = float(h["lr_eta"]), float(h["lr_beta"])
        self.operands, self.table = operands, table
        nb = int(config["num_buckets"])
        self.pairs, self.labels, self.ids = _parsed(tuple(blocks), nb)
        self.list_fault = None
        self.exact = [None] * len(self.pairs)
        if exact_pairs is not None:
            if len(exact_pairs) != len(self.pairs):
                raise ValueError(f"{len(exact_pairs)} overflow lists for "
                                 f"{len(self.pairs)} steps")
            for i, ((b, r), listed) in enumerate(zip(self.pairs,
                                                     exact_pairs)):
                fault = check_overflow_list(b, r, listed, nb, config["tile"])
                if fault is not None:
                    self.list_fault = f"step {i}: {fault}"
                    print("[reference] the overflow list handed for "
                          f"{self.list_fault}: every loss is NaN",
                          file=sys.stderr, flush=True)
                    break
                self.exact[i] = exact_mask(b, r, listed, nb)
        n = len(self.ids)
        self.w, self.z, self.cg = np.zeros(n), np.zeros(n), np.zeros(n)
        self.first_grad = None
        self._step = 0

    def step(self) -> float:
        """One update from the next block; returns its mean loss (NaN where
        the overflow list handed for a step failed its check)."""
        labels = self.labels[self._step]
        buckets, rows = self.pairs[self._step]
        exact = self.exact[self._step]
        idx = np.searchsorted(self.ids, buckets)
        m = np.bincount(rows, weights=take(self.w, idx, self.operands, exact),
                        minlength=len(labels))
        y = 2.0 * labels - 1.0
        loss = float(np.logaddexp(0.0, -y * m).mean())
        dual = -y / (1.0 + np.exp(y * m))
        grad = np.bincount(idx, weights=take(dual, rows, self.operands,
                                             exact),
                           minlength=len(self.ids))
        if self.first_grad is None:
            self.first_grad = grad
        cg = np.sqrt(self.cg * self.cg + grad * grad)
        z = self.z + grad - (cg - self.cg) / self.alpha * self.w
        w = (-np.sign(z) * np.maximum(np.abs(z) - self.l1, 0.0)
             / ((self.beta + cg) / self.alpha + self.l2))
        self.w, self.z, self.cg = (round_to(w, self.table),
                                   round_to(z, self.table),
                                   round_to(cg, self.table))
        self._step += 1
        return float("nan") if self.list_fault else loss

    def grad_norms(self) -> dict:
        """Norm of the first gradient as the optimizer got it, per leaf."""
        return {"w": float(np.linalg.norm(self.first_grad))}

    def change_norms(self) -> dict:
        """Norm of the parameters' change since the start (w0 = 0)."""
        return {"w": float(np.linalg.norm(self.w))}

    def state(self, buckets: np.ndarray) -> dict:
        """The parameters at ``buckets`` (each one a touched bucket)."""
        return {"w": self.w[np.searchsorted(self.ids, buckets)]}
