"""The general traffic generator: click-log rows from a list of field groups.

A traffic mix is a JSON file of parameters (``benchmark/traffic/<mix>.json``);
this module turns its ``fields`` list into blocks of ``(keys u32 (rows, nnz),
labels u8 (rows,))``. Nothing here depends on the mix's name: a later PR adds
a mix by adding a file.

Each entry of ``fields`` describes ``count`` adjacent columns:

    {"count": 39, "dist": "uniform", "cardinality": 20000}
    {"count": 1, "dist": "zipf", "cardinality": 39884406, "exponent": 1.05}
    {"count": 13, "dist": "lognormal_int", "mu": 2.0, "sigma": 2.0,
     "max": 65535}

What the seed decides, and what it does not: the traffic file fixes every
field's cardinality and shape, so pairs, hot-id frequencies and therefore the
work a block makes are the same for every seed in expectation; ``--seed``
draws the rows and, through ``salt``, WHICH keys (and so which buckets) are
the hot ones.

Labels come from a planted logistic model whose weight for a key is a hash of
the key: it needs no table, so a field of 40M values costs nothing to plant.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

_U32 = np.uint32
SENTINEL_KEY = 0xFFFFFFFF   # the crec format's missing-slot key


def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finaliser. The benchmark's own copy of the fold the
    crec2 writer applies (``wormhole_tpu.data.hashing.mix32_np``): the plain
    references import nothing of the program. tests/benchmark holds the two
    equal."""
    x = np.array(x, dtype=_U32, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> _U32(16)
        x *= _U32(0x85EBCA6B)
        x ^= x >> _U32(13)
        x *= _U32(0xC2B2AE35)
        return x ^ (x >> _U32(16))


def fold_keys32(keys: np.ndarray, num_buckets: int) -> np.ndarray:
    """u32 key -> bucket in [0, num_buckets), as the crec2 writer folds."""
    return (mix32(keys) % _U32(num_buckets)).astype(np.int64)


def key_weight(keys: np.ndarray, scale: float) -> np.ndarray:
    """The planted model: a unit-variance uniform in hash(key), times
    ``scale``. float64."""
    u = mix32(keys ^ _U32(0x5BD1E995)).astype(np.float64) / 2.0 ** 32
    return scale * (u - 0.5) * np.sqrt(12.0)


def _columns(fields: list) -> list:
    cols = []
    for group in fields:
        cols.extend([group] * int(group["count"]))
    return cols


def nnz_of(traffic: dict) -> int:
    return len(_columns(traffic["fields"]))


def _draw_values(rng, rows: int, cols: list) -> np.ndarray:
    """(rows, nnz) int64 value ids, column f in [0, cardinality_f)."""
    out = np.empty((rows, len(cols)), np.int64)
    f = 0
    while f < len(cols):
        dist = cols[f]["dist"]
        g = f
        while g < len(cols) and cols[g]["dist"] == dist:
            g += 1
        part = cols[f:g]
        n = g - f
        if dist == "uniform":
            card = np.array([c["cardinality"] for c in part], np.int64)
            out[:, f:g] = (rng.random((rows, n)) * card[None, :]
                           ).astype(np.int64)
        elif dist == "zipf":
            # inverse CDF of the continuous power law on [1, N+1), floored:
            # rank k has mass ~ k**-s. No table, whatever the cardinality.
            card = np.array([c["cardinality"] for c in part], np.float64)
            s = np.array([c["exponent"] for c in part], np.float64)
            if np.any(s == 1.0):
                raise ValueError("zipf exponent 1.0 is not supported")
            e = 1.0 - s
            u = rng.random((rows, n))
            x = (1.0 + u * ((card + 1.0) ** e - 1.0)[None, :]) \
                ** (1.0 / e)[None, :]
            out[:, f:g] = np.minimum(x.astype(np.int64) - 1,
                                     card.astype(np.int64)[None, :] - 1)
        elif dist == "lognormal_int":
            mu = np.array([c["mu"] for c in part], np.float64)
            sg = np.array([c["sigma"] for c in part], np.float64)
            top = np.array([c["max"] for c in part], np.float64)
            x = np.exp(rng.normal(size=(rows, n)) * sg[None, :]
                       + mu[None, :])
            out[:, f:g] = np.minimum(x, top[None, :]).astype(np.int64)
        else:
            raise ValueError(f"unknown field distribution {dist!r}")
        f = g
    return out


def make_block(traffic: dict, seed: int, index: int,
               rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """Block ``index`` of the stream that ``seed`` names: keys and labels.
    The same (traffic, seed, index, rows) gives the same bytes."""
    cols = _columns(traffic["fields"])
    rng = np.random.default_rng([int(seed), int(index)])
    v = _draw_values(rng, rows, cols)
    # one key per (field, value, seed): the salt moves the hot ids around
    salt = mix32(np.array([int(seed) & 0xFFFFFFFF], _U32))[0]
    field = mix32((np.arange(len(cols), dtype=_U32) + _U32(1))
                  * _U32(0x27D4EB2F))[None, :]
    keys = mix32(mix32(v.astype(_U32)) ^ field ^ salt)
    keys[keys == _U32(SENTINEL_KEY)] = _U32(0)
    planted = traffic.get("planted_model", {})
    margin = key_weight(keys, float(planted.get("weight_scale", 0.5))
                        ).sum(axis=1) + float(planted.get("bias", 0.0))
    labels = rng.random(rows) < 1.0 / (1.0 + np.exp(-margin))
    return keys, labels.astype(np.uint8)

