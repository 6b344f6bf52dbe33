"""How ``correct`` is decided: the program's first steps against the plain
reference, number by number, each beside its limit.

The numbers (PERF.md section 2 has the readings each limit was set from):

``loss_rel``         the worst of the first steps' |loss - ref| / ref
``grad_norm_rel``    the first gradient as the optimizer got it (read from the
                     state after one step): the gap between the program's norm
                     and the reference's, by the worst leaf, against the
                     reference's norm of that leaf or of the median leaf
``change_norm_rel``  the same for the norm of the parameters' change after
                     the last checked step
``state_rel_rms``    ||theta - theta_ref|| / ||theta_ref|| over a sample of
                     the touched buckets drawn from the seed, worst leaf: the
                     number that a lower operand precision moves

The reference runs on the host after the program's steps, from nothing but
the blocks (it imports nothing of the program), and its time is not set-up.

A configuration states a precision for each path of the step. Where it
states ``precision.overflow_operands`` (float32: the COO overflow path
gathers the weight and the row's dual unrounded), the reference is told which
(bucket, row) pairs of each checked block the file's overflow list holds
(``exact_pairs``: a fact of the crec2 file, two integer arrays a block) and
rounds only the others to ``kernel_operands``.
"""

from __future__ import annotations

import numpy as np


def round_to(x: np.ndarray, dtype) -> np.ndarray:
    """``x`` rounded through a lower-precision type of ``ml_dtypes`` (by
    name) and back to float64; ``None`` leaves it alone."""
    if dtype is None:
        return x
    import ml_dtypes
    return x.astype(getattr(ml_dtypes, dtype)).astype(np.float64)


def take(x: np.ndarray, at: np.ndarray, operands, exact=None) -> np.ndarray:
    """``x[at]`` as a step's pairs use it: rounded to ``operands``, but for
    the pairs that ``exact`` marks, which take it as it is."""
    out = round_to(x, operands)[at]
    return out if exact is None else np.where(exact, x[at], out)


def exact_mask(buckets: np.ndarray, rows: np.ndarray, exact: tuple,
               num_buckets: int) -> np.ndarray:
    """Which of a step's pairs ``exact`` = (buckets, rows) names. A (bucket,
    row) that occurs several times in a row (two fields folding to one
    bucket) is matched by count: as many of its occurrences are marked as
    ``exact`` holds. A named pair that the step does not have is an error."""
    key = rows.astype(np.int64) * num_buckets + buckets.astype(np.int64)
    named, quota = np.unique(
        np.asarray(exact[1], np.int64) * num_buckets
        + np.asarray(exact[0], np.int64), return_counts=True)
    mask = np.zeros(len(key), bool)
    if not len(named):
        return mask
    at = np.minimum(np.searchsorted(named, key), len(named) - 1)
    found = np.flatnonzero(named[at] == key)
    # among the pairs whose (bucket, row) is named: the occurrence number
    # of each among its equals, against the count that is named
    found = found[np.argsort(key[found], kind="stable")]
    skey = key[found]
    nth = np.arange(len(skey)) - np.searchsorted(skey, skey, side="left")
    mask[found] = nth < quota[at[found]]
    if int(mask.sum()) != int(quota.sum()):
        raise ValueError(f"{int(quota.sum())} exact pairs named, "
                         f"{int(mask.sum())} of them are the step's own")
    return mask


def exact_masks(pairs: list, exact_pairs, num_buckets: int):
    """For the references: one mask a step (None without ``exact_pairs``)."""
    if exact_pairs is None:
        return [None] * len(pairs)
    if len(exact_pairs) != len(pairs):
        raise ValueError(f"{len(exact_pairs)} lists of exact pairs for "
                         f"{len(pairs)} steps")
    return [exact_mask(b, r, e, num_buckets)
            for (b, r), e in zip(pairs, exact_pairs)]


def block_pairs(blocks: list, num_buckets: int) -> tuple:
    """For the references: each block's (bucket, row) pairs, and the sorted
    buckets that any of them touches."""
    from benchmark.generators.fields import fold_keys32
    pairs = []
    for keys, _labels in blocks:
        rows = np.repeat(np.arange(keys.shape[0]), keys.shape[1])
        pairs.append((fold_keys32(keys.reshape(-1), num_buckets), rows))
    return pairs, np.unique(np.concatenate([b for b, _ in pairs]))


def _worst_leaf_gap(ours: dict, ref: dict) -> float:
    """Gap between norms, by the worst leaf, each against the larger of
    the reference's norm of that leaf and of the median leaf."""
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(ours[k] - ref[k]) / max(ref[k], med, 1e-300)
               for k in ref)


def stated_precision(config: dict, overflow=None) -> dict:
    """What the reference is built with, path by path, read from the
    configuration's ``precision`` alone. ``operands``: the type the tile
    kernels round to (None where they compute in float32 or wider). And,
    where ``overflow_operands`` is stated (float32 or wider: those pairs are
    taken unrounded), ``exact_pairs``: the overflow pairs of each step. A
    configuration that does not state it rounds every pair, and its
    reference is not handed the argument."""
    wide = ("float32", "float64")
    kind = config["precision"]["kernel_operands"]
    out = {"operands": None if kind in wide else kind}
    kind = config["precision"].get("overflow_operands")
    if kind is not None:
        if kind not in wide:
            raise ValueError(f"overflow_operands {kind!r}: the references "
                             "take the overflow pairs unrounded or not at "
                             "all")
        out["exact_pairs"] = overflow
    return out


def sample_buckets(reference, seed: int, size: int) -> np.ndarray:
    """``size`` touched buckets drawn from the seed (with repeats when
    fewer are touched): a fixed shape, so the probe compiles once."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    ids = reference.ids
    if len(ids) >= size:
        return np.sort(rng.choice(ids, size=size, replace=False))
    return np.sort(ids[rng.integers(0, len(ids), size=size)])


def numbers(observed: dict, expected: dict) -> dict:
    """``observed``/``expected``: {"losses": [...], "grad_norms": {leaf:
    x}, "change_norms": {leaf: x}, "state": {leaf: array}}."""
    lo, le = observed["losses"], expected["losses"]
    out = {
        "loss_rel": max(abs(a - b) / abs(b) for a, b in zip(lo, le)),
        "grad_norm_rel": _worst_leaf_gap(observed["grad_norms"],
                                         expected["grad_norms"]),
        "change_norm_rel": _worst_leaf_gap(observed["change_norms"],
                                           expected["change_norms"]),
    }
    rms = []
    for leaf, ref in expected["state"].items():
        got = observed["state"][leaf]
        rms.append(float(np.linalg.norm(got - ref)
                         / max(np.linalg.norm(ref), 1e-300)))
    out["state_rel_rms"] = max(rms)
    return {k: float(v) for k, v in out.items()}


def merge_groups(blocks: list, group: int) -> list:
    """One update from ``group`` blocks read at the same weights (a mesh's
    data axis) is one update from their rows together."""
    if group == 1:
        return blocks
    return [(np.concatenate([k for k, _l in blocks[i:i + group]]),
             np.concatenate([l for _k, l in blocks[i:i + group]]))
            for i in range(0, len(blocks), group)]


def merge_exact_pairs(overflow: list, blocks: list, group: int) -> list:
    """The overflow pairs of ``merge_groups``' steps: a (buckets, rows) a
    block becomes one a step, the rows of a group's later blocks shifted by
    the rows of the blocks before them, as their keys are concatenated."""
    out = []
    for i in range(0, len(blocks), group):
        shift = np.cumsum([0] + [len(l) for _k, l in blocks[i:i + group]])
        out.append((
            np.concatenate([np.asarray(b, np.int64)
                            for b, _r in overflow[i:i + group]]),
            np.concatenate([np.asarray(r, np.int64) + s for (_b, r), s
                            in zip(overflow[i:i + group], shift)])))
    return out


def run_reference(module, config: dict, blocks: list, seed: int,
                  buckets=None, **precision) -> tuple:
    """Drive a reference (or its lower-precision control) through the
    checked steps, one block a step; returns (the expected/observed dict,
    the reference)."""
    ref = module.Reference(config, blocks, seed, **precision)
    losses, grad = [], None
    for i in range(len(blocks)):
        losses.append(ref.step())
        if i == 0:
            grad = ref.grad_norms()
    out = {"losses": losses, "grad_norms": grad,
           "change_norms": ref.change_norms()}
    # distinct buckets a step touches, for the roofline's count of bytes
    out["distinct"] = float(np.mean([len(np.unique(b))
                                     for b, _rows in ref.pairs]))
    if buckets is not None:
        out["state"] = ref.state(buckets)
    return out, ref


def limits_of(config: dict, traffic: str) -> dict:
    """The configuration's limits, with what ``check.limits_by_traffic``
    states for this traffic mix in their place."""
    by_traffic = config["check"].get("limits_by_traffic", {})
    return dict(config["check"]["limits"], **by_traffic.get(traffic, {}))


def verdict(nums: dict, limits: dict) -> tuple:
    """(correct, lines): every number printed beside its limit."""
    lines, ok = [], True
    for name, limit in limits.items():
        v = nums[name]
        good = bool(np.isfinite(v) and v <= limit)
        ok = ok and good
        lines.append(f"check {name} = {v:.6g} (limit {limit:g}) "
                     f"{'ok' if good else 'NOT OK'}")
    return ok, lines
