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


def stated_operands(config: dict):
    """The operand type the configuration states for the kernels, as the
    reference takes it: None where they compute in float32 or wider."""
    kind = config["precision"]["kernel_operands"]
    return None if kind in ("float32", "float64") else kind


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
