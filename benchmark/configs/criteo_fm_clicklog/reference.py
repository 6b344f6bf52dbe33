"""The plain reference of ``criteo_fm_clicklog``: float64 numpy factorization
machine whose pairs take one of two stated precisions, and its own check of
the overflow list it is handed.

Rendle 2010 with presence-only features (x = 1), AdaGrad on w and v, weight
decay on the touched factors, written out here (``README.md`` beside this
file has each departure from the paper's equations):

    margin_r = sum_i w_i + 1/2 sum_f [(sum_i v_if)^2 - sum_i v_if^2]
    g_w      = sum over the bucket's pairs of dual_r
    g_v      = sum of dual_r * s_rf  -  v * g_w  +  l2_v * v      (touched only)
    cg'      = sqrt(cg^2 + g^2);  eta = alpha / (beta + cg')
    w'       = shrink(w / eta - g_w, l1) / (1 / eta + l2);  v' = v - eta * g_v

``operands`` rounds what the tile kernels round to bfloat16, the pulled
[w, v, sum v^2] of a pair's bucket and the pushed [dual, dual * s] of its row,
to a lower-precision type; ``table`` rounds the stored state after each step:
the controls of ``correct``. ``exact_pairs`` (one ``(buckets, rows)`` a step)
names the pairs on the block's COO overflow list, which the program's spill
step pulls and pushes in float32: those take every channel UNROUNDED, two
fifths of a block's pairs in this configuration's cell. Without it every pair
is rounded, which is the reference of another program (the control that the
limits must refuse).

**The list is not taken on trust.** It comes from the program's own encoder,
so a fault there would otherwise move both sides alike. From its own pairs
(the keys folded here, ``benchmark.check.block_pairs``) and the tile geometry
that ``config.json`` states under ``tile`` the reference checks that every
handed pair is a pair of the block, as often as the block has it, and that
every tile's share of the list is exactly its pairs past the cap
(``check_overflow_list``: ``criteo_ftrl_clicklog``'s, imported and not
copied: the same block format; that module imports nothing of the program). A
list that fails is a fault of the program: every loss the reference then
returns is NaN, which fails ``correct``, and the reason is printed on standard
error.

Nothing of the program is imported, and nothing of ``criteo_fm``'s reference.
v0 is the benchmark's hash of (bucket, factor, seed) (``init_factors``), which
``system.py`` also writes into the program's planes; only the touched buckets
are held.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark.check import block_pairs, exact_mask, round_to, take
from benchmark.configs.criteo_ftrl_clicklog.reference import \
    check_overflow_list
from benchmark.generators.fields import mix32

LEAVES = ("w", "v")


def init_factors(buckets: np.ndarray, dim: int, seed: int,
                 scale: float) -> np.ndarray:
    """v0 of ``buckets``: (n, dim) float64, uniform with standard deviation
    ``scale``. The device twin is ``system.v0_plane``; 24 hash bits, so
    float32 holds it exactly."""
    salt = mix32(np.array([(int(seed) & 0xFFFFFFFF) ^ 0x6A09E667],
                          np.uint32))[0]
    cell = (buckets.astype(np.uint32)[:, None] * np.uint32(dim)
            + np.arange(dim, dtype=np.uint32)[None, :])
    u = (mix32(cell ^ salt) >> np.uint32(8)).astype(np.float64) / 2.0 ** 24
    return scale * np.sqrt(12.0) * (u - 0.5)


class Reference:
    def __init__(self, config: dict, blocks: list, seed: int,
                 operands=None, table=None, exact_pairs=None):
        h = config["hyper"]
        self.k = int(config["dim"])
        self.alpha, self.beta = float(h["lr_alpha"]), float(h["lr_beta"])
        self.l1, self.l2 = float(h["l1"]), float(h["l2"])
        self.l2_v = float(h["l2_v"])
        self.operands, self.table = operands, table
        nb = int(config["num_buckets"])
        self.pairs, self.ids = block_pairs(blocks, nb)
        self.labels = [labels for _keys, labels in blocks]
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
        self.w = np.zeros(n)
        self.v0 = init_factors(self.ids, self.k, seed,
                               float(h["init_scale"]))
        self.v = self.v0.copy()
        self.cg_w, self.cg_v = np.zeros(n), np.zeros((n, self.k))
        self.first_grad = None
        self._step = 0

    def step(self) -> float:
        """One update from the next block; returns its mean loss (NaN where
        the overflow list handed for a step failed its check)."""
        labels = self.labels[self._step]
        buckets, rows = self.pairs[self._step]
        exact = self.exact[self._step]
        idx = np.searchsorted(self.ids, buckets)
        n_rows, n, k = len(labels), len(self.ids), self.k

        def pull(x):        # a bucket value onto the pairs' rows
            return np.bincount(rows, weights=take(x, idx, self.operands,
                                                  exact), minlength=n_rows)

        def push(x):        # a row value onto the pairs' buckets
            return np.bincount(idx, weights=take(x, rows, self.operands,
                                                 exact), minlength=n)

        lin = pull(self.w)
        q = pull((self.v * self.v).sum(axis=1))
        s = np.stack([pull(self.v[:, f]) for f in range(k)], axis=1)
        m = lin + 0.5 * ((s * s).sum(axis=1) - q)
        y = 2.0 * labels - 1.0
        loss = float(np.logaddexp(0.0, -y * m).mean())
        dual = -y / (1.0 + np.exp(y * m))
        g_w = push(dual)
        pushed = np.stack([push(dual * s[:, f]) for f in range(k)], axis=1)
        touched = np.bincount(idx, minlength=n) > 0
        g_v = (pushed - self.v * g_w[:, None]
               + self.l2_v * self.v) * touched[:, None]
        if self.first_grad is None:
            self.first_grad = (g_w, g_v)
        cg_w = np.sqrt(self.cg_w ** 2 + g_w ** 2)
        cg_v = np.sqrt(self.cg_v ** 2 + g_v ** 2)
        eta_w = self.alpha / (self.beta + cg_w)
        eta_v = self.alpha / (self.beta + cg_v)
        zz = self.w / eta_w - g_w
        w = (np.sign(zz) * np.maximum(np.abs(zz) - self.l1, 0.0)
             / (1.0 / eta_w + self.l2))
        v = self.v - eta_v * g_v
        t = touched
        self.w = round_to(np.where(t, w, self.w), self.table)
        self.v = round_to(np.where(t[:, None], v, self.v), self.table)
        self.cg_w = round_to(np.where(t, cg_w, self.cg_w), self.table)
        self.cg_v = round_to(np.where(t[:, None], cg_v, self.cg_v),
                             self.table)
        self._step += 1
        return float("nan") if self.list_fault else loss

    def grad_norms(self) -> dict:
        """Norm of the first gradient as the optimizer got it, per leaf."""
        return {"w": float(np.linalg.norm(self.first_grad[0])),
                "v": float(np.linalg.norm(self.first_grad[1]))}

    def change_norms(self) -> dict:
        """Norm of the parameters' change since the start (w0 = 0)."""
        return {"w": float(np.linalg.norm(self.w)),
                "v": float(np.linalg.norm(self.v - self.v0))}

    def state(self, buckets: np.ndarray) -> dict:
        """The parameters at ``buckets`` (each one a touched bucket)."""
        i = np.searchsorted(self.ids, buckets)
        return {"w": self.w[i], "v": self.v[i]}
