"""The plain reference of ``criteo_ftrl``: float64 numpy FTRL-proximal.

Straight from the update rule (the reference's sgd_server_handle.h:111-141),
a copy of ``chip_smoke.Oracle`` made to hold only the buckets the check's
blocks touch (a dense float64 table of 2**28 x 3 is 6 GB of host memory).
Nothing of the program is imported: the key fold is the benchmark's own copy
(``generators/fields.py``).

``operands`` computes the same steps with the weights (forward) and the
duals (backward) rounded to a lower-precision type before use, as the tile
kernels round them to bfloat16; ``table`` rounds the stored state after each
step. Both are for the control of ``correct`` (PERF.md section 2): the
configuration states bfloat16 operands and a float32 table, so the control is
``operands="float8_e4m3fn"``.

``exact_pairs`` (one ``(buckets, rows)`` a step) names the pairs that the
file's COO overflow list holds: the configuration states float32 for that
path, so those pairs take the weight and the row's dual unrounded, whatever
``operands`` is. ``None`` rounds every pair.
"""

from __future__ import annotations

import numpy as np

from benchmark.check import block_pairs, exact_masks, round_to, take

LEAVES = ("w",)


class Reference:
    def __init__(self, config: dict, blocks: list, seed: int,
                 operands=None, table=None, exact_pairs=None):
        h = config["hyper"]
        self.l1, self.l2 = float(h["lambda1"]), float(h["lambda2"])
        self.alpha, self.beta = float(h["lr_eta"]), float(h["lr_beta"])
        self.operands, self.table = operands, table
        nb = int(config["num_buckets"])
        self.pairs, self.ids = block_pairs(blocks, nb)
        self.exact = exact_masks(self.pairs, exact_pairs, nb)
        n = len(self.ids)
        self.w, self.z, self.cg = np.zeros(n), np.zeros(n), np.zeros(n)
        self.first_grad = None
        self._blocks = blocks
        self._step = 0

    def step(self) -> float:
        """One update from the next block; returns its mean loss."""
        keys, labels = self._blocks[self._step]
        buckets, rows = self.pairs[self._step]
        exact = self.exact[self._step]
        idx = np.searchsorted(self.ids, buckets)
        n_rows = keys.shape[0]
        m = np.bincount(rows, weights=take(self.w, idx, self.operands, exact),
                        minlength=n_rows)
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
        return loss

    def grad_norms(self) -> dict:
        """Norm of the first gradient as the optimizer got it, per leaf."""
        return {"w": float(np.linalg.norm(self.first_grad))}

    def change_norms(self) -> dict:
        """Norm of the parameters' change since the start (w0 = 0)."""
        return {"w": float(np.linalg.norm(self.w))}

    def state(self, buckets: np.ndarray) -> dict:
        """The parameters at ``buckets`` (each one a touched bucket)."""
        return {"w": self.w[np.searchsorted(self.ids, buckets)]}
