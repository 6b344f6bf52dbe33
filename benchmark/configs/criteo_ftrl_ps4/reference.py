"""The plain reference of ``criteo_ftrl_ps4``: float64 numpy FTRL-proximal,
one process, one table, no shards.

The deployment splits the table over two key-range servers and the rows of
an update over two workers; what it computes is one FTRL update from all the
rows of a group read at the same weights, and that is all this reference
knows: a step here is the harness's merged group (``check.merge_groups``:
two 98,304-row blocks, 196,608 rows), its gradient summed over every row
once, applied once to every touched bucket (sgd_server_handle.h:111-141).
Held against it, a program that loses one worker's gradient, one shard's
margin or one shard's push is off by far more than any limit.

Its own copy of the update rule, not an import of ``criteo_ftrl``'s, so that
neither configuration's check moves when the other's reference is changed.
Only the touched buckets are held (a dense float64 table of 2**29 x 3 is 13
GB of host memory); bucket ids stay under 2**31. Nothing of the program is
imported: the key fold is the benchmark's own (``generators/fields.py``).

``operands`` rounds the weights (forward) and the duals (backward) to a
lower-precision type before use, as the tile kernels round them to bfloat16;
``table`` rounds the stored state after each step: the controls of
``correct``. ``exact_pairs`` (one ``(buckets, rows)`` a step) names the pairs
on the file's COO overflow list, which the mesh step takes unrounded
(float32) whatever ``operands`` is; the cell's uniform keys leave it empty.
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
        """One update from the next group's rows; returns their mean loss."""
        keys, labels = self._blocks[self._step]
        buckets, rows = self.pairs[self._step]
        exact = self.exact[self._step]
        idx = np.searchsorted(self.ids, buckets)
        # pull: every row's margin from the weights as the step found them
        margin = np.bincount(
            rows, weights=take(self.w, idx, self.operands, exact),
            minlength=keys.shape[0])
        y = 2.0 * labels - 1.0
        loss = float(np.logaddexp(0.0, -y * margin).mean())
        dual = -y / (1.0 + np.exp(y * margin))
        # push: every pair's dual into its bucket, each once
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
