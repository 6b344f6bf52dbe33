"""The plain reference of ``criteo_fm``: float64 numpy factorization machine.

Written from the update that ``models/fm.py`` documents for its tile step
(Rendle 2010; AdaGrad on w and v, weight decay on the touched factors):

    margin = sum_i w_i + 1/2 sum_f [(sum_i v_if)^2 - sum_i v_if^2]
    g_w    = sum over the bucket's pairs of dual_r
    g_v    = sum of dual_r * s_rf  -  v * g_w  +  l2_v * v      (touched only)
    cg'    = sqrt(cg^2 + g^2);  eta = alpha / (beta + cg')
    w'     = shrink(w / eta - g_w, l1) / (1 / eta + l2);  v' = v - eta * g_v

Departures from ``models/fm.py``: v0 comes from the benchmark's hash of
(bucket, factor, seed) (``init_factors``: uniform, standard deviation
``init_scale``), which the harness also writes into the program's table, where
the program draws normals on the host; only the touched buckets are held.
Nothing of the program is imported.

``operands`` rounds what the kernels round to bfloat16, the pulled
[w, v, sum v^2] and the pushed [dual, dual * s], to a lower-precision type;
``table`` rounds the stored state after each step (the control, PERF.md).
"""

from __future__ import annotations

import numpy as np

from benchmark.check import block_pairs, round_to
from benchmark.generators.fields import mix32

LEAVES = ("w", "v")


def init_factors(buckets: np.ndarray, dim: int, seed: int,
                 scale: float) -> np.ndarray:
    """v0 of ``buckets``: (n, dim) float64. The device twin is
    ``system.device_table``; 24 hash bits, so float32 holds it exactly."""
    salt = mix32(np.array([(int(seed) & 0xFFFFFFFF) ^ 0x6A09E667],
                          np.uint32))[0]
    cell = (buckets.astype(np.uint32)[:, None] * np.uint32(dim)
            + np.arange(dim, dtype=np.uint32)[None, :])
    u = (mix32(cell ^ salt) >> np.uint32(8)).astype(np.float64) / 2.0 ** 24
    return scale * np.sqrt(12.0) * (u - 0.5)


class Reference:
    def __init__(self, config: dict, blocks: list, seed: int,
                 operands=None, table=None):
        h = config["hyper"]
        self.k = int(config["dim"])
        self.alpha, self.beta = float(h["lr_alpha"]), float(h["lr_beta"])
        self.l1, self.l2 = float(h["l1"]), float(h["l2"])
        self.l2_v = float(h["l2_v"])
        self.operands, self.table = operands, table
        nb = int(config["num_buckets"])
        self.pairs, self.ids = block_pairs(blocks, nb)
        n = len(self.ids)
        self.w = np.zeros(n)
        self.v0 = init_factors(self.ids, self.k, seed,
                               float(h["init_scale"]))
        self.v = self.v0.copy()
        self.cg_w, self.cg_v = np.zeros(n), np.zeros((n, self.k))
        self.first_grad = None
        self._blocks = blocks
        self._step = 0

    def step(self) -> float:
        keys, labels = self._blocks[self._step]
        buckets, rows = self.pairs[self._step]
        idx = np.searchsorted(self.ids, buckets)
        n_rows, n = keys.shape[0], len(self.ids)
        pw = round_to(self.w, self.operands)
        pv = round_to(self.v, self.operands)
        pq = round_to((self.v * self.v).sum(axis=1), self.operands)
        lin = np.bincount(rows, weights=pw[idx], minlength=n_rows)
        q = np.bincount(rows, weights=pq[idx], minlength=n_rows)
        s = np.stack([np.bincount(rows, weights=pv[idx, f],
                                  minlength=n_rows)
                      for f in range(self.k)], axis=1)
        m = lin + 0.5 * ((s * s).sum(axis=1) - q)
        y = 2.0 * labels - 1.0
        loss = float(np.logaddexp(0.0, -y * m).mean())
        dual = -y / (1.0 + np.exp(y * m))
        d0 = round_to(dual, self.operands)
        ds = round_to(dual[:, None] * s, self.operands)
        g_w = np.bincount(idx, weights=d0[rows], minlength=n)
        push = np.stack([np.bincount(idx, weights=ds[rows, f], minlength=n)
                         for f in range(self.k)], axis=1)
        touched = np.bincount(idx, minlength=n) > 0
        g_v = (push - self.v * g_w[:, None]
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
        return loss

    def grad_norms(self) -> dict:
        return {"w": float(np.linalg.norm(self.first_grad[0])),
                "v": float(np.linalg.norm(self.first_grad[1]))}

    def change_norms(self) -> dict:
        return {"w": float(np.linalg.norm(self.w)),
                "v": float(np.linalg.norm(self.v - self.v0))}

    def state(self, buckets: np.ndarray) -> dict:
        i = np.searchsorted(self.ids, buckets)
        return {"w": self.w[i], "v": self.v[i]}
