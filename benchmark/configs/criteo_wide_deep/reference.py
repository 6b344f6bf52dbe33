"""The plain reference of ``criteo_wide_deep``: float64 numpy wide&deep.

Written from the equations (Cheng et al. 2016, section 3; the pooled form
``models/wide_deep.py`` documents for generic hashed bags):

    row r, its buckets B(r), presence only
    wide(r) = sum_{b in B(r)} w_b ;  p(r) = sum_{b in B(r)} v_b       (dim values)
    h_0 = p ;  h_{l+1} = relu(h_l W_l + b_l) ;  deep(r) = h_L W_L + b_L
    margin = wide + deep ;  loss = sum_r log(1 + exp(-y_r margin_r))
    dual_r = d loss / d margin_r
    g_w[b] = sum_{r with b} dual_r
    g_v[b] = sum_{r with b} d loss / d p(r)  +  l2_v v_b              (touched b)
    AdaGrad on the touched buckets and on every W_l, b_l:
        cg' = sqrt(cg^2 + g^2) ;  theta' = theta - alpha / (beta + cg') g
    (alpha = lr_alpha for w and v, lr_alpha_dense for the tower)

Departures from ``models/wide_deep.py``: v0 comes from the benchmark's hash
of (bucket, factor, seed), as ``criteo_fm``'s does (its ``init_factors``: the
weights' generator is shared, the update rule is this file's own), and the
tower from ``init_tower`` (He-scaled normals
of the seed, held as float32 values), both of which the harness writes into
the program, where the program draws its own; only the touched buckets are
held. Nothing of the program is imported.

Precision, path by path, as ``config.json`` states it. ``operands`` rounds
what the tile kernels round to bfloat16: the pulled ``[w, v]`` and the pushed
``[dual, d loss / d p]``. ``exact_pairs`` (one ``(buckets, rows)`` a step)
names the pairs that the crec2 file's COO overflow list holds: the program
gathers and scatters those in float32 (``precision.overflow_operands``), so
they are taken unrounded, pull and push alike. ``tower`` rounds BOTH
operands of every matmul of the tower, forward and backward (``h W``,
``g W^T``, ``h^T g``); the products are summed unrounded. It defaults to
the configuration's ``tower_operands``; a control passes another.
``table`` rounds the stored ``w, v, cg`` after each step (the control).
"""

from __future__ import annotations

import numpy as np

from benchmark.check import block_pairs, exact_masks, round_to, take
from benchmark.configs.criteo_fm.reference import init_factors

STATED = "stated"       # ``tower``: what the configuration states


def tower_sizes(config: dict) -> list:
    return [int(config["dim"]), *(int(h) for h in config["hidden"]), 1]


def init_tower(sizes: list, seed: int) -> list:
    """[(W_l, b_l)]: W_l He-scaled normals of the seed, rounded to float32
    (what the program holds) and given back as float64; b_l zero."""
    rng = np.random.default_rng([int(seed), 0x70E4])
    return [((rng.standard_normal((a, b)) * np.sqrt(2.0 / a))
             .astype(np.float32).astype(np.float64), np.zeros(b))
            for a, b in zip(sizes, sizes[1:])]


def leaf(w: np.ndarray, b: np.ndarray) -> np.ndarray:
    """One layer's parameters as one flat leaf."""
    return np.concatenate([np.ravel(w), np.ravel(b)])


class Reference:
    def __init__(self, config: dict, blocks: list, seed: int,
                 operands=None, table=None, tower=STATED,
                 exact_pairs=None):
        h = config["hyper"]
        self.k = int(config["dim"])
        self.alpha, self.beta = float(h["lr_alpha"]), float(h["lr_beta"])
        self.alpha_dense = float(h["lr_alpha_dense"])
        self.l2_v = float(h["l2_v"])
        if tower == STATED:
            tower = config["precision"]["tower_operands"]
            tower = None if tower in ("float32", "float64") else tower
        self.operands, self.table, self.tower = operands, table, tower
        nb = int(config["num_buckets"])
        self.pairs, self.ids = block_pairs(blocks, nb)
        self.exact = exact_masks(self.pairs, exact_pairs, nb)
        n = len(self.ids)
        self.w = np.zeros(n)
        self.v0 = init_factors(self.ids, self.k, seed,
                               float(h["init_scale"]))
        self.v = self.v0.copy()
        self.cg_w, self.cg_v = np.zeros(n), np.zeros((n, self.k))
        self.mlp0 = init_tower(tower_sizes(config), seed)
        self.mlp = [(w.copy(), b.copy()) for w, b in self.mlp0]
        self.acc = [(np.zeros_like(w), np.zeros_like(b))
                    for w, b in self.mlp0]
        self.first_grad = None
        self._blocks = blocks
        self._step = 0

    def _round(self, x):
        return round_to(x, self.tower)

    def _tower_forward(self, pooled: np.ndarray) -> tuple:
        """(deep, what the backward needs). Every matmul of the tower
        rounds both operands to ``self.tower``."""
        ws = [self._round(w) for w, _b in self.mlp]
        hs, pre = [self._round(pooled)], []
        for l, (_w, b) in enumerate(self.mlp):
            pre.append(hs[-1] @ ws[l] + b)
            if l + 1 < len(self.mlp):
                hs.append(self._round(np.maximum(pre[-1], 0.0)))
        return pre[-1][:, 0], (ws, hs, pre)

    def _tower_backward(self, dual: np.ndarray, kept: tuple) -> tuple:
        """([(gW_l, gb_l)], d loss / d pooled) from d loss / d deep."""
        ws, hs, pre = kept
        d = dual[:, None]
        grads = [None] * len(ws)
        for l in reversed(range(len(ws))):
            dr = self._round(d)
            grads[l] = (hs[l].T @ dr, d.sum(axis=0))
            d = dr @ ws[l].T
            if l:
                d = d * (pre[l - 1] > 0.0)
        return grads, d

    def step(self) -> float:
        keys, labels = self._blocks[self._step]
        buckets, rows = self.pairs[self._step]
        exact = self.exact[self._step]
        idx = np.searchsorted(self.ids, buckets)
        n_rows, n, k = keys.shape[0], len(self.ids), self.k
        wide = np.bincount(rows, weights=take(self.w, idx, self.operands,
                                              exact), minlength=n_rows)
        pooled = np.stack([np.bincount(rows, weights=take(
            self.v[:, f], idx, self.operands, exact), minlength=n_rows)
            for f in range(k)], axis=1)
        deep, kept = self._tower_forward(pooled)
        m = wide + deep
        y = 2.0 * labels - 1.0
        loss = float(np.logaddexp(0.0, -y * m).mean())
        dual = -y / (1.0 + np.exp(y * m))
        g_mlp, g_pooled = self._tower_backward(dual, kept)
        g_w = np.bincount(idx, weights=take(dual, rows, self.operands,
                                            exact), minlength=n)
        push = np.stack([np.bincount(idx, weights=take(
            g_pooled[:, f], rows, self.operands, exact), minlength=n)
            for f in range(k)], axis=1)
        touched = np.bincount(idx, minlength=n) > 0
        g_v = (push + self.l2_v * self.v) * touched[:, None]
        if self.first_grad is None:
            self.first_grad = (g_w, g_v, g_mlp)
        cg_w = np.sqrt(self.cg_w ** 2 + g_w ** 2)
        cg_v = np.sqrt(self.cg_v ** 2 + g_v ** 2)
        w = self.w - self.alpha / (self.beta + cg_w) * g_w
        v = self.v - self.alpha / (self.beta + cg_v) * g_v
        t = touched
        self.w = round_to(np.where(t, w, self.w), self.table)
        self.v = round_to(np.where(t[:, None], v, self.v), self.table)
        self.cg_w = round_to(np.where(t, cg_w, self.cg_w), self.table)
        self.cg_v = round_to(np.where(t[:, None], cg_v, self.cg_v),
                             self.table)
        for l, (gw, gb) in enumerate(g_mlp):
            aw = np.sqrt(self.acc[l][0] ** 2 + gw ** 2)
            ab = np.sqrt(self.acc[l][1] ** 2 + gb ** 2)
            self.acc[l] = (aw, ab)
            wl, bl = self.mlp[l]
            self.mlp[l] = (
                wl - self.alpha_dense / (self.beta + aw) * gw,
                bl - self.alpha_dense / (self.beta + ab) * gb)
        self._step += 1
        return loss

    def grad_norms(self) -> dict:
        g_w, g_v, g_mlp = self.first_grad
        out = {"w": float(np.linalg.norm(g_w)),
               "v": float(np.linalg.norm(g_v))}
        for l, (gw, gb) in enumerate(g_mlp):
            out[f"t{l}"] = float(np.linalg.norm(leaf(gw, gb)))
        return out

    def change_norms(self) -> dict:
        """``w``, ``v - v0`` and the whole tower as ONE leaf ``t``. The
        first gradient and the state have a leaf a layer; the norm of one
        layer's change after three steps is rough (a ReLU tower's
        gradient: two sound programs read 7e-4 apart in a layer, PERF.md
        section 2), and a rough leaf would set the limit for ``v``, the
        leaf that a rounded table moves."""
        return {"w": float(np.linalg.norm(self.w)),
                "v": float(np.linalg.norm(self.v - self.v0)),
                "t": float(np.linalg.norm(np.concatenate(
                    [leaf(w - w0, b - b0) for (w, b), (w0, b0)
                     in zip(self.mlp, self.mlp0)])))}

    def state(self, buckets: np.ndarray) -> dict:
        i = np.searchsorted(self.ids, buckets)
        out = {"w": self.w[i], "v": self.v[i]}
        for l, (w, b) in enumerate(self.mlp):
            out[f"t{l}"] = leaf(w, b)
        return out
