"""Per-key online update rules — the "server handles", functional.

Rebuild of ``learn/linear/sgd/sgd_server_handle.h`` (SGD / AdaGrad / FTRL,
each a lock-free per-key struct the KVServer applies under its receive
thread) and the experimental delay-tolerant variants
(``learn/linear/sgd/delay_tol_handle.h``). Here each handle is a *pure
function* over a ``(k, val_len)`` slot matrix — vmapped/vectorized over
keys, jitted into the train step, sharded over the ``model`` mesh axis by
the store. Slot layouts match the reference exactly:

- SGD      val = [w]           (sgd_server_handle.h:43-68)
- AdaGrad  val = [w, √Σg²]     (sgd_server_handle.h:80-99)
- FTRL     val = [w, z, √Σg²]  (sgd_server_handle.h:111-141)
- DT-SGD / DT-AdaGrad: learning-rate denominator inflated by the pull→push
  staleness τ (delay_tol_handle.h:141-194)
- DT2-AdaGrad: val = [w, √Σg², g_bak]; corrects the accumulator with the
  cross-term 2·g·g_bak of the gradient remembered at pull time
  (delay_tol_handle.h:70-111)

All updates end in the L1L2 proximal op (penalty.h:36-41); nnz/|Δw|² deltas
for the Progress chain are returned alongside.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from wormhole_tpu.ops.loss import opaque_one
from wormhole_tpu.ops.penalty import L1L2


@dataclass(frozen=True)
class LearnRate:
    """eta_t = alpha / (beta + √t-ish) (config.proto lr_eta/lr_beta)."""
    alpha: float = 0.1
    beta: float = 1.0


@dataclass(frozen=True)
class Handle:
    """Base: subclasses define val_len and push(); pull is always slot 0."""

    penalty: L1L2 = L1L2()
    lr: LearnRate = LearnRate()

    val_len: int = 1

    def init(self, num_keys: int) -> jax.Array:
        return jnp.zeros((num_keys, self.val_len), jnp.float32)

    def weights(self, slots: jax.Array) -> jax.Array:
        """Pull: slot 0 is always w (set_sync_val_len(1) semantics —
        servers store val_len values, sync only w, async_sgd.h:213-217)."""
        return slots[..., 0]

    def push(self, slots: jax.Array, grad: jax.Array, t: jax.Array,
             tau: jax.Array) -> jax.Array:
        raise NotImplementedError

    def push_planes(self, planes: tuple, grad: jax.Array, t: jax.Array,
                    tau: jax.Array) -> tuple:
        """push() over a table kept as one plane a slot
        (learners/table.py), each shaped like ``grad``. The default
        stacks the planes and splits the result again: inside a jit XLA
        removes a slice of a concatenate."""
        new = self.push(jnp.stack(planes, axis=-1), grad, t, tau)
        return tuple(new[..., k] for k in range(len(planes)))

    def warm_start(self, w: jax.Array) -> jax.Array:
        """Slots that make ``w`` a fixed point of a zero-gradient push
        (model_in warm start, linear.cc:115-123). Default: w in slot 0,
        accumulators zeroed — correct for the direct-update handles."""
        slots = jnp.zeros(w.shape + (self.val_len,), jnp.float32)
        return slots.at[..., 0].set(w)


@dataclass(frozen=True)
class SGDHandle(Handle):
    """w ← prox(w/η − g) with η = α/(β+√t) (sgd_server_handle.h:43-68)."""

    val_len: int = 1

    def push(self, slots, grad, t, tau):
        w = slots[..., 0]
        eta = self.lr.alpha / (self.lr.beta + jnp.sqrt(t))
        w_new = self.penalty.solve(w / eta - grad, 1.0 / eta)
        return w_new[..., None]


@dataclass(frozen=True)
class AdaGradHandle(Handle):
    """Per-key curvature: cg ← √(cg²+g²); η = α/(β+cg)
    (sgd_server_handle.h:80-99)."""

    val_len: int = 2

    def push(self, slots, grad, t, tau):
        w, cg = slots[..., 0], slots[..., 1]
        cg_new = jnp.sqrt(cg * cg + grad * grad)
        eta = self.lr.alpha / (self.lr.beta + cg_new)
        w_new = self.penalty.solve(w / eta - grad, 1.0 / eta)
        return jnp.stack([w_new, cg_new], axis=-1)


@dataclass(frozen=True)
class FTRLHandle(Handle):
    """FTRL-proximal (sgd_server_handle.h:111-141): z accumulates g − σ·w,
    w = prox(−z) with curvature (β+cg)/α. The −z sign matches the reference
    passing −z into L1L2::Solve (line 135)."""

    val_len: int = 3

    def update(self, w, z, cg, grad, one):
        """The elementwise slot math on unstacked planes — shared by
        push() and the fused tile-step kernel (ops/tilemm.py), which
        runs it per weight tile inside the Pallas grid. ``one`` is
        ``opaque_one(...)``: the ``*one`` guards pin each product to
        its rounded f32 value so both compilation contexts produce the
        same bits (fused/split bit parity; see ops/loss.opaque_one)."""
        cg_new = jnp.sqrt((cg * cg) * one + (grad * grad) * one)
        sigma = (cg_new - cg) / self.lr.alpha
        z_new = (z + grad) - (sigma * w) * one
        w_new = self.penalty.solve(
            -z_new, (self.lr.beta + cg_new) / self.lr.alpha)
        return w_new, z_new, cg_new

    def push_planes(self, planes, grad, t, tau):
        return self.update(*planes, grad, opaque_one(grad))

    def push(self, slots, grad, t, tau):
        planes = (slots[..., 0], slots[..., 1], slots[..., 2])
        return jnp.stack(self.push_planes(planes, grad, t, tau), axis=-1)

    def warm_start(self, w):
        """FTRL derives w from z (w = prox(−z)), so a warm start must seed
        z with the value whose prox is w — slot 0 alone would be erased by
        the first push. With cg=0: prox(−z) = shrink(−z, λ1)/(β/α + λ2),
        so z = −(w·(β/α + λ2) + λ1·sign(w))."""
        p = self.penalty
        z = -(w * (self.lr.beta / self.lr.alpha + p.lambda2)
              + p.lambda1 * jnp.sign(w))
        return jnp.stack([w, z, jnp.zeros_like(w)], axis=-1)


@dataclass(frozen=True)
class DTSGDHandle(Handle):
    """Staleness-inflated SGD: η = α/(β+√t+τ) (delay_tol_handle.h:141-166,
    lr_theta weighting folded into tau by the caller)."""

    val_len: int = 1

    def push(self, slots, grad, t, tau):
        w = slots[..., 0]
        eta = self.lr.alpha / (self.lr.beta + jnp.sqrt(t) + tau)
        w_new = self.penalty.solve(w / eta - grad, 1.0 / eta)
        return w_new[..., None]


@dataclass(frozen=True)
class DTAdaGradHandle(Handle):
    """AdaGrad with τ added to the denominator (delay_tol_handle.h:168-194)."""

    val_len: int = 2

    def push(self, slots, grad, t, tau):
        w, cg = slots[..., 0], slots[..., 1]
        cg_new = jnp.sqrt(cg * cg + grad * grad)
        eta = self.lr.alpha / (self.lr.beta + cg_new + tau)
        w_new = self.penalty.solve(w / eta - grad, 1.0 / eta)
        return jnp.stack([w_new, cg_new], axis=-1)


@dataclass(frozen=True)
class DT2AdaGradHandle(Handle):
    """Delay-compensated AdaGrad (DTAdaGradHandle2,
    delay_tol_handle.h:20-111). The reference keys a per-(sender,
    keyset-signature) memory of each key's CUMULATIVE gradient at pull
    time; at push, ``grad_bck = gsum_now − gsum_at_pull`` is the mass
    OTHER workers applied between this worker's pull and push, and the
    update corrects the accumulator by the cross-term ``2·g·grad_bck``
    plus a weight term for the learning-rate shift.

    Here the signature map is unnecessary: the driver's split pull/push
    pipeline (ShardedStore.dt2_pull/dt2_push) carries the pull-time
    ``gsum`` snapshot WITH the in-flight batch, so the correction is
    exact per batch — no hash collisions, no per-sender state. Slots:
    [w, gsum, cg2, cg2max] (val[0..3] of the reference handle)."""

    val_len: int = 4

    def push(self, slots, grad, t, tau, gsum_snap=None):
        """Without ``gsum_snap`` (the fused single-program paths) gbak is
        exactly 0 — NOT a degradation: a fused step has no pull→push gap,
        so there is no interleaved mass to compensate and the update is
        plain AdaGrad, which is the correct limit of the recurrence."""
        w, gsum = slots[..., 0], slots[..., 1]
        cg2, cg2max = slots[..., 2], slots[..., 3]
        gbak = (gsum - gsum_snap) if gsum_snap is not None \
            else jnp.zeros_like(grad)
        cg2_new = cg2 + grad * grad + 2.0 * grad * gbak
        # eta here is the reference's DIVISOR form: sqrt(cg2max+beta)/alpha
        d_old = jnp.sqrt(cg2max + self.lr.beta) / self.lr.alpha
        cg2max_new = jnp.maximum(cg2max, cg2_new)
        d = jnp.sqrt(cg2max_new + self.lr.beta) / self.lr.alpha
        # first-ever push with lr_beta=0 has d_old=0; gbak is 0 there, so
        # the correction term is defined as 0 (guard the 0*inf)
        corr = jnp.where(d_old > 0.0, gbak * (d / d_old - 1.0), 0.0)
        w_new = self.penalty.solve(d * w - grad + corr, d)
        return jnp.stack([w_new, gsum + grad, cg2_new, cg2max_new],
                         axis=-1)


_HANDLES = {
    "sgd": SGDHandle,
    "adagrad": AdaGradHandle,
    "ftrl": FTRLHandle,
    "dt_sgd": DTSGDHandle,
    "dt_adagrad": DTAdaGradHandle,
    "dt2_adagrad": DT2AdaGradHandle,
}


def create_handle(algo: str, penalty: L1L2 = L1L2(),
                  lr: LearnRate = LearnRate()) -> Handle:
    """Runtime handle dispatch (AsyncSGDServer::InitHandle,
    async_sgd.h:189-231)."""
    key = algo.lower() if isinstance(algo, str) else algo.value
    if key not in _HANDLES:
        raise ValueError(f"unknown algo {algo!r}; have {sorted(_HANDLES)}")
    return _HANDLES[key](penalty=penalty, lr=lr)
