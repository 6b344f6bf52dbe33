"""``criteo_ftrl`` under test: the linear learner's CLI path, and the probes
``correct`` reads from its table (FTRL slots are [w, z, cg] a bucket)."""

from __future__ import annotations

import functools

import numpy as np


def make_app(conf: str, tokens: list, config: dict, seed: int):
    from wormhole_tpu.learners import async_sgd
    return async_sgd.app_from_argv([conf, *tokens])


@functools.lru_cache(maxsize=None)
def _probes():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def col_norm(slots, col):
        x = slots[:, col].astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x))

    @jax.jit
    def rows_of(slots, idx):
        return slots[idx, 0].astype(jnp.float32)

    return col_norm, rows_of


def grad_norms(app, config: dict, seed: int) -> dict:
    """After ONE step from zero state FTRL's cg is |g|: the first gradient
    as the optimizer got it."""
    col_norm, _ = _probes()
    return {"w": float(col_norm(app.store.slots, 2))}


def change_norms(app, config: dict, seed: int) -> dict:
    col_norm, _ = _probes()
    return {"w": float(col_norm(app.store.slots, 0))}      # w0 = 0


def state(app, config: dict, seed: int, buckets: np.ndarray) -> dict:
    _, rows_of = _probes()
    return {"w": np.asarray(rows_of(app.store.slots,
                                    buckets.astype(np.int32)), np.float64)}
