"""``criteo_ftrl_ps4`` under test: the linear learner's CLI path on a
``data:2,model:2`` mesh, and the probes ``correct`` reads from its table.

The table is ``f32[2**29, 3]`` ([w, z, cg] a bucket), its rows split over
the MODEL axis and repeated over the DATA axis: 8.6 GB as laid out, which
neither one chip nor a careless host copy should ever hold. So the probes
are ``shard_map`` programs over the store's own mesh: every chip reads its
own shard, and only a scalar (a norm) or the sampled rows (262,144 floats)
cross the MODEL axis, by ``psum``. Bucket ids are int32: 2**29 < 2**31.

What a norm reads: on each shard the float32 sum of squares of one column
over its 2**28 buckets, in the order XLA's reduction takes them, then the
float32 sum of the two shards' sums, then the square root on the host. A
sampled row is its owner's value plus the other shard's 0.0: exact.
"""

from __future__ import annotations

import functools

import numpy as np


def make_app(conf: str, tokens: list, config: dict, seed: int):
    from wormhole_tpu.learners import async_sgd
    return async_sgd.app_from_argv([conf, *tokens])


@functools.lru_cache(maxsize=None)
def _probes(mesh):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from wormhole_tpu.parallel.mesh import MODEL_AXIS, shard_map_compat
    shard = P(MODEL_AXIS, None)          # as the store splits the table

    def over_shards(fn, *more):
        return jax.jit(shard_map_compat(fn, mesh=mesh,
                                        in_specs=(shard, *more),
                                        out_specs=P()))

    def sum_squares(col):
        def fn(slots):
            x = slots[:, col].astype(jnp.float32)
            return jax.lax.psum(jnp.sum(x * x), MODEL_AXIS)
        return over_shards(fn)

    def rows_of(slots, idx):
        local = idx - jax.lax.axis_index(MODEL_AXIS) * slots.shape[0]
        mine = (local >= 0) & (local < slots.shape[0])
        w = slots[jnp.where(mine, local, 0), 0].astype(jnp.float32)
        return jax.lax.psum(jnp.where(mine, w, 0.0), MODEL_AXIS)

    # FTRL's slots a bucket are [w, z, cg]
    return {"w_squares": sum_squares(0), "cg_squares": sum_squares(2),
            "w_rows": over_shards(rows_of, P())}


def _norm(app, squares: str) -> float:
    probe = _probes(app.store.rt.mesh)[squares]
    return float(np.sqrt(np.float64(probe(app.store.slots))))


def grad_norms(app, config: dict, seed: int) -> dict:
    """After ONE step from zero state FTRL's cg is |g|: the first gradient
    as the optimizer got it, summed over both workers."""
    return {"w": _norm(app, "cg_squares")}


def change_norms(app, config: dict, seed: int) -> dict:
    return {"w": _norm(app, "w_squares")}      # w0 = 0


def state(app, config: dict, seed: int, buckets: np.ndarray) -> dict:
    rows_of = _probes(app.store.rt.mesh)["w_rows"]
    return {"w": np.asarray(rows_of(app.store.slots,
                                    buckets.astype(np.int32)), np.float64)}
