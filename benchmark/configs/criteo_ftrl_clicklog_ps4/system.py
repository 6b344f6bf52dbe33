"""``criteo_ftrl_clicklog_ps4`` under test: the linear learner's CLI path with
``data_format = criteo`` and ``tile_online = on`` on a ``data:2,model:2``
mesh, and the probes ``correct`` reads from its sharded table.

The table is ``f32[2**29, 3]`` ([w, z, cg] a bucket), its rows split over the
MODEL axis and repeated over the DATA axis: 8.6 GB as laid out, which neither
one chip nor a host copy should ever hold. So the probes are ``shard_map``
programs over the store's own mesh, as ``criteo_ftrl_ps4``'s are (written out
here: nothing of another configuration is imported): every chip reads its own
shard, and only a scalar (a norm) or the sampled rows (262,144 floats) cross
the MODEL axis, by ``psum``. Bucket ids are int32: 2**29 < 2**31.

``counters`` reports, for the log, what the online encoder put on the blocks'
overflow lists (the pairs, the room in force, how often it grew:
``obs.metrics.online_overflow_metrics``) and what the mesh feed shipped of
them (the Timer's ``mesh_overflow_slots`` and ``mesh_widened_groups``, read
with ``.get``: a program from before PR 43 has neither). The configuration
states no counter that must stay 0: a mesh store's table is stacked from the
start, and an online block has no other step to take than the tile step.
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
    as the optimizer got it, summed over both workers, listed pairs and
    all."""
    return {"w": _norm(app, "cg_squares")}


def change_norms(app, config: dict, seed: int) -> dict:
    return {"w": _norm(app, "w_squares")}      # w0 = 0


def state(app, config: dict, seed: int, buckets: np.ndarray) -> dict:
    rows_of = _probes(app.store.rt.mesh)["w_rows"]
    return {"w": np.asarray(rows_of(app.store.slots,
                                    buckets.astype(np.int32)), np.float64)}


def counters(app) -> dict:
    """The program's own counts of what became of the pairs past the
    per-tile cap, in the feed's encoder and in the mesh feed."""
    from wormhole_tpu.obs import metrics
    pairs, room, grown = metrics.online_overflow_metrics(app.obs.registry)
    totals = app.timer.totals
    return {"online_overflow_pairs": int(pairs.value),
            "online_overflow_room": int(room.value),
            "online_room_grown": int(grown.value),
            "mesh_overflow_slots": int(totals.get("mesh_overflow_slots", 0)),
            "mesh_widened_groups": int(totals.get("mesh_widened_groups",
                                                  0))}
