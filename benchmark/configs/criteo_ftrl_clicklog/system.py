"""``criteo_ftrl_clicklog`` under test: the linear learner's CLI path with
``data_format = criteo`` and ``tile_online = on`` over a log with skew and
empty columns, and the probes ``correct`` reads from its table.

The table is three float32 planes of 2**29 buckets (``w``, ``z``, ``cg``: 6.4
GB), which must never be stacked on the device or copied to the host. The
probes index the table as the stores' own code does (``slots[:, col]``,
``slots[rows, col]`` with a static column), which a ``PlaneTable`` answers
from the one plane. ``counters`` reports the crossings of the table's form
(``table_cross``: calls of the program's timer scope), which the configuration
states stay 0, and beside it, for the log, what the online encoder put on the
blocks' overflow lists: the pairs, the room in force and how often it grew
(``obs.metrics.online_overflow_metrics``). No counter says "a block left the
tile path": since PR 39 the program has no other path for an online block to
take, and the harness holds every step's rows and steps.
"""

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

    @functools.partial(jax.jit, static_argnums=1)
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


def counters(app) -> dict:
    """The program's own counts: what this cell states never happens
    (``table_cross``), and what its online encoder did with the pairs past
    the per-tile cap."""
    from wormhole_tpu.obs import metrics
    pairs, room, grown = metrics.online_overflow_metrics(app.obs.registry)
    return {"table_cross": int(app.timer.counts.get("table_cross", 0)),
            "online_overflow_pairs": int(pairs.value),
            "online_overflow_room": int(room.value),
            "online_room_grown": int(grown.value)}
