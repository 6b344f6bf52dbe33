"""``criteo_fm_clicklog`` under test: ``AsyncSGD`` with an ``FMStore`` plugged
in, as ``models/fm.main`` builds it, over resident crec2 blocks that each
bring a COO overflow list, and the probes ``correct`` reads from its table.

The table is 18 float32 planes of 2**26 buckets (``w``, ``v_1..v_8``, ``cg_w``,
``cg_v_1..8``: 4.83 GB), which must never be stacked on the device. So the
benchmark's weights are written plane by plane (``seed_table``: v0 is a hash of
(bucket, factor, seed), a plane a factor; ``reference.init_factors`` is its
float64 twin), and the probes index the table as the stores' own code does
(``slots[:, col]``, ``slots[rows, col]`` with a static column), which a
``PlaneTable`` answers from the one plane. ``counters`` reports what the
configuration states stays 0: the crossings of the table's form
(``table_cross``) and the blocks that took the in-place step
(``fm_in_place_blocks``: a block of this cell that lost its list would take
it); and beside them the blocks that took the spill step and the pairs their
lists held.
"""

from __future__ import annotations

import functools

import numpy as np

# a program without the FM step's counts cannot run this configuration's
# cell: the import fails here, before any data is made or any table built
from wormhole_tpu.obs.metrics import fm_step_metrics  # noqa: F401

# the hash of (bucket, factor, seed) is criteo_fm's: the same weights a seed
from benchmark.configs.criteo_fm.system import _mix32, _salt


def v0_plane(nb: int, k: int, j: int, salt, scale: float):
    """Factor ``j`` of v0 for buckets 0..nb-1, flat (a plane's bytes)."""
    import jax
    import jax.numpy as jnp
    b = jax.lax.iota(jnp.uint32, nb)
    u = (_mix32((b * jnp.uint32(k) + jnp.uint32(j)) ^ salt) >> 8
         ).astype(jnp.float32) / jnp.float32(2.0 ** 24)
    return jnp.float32(scale * np.sqrt(12.0)) * (u - jnp.float32(0.5))


@functools.lru_cache(maxsize=None)
def _seeder(nb: int, k: int, scale: float):
    import jax
    from wormhole_tpu.learners import table as tbl

    def seeded(table, salt):
        # every plane is written over WHERE IT LIES: each result is made of
        # its own donated plane (times zero: the store's draw is finite), so
        # the compiler aliases it and the table stays where the program's
        # constructor put it. Planes made anew would be a second table
        # beside the first (9.67 GB at the peak), at addresses that the
        # runtime's allocation order decides, and the list's plane gathers
        # read 309 to 388 ms a step by where the planes lie (PERF.md
        # section 6, PR 47): the cell's rate then changed from run to run
        planes = table.planes
        shape = planes[0].shape
        v = [planes[1 + j] * 0 + v0_plane(nb, k, j, salt, scale).reshape(shape)
             for j in range(k)]
        return tbl.PlaneTable([planes[0] * 0, *v]
                              + [p * 0 for p in planes[1 + k:]])

    return jax.jit(seeded, donate_argnums=(0,))


def seed_table(store, config: dict, seed: int) -> None:
    """The benchmark's weights into the store's planes: w = 0, v = v0(seed),
    accumulators 0. No stacked table is made (a store whose table is not
    planes is refused: the configuration states ``table_cross`` stays 0)."""
    import jax.numpy as jnp
    from wormhole_tpu.learners import table as tbl
    if not isinstance(store.slots, tbl.PlaneTable):
        raise RuntimeError("FMStore's table is not planes: this "
                           "configuration states it is never stacked")
    k, nb = int(config["dim"]), int(config["num_buckets"])
    store.slots = _seeder(nb, k, float(config["hyper"]["init_scale"]))(
        store.slots, jnp.uint32(_salt(seed)))


def make_app(conf: str, tokens: list, config: dict, seed: int):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.models.fm import FMConfig, FMStore
    from wormhole_tpu.parallel.mesh import MeshRuntime
    from wormhole_tpu.utils.config import apply_kvs, load_config
    cfg = load_config(conf, tokens)
    h = config["hyper"]
    mcfg = FMConfig(num_buckets=cfg.num_buckets, loss=cfg.loss.value,
                    seed=cfg.seed, tile_step_kernel=cfg.tile_step_kernel,
                    tile_onehot_cache=cfg.tile_onehot_cache)
    apply_kvs(mcfg, list(config["program"].get("model_conf", ())))
    for key in ("lr_alpha", "lr_beta", "l1", "l2", "l2_v", "init_scale"):
        if getattr(mcfg, key) != h[key]:
            raise ValueError(f"config.json hyper.{key}={h[key]} but the "
                             f"program's FMConfig has {getattr(mcfg, key)}")
    rt = MeshRuntime.create(cfg.mesh_shape)
    store = FMStore(mcfg, rt)
    seed_table(store, config, seed)
    return AsyncSGD(cfg, rt, store=store)


@functools.lru_cache(maxsize=None)
def _probes(k: int, nb: int, scale: float):
    import jax
    import jax.numpy as jnp

    def norm_of(cols):
        return jnp.sqrt(sum(jnp.sum(c * c) for c in cols))

    @jax.jit
    def norms(slots, salt):
        dv = [slots[:, 1 + j] - v0_plane(nb, k, j, salt, scale)
              for j in range(k)]
        return (norm_of([slots[:, 0]]), norm_of(dv),
                norm_of([slots[:, 1 + k]]),
                norm_of([slots[:, 2 + k + j] for j in range(k)]))

    @jax.jit
    def rows_of(slots, idx):
        return jnp.stack([slots[idx, c] for c in range(1 + k)], axis=-1)

    return norms, rows_of


def _get(config: dict):
    return _probes(int(config["dim"]), int(config["num_buckets"]),
                   float(config["hyper"]["init_scale"]))


def _norms(app, config, seed):
    import jax.numpy as jnp
    norms, _ = _get(config)
    return [float(x) for x in norms(app.store.slots,
                                    jnp.uint32(_salt(seed)))]


def grad_norms(app, config: dict, seed: int) -> dict:
    """After ONE step from zero accumulators AdaGrad's cg is |g|."""
    _w, _v, cg_w, cg_v = _norms(app, config, seed)
    return {"w": cg_w, "v": cg_v}


def change_norms(app, config: dict, seed: int) -> dict:
    w, dv, _cw, _cv = _norms(app, config, seed)
    return {"w": w, "v": dv}


def state(app, config: dict, seed: int, buckets: np.ndarray) -> dict:
    _, rows_of = _get(config)
    rows = np.asarray(rows_of(app.store.slots, buckets.astype(np.int32)),
                      np.float64)
    return {"w": rows[:, 0], "v": rows[:, 1:]}


def counters(app) -> dict:
    """The program's own counts: what this cell states never happens (a
    crossing of the table's form; a block on the in-place step), and what
    did: the blocks on the spill step and the pairs their lists held."""
    totals = app.timer.totals
    return {"table_cross": int(app.timer.counts.get("table_cross", 0)),
            "fm_in_place_blocks": int(totals.get("fm_in_place_blocks", 0)),
            "fm_spill_blocks": int(totals.get("fm_spill_blocks", 0)),
            "fm_listed_pairs": int(totals.get("fm_listed_pairs", 0))}
