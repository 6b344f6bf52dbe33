"""``criteo_fm`` under test: ``AsyncSGD`` with an ``FMStore`` plugged in, as
``models/fm.main`` builds it, and the probes ``correct`` reads from its table
(FM slots are [w, v_1..v_k, cg_w, cg_v1..k] a bucket).

The benchmark makes the weights: v0 is a hash of (bucket, factor, seed),
written on the device in one jitted call over the table the program's
constructor made (``reference.init_factors`` is its float64 twin).
"""

from __future__ import annotations

import functools

import numpy as np


def _salt(seed: int) -> int:
    from benchmark.generators.fields import mix32
    return int(mix32(np.array([(int(seed) & 0xFFFFFFFF) ^ 0x6A09E667],
                              np.uint32))[0])


def _mix32(x):
    import jax.numpy as jnp
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _v0(nb: int, k: int, salt, scale: float):
    import jax
    import jax.numpy as jnp
    b = jax.lax.broadcasted_iota(jnp.uint32, (nb, k), 0)
    j = jax.lax.broadcasted_iota(jnp.uint32, (nb, k), 1)
    u = (_mix32((b * jnp.uint32(k) + j) ^ salt) >> 8).astype(jnp.float32) \
        / jnp.float32(2.0 ** 24)
    return jnp.float32(scale * np.sqrt(12.0)) * (u - jnp.float32(0.5))


def make_app(conf: str, tokens: list, config: dict, seed: int):
    import jax
    import jax.numpy as jnp
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
    k, nb = mcfg.dim, mcfg.num_buckets
    sharding = store.slots.sharding

    def seeded(slots, salt):
        return slots.at[:, 1:1 + k].set(
            _v0(nb, k, salt, float(h["init_scale"])))

    store.slots = jax.jit(seeded, donate_argnums=(0,),
                          out_shardings=sharding)(
        store.slots, jnp.uint32(_salt(seed)))
    return AsyncSGD(cfg, rt, store=store)


@functools.lru_cache(maxsize=None)
def _probes(k: int, nb: int, scale: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(slots, salt):
        s = slots.astype(jnp.float32)
        dv = s[:, 1:1 + k] - _v0(nb, k, salt, scale)
        return (jnp.sqrt(jnp.sum(s[:, 0] ** 2)),
                jnp.sqrt(jnp.sum(dv * dv)),
                jnp.sqrt(jnp.sum(s[:, 1 + k] ** 2)),
                jnp.sqrt(jnp.sum(s[:, 2 + k:] ** 2)))

    @jax.jit
    def rows_of(slots, idx):
        return slots[idx, :1 + k].astype(jnp.float32)

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
