"""``criteo_wide_deep`` under test: ``AsyncSGD`` with a ``WideDeepStore``
plugged in, built by the program's own ``models/wide_deep.build_app`` (what
``python -m wormhole_tpu.models.wide_deep <conf> dim=32 hidden=1024,512,256``
runs), and the probes ``correct`` reads from its table (slots are
[w, v_1..v_k, cg_w, cg_v1..k] a bucket) and from its tower.

The table is laid out as ``criteo_fm``'s ([w, v, cg_w, cg_v] a bucket), so
its seeding and its probes are that configuration's (``criteo_fm/system.py``:
v0 a hash of (bucket, factor, seed) written on the device, the norms and the
sampled rows of ``w`` and ``v``); this file adds the tower, a leaf a layer
(``t0``..): ``reference.init_tower``'s float32 values on both sides.
"""

from __future__ import annotations

import numpy as np

from benchmark.configs.criteo_fm import system as table
from benchmark.configs.criteo_fm.system import _salt, _v0
from benchmark.configs.criteo_wide_deep.reference import (init_tower, leaf,
                                                          tower_sizes)


def make_app(conf: str, tokens: list, config: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.models import wide_deep
    app = wide_deep.build_app(
        [conf, *tokens, *config["program"]["model_conf"]])
    store, h = app.store, config["hyper"]
    mcfg = store.cfg
    stated = dict(h, dim=config["dim"], hidden=tuple(config["hidden"]))
    for key in ("lr_alpha", "lr_alpha_dense", "lr_beta", "l2_v",
                "init_scale", "dim", "hidden"):
        if getattr(mcfg, key) != stated[key]:
            raise ValueError(f"config.json states {key}={stated[key]} but "
                             "the program's WideDeepConfig has "
                             f"{getattr(mcfg, key)}")
    k, nb = mcfg.dim, mcfg.num_buckets

    def seeded(slots, salt):
        return slots.at[:, 1:1 + k].set(
            _v0(nb, k, salt, float(h["init_scale"])))

    # no out_shardings: a table committed to its device here would make
    # every output of the first step committed, and the step compile again
    # for the changed argument shardings (twice: 50 s each on the v5e)
    store.slots = jax.jit(seeded, donate_argnums=(0,))(
        store.slots, jnp.uint32(_salt(seed)))
    for l, (w, b) in enumerate(init_tower(tower_sizes(config), seed)):
        store.mlp[f"W{l}"] = jnp.asarray(w, jnp.float32)
        store.mlp[f"b{l}"] = jnp.asarray(b, jnp.float32)
    return app


def _layers(tree: dict, config: dict) -> list:
    """A tower pytree of the store (``mlp`` or ``mlp_accum``) as one flat
    float64 leaf a layer."""
    return [leaf(np.asarray(tree[f"W{l}"], np.float64),
                 np.asarray(tree[f"b{l}"], np.float64))
            for l in range(len(tower_sizes(config)) - 1)]


def grad_norms(app, config: dict, seed: int) -> dict:
    """After ONE step from zero accumulators AdaGrad's cg is |g|, in the
    table and in the tower alike: a leaf a layer."""
    out = table.grad_norms(app, config, seed)
    for l, acc in enumerate(_layers(app.store.mlp_accum, config)):
        out[f"t{l}"] = float(np.linalg.norm(acc))
    return out


def change_norms(app, config: dict, seed: int) -> dict:
    """``w``, ``v`` and the whole tower as ONE leaf ``t`` (as the
    reference's: after three steps a single layer's norm is rough)."""
    out = table.change_norms(app, config, seed)
    first = init_tower(tower_sizes(config), seed)
    out["t"] = float(np.linalg.norm(np.concatenate(
        [now - leaf(*first[l])
         for l, now in enumerate(_layers(app.store.mlp, config))])))
    return out


def state(app, config: dict, seed: int, buckets: np.ndarray) -> dict:
    out = table.state(app, config, seed, buckets)
    for l, now in enumerate(_layers(app.store.mlp, config)):
        out[f"t{l}"] = now
    return out
