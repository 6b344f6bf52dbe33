"""``criteo_wide_deep_clicklog`` under test: ``AsyncSGD`` with a
``WideDeepStore`` plugged in, built by the program's own
``models/wide_deep.build_app`` (what ``python -m wormhole_tpu.models.wide_deep
<conf> dim=32 hidden=1024,512,256`` runs), over resident crec2 blocks that
each bring a COO overflow list of a million pairs, and the probes ``correct``
reads from its table and its tower.

The table is 66 float32 planes of 2**24 buckets (``w``, ``v_1..v_32``,
``cg_w``, ``cg_v_1..32``: 4.43 GB), which must never be stacked on the device:
``criteo_wide_deep``'s hook stacks it (one stacked step holding 15.1 of 16.9
GB), and a 1.64M-slot list has no room beside that. So the benchmark's
weights are written plane by plane WHERE THE PLANES LIE (``seed_table``,
through ``criteo_fm_clicklog``'s seeder: v0 is a hash of (bucket, factor,
seed), ``criteo_fm``'s, so a seed's weights are the same in every
multi-channel configuration; each result is made of its own donated plane,
so the table stays where the program's constructor put it), and the
probes index the table
as the stores' own code does (``slots[:, col]``, ``slots[rows, col]`` with a
static column: ``criteo_fm_clicklog``'s, the layout ``[w, v, cg_w, cg_v]`` is
the same). The tower is ``criteo_wide_deep``'s ``reference.init_tower`` on
both sides, a leaf a layer. ``counters`` reports what the configuration
states stays 0: the crossings of the table's form (``table_cross``) and the
train blocks that stepped without a list (``wd_listless_blocks``); and beside
them the blocks that took the spill step and the pairs their lists held.
"""

from __future__ import annotations

import functools

import numpy as np

# a program without wide&deep's step counts cannot run this configuration's
# cell: the import fails here, before any data is made or any table built
from wormhole_tpu.obs.metrics import wd_step_metrics  # noqa: F401

from benchmark.configs.criteo_fm.system import _salt
from benchmark.configs.criteo_fm_clicklog import system as table
from benchmark.configs.criteo_wide_deep.reference import (init_tower, leaf,
                                                          tower_sizes)
from benchmark.configs.criteo_wide_deep.system import _layers
from benchmark.configs.criteo_wide_deep_clicklog.reference import (SINGLES,
                                                                   logit)


def seed_table(store, config: dict, seed: int) -> None:
    """The benchmark's weights into the store where it stands: w = 0, v =
    v0(seed) and zero accumulators onto the donated planes, the seed's tower
    with zero accumulators. No stacked table is made (a store whose table is
    not planes is refused: the configuration states ``table_cross`` stays
    0)."""
    import jax
    import jax.numpy as jnp
    from wormhole_tpu.learners import table as tbl
    if not isinstance(store.slots, tbl.PlaneTable):
        raise RuntimeError("WideDeepStore's table is not planes: this "
                           "configuration states it is never stacked")
    store.slots = table._seeder(
        int(config["num_buckets"]), int(config["dim"]),
        float(config["hyper"]["init_scale"]))(
        store.slots, jnp.uint32(_salt(seed)))
    for l, (w, b) in enumerate(init_tower(tower_sizes(config), seed)):
        store.mlp[f"W{l}"] = jnp.asarray(w, jnp.float32)
        store.mlp[f"b{l}"] = jnp.asarray(b, jnp.float32)
    store.mlp_accum = jax.tree.map(jnp.zeros_like, store.mlp)


def make_app(conf: str, tokens: list, config: dict, seed: int):
    from wormhole_tpu.models import wide_deep
    app = wide_deep.build_app(
        [conf, *tokens, *config["program"]["model_conf"]])
    mcfg = app.store.cfg
    stated = dict(config["hyper"], dim=config["dim"],
                  hidden=tuple(config["hidden"]))
    for key in ("lr_alpha", "lr_alpha_dense", "lr_beta", "l2_v",
                "init_scale", "dim", "hidden"):
        if getattr(mcfg, key) != stated[key]:
            raise ValueError(f"config.json states {key}={stated[key]} but "
                             "the program's WideDeepConfig has "
                             f"{getattr(mcfg, key)}")
    seed_table(app.store, config, seed)
    return app


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


@functools.lru_cache(maxsize=None)
def _cg_w_of(k: int):
    import jax
    return jax.jit(lambda slots, idx: slots[idx, 1 + k])


def state(app, config: dict, seed: int, buckets: np.ndarray) -> dict:
    """``criteo_wide_deep``'s leaves and ``m_list``: the logit of ``cg_w``
    at the buckets whose one pair in the checked blocks is a listed pair of
    the first (``reference.SINGLES``: the data decide which, and the harness
    hands the data to the reference alone). There ``cg_w`` is that pair's
    ``|dual|`` as the list path scattered it, and its logit the margin the
    row was scored at: the one leaf in which a listed pair's precision is
    not under the tower's roughness."""
    out = table.state(app, config, seed, buckets)
    for l, now in enumerate(_layers(app.store.mlp, config)):
        out[f"t{l}"] = now
    singles = SINGLES.get(int(seed))
    if singles is not None:
        out["m_list"] = logit(np.asarray(_cg_w_of(int(config["dim"]))(
            app.store.slots, singles.astype(np.int32)), np.float64))
    return out


def counters(app) -> dict:
    """The program's own counts: what this cell states never happens (a
    crossing of the table's form; a train block stepped without its list),
    and what did: the blocks on the spill step and the pairs their lists
    held."""
    totals = app.timer.totals
    return {"table_cross": int(app.timer.counts.get("table_cross", 0)),
            "wd_listless_blocks": int(totals.get("wd_listless_blocks", 0)),
            "wd_spill_blocks": int(totals.get("wd_spill_blocks", 0)),
            "wd_listed_pairs": int(totals.get("wd_listed_pairs", 0))}
