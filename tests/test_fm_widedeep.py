"""FM and Wide&Deep: must capture feature interactions a linear model
cannot, run through the same AsyncSGD driver, and round-trip their
embedding tables."""

import numpy as np
import pytest

from wormhole_tpu.data.feed import next_bucket, pad_to_batch
from wormhole_tpu.data.localizer import Localizer
from wormhole_tpu.learners.handles import FTRLHandle
from wormhole_tpu.learners.store import ShardedStore, StoreConfig
from wormhole_tpu.models.fm import FMConfig, FMStore
from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
from wormhole_tpu.parallel.mesh import MeshRuntime

NB = 2048
N_USERS, N_ITEMS = 40, 40


def interaction_rows(rng, n=3000, latent=4):
    """(user, item) pairs; label from the sign of a low-rank affinity —
    pure interaction signal, zero per-feature main effect."""
    u = rng.standard_normal((N_USERS, latent))
    it = rng.standard_normal((N_ITEMS, latent))
    rows, labels = [], []
    for _ in range(n):
        a, b = rng.integers(N_USERS), rng.integers(N_ITEMS)
        y = 1.0 if u[a] @ it[b] > 0 else 0.0
        rows.append(np.asarray([a, N_USERS + b], np.uint64))
        labels.append(y)
    return rows, np.asarray(labels, np.float32)


def write_libsvm_rows(path, rows, labels):
    with open(path, "w") as f:
        for r, y in zip(rows, labels):
            f.write(f"{int(y)} " + " ".join(f"{int(k)}:1" for k in r) + "\n")


def drive(store, rows, labels, mb=100, passes=6):
    """Feed (rows, labels) through a store's train steps; returns final
    train AUC measured with eval steps."""
    from wormhole_tpu.data.rowblock import RowBlockContainer
    loc = Localizer(num_buckets=NB)
    batches = []
    for lo in range(0, len(rows), mb):
        c = RowBlockContainer()
        for r, y in zip(rows[lo:lo + mb], labels[lo:lo + mb]):
            c.push(float(y), r)
        lz = loc.localize(c.finalize())
        kpad = next_bucket(len(lz.uniq_keys), 64)
        batches.append(pad_to_batch(lz, mb, 8, kpad))
    for _ in range(passes):
        for b in batches:
            store.train_step(b)
    num, den = 0.0, 0
    for b in batches:
        m = store.eval_step(b)
        num += float(np.asarray(m[2]))
        den += 1
    return num / den


def test_fm_beats_linear_on_interactions(rng):
    rows, labels = interaction_rows(rng)
    lin = ShardedStore(StoreConfig(num_buckets=NB, fixed_bytes=0),
                       FTRLHandle())
    lin_auc = drive(lin, rows, labels)
    fm = FMStore(FMConfig(num_buckets=NB, dim=8, lr_alpha=0.2))
    fm_auc = drive(fm, rows, labels)
    # the signal is pure interaction: linear ~coin-flip, FM must crack it
    assert lin_auc < 0.75, lin_auc
    assert fm_auc > 0.9, fm_auc
    assert fm_auc > lin_auc + 0.15


def test_wide_deep_learns_interactions(rng):
    rows, labels = interaction_rows(rng)
    wd = WideDeepStore(WideDeepConfig(num_buckets=NB, dim=16,
                                      hidden=(64, 32), lr_alpha=0.2,
                                      lr_alpha_dense=0.05))
    wd_auc = drive(wd, rows, labels, passes=10)
    assert wd_auc > 0.8, wd_auc


def test_fm_through_async_driver(rng, tmp_path):
    rows, labels = interaction_rows(rng, n=2000)
    path = str(tmp_path / "fm.libsvm")
    write_libsvm_rows(path, rows, labels)
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import Config
    cfg = Config(train_data=path, minibatch=100, max_data_pass=6,
                 max_delay=2, num_buckets=NB, disp_itv=1e9)
    store = FMStore(FMConfig(num_buckets=NB, dim=8, lr_alpha=0.2))
    app = AsyncSGD(cfg, MeshRuntime.create(), store=store)
    prog = app.run()
    assert prog.auc / max(prog.count, 1) > 0.75  # includes early passes


def test_fm_save_load(rng, tmp_path):
    rows, labels = interaction_rows(rng, n=500)
    fm = FMStore(FMConfig(num_buckets=NB, dim=4))
    drive(fm, rows, labels, passes=2)
    fm.save_model(str(tmp_path / "fm"), rank=0)
    fm2 = FMStore(FMConfig(num_buckets=NB, dim=4, seed=99))
    fm2.load_model(str(tmp_path / "fm_0.npz"))
    np.testing.assert_allclose(np.asarray(fm2.slots[:, :5]),
                               np.asarray(fm.slots[:, :5]), atol=1e-6)


def test_wide_deep_save_load(rng, tmp_path):
    rows, labels = interaction_rows(rng, n=500)
    wd = WideDeepStore(WideDeepConfig(num_buckets=NB, dim=4, hidden=(8,)))
    drive(wd, rows, labels, passes=1)
    wd.save_model(str(tmp_path / "wd"), rank=0)
    wd2 = WideDeepStore(WideDeepConfig(num_buckets=NB, dim=4, hidden=(8,),
                                       seed=99))
    wd2.load_model(str(tmp_path / "wd_0.npz"))
    np.testing.assert_allclose(np.asarray(wd2.slots[:, :5]),
                               np.asarray(wd.slots[:, :5]), atol=1e-6)
    for k in wd.mlp:
        np.testing.assert_allclose(np.asarray(wd2.mlp[k]),
                                   np.asarray(wd.mlp[k]), atol=1e-6)


def test_wide_deep_cli_builds_the_app_from_tokens(tmp_path):
    """``build_app`` is what ``main`` runs and what anything that wants the
    same app calls (the benchmark's hook): model keys go to WideDeepConfig,
    ``hidden`` through the one tuple parse, the rest to the driver, with
    ``num_buckets`` mirrored."""
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.models import wide_deep
    conf = tmp_path / "wd.conf"
    conf.write_text("data_format = crec2\nnum_buckets = 32768\n"
                    "max_delay = 2\n")
    app = wide_deep.build_app([str(conf), "dim=4", "hidden=16,8",
                               "lr_alpha_dense=0.002", "cache_device=1"])
    assert isinstance(app, AsyncSGD)
    assert isinstance(app.store, WideDeepStore)
    mcfg = app.store.cfg
    assert (mcfg.dim, mcfg.hidden, mcfg.lr_alpha_dense) == (4, (16, 8), 0.002)
    assert mcfg.num_buckets == app.cfg.num_buckets == 32768
    assert app.cfg.max_delay == 2 and app.cfg.cache_device
    assert app.store.mlp["W0"].shape == (4, 16)
    assert app.store.mlp["W2"].shape == (8, 1)
    # the store's timer is the app's: the tower's counters reach the driver
    assert app.timer is app.store.timer
    with pytest.raises(ValueError):
        wide_deep.build_app([str(conf), "no_such_key=1"])
