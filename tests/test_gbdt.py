"""GBDT hist booster: nonlinear learning power, monotone training loss,
checkpoint resume, model dump, sharded-row parity."""

import numpy as np
import pytest

from wormhole_tpu.models.gbdt import GBDT, GBDTConfig, quantile_bins, apply_bins
from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh


def xor_data(rng, n=800, f=6):
    """XOR of two coordinates — linearly inseparable, trivial for depth-2
    trees."""
    x = rng.standard_normal((n, f)).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 1] > 0)).astype(np.float32)
    return x, y


def test_gbdt_learns_xor(rng):
    x, y = xor_data(rng)
    model = GBDT(GBDTConfig(num_round=10, max_depth=3, eta=0.5),
                 MeshRuntime.create())
    model.fit(x, y)
    m = model.evaluate(x, y)
    assert m["accuracy"] > 0.97, m
    assert m["auc"] > 0.99, m
    # train logloss decreases monotonically
    assert all(b <= a + 1e-9 for a, b in zip(model.history,
                                             model.history[1:]))


def test_gbdt_generalizes(rng):
    x, y = xor_data(rng, n=1000)
    xt, yt = xor_data(rng, n=400)
    model = GBDT(GBDTConfig(num_round=15, max_depth=3, eta=0.4),
                 MeshRuntime.create())
    model.fit(x, y)
    m = model.evaluate(xt, yt)
    assert m["accuracy"] > 0.95, m


def test_gbdt_regression(rng):
    x = rng.uniform(-3, 3, size=(600, 1)).astype(np.float32)
    y = np.sin(x[:, 0]).astype(np.float32)
    model = GBDT(GBDTConfig(num_round=30, max_depth=4, eta=0.3,
                            objective="reg:squarederror", base_score=0.5),
                 MeshRuntime.create())
    model.base_margin = 0.0
    model.fit(x, y)
    pred = model.predict_margin(x)
    mse = float(np.mean((pred - y) ** 2))
    assert mse < 0.01, mse


def test_gbdt_checkpoint_resume(rng, tmp_path):
    x, y = xor_data(rng)
    cfg = dict(num_round=8, max_depth=3, eta=0.5)
    full = GBDT(GBDTConfig(**cfg), MeshRuntime.create())
    full.fit(x, y)

    ckdir = str(tmp_path / "ck")
    half = GBDT(GBDTConfig(**cfg, checkpoint_dir=ckdir),
                MeshRuntime.create())
    half.cfg.num_round = 4
    half.fit(x, y)
    resumed = GBDT(GBDTConfig(**cfg, checkpoint_dir=ckdir),
                   MeshRuntime.create())
    resumed.fit(x, y)
    assert len(resumed.trees) == 8
    np.testing.assert_allclose(resumed.predict_margin(x),
                               full.predict_margin(x), atol=1e-5)


def test_gbdt_dump_model(rng, tmp_path):
    x, y = xor_data(rng, n=400)
    model = GBDT(GBDTConfig(num_round=3, max_depth=2),
                 MeshRuntime.create())
    model.fit(x, y)
    path = str(tmp_path / "dump.txt")
    model.dump_model(path)
    text = open(path).read()
    assert text.count("booster[") == 3
    assert "leaf=" in text and ":[f" in text


def test_gbdt_sharded_matches_single(rng):
    import jax
    x, y = xor_data(rng, n=512)
    cfg = dict(num_round=5, max_depth=3, eta=0.5)
    single = GBDT(GBDTConfig(**cfg), MeshRuntime.create())
    single.rt.mesh = make_mesh("data:1", jax.devices()[:1])
    single.fit(x, y)

    multi = GBDT(GBDTConfig(**cfg), MeshRuntime.create("data:8"))
    multi.fit(x, y)
    np.testing.assert_allclose(multi.predict_margin(x),
                               single.predict_margin(x), atol=1e-5)


def test_quantile_bins_roundtrip(rng):
    x = rng.standard_normal((500, 4)).astype(np.float32)
    bins, cuts = quantile_bins(x, 64)
    assert bins.max() < 64
    again = apply_bins(x, cuts)
    np.testing.assert_array_equal(bins, again)
    # binning preserves order within a feature
    f0 = x[:, 0]
    order = np.argsort(f0)
    assert (np.diff(bins[order, 0].astype(int)) >= 0).all()


def test_sparse_path_matches_dense_on_full_data(tmp_path):
    """On data with NO missing values the sparse-entry path must build the
    same trees as the dense path (identical hists, identical gains; the
    default direction is irrelevant when nothing is missing)."""
    import numpy as np
    from wormhole_tpu.models.gbdt import (GBDT, GBDTConfig, SparseBins,
                                          quantile_bins)
    rng = np.random.default_rng(11)
    n, F = 400, 6
    x = rng.standard_normal((n, F)).astype(np.float32)
    y = (x[:, 1] - 0.5 * x[:, 4] > 0).astype(np.float32)
    dense = GBDT(GBDTConfig(num_round=4, max_depth=3))
    dense.fit(x, y)
    # same bins via the same cuts -> identical histograms
    bins, cuts = quantile_bins(x, 256)
    er = np.repeat(np.arange(n), F)
    ef = np.tile(np.arange(F), n)
    eb = bins.reshape(-1).astype(np.int32)
    sp = GBDT(GBDTConfig(num_round=4, max_depth=3))
    sp.fit_sparse(SparseBins(er, ef, eb, y, cuts, np.arange(F)))
    for td, ts in zip(dense.trees, sp.trees):
        np.testing.assert_array_equal(np.asarray(td.feature),
                                      np.asarray(ts.feature))
        np.testing.assert_array_equal(np.asarray(td.split_bin),
                                      np.asarray(ts.split_bin))
        np.testing.assert_allclose(np.asarray(td.weight),
                                   np.asarray(ts.weight), atol=1e-5)


def test_sparse_missing_direction_learns(tmp_path):
    """Presence/absence of a feature carries the label: the sparse path
    must exploit the missing direction to separate the classes (a dense
    0-fill could also split on the 0 value here, but the sparse learner
    must route missing rows correctly at inference too)."""
    import numpy as np
    from wormhole_tpu.models.gbdt import GBDT, GBDTConfig, load_sparse_binned
    rng = np.random.default_rng(12)
    n = 600
    lines = []
    for i in range(n):
        y = int(rng.random() < 0.5)
        feats = [f"{j}:{rng.standard_normal():.4f}"
                 for j in sorted(rng.choice(np.arange(1, 8), 3,
                                            replace=False))]
        if y:
            feats.insert(0, "0:1")      # feature 0 present only for y=1
        lines.append(f"{y} " + " ".join(feats))
    p = tmp_path / "sp.libsvm"
    p.write_text("\n".join(lines) + "\n")
    data = load_sparse_binned(str(p), "libsvm", 64)
    model = GBDT(GBDTConfig(num_round=5, max_depth=3))
    model.fit_sparse(data)
    mets = model.evaluate_sparse(data)
    assert mets["auc"] > 0.95, mets
    assert mets["accuracy"] > 0.9, mets


def test_sparse_loader_never_densifies(tmp_path):
    """A file with a huge feature id trains fine through the sparse path
    (the dense loader would need gigabytes)."""
    import numpy as np
    from wormhole_tpu.models.gbdt import GBDT, GBDTConfig, load_sparse_binned
    rng = np.random.default_rng(13)
    big = (1 << 21)       # 2M-wide feature space
    lines = []
    for i in range(200):
        y = int(rng.random() < 0.5)
        planted = 5 if y else 9
        hi = int(rng.integers(big - 1000, big))
        lines.append(f"{y} {planted}:1 {hi}:1")
    p = tmp_path / "wide.libsvm"
    p.write_text("\n".join(lines) + "\n")
    data = load_sparse_binned(str(p), "libsvm", 16)
    # the 2M-wide id space compacts to the handful of ACTIVE features
    assert data.num_feat <= 1002 + 2
    assert int(data.feat_ids.max()) >= big - 1000
    model = GBDT(GBDTConfig(num_round=3, max_depth=2))
    model.fit_sparse(data)
    assert model.evaluate_sparse(data)["accuracy"] > 0.95
    # dump refers to ORIGINAL feature ids
    model.dump_model(str(tmp_path / "dump.txt"))
    txt = (tmp_path / "dump.txt").read_text()
    assert "[f5<" in txt or "[f9<" in txt, txt[:400]


def _write_libsvm(path, x, y):
    lines = []
    for i in range(len(y)):
        toks = [f"{j}:{x[i, j]:.3f}" for j in range(x.shape[1])
                if x[i, j] != 0.0]
        lines.append(f"{int(y[i])} " + " ".join(toks))
    path.write_text("\n".join(lines) + "\n")


def test_gbdt_external_matches_in_memory(tmp_path):
    """External-memory boosting (streamed BinnedCache chunks) builds
    the same trees as the in-memory fit on identical
    data: the chunked histogram accumulation and streamed routing must
    reproduce the all-rows scans exactly."""
    from wormhole_tpu.models.gbdt import GBDT, GBDTConfig, load_dense
    rng = np.random.default_rng(17)
    n, F = 3000, 8
    # quantize values so the libsvm text round-trip is exact
    x = np.round(rng.standard_normal((n, F)), 3).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 2] > 0)).astype(np.float32)
    path = tmp_path / "train.libsvm"
    _write_libsvm(path, x, y)
    # in-memory reference on the SAME parsed values
    xd, yd = load_dense(str(path), "libsvm")
    ref = GBDT(GBDTConfig(num_round=5, max_depth=3, eta=0.5))
    ref.fit(xd, yd)
    # external: 128-row chunks -> resident binned bytes ~ 1/24 of the
    # matrix; the cache file holds the rest
    ext = GBDT(GBDTConfig(num_round=5, max_depth=3, eta=0.5))
    ext.fit_external(str(path), "libsvm", chunk_rows=128,
                     cache_path=str(tmp_path / "c.cache"))
    from wormhole_tpu.models.gbdt import BinnedCache
    cache = BinnedCache.open(str(tmp_path / "c.cache"))
    assert cache.num_chunks >= 20      # genuinely streamed
    assert cache.total == n
    np.testing.assert_allclose(ref.cuts, ext.cuts, atol=1e-6)
    assert len(ref.trees) == len(ext.trees)
    for td, te in zip(ref.trees, ext.trees):
        np.testing.assert_array_equal(np.asarray(td.feature),
                                      np.asarray(te.feature))
        np.testing.assert_array_equal(np.asarray(td.split_bin),
                                      np.asarray(te.split_bin))
        np.testing.assert_array_equal(np.asarray(td.is_leaf),
                                      np.asarray(te.is_leaf))
        np.testing.assert_allclose(np.asarray(td.weight),
                                   np.asarray(te.weight), atol=1e-4)
    # streamed final metric agrees with an in-memory evaluation
    m = ext.evaluate(xd, yd)
    assert abs(m["logloss"] - ext.history[-1]) < 1e-4
    assert m["accuracy"] > 0.95


def test_gbdt_external_checkpoint_resume(tmp_path):
    """A crashed external-memory run resumes from the checkpointed round
    with replayed margins and finishes with the same trees as an
    uninterrupted run."""
    from wormhole_tpu.models.gbdt import GBDT, GBDTConfig
    rng = np.random.default_rng(19)
    n, F = 1200, 6
    x = np.round(rng.standard_normal((n, F)), 3).astype(np.float32)
    y = (x[:, 1] > 0).astype(np.float32)
    path = tmp_path / "t.libsvm"
    _write_libsvm(path, x, y)
    full = GBDT(GBDTConfig(num_round=6, max_depth=3))
    full.fit_external(str(path), chunk_rows=256,
                      cache_path=str(tmp_path / "f.cache"))
    ck = str(tmp_path / "ck")
    a = GBDT(GBDTConfig(num_round=3, max_depth=3, checkpoint_dir=ck))
    a.fit_external(str(path), chunk_rows=256,
                   cache_path=str(tmp_path / "a.cache"))
    b = GBDT(GBDTConfig(num_round=6, max_depth=3, checkpoint_dir=ck))
    b.fit_external(str(path), chunk_rows=256,
                   cache_path=str(tmp_path / "b.cache"))
    assert len(b.trees) == 6
    for tf, tb in zip(full.trees, b.trees):
        np.testing.assert_array_equal(np.asarray(tf.feature),
                                      np.asarray(tb.feature))
        np.testing.assert_allclose(np.asarray(tf.weight),
                                   np.asarray(tb.weight), atol=1e-4)


# -- ops/histmm kernel modes + pipelined chunk feed (PR 2) -------------------

def _assert_same_trees(a, b, w_atol=1e-4):
    assert len(a.trees) == len(b.trees)
    for ta, tb in zip(a.trees, b.trees):
        np.testing.assert_array_equal(np.asarray(ta.feature),
                                      np.asarray(tb.feature))
        np.testing.assert_array_equal(np.asarray(ta.split_bin),
                                      np.asarray(tb.split_bin))
        np.testing.assert_array_equal(np.asarray(ta.is_leaf),
                                      np.asarray(tb.is_leaf))
        np.testing.assert_allclose(np.asarray(ta.weight),
                                   np.asarray(tb.weight), atol=w_atol)


def test_hist_kernel_modes_build_identical_trees(rng):
    """The MXU one-hot matmul histograms (ops/histmm) and the scatter
    oracle pick the same splits, leaf weights, and per-round logloss —
    whole-model parity across gbdt_hist_kernel modes, dense path."""
    x, y = xor_data(rng)
    models = {}
    for k in ("scatter", "matmul", "auto"):
        m = GBDT(GBDTConfig(num_round=4, max_depth=3, eta=0.5,
                            gbdt_hist_kernel=k))
        m.fit(x, y)
        models[k] = m
    _assert_same_trees(models["scatter"], models["matmul"])
    _assert_same_trees(models["scatter"], models["auto"])
    np.testing.assert_allclose(models["scatter"].history,
                               models["matmul"].history, rtol=1e-5)
    # the hist-kernel counter accumulated into the per-pass progress slot
    assert models["matmul"].progress.gbdt_hist > 0.0


def test_hist_kernel_modes_sparse_identical_trees():
    """Kernel-mode parity on the CSR-entry path (hists + per-node totals
    both go through ops/histmm)."""
    from wormhole_tpu.models.gbdt import SparseBins
    rng = np.random.default_rng(23)
    n, F = 400, 6
    x = rng.standard_normal((n, F)).astype(np.float32)
    y = (x[:, 1] - 0.5 * x[:, 4] > 0).astype(np.float32)
    bins, cuts = quantile_bins(x, 64)
    er = np.repeat(np.arange(n), F)
    ef = np.tile(np.arange(F), n)
    eb = bins.reshape(-1).astype(np.int32)
    models = {}
    for k in ("scatter", "matmul"):
        m = GBDT(GBDTConfig(num_round=4, max_depth=3, num_bins=64,
                            gbdt_hist_kernel=k))
        m.fit_sparse(SparseBins(er, ef, eb, y, cuts, np.arange(F)))
        models[k] = m
    _assert_same_trees(models["scatter"], models["matmul"], w_atol=1e-5)
    np.testing.assert_allclose(models["scatter"].history,
                               models["matmul"].history, rtol=1e-5)


def test_external_kernel_modes_and_pipeline_parity(tmp_path):
    """External-memory training is invariant to BOTH the histogram
    kernel mode and the chunk-feed pipelining (workers=0 serial oracle
    vs threaded DeviceFeed): identical trees and logloss history."""
    from wormhole_tpu.models.gbdt import load_dense
    rng = np.random.default_rng(31)
    n, F = 2000, 8
    x = np.round(rng.standard_normal((n, F)), 3).astype(np.float32)
    y = ((x[:, 0] > 0) ^ (x[:, 2] > 0)).astype(np.float32)
    path = tmp_path / "train.libsvm"
    _write_libsvm(path, x, y)
    variants = {}
    for name, kernel, workers in (("serial_scatter", "scatter", 0),
                                  ("piped_scatter", "scatter", 2),
                                  ("piped_matmul", "matmul", 2)):
        m = GBDT(GBDTConfig(num_round=3, max_depth=3, eta=0.5,
                            gbdt_hist_kernel=kernel,
                            pipeline_workers=workers))
        m.fit_external(str(path), "libsvm", chunk_rows=256,
                       cache_path=str(tmp_path / f"{name}.cache"))
        variants[name] = m
    _assert_same_trees(variants["serial_scatter"],
                       variants["piped_scatter"])
    _assert_same_trees(variants["serial_scatter"],
                       variants["piped_matmul"])
    np.testing.assert_allclose(variants["serial_scatter"].history,
                               variants["piped_matmul"].history,
                               rtol=1e-5)
    # chunk-feed counters drained into the progress slots + timer
    piped = variants["piped_scatter"]
    assert piped.progress.feed_batches > 0
    assert piped.progress.gbdt_hist > 0.0
    assert "gbdt_chunk_feed_stall" in piped.timer.totals
    # in-memory fit on the same data builds the same trees as external
    xd, yd = load_dense(str(path), "libsvm")
    mem = GBDT(GBDTConfig(num_round=3, max_depth=3, eta=0.5,
                          gbdt_hist_kernel="matmul"))
    mem.fit(xd, yd)
    _assert_same_trees(mem, variants["piped_matmul"])


def test_gbdt_rejects_unknown_hist_kernel():
    with pytest.raises(ValueError):
        GBDT(GBDTConfig(gbdt_hist_kernel="mxu"))
