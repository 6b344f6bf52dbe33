"""Launcher multi-process mode: real jax.distributed over localhost (the
DCN code path the reference exercises with dmlc_local.py multi-process
runs, SURVEY.md §4.3)."""

import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_num_ex(out: str):
    """Line-anchored per-rank ``num_ex`` parse (the launcher merges rank
    output line-atomically and prefixes each line with its ``[w<rank>]``
    tag; anchoring makes the parse robust even if a rank's line is
    preceded by other output)."""
    vals = [int(m) for m in
            re.findall(r"^(?:\[w\d+\] )?OK rank \d+ num_ex=(\d+)",
                       out, re.M)]
    assert vals, f"no 'OK rank N num_ex=' line in:\n{out}"
    return vals


# A jax CPU backend without multiprocess collectives rejects the
# launch almost immediately with this message; bodies that never touch
# jax.distributed (trace merges, supervised drills with plain
# children) still run fine, so the skip is decided per launch from the
# observed error — never cached across tests.
_MP_ERR = "Multiprocess computations aren't"


def _skip_if_mp_unsupported(r) -> None:
    """Skip (not fail) when the backend rejects mp collectives — the
    same guard test_ft_chaos_e2e.py applies to its supervised drills."""
    if r.returncode != 0 and _MP_ERR in r.stdout + r.stderr:
        pytest.skip("jax CPU backend lacks multiprocess collectives "
                    "in this environment")


def run_mp(n: int, body: str, timeout=240, launcher_args=(),
           raw=False):
    """Run ``body`` under the mp launcher. ``raw=True`` returns the
    CompletedProcess (for tests asserting on stderr/returncode).
    Either way an environment whose backend cannot run multiprocess
    collectives skips the caller instead of failing it."""
    script = os.path.join(REPO, ".pytest_cache", f"mp_body_{os.getpid()}.py")
    os.makedirs(os.path.dirname(script), exist_ok=True)
    with open(script, "w") as f:
        f.write(textwrap.dedent(body))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS",)}  # children get their own device count
    r = subprocess.run(
        [sys.executable, "-m", "wormhole_tpu.parallel.launcher",
         "-n", str(n), "--cluster", "mp", *launcher_args, "--",
         sys.executable, script],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)
    _skip_if_mp_unsupported(r)
    if raw:
        return r
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_mp_collectives():
    out = run_mp(2, """
        from wormhole_tpu.parallel.mesh import MeshRuntime
        import numpy as np
        rt = MeshRuntime.create()
        assert rt.world == 2, rt.world
        from wormhole_tpu.parallel.collectives import (allreduce_tree,
                                                       broadcast_tree)
        total = allreduce_tree(np.asarray(float(rt.rank + 1)),
                               rt.mesh, "sum")
        assert float(total) == 3.0, total
        mx = allreduce_tree(np.asarray(float(rt.rank)), rt.mesh, "max")
        assert float(mx) == 1.0, mx
        root = broadcast_tree(
            np.asarray(42.0 if rt.rank == 0 else -1.0), rt.mesh)
        assert float(root) == 42.0, root
        # COMPRESSING filter analogue: zlib'd payloads reduce identically
        big = np.full(4096, float(rt.rank + 1), np.float64)
        z = allreduce_tree(big, rt.mesh, "sum", compress=True)
        assert np.allclose(np.asarray(z), 3.0), z
        print(f"OK rank {rt.rank}")
    """)
    assert out.count("OK rank") == 2


def _learnable_libsvm(tmp_path, rng, n_files=2, rows=400, dim=64):
    """Files where one planted feature decides the label."""
    paths = []
    for k in range(n_files):
        lines = []
        for _ in range(rows):
            y = rng.random() < 0.5
            feats = sorted(rng.choice(np.arange(2, dim), size=6,
                                      replace=False))
            planted = 0 if y else 1
            toks = [f"{planted}:1"] + [f"{j}:1" for j in feats]
            lines.append(f"{int(y)} " + " ".join(toks))
        p = tmp_path / f"part{k}.libsvm"
        p.write_text("\n".join(lines) + "\n")
        paths.append(str(p))
    return str(tmp_path / "part*.libsvm")


CFG_COMMON = ("data_format=libsvm num_buckets=4096 minibatch=100 "
              "max_nnz=16 key_pad=256 lr_eta=0.5 max_delay=1 "
              "disp_itv=1e12")


def test_mp_async_ftrl_converges(tmp_path):
    """2-process synchronized FTRL via the replicated dynamic pool: both
    hosts converge to the same global metrics, and quality statistically
    matches a single-process run on the same data (the reference's
    single-process-oracle strategy, test/ftrl.cc)."""
    rng = np.random.default_rng(3)
    pattern = _learnable_libsvm(tmp_path, rng)
    out = run_mp(2, f"""
        import numpy as np
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, {CFG_COMMON.split()!r} + [
            "train_data={pattern}", "max_data_pass=4",
            "model_out={tmp_path}/mp_model"])
        app = AsyncSGD(cfg)
        prog = app.run()
        pooled = []
        vp = app._multihost_pass(cfg.train_data, "val", pooled)
        pa = app._allreduce_pooled_auc(pooled)
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}} "
              f"auc={{pa:.4f}} vacc={{vp.acc / max(vp.count, 1):.4f}}")
    """, timeout=420)
    assert out.count("OK rank") == 2
    rows = [ln for ln in out.splitlines() if "num_ex=" in ln]
    # both hosts computed the same GLOBAL progress and eval metrics
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    num_ex = int(rows[0].split("num_ex=")[1].split()[0])
    assert num_ex == 4 * 800          # every row of every file, each pass
    auc_mp = float(rows[0].split("auc=")[1].split()[0])
    # single-process oracle on the same data (test/ftrl.cc strategy)
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import load_config
    cfg = load_config(None, CFG_COMMON.split() + [
        f"train_data={pattern}", "max_data_pass=4"])
    solo = AsyncSGD(cfg)
    solo.run()
    _, solo_auc = solo._run_eval(pattern)
    assert auc_mp > 0.9, out
    assert abs(auc_mp - solo_auc) < 0.05, (auc_mp, solo_auc)
    # per-host model shards were written
    assert (tmp_path / "mp_model_0").exists()
    assert (tmp_path / "mp_model_1").exists()


def test_mp_async_restart_resumes(tmp_path):
    """Checkpoint every pass; a restarted job resumes from the saved
    version instead of pass 0 (rabit LoadCheckPoint semantics for the
    flagship learner)."""
    rng = np.random.default_rng(4)
    pattern = _learnable_libsvm(tmp_path, rng, n_files=1, rows=200)
    body = f"""
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, {CFG_COMMON.split()!r} + [
            "train_data={pattern}", "max_data_pass=MAXPASS",
            "checkpoint_dir={tmp_path}/ckpt"])
        app = AsyncSGD(cfg)
        prog = app.run()
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}}")
    """
    out1 = run_mp(2, body.replace("MAXPASS", "2"), timeout=420)
    assert out1.count("OK rank") == 2
    # "restart": same job continues to 4 passes — must resume at pass 2,
    # training only 2 more passes (num_ex counts post-resume rows)
    out2 = run_mp(2, body.replace("MAXPASS", "4"), timeout=420)
    assert out2.count("OK rank") == 2
    num_ex = parse_num_ex(out2)[0]
    # only passes 2 and 3 ran — the job resumed from the v2 checkpoint
    assert num_ex == 2 * 200, out2


def test_mp_crec2_tile_training_converges(tmp_path):
    """2-process crec2: per-host block shards feed the mesh tile step
    (model table replicated over data:2 across hosts); the planted
    feature is learned and both hosts report identical global metrics."""
    rng = np.random.default_rng(5)
    n, nnz = 4096, 8
    import wormhole_tpu  # noqa: F401  (path check)
    from wormhole_tpu.data.crec import CRec2Writer
    from wormhole_tpu.ops import tilemm
    nb = 2 * tilemm.TILE
    keys = rng.integers(1, 1 << 31, size=(n, nnz), dtype=np.uint32)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    path = tmp_path / "mp.crec2"
    with CRec2Writer(str(path), nnz=nnz, nb=nb, subblocks=1) as w:
        w.append(keys, labels)
    out = run_mp(2, f"""
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, [
            "train_data={path}", "data_format=crec2", "num_buckets={nb}",
            "lr_eta=0.5", "max_data_pass=6", "disp_itv=1e12",
            "num_parts_per_file=2"])
        app = AsyncSGD(cfg)
        prog = app.run()
        acc = prog.acc / max(prog.count, 1)
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}} "
              f"acc={{acc:.4f}}")
    """, timeout=420)
    assert out.count("OK rank") == 2
    rows = [ln for ln in out.splitlines() if "num_ex=" in ln]
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    acc = float(rows[0].split("acc=")[1].split()[0])
    assert acc > 0.85, out


def test_mp_gbdt_matches_single_process(tmp_path):
    """dsplit=row GBDT: 2 processes each hold half the rows, histograms
    allreduce per level — the trees must be IDENTICAL to a single-process
    run over all rows (same global cuts, same global hists, same
    deterministic split selection)."""
    out = run_mp(2, f"""
        import numpy as np
        from wormhole_tpu.models.gbdt import GBDT, GBDTConfig
        from wormhole_tpu.parallel.mesh import MeshRuntime
        rt = MeshRuntime.create()
        rng = np.random.default_rng(7)         # same stream on both ranks
        x = rng.standard_normal((600, 8)).astype(np.float32)
        y = ((x[:, 0] + 0.5 * x[:, 3] > 0)).astype(np.float32)
        half = x.shape[0] // 2
        sl = slice(0, half) if rt.rank == 0 else slice(half, None)
        model = GBDT(GBDTConfig(num_round=5, max_depth=3), rt)
        model.fit(x[sl], y[sl])
        feats = np.concatenate([np.asarray(t.feature) for t in model.trees])
        sbs = np.concatenate([np.asarray(t.split_bin) for t in model.trees])
        mets = model.evaluate(x[sl], y[sl])
        print(f"OK rank {{rt.rank}} trees="
              f"{{feats.tolist()}}|{{sbs.tolist()}} "
              f"auc={{mets['auc']:.6f}} ll={{model.history[-1]:.8f}}")
    """, timeout=420)
    assert out.count("OK rank") == 2
    rows = [ln for ln in out.splitlines() if "trees=" in ln]
    # both ranks built the same trees and merged metrics
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    # single-process oracle over ALL rows builds the same trees
    from wormhole_tpu.models.gbdt import GBDT, GBDTConfig
    rng = np.random.default_rng(7)
    x = rng.standard_normal((600, 8)).astype(np.float32)
    y = ((x[:, 0] + 0.5 * x[:, 3] > 0)).astype(np.float32)
    solo = GBDT(GBDTConfig(num_round=5, max_depth=3))
    solo.fit(x, y)
    feats = np.concatenate([np.asarray(t.feature) for t in solo.trees])
    sbs = np.concatenate([np.asarray(t.split_bin) for t in solo.trees])
    got_f, got_s = rows[0].split("trees=")[1].split(" auc=")[0].split("|")
    same = (np.array_equal(np.asarray(eval(got_f)), feats)
            and np.array_equal(np.asarray(eval(got_s)), sbs))
    auc_mp = float(rows[0].split("auc=")[1].split()[0])
    if not same:
        # f32 histogram partial-sum ORDER differs between the 8-shard solo
        # scatter and the 2-host allreduce, so a near-tie in gain may
        # legitimately flip a split; then the models must still agree
        # statistically (nodes mostly equal, same quality)
        frac = np.mean(np.asarray(eval(got_f)) == feats)
        assert frac > 0.9, (frac, out)
        assert abs(auc_mp - solo.evaluate(x, y)["auc"]) < 0.01, out
    assert auc_mp > 0.9, out


def test_mp_gbdt_sparse_matches_single_process(tmp_path):
    """dsplit=row SPARSE GBDT: each process
    loads its CSR shard of a wide libsvm file, feature ids and quantile
    cuts are agreed globally (_global_sparse_sketch), and the per-level
    histogram allreduce makes both ranks build the same trees as a
    single-process fit over all rows — without any (rows, F)
    densification (reference: distributed xgboost on sparse libsvm,
    learn/xgboost/README.md:35-44)."""
    rng = np.random.default_rng(13)
    n, dim = 600, 500
    lines = []
    for _ in range(n):
        y = rng.random() < 0.5
        feats = np.sort(rng.choice(np.arange(2, dim), size=12,
                                   replace=False))
        vals = np.round(rng.standard_normal(12), 3)
        planted = 0 if y else 1
        toks = [f"{planted}:1"] + [f"{j}:{v}" for j, v in zip(feats, vals)]
        lines.append(f"{int(y)} " + " ".join(toks))
    p = tmp_path / "wide.libsvm"
    p.write_text("\n".join(lines) + "\n")
    out = run_mp(2, f"""
        import numpy as np
        from wormhole_tpu.models.gbdt import (GBDT, GBDTConfig,
                                              load_sparse_binned)
        from wormhole_tpu.parallel.mesh import MeshRuntime
        rt = MeshRuntime.create()
        part, nparts = rt.local_part()
        data = load_sparse_binned({str(p)!r}, "libsvm", 16,
                                  part, nparts, runtime=rt)
        model = GBDT(GBDTConfig(num_round=4, max_depth=3, num_bins=16),
                     rt)
        model.fit_sparse(data)
        feats = np.concatenate([np.asarray(t.feature)
                                for t in model.trees])
        sbs = np.concatenate([np.asarray(t.split_bin)
                              for t in model.trees])
        mets = model.evaluate_sparse(data)
        print(f"OK rank {{rt.rank}} trees="
              f"{{feats.tolist()}}|{{sbs.tolist()}} "
              f"auc={{mets['auc']:.6f}}")
    """, timeout=420)
    assert out.count("OK rank") == 2
    rows = [ln for ln in out.splitlines() if "trees=" in ln]
    # both ranks agreed on cuts, hists, and therefore trees
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    # single-process oracle over ALL rows
    from wormhole_tpu.models.gbdt import GBDT, GBDTConfig, \
        load_sparse_binned
    data = load_sparse_binned(str(p), "libsvm", 16)
    solo = GBDT(GBDTConfig(num_round=4, max_depth=3, num_bins=16))
    solo.fit_sparse(data)
    feats = np.concatenate([np.asarray(t.feature) for t in solo.trees])
    sbs = np.concatenate([np.asarray(t.split_bin) for t in solo.trees])
    got_f, got_s = rows[0].split("trees=")[1].split(" auc=")[0].split("|")
    same = (np.array_equal(np.asarray(eval(got_f)), feats)
            and np.array_equal(np.asarray(eval(got_s)), sbs))
    auc_mp = float(rows[0].split("auc=")[1].split()[0])
    if not same:
        # f32 histogram partial-sum order differs between the sharded
        # solo scatter and the 2-host allreduce; near-tie gains may flip
        frac = np.mean(np.asarray(eval(got_f)) == feats)
        assert frac > 0.9, (frac, out)
    assert auc_mp > 0.9, out


def test_mp_kmeans_two_hosts(tmp_path):
    """Each process reads its shard (rank/world), stats allreduce across
    processes — the reference's multi-node-without-a-cluster test."""
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((3, 12))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    lines = []
    for i in range(240):
        x = centers[i % 3] + 0.05 * rng.standard_normal(12)
        feats = " ".join(f"{j}:{x[j]:.5g}" for j in range(12))
        lines.append(f"0 {feats}")
    data = tmp_path / "km.libsvm"
    data.write_text("\n".join(lines) + "\n")

    out = run_mp(2, f"""
        from wormhole_tpu.models.kmeans import KMeans, KMeansConfig
        from wormhole_tpu.parallel.mesh import MeshRuntime
        rt = MeshRuntime.create()
        km = KMeans(KMeansConfig(num_clusters=3, max_iter=6,
                                 minibatch_size=64), rt)
        batches = km.load_batches({str(data)!r})
        km.fit(batches)
        assert km.history[-1] < 0.05, km.history
        print(f"OK rank {{rt.rank}} objv={{km.history[-1]:.4f}}")
    """)
    assert out.count("OK rank") == 2
    # both processes converged to the same global objective
    objvs = {ln.split("objv=")[1] for ln in out.splitlines()
             if "objv=" in ln}
    assert len(objvs) == 1, out


def test_mp_restarts_resume_after_crash(tmp_path):
    """Fault injection (the reference's tracker-relaunch + rabit restart
    story): rank 1 kills itself mid-training on the first attempt; the
    launcher's --restarts relaunches the whole job, which resumes from
    the last committed checkpoint version instead of pass 0."""
    rng = np.random.default_rng(6)
    pattern = _learnable_libsvm(tmp_path, rng, n_files=1, rows=200)
    marker = tmp_path / "crashed_once"
    body = f"""
        import os, sys
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, {CFG_COMMON.split()!r} + [
            "train_data={pattern}", "max_data_pass=4",
            "checkpoint_dir={tmp_path}/ckpt"])
        app = AsyncSGD(cfg)
        if not os.path.exists("{marker}") and app.rt.rank == 1:
            # crash AFTER pass-2 checkpoints exist: run 2 passes, die
            cfg2 = cfg.merged(["max_data_pass=2"])
            app2 = AsyncSGD(cfg2, app.rt, store=app.store)
            app2.run()
            open("{marker}", "w").close()
            os._exit(17)
        prog = app.run()
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}}")
    """
    # generous timeout: under the full suite this test shares the host
    # with other mp tests and has flaked on load (round-3 advisor note)
    r = run_mp(2, body, timeout=900, launcher_args=("--restarts", "2"),
               raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "restart 1/2" in r.stderr, r.stderr
    assert marker.exists()
    assert "num_ex=" in r.stdout, (
        "worker never printed its final Progress line:\n"
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
    # the retry resumed at pass 2: ranks trained only passes 2-3
    num_ex = parse_num_ex(r.stdout)[0]
    assert num_ex == 2 * 200, r.stdout


def test_mp_crec_v1_dense_training_converges(tmp_path):
    """2-process crec v1: per-host block shards feed the mesh dense-apply
    step (data:2 across hosts, on-device key fold + range-sharded
    scatter); the planted feature is learned and both hosts report
    identical global metrics (crec v1's multi-process path)."""
    rng = np.random.default_rng(11)
    n, nnz = 4096, 8
    from wormhole_tpu.data.crec import CRecWriter
    nb = 1 << 16
    keys = rng.integers(1, 1 << 31, size=(n, nnz), dtype=np.uint32)
    sel = rng.random(n) < 0.5
    keys[sel, 0] = np.uint32(123456)
    keys[~sel, 0] = np.uint32(654321)
    labels = sel.astype(np.uint8)
    path = tmp_path / "mp.crec"
    with CRecWriter(str(path), nnz=nnz, block_rows=1024) as w:
        w.append(keys, labels)
    out = run_mp(2, f"""
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, [
            "train_data={path}", "data_format=crec", "num_buckets={nb}",
            "lr_eta=0.5", "max_data_pass=6", "disp_itv=1e12",
            "num_parts_per_file=2"])
        app = AsyncSGD(cfg)
        prog = app.run()
        acc = prog.acc / max(prog.count, 1)
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}} "
              f"acc={{acc:.4f}}")
    """, timeout=420)
    assert out.count("OK rank") == 2
    rows = [ln for ln in out.splitlines() if "num_ex=" in ln]
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    acc = float(rows[0].split("acc=")[1].split()[0])
    assert acc > 0.85, out


def test_mp_straggler_reexecution_crec(tmp_path):
    """Deterministic straggler re-execution: one
    host's part is 8x the other's (uneven parts — the scenario the
    replicated pool exists for). After the fast host drains, the big
    part crosses the 3x-mean-ROUNDS threshold, is re-issued to the idle
    host WITH a skip count, and the original abandons — every block
    processed exactly once, proven by exact global row accounting."""
    rng = np.random.default_rng(23)
    from wormhole_tpu.data.crec import CRecWriter
    nnz, br = 8, 512
    sizes = {"aa_big": 24 * br, "bb_small": 3 * br}
    for name, n in sizes.items():
        keys = rng.integers(1, 1 << 31, size=(n, nnz), dtype=np.uint32)
        labels = (rng.random(n) < 0.5).astype(np.uint8)
        with CRecWriter(str(tmp_path / f"{name}.crec"), nnz=nnz,
                        block_rows=br) as w:
            w.append(keys, labels)
    total = sum(sizes.values())
    r = run_mp(2, f"""
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, [
            "train_data={tmp_path}/*.crec", "data_format=crec",
            "num_buckets=65536", "lr_eta=0.1", "max_data_pass=1",
            "disp_itv=1e12"])
        app = AsyncSGD(cfg)
        prog = app.run()
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}}")
    """, timeout=420, raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert out.count("OK rank") == 2
    # the mechanism actually fired...
    assert "straggler: re-queue" in r.stderr, r.stderr
    assert "abandoning at block" in r.stderr, r.stderr
    # ...and accounting stayed exact: every row of every file once
    rows = [ln for ln in out.splitlines() if "num_ex=" in ln]
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    num_ex = int(rows[0].split("num_ex=")[1].split()[0])
    assert num_ex == total, out


def test_mp_straggler_crash_during_reissue(tmp_path):
    """Straggler x failure interaction: the host
    that CLAIMS a re-issued straggler part kills itself at the moment of
    the takeover claim. The launcher's --restarts relaunches the whole
    world, the rebuilt pool re-runs the pass (no checkpoint configured:
    recovery = full-pass re-execution), the straggler re-issue fires
    again, and the job completes with exact global row accounting.
    Reference: failure handler and straggler killer coexisting on live
    pool state, workload_pool.h:111,125-140,169-190."""
    rng = np.random.default_rng(31)
    from wormhole_tpu.data.crec import CRecWriter
    nnz, br = 8, 512
    sizes = {"aa_big": 24 * br, "bb_small": 3 * br}
    for name, n in sizes.items():
        keys = rng.integers(1, 1 << 31, size=(n, nnz), dtype=np.uint32)
        labels = (rng.random(n) < 0.5).astype(np.uint8)
        with CRecWriter(str(tmp_path / f"{name}.crec"), nnz=nnz,
                        block_rows=br) as w:
            w.append(keys, labels)
    total = sum(sizes.values())
    marker = tmp_path / "crashed_once"
    r = run_mp(2, f"""
        import os
        from wormhole_tpu.sched.workload_pool import ReplicatedRounds
        _claimed = ReplicatedRounds.claimed
        def claimed(self, r, wl):
            skip = _claimed(self, r, wl)
            # first straggler takeover: the NEW holder dies mid-claim
            if (r == self.rank and skip > 0
                    and not os.path.exists({str(marker)!r})):
                open({str(marker)!r}, "w").close()
                os._exit(17)
            return skip
        ReplicatedRounds.claimed = claimed
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, [
            "train_data={tmp_path}/*.crec", "data_format=crec",
            "num_buckets=65536", "lr_eta=0.1", "max_data_pass=1",
            "disp_itv=1e12"])
        app = AsyncSGD(cfg)
        prog = app.run()
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}}")
    """, timeout=600, launcher_args=("--restarts", "2"), raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert marker.exists(), "crash never fired: re-issue claim not seen"
    assert "straggler: re-queue" in r.stderr, r.stderr
    assert "restart 1/2" in r.stderr, r.stderr
    out = r.stdout
    assert out.count("OK rank") == 2
    rows = [ln for ln in out.splitlines() if "num_ex=" in ln]
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    # the post-restart pass processed every row of every file exactly once
    assert parse_num_ex(out)[0] == total, out


def test_mp_straggler_reexecution_sparse(tmp_path):
    """Same straggler handoff through the sparse/text multihost pass:
    minibatch-granular skip, exact row accounting."""
    rng = np.random.default_rng(29)
    for name, rows in (("aa_big", 2400), ("bb_small", 300)):
        lines = []
        for _ in range(rows):
            y = rng.random() < 0.5
            feats = sorted(rng.choice(np.arange(2, 64), size=6,
                                      replace=False))
            toks = [f"{0 if y else 1}:1"] + [f"{j}:1" for j in feats]
            lines.append(f"{int(y)} " + " ".join(toks))
        (tmp_path / f"{name}.libsvm").write_text("\n".join(lines) + "\n")
    r = run_mp(2, f"""
        from wormhole_tpu.learners.async_sgd import AsyncSGD
        from wormhole_tpu.utils.config import load_config
        cfg = load_config(None, {CFG_COMMON.split()!r} + [
            "train_data={tmp_path}/*.libsvm", "max_data_pass=1"])
        app = AsyncSGD(cfg)
        prog = app.run()
        print(f"OK rank {{app.rt.rank}} num_ex={{prog.num_ex}}")
    """, timeout=420, raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    out = r.stdout
    assert out.count("OK rank") == 2
    assert "straggler: re-queue" in r.stderr, r.stderr
    assert "abandoning at block" in r.stderr, r.stderr
    rows = [ln for ln in out.splitlines() if "num_ex=" in ln]
    assert len({ln.split("rank ")[1][2:] for ln in rows}) == 1, out
    num_ex = int(rows[0].split("num_ex=")[1].split()[0])
    assert num_ex == 2700, out


def test_mp_trace_merge_and_skew_report(tmp_path):
    """--trace-dir end to end (PR-6): both ranks trace into the exported
    directory via the obs.setup env fallback, rank 1 arrives late at
    every sited collective, and the launcher's exit-time merge produces
    one merged Perfetto trace plus a skew report naming rank 1 with its
    per-collective lateness."""
    import json
    trace_dir = tmp_path / "traces"
    hb_dir = tmp_path / "hb"
    r = run_mp(2, """
        import time
        import numpy as np
        from wormhole_tpu.parallel.mesh import MeshRuntime
        from wormhole_tpu import obs
        from wormhole_tpu.parallel.collectives import allreduce_tree
        from wormhole_tpu.utils.config import Config
        rt = MeshRuntime.create()
        hub = obs.setup(Config(), rank=rt.rank)
        # both launcher env fallbacks picked up: heartbeat + trace dirs
        assert hub.active and hub.export_dir, "env fallbacks missing"
        from wormhole_tpu.obs import trace as _t
        assert _t.enabled(), "trace env fallback missing"
        hub.heartbeat_tick(step=0, num_ex=0)
        for i in range(4):
            if rt.rank == 1:
                time.sleep(0.1)        # the planted straggler
            total = allreduce_tree(np.asarray(float(rt.rank + 1)),
                                   rt.mesh, "sum", site="test/step")
            assert float(total) == 3.0, total
        hub.finalize(step=4, num_ex=400, wall_s=1.0)
        print(f"OK rank {rt.rank}")
    """, launcher_args=("--heartbeat-dir", str(hb_dir),
                        "--trace-dir", str(trace_dir)), raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK rank") == 2

    # per-rank trace files + the merged artifacts exist
    assert (trace_dir / "trace.json").exists()
    assert (trace_dir / "trace.r1.json").exists()
    assert (trace_dir / "merged.trace.json").exists()
    assert (trace_dir / "skew_report.json").exists()

    report = json.load(open(trace_dir / "skew_report.json"))
    assert report["ranks"] == [0, 1]
    assert report["clock_source"] == "heartbeat"
    assert report["collectives_matched"] >= 3
    # the delayed rank is named, last in (nearly) every collective,
    # ~100 ms late each time
    w = report["worst"]
    assert w["rank"] == 1, report
    assert w["last_in"] >= report["collectives_matched"] - 1, report
    assert w["lateness_ms"] > 50 * w["last_in"], report
    assert report["sites"]["test/step"]["max_skew_ms"] > 50, report

    # the merged doc carries both ranks' events on one timeline
    merged = json.load(open(trace_dir / "merged.trace.json"))
    assert merged["metadata"]["merged"] is True
    pids = {e.get("pid") for e in merged["traceEvents"]
            if e.get("ph") == "X"}
    assert {0, 1} <= pids, pids

    # and the launcher printed the attribution lines
    assert "merged trace:" in r.stderr, r.stderr
    assert "collective skew: w1" in r.stderr, r.stderr


def test_mp_trace_merge_without_jax_distributed(tmp_path):
    """The exit-time merge, backend-independent: workers skip
    jax.distributed (no CPU multiprocess collectives needed) and record
    sited collective spans on the single-process fast path — the span
    boundary and (site, seq) stamping are identical. Rank 1 sleeps
    before every collective, so the launcher-side merge must name it
    with growing per-collective lateness."""
    import json
    trace_dir = tmp_path / "traces"
    hb_dir = tmp_path / "hb"
    r = run_mp(2, """
        import os, time
        import numpy as np
        from wormhole_tpu import obs
        from wormhole_tpu.obs import trace
        from wormhole_tpu.obs.metrics import Registry
        from wormhole_tpu.parallel.collectives import allreduce_tree
        from wormhole_tpu.utils.config import Config
        rank = int(os.environ["PROCESS_ID"])
        hub = obs.setup(Config(), rank=rank, registry=Registry())
        assert hub.active and trace.enabled(), "env fallbacks missing"
        hub.heartbeat_tick(step=0, num_ex=0)
        # the two start the loop together: the ranks exchange nothing
        # here, so without this the spawn skew between the children (a
        # rank 0 that came up 200 ms late on a loaded host) comes off
        # rank 1's lateness at every collective
        open(os.path.join(READY_DIR, f"ready{rank}"), "w").close()
        while not os.path.exists(os.path.join(READY_DIR,
                                              f"ready{1 - rank}")):
            time.sleep(0.002)
        for i in range(4):
            if rank == 1:
                time.sleep(0.1)            # the planted straggler
            allreduce_tree(np.asarray(1.0), None, "sum",
                           site="test/step")
        hub.finalize(step=4, num_ex=400, wall_s=1.0)
        print(f"OK rank {rank}")
    """.replace("READY_DIR", repr(str(tmp_path))),
               launcher_args=("--heartbeat-dir", str(hb_dir),
                        "--trace-dir", str(trace_dir)), raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK rank") == 2

    assert (trace_dir / "trace.json").exists()
    assert (trace_dir / "trace.r1.json").exists()
    assert (trace_dir / "merged.trace.json").exists()
    report = json.load(open(trace_dir / "skew_report.json"))
    assert report["ranks"] == [0, 1]
    assert report["clock_source"] == "heartbeat"
    assert report["collectives_matched"] == 4
    w = report["worst"]
    assert w["rank"] == 1, report
    # cumulative sleeps: rank 1 trails by ~100*k ms at the k-th
    # collective (1,000 ms in all); the children leave a barrier together
    assert w["lateness_ms"] > 300, report
    # JSON object keys are strings on disk
    assert report["per_rank"]["1"]["last_in"] >= 3, report
    assert report["sites"]["test/step"]["max_skew_ms"] > 100, report
    merged = json.load(open(trace_dir / "merged.trace.json"))
    pids = {e.get("pid") for e in merged["traceEvents"]
            if e.get("ph") == "X"}
    assert {0, 1} <= pids, pids
    assert "merged trace:" in r.stderr, r.stderr
    assert "collective skew: w1" in r.stderr, r.stderr


def test_mp_socket_wire_trace_merge(tmp_path):
    """The trace-merge drill with REAL cross-rank exchange and no
    jax.distributed (runs in every environment): children peer over
    the TCP wire (SocketWire loopback, built from the launcher's
    PROCESS_ID/NUM_PROCESSES exports), run sited allreduces through
    the full transport stack, and rank 1's planted lateness lands in
    the launcher's exit-time skew report exactly as over the jax
    wire — while the allreduce RESULT proves real cross-rank bytes,
    which the single-process fast-path variant above cannot."""
    import json
    trace_dir = tmp_path / "traces"
    hb_dir = tmp_path / "hb"
    rdv = tmp_path / "rdv"
    r = run_mp(2, f"""
        import os, time
        import numpy as np
        from wormhole_tpu import obs
        from wormhole_tpu.obs import trace
        from wormhole_tpu.obs.metrics import Registry
        from wormhole_tpu.parallel.socket_wire import SocketWire
        from wormhole_tpu.parallel.transport import TransportStack
        from wormhole_tpu.utils.config import Config
        rank = int(os.environ["PROCESS_ID"])
        hub = obs.setup(Config(), rank=rank, registry=Registry())
        assert hub.active and trace.enabled(), "env fallbacks missing"
        hub.heartbeat_tick(step=0, num_ex=0)
        stack = TransportStack(wire=SocketWire(rendezvous={str(rdv)!r}))
        # both ranks leave this barrier together: without it the first
        # collective's arrival order is the processes' start-up order,
        # and a rank 0 that came up 100 ms late (a loaded host) took the
        # first collective's lateness off rank 1
        stack.sync("start")
        for i in range(4):
            if rank == 1:
                time.sleep(0.1)            # the planted straggler
            total = stack.allreduce(np.asarray(float(rank + 1)), None,
                                    op="sum", site="test/step")
            assert float(total) == 3.0, total   # real 2-rank sum
        stack.sync("done")
        hub.finalize(step=4, num_ex=400, wall_s=1.0)
        stack.wire.close()
        print(f"OK rank {{rank}}")
    """, launcher_args=("--heartbeat-dir", str(hb_dir),
                        "--trace-dir", str(trace_dir)), raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("OK rank") == 2

    assert (trace_dir / "merged.trace.json").exists()
    report = json.load(open(trace_dir / "skew_report.json"))
    assert report["ranks"] == [0, 1]
    assert report["clock_source"] == "heartbeat"
    assert report["collectives_matched"] == 4
    w = report["worst"]
    assert w["rank"] == 1, report
    # an allreduce is a rendezvous, so rank 1 trails by its one sleep,
    # ~100 ms, at EACH of the four collectives (arrival skew survives
    # the socket hop unchanged): ~400 ms in all, never less than the
    # sleeps, more on a loaded host
    assert w["lateness_ms"] > 300, report
    assert report["sites"]["test/step"]["max_skew_ms"] > 100, report
    assert "collective skew: w1" in r.stderr, r.stderr


def test_mp_socket_wire_supervised_drill(tmp_path):
    """Supervised PEER_LOST drill over the TCP wire: rank 1 dies
    mid-program on the first attempt, rank 0's wire DETECTS the
    disconnect (no timeout wait) and takes the watchdog's PEER_LOST
    exit, the launcher's --restarts relaunches the world, and the
    retry completes over a fresh per-attempt mesh."""
    marker = tmp_path / "crashed_once"
    rdv = tmp_path / "rdv"
    body = f"""
        import os
        import numpy as np
        from wormhole_tpu.ft import watchdog
        from wormhole_tpu.parallel.socket_wire import SocketWire
        from wormhole_tpu.parallel.transport import TransportStack
        rank = int(os.environ["PROCESS_ID"])
        watchdog.configure(60.0)
        # per-attempt rendezvous dir: the retry must not dial attempt
        # 1's dead ports out of a stale committed peer table
        rdv = os.path.join({str(rdv)!r}, os.environ["WORMHOLE_ATTEMPT"])
        stack = TransportStack(wire=SocketWire(rendezvous=rdv))
        stack.sync("mesh_up")
        if rank == 1 and not os.path.exists({str(marker)!r}):
            open({str(marker)!r}, "w").close()
            os._exit(17)                   # die mid-program
        total = stack.allreduce(np.asarray(float(rank + 1)), None,
                                op="sum", site="drill/step")
        assert float(total) == 3.0, total
        stack.wire.close()
        print(f"OK rank {{rank}}")
    """
    r = run_mp(2, body, timeout=240, launcher_args=("--restarts", "2"),
               raw=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert marker.exists(), "crash never fired"
    # rank 0 did not wait out a timeout: the wire detected the loss
    # and surfaced it through the watchdog exit-code scheme
    assert "peer rank 1 lost" in r.stderr, r.stderr
    assert "restart 1/2" in r.stderr, r.stderr
    assert r.stdout.count("OK rank") == 2
