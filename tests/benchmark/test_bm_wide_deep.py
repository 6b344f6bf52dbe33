"""``criteo_wide_deep``: the plain reference against the program's three steps
(split and fused tile steps in interpret mode, the sparse step), broken paths
that must come out not ``correct``, the controls against the limits, the
seeded weights, the work counts at the published widths and the tower's
reader. The cell's own end-to-end run on the CPU is
``test_bm_wide_deep_cell_cpu.py`` (a file of its own: the interpreter walks
the 34-channel kernels slowly, and the driver spreads files over workers)."""

import types

import numpy as np
import pytest

import bm_helpers
from benchmark import check
from benchmark.configs.criteo_wide_deep import reference as wd_ref
from benchmark.configs.criteo_wide_deep import roofline as wd_roofline
from benchmark.generators import fields

CONFIG = bm_helpers.load("benchmark/configs/criteo_wide_deep/config.json")
TRAFFIC = bm_helpers.load("benchmark/traffic/replay_uniform.json")
NB, ROWS, SUBBLOCKS = bm_helpers.TINY_NB, 8192, 1
SMALL = dict(dim=8, hidden=[64, 32])      # widths the interpreter walks fast


def _config(**over):
    return dict(CONFIG, num_buckets=NB, **over)


def _blocks(seed, n=CONFIG["check"]["steps"], rows=ROWS):
    return [fields.make_block(TRAFFIC, seed, i, rows) for i in range(n)]


def _store(config, seed, kernel="split", **model):
    """A ``WideDeepStore`` at the configuration's hyper-parameters with the
    benchmark's seeded weights, as ``system.make_app`` leaves it."""
    import jax.numpy as jnp
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    h = config["hyper"]
    k = int(config["dim"])
    mcfg = WideDeepConfig(
        num_buckets=NB, dim=k, hidden=tuple(config["hidden"]),
        lr_alpha=h["lr_alpha"], lr_alpha_dense=h["lr_alpha_dense"],
        lr_beta=h["lr_beta"], l2_v=h["l2_v"], init_scale=h["init_scale"],
        tile_step_kernel=kernel)
    for key, value in model.items():
        setattr(mcfg, key, value)
    st = WideDeepStore(mcfg)
    slots = np.zeros((NB, 2 * (1 + k)), np.float32)
    slots[:, 1:1 + k] = wd_ref.init_factors(np.arange(NB), k, seed,
                                            float(h["init_scale"]))
    st.slots = jnp.asarray(slots)
    for l, (w, b) in enumerate(wd_ref.init_tower(wd_ref.tower_sizes(config),
                                                 seed)):
        st.mlp[f"W{l}"] = jnp.asarray(w, jnp.float32)
        st.mlp[f"b{l}"] = jnp.asarray(b, jnp.float32)
    return st


def _tile_steps(st, blocks, ovf_cap=1024):
    """The blocks through ``tile_train_step``; yields after each step."""
    import jax.numpy as jnp
    from wormhole_tpu.data.crec import (CRec2Info, default_cap,
                                        encode_tile_block)
    from wormhole_tpu.ops import tilemm
    spec = tilemm.make_spec(NB, SUBBLOCKS, default_cap(39, NB))
    info = CRec2Info(nnz=39, block_rows=ROWS, total_rows=ROWS, nb=NB,
                     ovf_cap=ovf_cap, subblocks=SUBBLOCKS, cap=spec.cap)
    for keys, labels in blocks:
        pw, ovf_b, ovf_r, n = encode_tile_block(keys, NB, spec, 1024)
        assert n == 0
        block = {"pw": jnp.asarray(pw), "labels": jnp.asarray(labels)}
        if ovf_cap:
            block.update(ovf_b=jnp.asarray(ovf_b), ovf_r=jnp.asarray(ovf_r))
        st.tile_train_step(block, info)
        row = np.asarray(st.fetch_metrics())
        yield float(row[0] / row[1])


def _sparse_steps(st, blocks):
    from wormhole_tpu.data.feed import SparseBatch
    import jax.numpy as jnp
    for keys, labels in blocks:
        buckets = fields.fold_keys32(keys.reshape(-1), NB).reshape(keys.shape)
        uniq, inv = np.unique(buckets, return_inverse=True)
        kpad = -(-len(uniq) // 1024) * 1024
        batch = SparseBatch(
            cols=jnp.asarray(inv.reshape(keys.shape).astype(np.int32)),
            vals=jnp.ones(keys.shape, jnp.float32),
            labels=jnp.asarray(labels.astype(np.float32)),
            row_mask=jnp.ones(len(labels), jnp.float32),
            uniq_keys=jnp.asarray(np.pad(uniq, (0, kpad - len(uniq)))
                                  .astype(np.int32)),
            key_mask=jnp.asarray((np.arange(kpad) < len(uniq))
                                 .astype(np.float32)))
        objv, num_ex = st.train_step(batch)[:2]
        yield float(objv) / float(num_ex)


def _observe(st, config, seed, steps, ref_of):
    """What ``run.check_first_steps`` gathers, through the configuration's
    own probes; ``ref_of(buckets=...)`` runs the reference."""
    from benchmark.configs.criteo_wide_deep import system
    app = types.SimpleNamespace(store=st)
    observed = {"losses": []}
    for i, loss in enumerate(steps):
        observed["losses"].append(loss)
        if i == 0:
            observed["grad_norms"] = system.grad_norms(app, config, seed)
    observed["change_norms"] = system.change_norms(app, config, seed)
    expected, ref = ref_of()
    buckets = check.sample_buckets(ref, seed, 4096)
    expected["state"] = ref.state(buckets)
    observed["state"] = system.state(app, config, seed, buckets)
    return check.numbers(observed, expected)


def _numbers(config, seed, kernel="split", steps=_tile_steps,
             operands="bfloat16", prepare=None, n=CONFIG["check"]["steps"],
             **model):
    blocks = _blocks(seed, n)
    st = _store(config, seed, kernel, **model)
    if prepare is not None:
        prepare(st)
    kw = {"ovf_cap": 0} if kernel == "fused" else {}
    nums = _observe(
        st, config, seed, steps(st, blocks, **kw),
        lambda: check.run_reference(wd_ref, config, blocks, seed,
                                    operands=operands))
    return nums, st


@pytest.mark.parametrize("kernel", ["split", "fused"])
def test_tile_step_is_the_reference(kernel):
    """The three checked steps through the program's tile step, the split
    pair with XLA's tower between and the one kernel with the tower inside,
    on the benchmark's seeded weights: every number inside the cell's
    limits."""
    config = _config(**SMALL)
    assert config["check"]["steps"] == 3
    nums, st = _numbers(config, 3, kernel)
    assert st.step_kernel[0] == kernel, st.step_kernel
    ok, lines = check.verdict(nums, config["check"]["limits"])
    assert ok, lines


def _forget(table: bool, tower: bool):
    """A step that does not carry the accumulators it was handed."""
    def prepare(st):
        import jax
        import jax.numpy as jnp
        real = st.tile_train_step

        def step(block, info, tau=0.0):
            k = st.cfg.dim
            if table:
                st.slots = st.slots.at[:, 1 + k:].set(0.0)
            if tower:
                st.mlp_accum = jax.tree.map(jnp.zeros_like, st.mlp_accum)
            return real(block, info, tau)
        st.tile_train_step = step
    return prepare


@pytest.mark.parametrize("what", ["table", "tower", "no_relu"])
def test_three_steps_carry_the_accumulators(what):
    """What one step cannot show: accumulators that are carried and used
    (``cg' = sqrt(cg^2 + g^2)`` with ``cg != 0``). Over the cell's three
    steps a program that forgets the table's, or the tower's alone, is not
    ``correct``; nor on a tower without a ReLU (no hidden layer: pooled ->
    1), where no unit can flip and the sound program is inside the limits
    the other cells have."""
    if what == "no_relu":
        config = _config(dim=8, hidden=[])
        tight = dict(config["check"]["limits"], change_norm_rel=3e-4,
                     state_rel_rms=5e-4)
        nums, _st = _numbers(config, 10)
        ok, lines = check.verdict(nums, tight)
        assert ok, lines
        nums, _st = _numbers(config, 10, prepare=_forget(True, True))
        assert not check.verdict(nums, tight)[0], nums
        return
    config = _config(**SMALL)
    nums, _st = _numbers(config, 10, prepare=_forget(what == "table",
                                                     what == "tower"))
    assert not check.verdict(nums, config["check"]["limits"])[0], nums


def test_split_step_at_the_published_widths_is_the_reference():
    config = _config()
    assert (config["dim"], config["hidden"]) == (32, [1024, 512, 256])
    nums, _st = _numbers(config, 4)
    ok, lines = check.verdict(nums, config["check"]["limits"])
    assert ok, lines


def test_sparse_step_is_the_reference_with_unrounded_pulls():
    """The gather/scatter step computes the same update from float32 pulls
    and pushes (no kernel rounds them); the tower's precision is the same."""
    config = _config(**SMALL)
    nums, _st = _numbers(config, 5, steps=_sparse_steps, operands=None)
    ok, lines = check.verdict(nums, config["check"]["limits"])
    assert ok, lines


# -- broken paths: each must fail `correct` by at least one number ----------

def _drop_tower_gradient(st):
    """g_v without the tower's part: the pushed d loss / d pooled zeroed."""
    from wormhole_tpu.ops import tilemm
    real, k = tilemm.backward_pushes, st.cfg.dim

    def pushes(pw, dvals, spec, ovf_b=None, ovf_r=None):
        return real(pw, dvals.at[:, 1:1 + k].set(0.0), spec, ovf_b, ovf_r)
    st._patch = ("backward_pushes", pushes)


def _skip_dense_update(st):
    real = st.tile_train_step

    def step(block, info, tau=0.0):
        kept = {k: v + 0 for k, v in st.mlp.items()}   # the step donates
        ticket = real(block, info, tau)
        st.mlp = kept                                  # as before the step
        return ticket
    st.tile_train_step = step


BROKEN = {
    "tower_gradient_dropped_from_g_v": dict(prepare=_drop_tower_gradient),
    "dense_update_skipped": dict(prepare=_skip_dense_update),
    "tower_in_fp8": dict(patch_tower="float8_e4m3fn"),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_broken_path_is_not_correct(how, monkeypatch):
    import jax.numpy as jnp
    from wormhole_tpu.ops import tilemm
    config, broken = _config(**SMALL), BROKEN[how]
    if "patch_tower" in broken:
        monkeypatch.setattr(tilemm, "TOWER_OPERANDS",
                            jnp.dtype(broken["patch_tower"]))

    def prepare(st):
        if "prepare" in broken:
            broken["prepare"](st)
            if hasattr(st, "_patch"):
                monkeypatch.setattr(tilemm, *st._patch)
    nums, _st = _numbers(config, 6, prepare=prepare)
    ok, lines = check.verdict(nums, config["check"]["limits"])
    assert not ok, lines


def test_l2_v_left_out_is_not_correct_where_it_can_be_seen():
    """At the stated ``l2_v`` (1e-5 on factors of 0.01) the decay is a
    millionth of a factor's gradient, below float32's resolution of the sum:
    no limit can see it, nor any user. At a decay as large as the gradient's
    tenth (5.0), a program that leaves it out is not ``correct``; with it,
    it is."""
    config = _config(**SMALL)
    config["hyper"] = dict(config["hyper"], l2_v=5.0)
    sound, _st = _numbers(config, 7)
    assert check.verdict(sound, config["check"]["limits"])[0], sound
    nums, _st = _numbers(config, 7, l2_v=0.0)
    assert not check.verdict(nums, config["check"]["limits"])[0], nums


# -- the reference by itself ------------------------------------------------

def test_reference_gradients_are_the_derivatives_of_its_loss():
    """g_w, g_v and the tower's gradients as the unrounded reference pushes
    them, against central finite differences of its own forward pass."""
    config = _config(dim=4, hidden=[6, 5])
    config["precision"] = dict(config["precision"], tower_operands="float64")
    keys, labels = fields.make_block(TRAFFIC, 8, 0, 64)
    ref = wd_ref.Reference(config, [(keys, labels)], 8)
    rng = np.random.default_rng(0)
    ref.w = rng.normal(0, 0.1, len(ref.ids))
    ref.v = ref.v * 30.0
    ref.mlp = [(w, rng.normal(0, 0.1, len(b))) for w, b in ref.mlp]
    buckets, rows = ref.pairs[0]
    idx = np.searchsorted(ref.ids, buckets)
    y = 2.0 * labels - 1.0

    def loss(w, v, mlp):
        wide = np.bincount(rows, weights=w[idx], minlength=64)
        h = np.stack([np.bincount(rows, weights=v[idx, f], minlength=64)
                      for f in range(ref.k)], 1)
        for l, (wl, bl) in enumerate(mlp):
            h = h @ wl + bl
            if l + 1 < len(mlp):
                h = np.maximum(h, 0.0)
        return (np.logaddexp(0, -y * (wide + h[:, 0])).sum()
                + 0.5 * ref.l2_v * (v * v).sum())

    w0, v0 = ref.w.copy(), ref.v.copy()
    mlp0 = [(w.copy(), b.copy()) for w, b in ref.mlp]
    ref.step()
    g_w, g_v, g_mlp = ref.first_grad
    eps = 1e-6

    def slope(f):
        return (f(eps) - f(-eps)) / (2 * eps)

    for b in (0, 9, len(ref.ids) - 1):
        e = np.zeros_like(w0)
        e[b] = 1.0
        assert slope(lambda t: loss(w0 + t * e, v0, mlp0)) \
            == pytest.approx(g_w[b], rel=1e-5, abs=1e-8)
        ev = np.zeros_like(v0)
        ev[b, 2] = 1.0
        assert slope(lambda t: loss(w0, v0 + t * ev, mlp0)) \
            == pytest.approx(g_v[b, 2], rel=1e-5, abs=1e-8)
    for l, at in ((0, (1, 2)), (1, (3, 4)), (2, (2, 0))):
        def moved(t, l=l, at=at):
            mlp = [(w.copy(), b.copy()) for w, b in mlp0]
            mlp[l][0][at] += t
            return loss(w0, v0, mlp)
        assert slope(moved) == pytest.approx(g_mlp[l][0][at], rel=1e-5,
                                             abs=1e-8)

        def biased(t, l=l, at=at):
            mlp = [(w.copy(), b.copy()) for w, b in mlp0]
            mlp[l][1][at[1]] += t
            return loss(w0, v0, mlp)
        assert slope(biased) == pytest.approx(g_mlp[l][1][at[1]], rel=1e-5,
                                              abs=1e-8)


def test_seeded_weights_and_their_device_twin():
    import jax.numpy as jnp
    from benchmark.configs.criteo_wide_deep import system
    b = np.arange(50000)
    v = wd_ref.init_factors(b, 32, 11, 0.01)
    assert v.shape == (50000, 32)
    assert v.std() == pytest.approx(0.01, rel=0.01) and abs(v.mean()) < 1e-4
    assert not np.array_equal(v, wd_ref.init_factors(b, 32, 12, 0.01))
    twin = system._v0(50000, 32, jnp.uint32(system._salt(11)), 0.01)
    # float32 arithmetic on the device, float64 here: the last bit may differ
    assert np.allclose(np.asarray(twin, np.float64), v, rtol=3e-7, atol=0.0)
    sizes = wd_ref.tower_sizes(CONFIG)
    assert sizes == [32, 1024, 512, 256, 1]
    tower = wd_ref.init_tower(sizes, 2**31 + 5)
    again = wd_ref.init_tower(sizes, 2**31 + 5)
    assert sum(w.size + b.size for w, b in tower) \
        == CONFIG["tower_parameters"] == 690177
    for (w, b), (w2, _b2), a in zip(tower, again, sizes):
        assert np.array_equal(w, w2) and not b.any()
        assert np.array_equal(w, w.astype(np.float32))      # float32 values
        if w.size > 1000:
            assert w.std() == pytest.approx(np.sqrt(2.0 / a), rel=0.05)
    assert not np.array_equal(tower[0][0],
                              wd_ref.init_tower(sizes, 6)[0][0])


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_each_control_fails_a_limit(seed):
    """The reference one precision below the stated one, in kernel operands,
    table or tower, put in the program's place, is not ``correct``; the
    stated precision itself reads zero everywhere."""
    config = _config()
    blocks = _blocks(seed, rows=16384)
    stated = check.stated_precision(config)
    # the overflow pairs are a fact of a crec2 file: none here
    assert stated == {"operands": "bfloat16", "exact_pairs": None}
    assert config["precision"]["tower_operands"] == "bfloat16"
    want, ref = check.run_reference(wd_ref, config, blocks, seed, **stated)
    buckets = check.sample_buckets(ref, seed, 4096)
    want["state"] = ref.state(buckets)
    same, _ = check.run_reference(wd_ref, config, blocks, seed,
                                  buckets=buckets, **stated)
    assert not any(check.numbers(same, want).values())
    limits = config["check"]["limits"]
    assert set(config["check"]["controls"]) == {
        "fp8_operands", "bf16_table", "fp8_tower"}
    for control, precision in config["check"]["controls"].items():
        got, _ = check.run_reference(wd_ref, config, blocks, seed,
                                     buckets=buckets,
                                     **dict(stated, **precision))
        nums = check.numbers(got, want)
        # twice a limit or more at the timed size (README.md); these
        # 16,384-row blocks fold 39 x 20,000 keys into 65,536 buckets
        # and a rounded table moves the change's norm less
        assert max(nums[n] / limits[n] for n in limits) >= 1.5, (control,
                                                                  nums)


# -- the work counts and the tower's reader ---------------------------------

def test_work_counts_at_the_published_widths():
    from wormhole_tpu.ops import tilemm
    flops = wd_roofline.tower_flops(CONFIG, 98304)
    assert flops == 6 * 98304 * 688384 == 406_025_404_416     # 406 GFLOP
    assert flops == tilemm.tower_flops(98304, 32, (1024, 512, 256))
    work = wd_roofline.block_work(CONFIG, 98304 * 39, 98304, 700000)
    assert work["flops"] == 2 * 2 * 98304 * 39 * 34
    assert work["bytes"] == 4 * 98304 * 39 + 98304 + 2 * 264 * 700000
    assert CONFIG["state_bytes_per_bucket"] == 2 * (1 + 32) * 4


def test_program_counts_the_towers_work_a_step():
    config = _config(**SMALL)
    st = _store(config, 9)
    list(_tile_steps(st, _blocks(9, n=2)))
    assert st.timer.counts["tower_flops"] == 2
    assert st.timer.totals["tower_flops"] \
        == 2 * wd_roofline.tower_flops(config, ROWS)
    params = sum(w.size + b.size for w, b in
                 wd_ref.init_tower(wd_ref.tower_sizes(config), 9))
    assert st.timer.totals["dense_param_bytes"] == 2 * 16 * params
    assert "table_cross" not in st.timer.totals


def test_tower_reader_reads_scopes_from_a_trace_and_nothing_without():
    from benchmark.readers import tower_ms_per_step, tower_mxu_roofline
    import os
    xplane = os.path.join(bm_helpers.DATA, "ftrl_replay.xplane.pb")
    scopes = tower_ms_per_step.scoped_ops(xplane)
    assert scopes and all(isinstance(v, str) for v in scopes.values())
    assert any("pallas_call" in v for v in scopes.values())
    # FTRL's step has no tower: nothing under the tower's scopes
    assert not any(w in v for v in scopes.values()
                   for w in tower_ms_per_step.SCOPES)
    reading = {"trace": None, "config": CONFIG,
               "traffic": {"name": "replay_uniform"}}
    assert tower_ms_per_step.read(reading) is None
    assert tower_mxu_roofline.read(reading) is None
    traced = dict(reading, trace={"steps": 4})      # a trace that is gone
    assert tower_ms_per_step.read(traced) is None
    assert tower_mxu_roofline.read(traced) is None


def test_hook_refuses_hyper_parameters_the_program_does_not_have(tmp_path):
    from benchmark.configs.criteo_wide_deep import system
    conf = tmp_path / "cell.conf"
    conf.write_text("data_format = crec2\nnum_buckets = 65536\n")
    config = _config(**SMALL)
    config["hyper"] = dict(config["hyper"], lr_beta=2.0)
    config["program"] = dict(
        config["program"], model_conf=["dim=8", "hidden=64,32"]
        + config["program"]["model_conf"][2:])
    with pytest.raises(ValueError, match="lr_beta"):
        system.make_app(str(conf), [], config, 1)
