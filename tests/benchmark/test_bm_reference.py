"""The plain references: FTRL against the smoke's dense float64 oracle, FM
against finite differences of its own loss, and the lower-precision controls
against the limits of ``correct`` (the control kept as a test, at a size a
test run can hold)."""

import numpy as np
import pytest

import bm_helpers
from benchmark import check
from benchmark.configs.criteo_fm import reference as fm_ref
from benchmark.configs.criteo_ftrl import reference as ftrl_ref
from benchmark.generators import fields

TRAFFIC = bm_helpers.load("benchmark/traffic/replay_uniform.json")


def _config(name):
    cfg = bm_helpers.load(f"benchmark/configs/{name}/config.json")
    return dict(cfg, num_buckets=bm_helpers.TINY_NB)


def _blocks(seed, n=3, rows=4096):
    return [fields.make_block(TRAFFIC, seed, i, rows) for i in range(n)]


def test_ftrl_reference_is_the_smokes_oracle():
    import chip_smoke
    cfg, blocks = _config("criteo_ftrl"), _blocks(3)
    ref = ftrl_ref.Reference(cfg, blocks, 3)
    oracle = chip_smoke.Oracle(cfg["num_buckets"])
    for keys, labels in blocks:
        loss = ref.step()
        objv, _m, _y = oracle.step([(keys, labels)])
        assert loss == pytest.approx(objv / len(labels), rel=1e-12)
    assert np.allclose(oracle.w[ref.ids], ref.w, rtol=1e-9, atol=1e-15)
    untouched = np.ones(cfg["num_buckets"], bool)
    untouched[ref.ids] = False
    assert not oracle.w[untouched].any()


def test_ftrl_first_gradient_and_change_norms():
    cfg, blocks = _config("criteo_ftrl"), _blocks(4)
    got, ref = check.run_reference(ftrl_ref, cfg, blocks, 4)
    assert got["losses"][0] == pytest.approx(np.log(2.0))   # w0 = 0
    assert got["grad_norms"]["w"] == pytest.approx(
        np.linalg.norm(ref.first_grad))
    assert got["change_norms"]["w"] == pytest.approx(np.linalg.norm(ref.w))
    assert got["losses"][1] != got["losses"][0]      # the state moved


def test_fm_gradient_is_the_derivative_of_its_loss():
    """g_w and g_v as the reference pushes them, against central finite
    differences of its own forward pass (weight decay on the touched)."""
    cfg = _config("criteo_fm")
    keys, labels = fields.make_block(TRAFFIC, 5, 0, 64)
    ref = fm_ref.Reference(cfg, [(keys, labels)], 5)
    ref.w = np.random.default_rng(0).normal(0, 0.1, len(ref.ids))
    buckets, rows = ref.pairs[0]
    idx = np.searchsorted(ref.ids, buckets)

    def loss(w, v):
        lin = np.bincount(rows, weights=w[idx], minlength=64)
        s = np.stack([np.bincount(rows, weights=v[idx, f], minlength=64)
                      for f in range(ref.k)], 1)
        q = np.bincount(rows, weights=(v * v).sum(1)[idx], minlength=64)
        m = lin + 0.5 * ((s * s).sum(1) - q)
        y = 2.0 * labels - 1.0
        return np.logaddexp(0, -y * m).sum() + 0.5 * ref.l2_v * (v * v).sum()

    w0, v0 = ref.w.copy(), ref.v.copy()
    ref.step()
    g_w, g_v = ref.first_grad
    eps = 1e-6
    for b in (0, 7, len(ref.ids) - 1):
        dw = np.zeros_like(w0)
        dw[b] = eps
        assert (loss(w0 + dw, v0) - loss(w0 - dw, v0)) / (2 * eps) \
            == pytest.approx(g_w[b], rel=1e-5, abs=1e-8)
        dv = np.zeros_like(v0)
        dv[b, 3] = eps
        assert (loss(w0, v0 + dv) - loss(w0, v0 - dv)) / (2 * eps) \
            == pytest.approx(g_v[b, 3], rel=1e-5, abs=1e-8)


def test_fm_init_is_seeded_uniform_with_the_stated_deviation():
    b = np.arange(100000)
    v = fm_ref.init_factors(b, 8, 11, 0.01)
    assert v.shape == (100000, 8)
    assert v.std() == pytest.approx(0.01, rel=0.01)
    assert abs(v.mean()) < 1e-4
    assert np.array_equal(v, fm_ref.init_factors(b, 8, 11, 0.01))
    assert not np.array_equal(v, fm_ref.init_factors(b, 8, 12, 0.01))
    assert np.array_equal(v.astype(np.float32).astype(np.float64) * 0 + 1,
                          np.ones_like(v))


@pytest.mark.parametrize("name,module", [("criteo_ftrl", ftrl_ref),
                                         ("criteo_fm", fm_ref)])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_each_control_fails_a_limit(name, module, seed):
    """The reference in the next precision below the stated one, put in the
    program's place, has to come out as not correct; the stated precision
    itself passes with every number at zero."""
    cfg = _config(name)
    blocks = _blocks(seed, rows=16384)
    stated = {"operands": check.stated_operands(cfg)}
    assert stated["operands"] == "bfloat16"
    want, ref = check.run_reference(module, cfg, blocks, seed, **stated)
    buckets = check.sample_buckets(ref, seed, 4096)
    want["state"] = ref.state(buckets)
    same, _ = check.run_reference(module, cfg, blocks, seed,
                                  buckets=buckets, **stated)
    ok, lines = check.verdict(check.numbers(same, want),
                              cfg["check"]["limits"])
    assert ok and len(lines) == len(cfg["check"]["limits"])
    assert set(cfg["check"]["controls"]) == {"fp8_operands", "bf16_table"}
    for control, precision in cfg["check"]["controls"].items():
        got, _ = check.run_reference(module, cfg, blocks, seed,
                                     buckets=buckets, **precision)
        nums = check.numbers(got, want)
        ok, _ = check.verdict(nums, cfg["check"]["limits"])
        assert not ok, (control, nums)
        assert nums["state_rel_rms"] > 3 * cfg["check"]["limits"][
            "state_rel_rms"], (control, nums)


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_median_leaf():
    ours = {"a": 1.01, "b": 100.0, "c": 1e-9}
    ref = {"a": 1.0, "b": 100.0, "c": 0.0}
    # leaf c is all but zero: held against the median leaf's norm (1.0)
    assert check._worst_leaf_gap(ours, ref) == pytest.approx(0.01)


def test_sample_is_seeded_and_of_fixed_size():
    class Ref:
        ids = np.arange(100, 1100)
    a = check.sample_buckets(Ref, 3, 256)
    assert len(a) == 256 and np.array_equal(a, check.sample_buckets(Ref, 3,
                                                                    256))
    assert not np.array_equal(a, check.sample_buckets(Ref, 4, 256))
    many = check.sample_buckets(Ref, 3, 4096)     # fewer touched than asked
    assert len(many) == 4096 and set(many) <= set(Ref.ids)
