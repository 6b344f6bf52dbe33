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
STREAM = bm_helpers.load("benchmark/traffic/stream_fields.json")


def _config(name):
    cfg = bm_helpers.load(f"benchmark/configs/{name}/config.json")
    return dict(cfg, num_buckets=bm_helpers.TINY_NB)


def _blocks(seed, n=3, rows=4096, traffic=TRAFFIC):
    return [fields.make_block(traffic, seed, i, rows) for i in range(n)]


def _overflow(blocks, subblocks=2):
    """The blocks' overflow pairs as a crec2 file of the tiny geometry
    would list them (blocks of 16,384 rows, two subblocks)."""
    return [bm_helpers.overflow_of(keys, bm_helpers.TINY_NB, subblocks,
                                   262144) for keys, _labels in blocks]


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


@pytest.mark.parametrize("name,module,traffic", [
    ("criteo_ftrl", ftrl_ref, TRAFFIC), ("criteo_fm", fm_ref, TRAFFIC),
    ("criteo_ftrl", ftrl_ref, STREAM)],
    ids=["criteo_ftrl-replay_uniform", "criteo_fm-replay_uniform",
         "criteo_ftrl-stream_fields"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_each_control_fails_a_limit(name, module, traffic, seed):
    """The reference in the next precision below the stated one, put in the
    program's place, has to come out as not correct; the stated precision
    itself passes with every number at zero. Under the stream's skewed keys
    the overflow pairs stay unrounded in the controls too: each differs
    from the reference in the one thing its name says."""
    cfg = _config(name)
    blocks = _blocks(seed, rows=16384, traffic=traffic)
    stated = check.stated_precision(cfg, _overflow(blocks))
    assert stated["operands"] == "bfloat16"
    assert ("exact_pairs" in stated) == (name == "criteo_ftrl")
    if traffic is STREAM:
        assert min(len(b) for b, _r in stated["exact_pairs"]) > 5000
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
                                     buckets=buckets,
                                     **dict(stated, **precision))
        nums = check.numbers(got, want)
        ok, _ = check.verdict(nums, cfg["check"]["limits"])
        assert not ok, (control, nums)
        assert nums["state_rel_rms"] > 3 * cfg["check"]["limits"][
            "state_rel_rms"], (control, nums)


def _ftrl_of_pr25(cfg, blocks, operands):
    """The FTRL reference's step as PR 25 wrote it, every pair rounded:
    kept here so that ``exact_pairs=None`` is held to it bit for bit."""
    h = cfg["hyper"]
    l1, l2 = float(h["lambda1"]), float(h["lambda2"])
    alpha, beta = float(h["lr_eta"]), float(h["lr_beta"])
    pairs, ids = check.block_pairs(blocks, int(cfg["num_buckets"]))
    w, z, cg = (np.zeros(len(ids)) for _ in range(3))
    losses = []
    for (keys, labels), (buckets, rows) in zip(blocks, pairs):
        idx = np.searchsorted(ids, buckets)
        m = np.bincount(rows, weights=check.round_to(w, operands)[idx],
                        minlength=keys.shape[0])
        y = 2.0 * labels - 1.0
        losses.append(float(np.logaddexp(0.0, -y * m).mean()))
        dual = check.round_to(-y / (1.0 + np.exp(y * m)), operands)
        grad = np.bincount(idx, weights=dual[rows], minlength=len(ids))
        cg1 = np.sqrt(cg * cg + grad * grad)
        z = z + grad - (cg1 - cg) / alpha * w
        w = (-np.sign(z) * np.maximum(np.abs(z) - l1, 0.0)
             / ((beta + cg1) / alpha + l2))
        cg = cg1
    return losses, w


@pytest.mark.parametrize("seed", [6, 2**31 + 7])
def test_no_exact_pairs_is_the_rounded_reference_and_all_is_the_unrounded(
        seed):
    """On a small skewed block: ``exact_pairs=None`` is PR 25's reference
    bit for bit; with every pair named it is ``operands=None``; with the
    file's overflow pairs named it lies between, away from both."""
    cfg = _config("criteo_ftrl")
    blocks = _blocks(seed, rows=16384, traffic=STREAM)
    old_losses, old_w = _ftrl_of_pr25(cfg, blocks, "bfloat16")
    got, ref = check.run_reference(ftrl_ref, cfg, blocks, seed,
                                   operands="bfloat16", exact_pairs=None)
    assert got["losses"] == old_losses and np.array_equal(ref.w, old_w)
    every = [(b, r) for b, r in ref.pairs]
    all_exact, ref_all = check.run_reference(
        ftrl_ref, cfg, blocks, seed, operands="bfloat16", exact_pairs=every)
    plain, ref_plain = check.run_reference(ftrl_ref, cfg, blocks, seed,
                                           operands=None)
    assert all_exact["losses"] == plain["losses"]
    assert np.array_equal(ref_all.w, ref_plain.w)
    assert all(m.all() for m in ref_all.exact)
    some, ref_some = check.run_reference(
        ftrl_ref, cfg, blocks, seed, operands="bfloat16",
        exact_pairs=_overflow(blocks))
    assert 0 < sum(int(m.sum()) for m in ref_some.exact) \
        < sum(len(m) for m in ref_some.exact)
    assert not np.array_equal(ref_some.w, old_w)
    assert not np.array_equal(ref_some.w, ref_plain.w)


def test_a_repeated_pair_is_matched_by_count():
    """Two fields of a row folding to one bucket give the same (bucket,
    row) twice: as many of its occurrences are exact as the list holds."""
    buckets = np.array([7, 3, 7, 7, 3, 9])
    rows = np.array([0, 0, 0, 0, 1, 1])
    nb = 16
    none = check.exact_mask(buckets, rows, (np.zeros(0, int),
                                            np.zeros(0, int)), nb)
    assert not none.any()
    two = check.exact_mask(buckets, rows, (np.array([7, 7]),
                                           np.array([0, 0])), nb)
    assert two.sum() == 2 and two[[0, 2, 3]].sum() == 2
    mixed = check.exact_mask(buckets, rows, (np.array([3, 7, 9]),
                                             np.array([1, 0, 1])), nb)
    assert list(np.flatnonzero(mixed)) == [0, 4, 5]   # (3, row 0) is not
    with pytest.raises(ValueError, match="exact pairs"):    # four of three
        check.exact_mask(buckets, rows, (np.array([7] * 4),
                                         np.array([0] * 4)), nb)
    with pytest.raises(ValueError, match="exact pairs"):    # not a pair
        check.exact_mask(buckets, rows, (np.array([9]), np.array([0])), nb)
    # what a marked pair takes: the value as it is, the others rounded
    x = np.array([1.0 + 2.0 ** -10, 3.0])
    at = np.array([0, 0, 1])
    assert list(check.take(x, at, "bfloat16")) == [1.0, 1.0, 3.0]
    assert list(check.take(x, at, "bfloat16", np.array(
        [True, False, False]))) == [1.0 + 2.0 ** -10, 1.0, 3.0]
    assert list(check.take(x, at, None)) == [x[0], x[0], 3.0]


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_an_empty_overflow_list_changes_no_number(seed):
    """The replay cells have no overflow pair: with the empty lists their
    files give, the reference and so the four numbers of ``correct`` are
    what they were, to the last digit."""
    cfg = _config("criteo_ftrl")
    blocks = _blocks(seed, rows=16384)
    empty = [(np.zeros(0, np.int64), np.zeros(0, np.int64))] * len(blocks)
    assert check.stated_precision(cfg, empty) == {
        "operands": "bfloat16", "exact_pairs": empty}
    was, ref_was = check.run_reference(ftrl_ref, cfg, blocks, seed,
                                       operands="bfloat16")
    now, ref_now = check.run_reference(
        ftrl_ref, cfg, blocks, seed, **check.stated_precision(cfg, empty))
    assert was == now
    assert np.array_equal(ref_was.w, ref_now.w)
    buckets = check.sample_buckets(ref_was, seed, 4096)
    was["state"], now["state"] = ref_was.state(buckets), ref_now.state(buckets)
    observed = dict(was, losses=[x * (1 + 1e-6) for x in was["losses"]])
    assert check.numbers(observed, was) == check.numbers(observed, now)


def test_the_configuration_states_each_paths_precision():
    """One rule, read from the configuration file: a reference is handed
    ``exact_pairs`` where ``precision.overflow_operands`` is stated."""
    ftrl, fm = _config("criteo_ftrl"), _config("criteo_fm")
    assert ftrl["precision"]["overflow_operands"] == "float32"
    assert check.stated_precision(ftrl, "lists") == {
        "operands": "bfloat16", "exact_pairs": "lists"}
    assert "overflow_operands" not in fm["precision"]
    assert check.stated_precision(fm, "lists") == {"operands": "bfloat16"}
    odd = dict(ftrl, precision=dict(ftrl["precision"],
                                    overflow_operands="float8_e4m3fn"))
    with pytest.raises(ValueError, match="overflow_operands"):
        check.stated_precision(odd, "lists")


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
