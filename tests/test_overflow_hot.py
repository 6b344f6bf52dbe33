"""The overflow list through the hot tile (ISSUE 42): a long list of few
buckets rides to the one-device train step as its distinct buckets and
the pairs packed over their rank among them, and runs through the
multi-channel kernel pair at three bfloat16 channels a float32 value.

The properties pinned here:
  * the three-way split gives back every float32 it is given, to the
    bit, each part a bfloat16 value;
  * the hot helpers give the COO helpers' sums: to the bit where a row (a
    bucket) has one listed pair, to the order of float32 additions where
    it has many;
  * the hot form decodes to the list it was made from, as a multiset;
    the native and the numpy encoder give the same bits;
  * the rule (``crec.HotRoom``): a short list stays COO, a list of mostly
    distinct buckets stays COO, every other goes hot; each reaches the
    program the rule says and the counter that says so;
  * an FTRL run on Zipf keys lands where the COO path lands and on the
    float64 oracle, through the fused and the split kernel alike;
  * ``FMStore``'s spill step takes the same form (ISSUE 48): every one of
    its ``k + 2`` float32 channels as three parts through the same kernel
    pair, the same sums as its COO helpers, the same rule, counters and
    oracle, and a list rounded to bfloat16 on its way is refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import wormhole_tpu.data.crec as crec
from wormhole_tpu.data import native
from wormhole_tpu.data.crec import HotRoom, online_info
from wormhole_tpu.data.hashing import fold_keys32
from wormhole_tpu.ops import tilemm

from test_tile_online import NNZ, make_app, weights, write_v1

NB = 4 * tilemm.TILE          # 2**16 buckets
S = 2                         # subblocks of the helper-level blocks
SPEC = tilemm.make_spec(NB, S, 128)
ROWS = S * tilemm.RSUB


# -- the three-way split -----------------------------------------------------

def _split_cases():
    rng = np.random.default_rng(3)
    tiny, huge = np.finfo(np.float32).tiny, np.finfo(np.float32).max
    wide = (rng.standard_normal(4096)
            * np.exp2(rng.uniform(-100, 126, 4096))).astype(np.float32)
    bf16 = np.asarray(jnp.asarray(rng.standard_normal(512), jnp.bfloat16)
                      .astype(jnp.float32))
    return {
        "normals_over_the_exponent_range": wide,
        "zeros": np.array([0.0, -0.0], np.float32),
        # a bfloat16 value: mid and lo are 0; 16 significant bits: lo is 0
        "mid_and_lo_zero": bf16,
        "lo_zero": (bf16 * np.float32(1 + 2.0 ** -12)).astype(np.float32),
        "largest_normals": np.array([huge, -huge, np.nextafter(
            huge, np.float32(0))], np.float32),
        # the smallest values whose every part is a normal float32 (or 0)
        "smallest_exact": np.array([2.0 ** -103, -2.0 ** -103, np.nextafter(
            np.float32(2.0 ** -103), np.float32(1))], np.float32),
        "smallest_normals_of_one_part": np.array(
            [tiny, -tiny, tiny * 2, tiny * 256], np.float32),
    }


@pytest.mark.parametrize("case", list(_split_cases()))
def test_split3_gives_back_every_float32(case):
    """``(hi + mid) + lo`` is the value to the bit and each part survives
    a cast to bfloat16 and back. Subnormal parts: the chip flushes them
    to zero (this CPU keeps them), so below ``2**-103`` the sum may be
    off by less than ``2**-126`` there; the case that reaches down to the
    smallest normals has no part but ``hi``."""
    x = _split_cases()[case]
    parts = [np.asarray(p) for p in tilemm.split3(jnp.asarray(x))]
    back = (parts[0] + parts[1]) + parts[2]
    if case == "zeros":
        # -0.0 comes back as +0.0 (its remainder is +0.0): the one value
        # whose bits are not kept, and it reads the same in every sum
        assert not back.any() and not np.signbit(back[0])
    else:
        assert back.tobytes() == x.tobytes()
    for p in parts:
        assert p.dtype == np.float32
        rounded = np.asarray(jnp.asarray(p).astype(jnp.bfloat16)
                             .astype(jnp.float32))
        assert rounded.tobytes() == p.tobytes()
    if case in ("mid_and_lo_zero", "smallest_normals_of_one_part"):
        assert not parts[1].any() and not parts[2].any()
    if case == "lo_zero":
        assert parts[1].any() and not parts[2].any()


# -- lists ------------------------------------------------------------------

def _list(kind: str, rng, subblocks: int = S, nb: int = NB):
    """``(ovf_b, ovf_r)`` in the encoder's order (by subblock)."""
    rows = subblocks * tilemm.RSUB
    if kind == "empty":
        n, pool = 0, 1
    elif kind == "one_pair":
        n, pool = 1, 1
    elif kind == "skewed":
        n, pool = 6000, 300
    elif kind == "uniform":
        n, pool = 3000, 3000
    elif kind == "two_hot_tiles":        # over 16,384 distinct buckets
        n, pool = 80000, 17000
    elif kind == "two_small_hot_tiles":  # ... at a room the kernels
        n, pool = 40000, 20000           # interpret in seconds
    buckets = rng.choice(nb, pool, replace=False)
    draw = (rng.zipf(1.3, n) % pool if kind == "skewed"
            else rng.integers(0, pool, n))
    ovf_r = np.sort(rng.integers(0, rows, n)).astype(np.uint32)
    return buckets[draw].astype(np.uint32), ovf_r


def _multiset(b, r):
    return sorted(zip(np.asarray(b).tolist(), np.asarray(r).tolist()))


LISTS = ["skewed", "uniform", "empty", "one_pair", "two_hot_tiles"]


@pytest.mark.parametrize("kind", LISTS)
def test_hot_form_decodes_to_its_list(kind):
    rng = np.random.default_rng(5)
    ovf_b, ovf_r = _list(kind, rng)
    uniq, rank, cell_max = tilemm.hot_ranks(ovf_b, ovf_r, S)
    assert np.array_equal(uniq[rank], ovf_b)
    tiles = max(-(-len(uniq) // tilemm.TILE), 1)
    assert tiles == (2 if kind == "two_hot_tiles" else 1)
    vtiles = crec.hot_vtiles(cell_max)
    ovf_u, ovf_pw = tilemm.encode_hot(uniq, rank, ovf_r, S, tiles, vtiles)
    assert ovf_u.shape == (tiles * tilemm.TILE,)
    assert ovf_pw.shape == tilemm.hot_spec(tiles * vtiles, S).pairs_shape
    assert np.array_equal(ovf_u[:len(uniq)], uniq)
    assert (ovf_u[len(uniq):] == tilemm.UNUSED).all()
    assert _multiset(*tilemm.decode_hot(ovf_u, ovf_pw, S)) == _multiset(
        ovf_b, ovf_r)
    if len(ovf_b):
        # packed to the room: a cell's pairs fill its virtual tiles in
        # order, so only the last one of a cell is short
        _b, _r, pad = tilemm.unpack_fields(
            ovf_pw.reshape(tiles * vtiles, S, tilemm.HOT_CAP))
        full = (~pad).sum(axis=2)
        assert full.max() == min(cell_max, tilemm.HOT_CAP)
    with pytest.raises(ValueError):
        tilemm.encode_hot(uniq, rank, ovf_r, S, tiles,
                          0 if len(ovf_b) else -1)


@pytest.mark.parametrize("kind", LISTS)
def test_native_and_numpy_hot_encoders_give_the_same_bits(kind):
    enc = native.get_hot_encoder()
    if enc is None:
        pytest.skip(f"no native library: {native.build_error()}")
    ranks, place = enc
    rng = np.random.default_rng(7)
    ovf_b, ovf_r = _list(kind, rng)
    want = tilemm.hot_ranks(ovf_b, ovf_r, S)
    got = ranks(ovf_b, ovf_r, S)
    assert np.array_equal(got[0], want[0]) and got[0].dtype == np.uint32
    assert np.array_equal(got[1], want[1]) and got[1].dtype == np.uint32
    assert got[2] == want[2]
    tiles = max(-(-len(want[0]) // tilemm.TILE), 1)
    vtiles = crec.hot_vtiles(want[2])
    a = tilemm.encode_hot(want[0], want[1], ovf_r, S, tiles, vtiles)
    b = place(got[0], got[1], ovf_r, S, tiles, vtiles)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()
    if len(ovf_b) > tilemm.HOT_CAP:
        with pytest.raises(ValueError):       # a room the list passes
            place(got[0], got[1], ovf_r, S, tiles, 0)


# -- the device helpers against the COO helpers -------------------------------

def _with_lone_pairs(ovf_b, ovf_r, n=50):
    """The list with ``n`` pairs planted on rows and buckets that have no
    other listed pair, in the encoder's order: ``(ovf_b, ovf_r, the lone
    buckets, the lone rows)``."""
    lone_b = np.setdiff1d(np.arange(NB), ovf_b)[:n].astype(np.uint32)
    lone_r = np.setdiff1d(np.arange(ROWS), ovf_r)[:n].astype(np.uint32)
    ovf_b = np.concatenate([ovf_b, lone_b])
    ovf_r = np.concatenate([ovf_r, lone_r])
    order = np.argsort(ovf_r // tilemm.RSUB, kind="stable")
    return ovf_b[order], ovf_r[order], lone_b, lone_r


@pytest.fixture(scope="module")
def helper_case():
    """A skewed list over a table with values across 40 binades, and both
    paths' sums (one interpret-mode build of each kernel for the module)."""
    rng = np.random.default_rng(11)
    # rows and buckets with exactly one listed pair, planted
    ovf_b, ovf_r, _, _ = _with_lone_pairs(*_list("skewed", rng))
    uniq, rank, cell_max = tilemm.hot_ranks(ovf_b, ovf_r, S)
    ovf_u, ovf_pw = tilemm.encode_hot(uniq, rank, ovf_r, S, 1,
                                      crec.hot_vtiles(cell_max))
    w = (rng.standard_normal(NB)
         * np.exp2(rng.uniform(-20, 20, NB))).astype(np.float32)
    dual = rng.standard_normal(ROWS).astype(np.float32)
    g0 = rng.standard_normal(NB).astype(np.float32)
    dev = jax.device_put
    hot_m = tilemm.hot_margin_rows(dev(w), dev(ovf_u), dev(ovf_pw), SPEC)
    coo_m = tilemm.spill_margin_rows(dev(w), dev(ovf_b), dev(ovf_r), SPEC)
    hot_g = tilemm.hot_grad_scatter(dev(g0), dev(dual), dev(ovf_u),
                                    dev(ovf_pw), SPEC)
    coo_g = tilemm.spill_grad_scatter(dev(g0), dev(dual), dev(ovf_b),
                                      dev(ovf_r), SPEC)
    return dict(ovf_b=ovf_b, ovf_r=ovf_r, w=w, dual=dual, g0=g0,
                hot_m=np.asarray(hot_m), coo_m=np.asarray(coo_m),
                hot_g=np.asarray(hot_g), coo_g=np.asarray(coo_g))


def test_hot_margins_are_the_coo_margins(helper_case):
    c = helper_case
    per_row = np.bincount(c["ovf_r"], minlength=ROWS)
    assert (per_row == 1).sum() >= 50 and (per_row > 1).sum() > 100
    one = per_row <= 1
    assert c["hot_m"][one].tobytes() == c["coo_m"][one].tobytes()
    exact = np.zeros(ROWS)
    np.add.at(exact, c["ovf_r"], c["w"][c["ovf_b"]].astype(np.float64))
    scale = np.zeros(ROWS)
    np.add.at(scale, c["ovf_r"], np.abs(c["w"][c["ovf_b"]], dtype=np.float64))
    # float32 sums of a row's few pairs, in some order: a few ulps of the
    # largest magnitude summed, for both paths alike
    for got in (c["hot_m"], c["coo_m"]):
        assert np.all(np.abs(got - exact) <= 4 * 2.0 ** -24 * scale)


def test_hot_gradient_is_the_coo_gradient(helper_case):
    c = helper_case
    per_bucket = np.bincount(c["ovf_b"], minlength=NB)
    assert (per_bucket == 1).sum() >= 50 and (per_bucket > 1).sum() > 100
    one = per_bucket <= 1
    assert c["hot_g"][one].tobytes() == c["coo_g"][one].tobytes()
    assert np.array_equal(c["hot_g"][per_bucket == 0],
                          c["g0"][per_bucket == 0])
    exact = c["g0"].astype(np.float64)
    np.add.at(exact, c["ovf_b"], c["dual"][c["ovf_r"]].astype(np.float64))
    scale = np.abs(c["g0"]).astype(np.float64)
    np.add.at(scale, c["ovf_b"], np.abs(c["dual"][c["ovf_r"]],
                                        dtype=np.float64))
    # a hot bucket sums thousands of duals: float32 accumulation in
    # whatever order, bounded by the count times an ulp of the magnitude
    bound = (per_bucket + 4) * 2.0 ** -24 * scale
    for got in (c["hot_g"], c["coo_g"]):
        assert np.all(np.abs(got - exact) <= bound)


# -- the rule -----------------------------------------------------------------

def test_hot_room_rule_and_counters(monkeypatch):
    """Size first (a static shape), then pairs a distinct bucket; the
    room grows when passed and never shrinks; ``drain`` says what was
    chosen and starts over."""
    rng = np.random.default_rng(13)
    room = HotRoom()
    pad = lambda b, r, n: tilemm.cap_overflow(b, r, n)   # noqa: E731
    b, r = _list("skewed", rng)
    # under HOT_MIN_ROOM slots: COO whatever it names
    assert len(b) < crec.HOT_MIN_ROOM
    assert room.form(*pad(b, r, crec.overflow_room(len(b))), S) is None
    # an empty list is no list: nothing is counted
    assert room.form(*pad(b[:0], r[:0], crec.HOT_MIN_ROOM), S) is None
    # the same pairs at a long room: hot
    form = room.form(*pad(b, r, crec.HOT_MIN_ROOM), S)
    assert set(form) == {"ovf_u", "ovf_pw"}
    assert _multiset(*tilemm.decode_hot(form["ovf_u"], form["ovf_pw"],
                                        S)) == _multiset(b, r)
    distinct = len(np.unique(b))
    # mostly distinct buckets at a long room: COO
    ub, ur = _list("uniform", rng)
    assert len(np.unique(ub)) * crec.HOT_MIN_SHARE > len(ub)
    assert room.form(*pad(ub, ur, crec.HOT_MIN_ROOM), S) is None
    assert room.drain() == {"hot_blocks": 1, "coo_blocks": 2,
                            "hot_buckets": distinct,
                            "hot_room": form["ovf_pw"].size}
    assert room.drain()["hot_blocks"] == 0
    # a list past the room grows it; a smaller one keeps what it has
    tiles, vtiles = room.tiles, room.vtiles
    big_b, big_r = _list("two_hot_tiles", rng)
    grown = room.form(*pad(big_b, big_r, 3 * crec.HOT_MIN_ROOM), S)
    assert (room.tiles, room.vtiles) == (2, crec.hot_vtiles(
        tilemm.hot_ranks(big_b, big_r, S)[2]))
    assert room.tiles > tiles and room.vtiles >= vtiles
    again = room.form(*pad(b, r, crec.HOT_MIN_ROOM), S)
    assert again["ovf_pw"].shape == grown["ovf_pw"].shape
    assert _multiset(*tilemm.decode_hot(again["ovf_u"], again["ovf_pw"],
                                        S)) == _multiset(b, r)
    # a list with a hole in it is nobody's writer's
    holed = pad(b, r, crec.HOT_MIN_ROOM)
    holed[0][3] = tilemm.UNUSED
    with pytest.raises(ValueError):
        room.form(*holed, S)


@pytest.mark.parametrize("cell_max, vtiles", [
    (0, 8), (1, 8), (3641, 8), (3642, 16),      # an eighth more
    (91_000, 224),             # the click log's cells (1.09M pairs, 12)
    (110_600, 256),            # stream_fields' (1.33M pairs)
])
def test_hot_vtiles_of_a_count(cell_max, vtiles):
    assert crec.hot_vtiles(cell_max) == vtiles
    assert vtiles * tilemm.HOT_CAP >= cell_max


# -- the step -----------------------------------------------------------------

def _zipf_keys(rng, n):
    """Heavy-tailed keys over 5,000 values a row slot: the hottest key's
    tile passes the per-tile cap, and what is listed is a few hundred
    buckets some thousand times."""
    keys = (rng.zipf(1.3, size=(n, NNZ)) % 5000).astype(np.uint32)
    labels = ((keys[:, 0] % 2) ^ (rng.random(n) < 0.1)).astype(np.uint8)
    return keys, labels


def _one_tile_keys(rng, n):
    """Every key of another bucket of ONE tile, as many as the tile has:
    what passes the cap names mostly distinct buckets."""
    pool = np.arange(400_000, dtype=np.uint32)
    pool = pool[fold_keys32(pool, NB) < tilemm.TILE]
    keys = pool[rng.integers(0, len(pool), size=(n, NNZ))]
    return keys, (rng.random(n) < 0.4).astype(np.uint8)


def _ftrl64(blocks, nb, alpha, beta, l1, l2):
    """float64 FTRL over whole blocks (sgd_server_handle.h:111-141), every
    bucket updated by the dense-apply rule the tile step follows."""
    w, z, cg = (np.zeros(nb) for _ in range(3))
    for keys, labels in blocks:
        rr, cc = np.nonzero(keys != crec.SENTINEL_KEY)
        b = fold_keys32(keys[rr, cc], nb).astype(np.int64)
        margin = np.zeros(len(keys))
        np.add.at(margin, rr, w[b])
        y = 2.0 * labels - 1.0
        dual = -y / (1 + np.exp(y * margin))
        grad = np.zeros(nb)
        np.add.at(grad, b, dual[rr])
        cg_new = np.sqrt(cg * cg + grad * grad)
        z = z + grad - (cg_new - cg) / alpha * w
        cg = cg_new
        w = (np.sign(-z) * np.maximum(np.abs(z) - l1, 0.0)
             / ((beta + cg) / alpha + l2))
    return w


FTRL_HELPERS = ("hot_margin_rows", "hot_grad_scatter", "spill_margin_rows",
                "spill_grad_scatter")
FM_HELPERS = ("fm_hot_pull_rows", "hot_push_scatter_planes",
              "fm_spill_pull_rows", "spill_push_scatter_planes")
FM_DIM = 4


def _ftrl_app(path, **over):
    return make_app(path, "crec", num_buckets=NB, tile_online="on",
                    max_data_pass=1, lr_eta=0.1, **over)


def _fm_app(path, tile_step_kernel="fused", **over):
    """``AsyncSGD`` over an ``FMStore`` as ``models/fm.main`` builds it,
    online tile path, one device."""
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.models.fm import FMConfig, FMStore
    from wormhole_tpu.utils.config import Config
    from test_tile_online import single_device_rt
    kw = dict(train_data=str(path), data_format="crec", num_buckets=NB,
              tile_online="on", max_data_pass=1, disp_itv=1e12, max_delay=1,
              pipeline_workers=0, tile_step_kernel=tile_step_kernel)
    kw.update(over)
    rt = single_device_rt()
    store = FMStore(FMConfig(num_buckets=NB, dim=FM_DIM, seed=3,
                             tile_step_kernel=tile_step_kernel), rt)
    return AsyncSGD(Config(**kw), rt, store=store)


def _run(tmp_path, name, blocks, monkeypatch, min_room, app=_ftrl_app,
         helpers=FTRL_HELPERS, **over):
    """One pass of ``blocks`` through the online tile path; what crossed
    to the device and which helpers the step programs were traced with."""
    n = tilemm.RSUB
    v1 = tmp_path / f"{name}.crec"
    write_v1(v1, np.concatenate([k for k, _l in blocks]),
             np.concatenate([l for _k, l in blocks]), block_rows=n)
    monkeypatch.setattr(crec, "HOT_MIN_ROOM", min_room)
    traced = []
    for fn in helpers:
        def spy(*a, _fn=getattr(tilemm, fn), _name=fn, **k):
            traced.append(_name)
            return _fn(*a, **k)
        monkeypatch.setattr(tilemm, fn, spy)
    app = app(v1, **over)
    shipped = []
    put = app.store.put_block
    app.store.put_block = lambda b: (shipped.append(put(b)), shipped[-1])[1]
    app.run()
    return app, shipped, traced


@pytest.mark.parametrize("outcome", ["hot", "coo_by_size",
                                     "coo_by_distinct"])
def test_each_outcome_reaches_its_program_and_counter(tmp_path, monkeypatch,
                                                      outcome):
    rng = np.random.default_rng(17)
    n = tilemm.RSUB
    make = _one_tile_keys if outcome == "coo_by_distinct" else _zipf_keys
    blocks = [make(rng, n) for _ in range(2)]
    info = online_info(NNZ, n, NB)
    lists = [crec.encode_tile_pairs(k, NB, info.spec)[1] for k, _l in blocks]
    assert min(len(b) for b in lists) > crec.ONLINE_OVF_CAP
    small = outcome != "coo_by_size"
    from wormhole_tpu import obs
    before = [m.value for m in obs.metrics.overflow_hot_metrics()[:2]]
    app, shipped, traced = _run(tmp_path, outcome, blocks, monkeypatch,
                                1024 if small else crec.HOT_MIN_ROOM)
    t = app.timer.totals
    if outcome == "hot":
        assert all(set(b) == {"pw", "labels", "ovf_u", "ovf_pw"}
                   for b in shipped)
        assert set(traced) == {"hot_margin_rows", "hot_grad_scatter"}
        assert t["overflow_hot_blocks"] == 2 and t["overflow_coo_blocks"] == 0
        assert t["overflow_hot_buckets"] == sum(len(np.unique(b))
                                                for b in lists)
        for b, dev in zip(lists, shipped):
            assert dev["ovf_u"].shape == (tilemm.TILE,)
            assert int((np.asarray(dev["ovf_u"]) != tilemm.UNUSED).sum()
                       ) == len(np.unique(b))
    else:
        assert all(set(b) == {"pw", "labels", "ovf_b", "ovf_r"}
                   for b in shipped)
        assert set(traced) == {"spill_margin_rows", "spill_grad_scatter"}
        assert t["overflow_hot_blocks"] == 0 and t["overflow_coo_blocks"] == 2
        assert t["overflow_hot_buckets"] == 0
    assert t["online_overflow_pairs"] == sum(len(b) for b in lists)
    if outcome == "coo_by_distinct":
        assert all(len(np.unique(b)) * crec.HOT_MIN_SHARE > len(b)
                   for b in lists)
    hot_c, coo_c, _buckets_c, room_g = obs.metrics.overflow_hot_metrics(
        app.obs.registry)
    assert (hot_c.value - before[0], coo_c.value - before[1]) == (
        t["overflow_hot_blocks"], t["overflow_coo_blocks"])
    if outcome == "hot":
        assert room_g.value == shipped[0]["ovf_pw"].size


def test_eval_pass_keeps_the_coo_list(tmp_path, monkeypatch):
    """An eval pass's feed makes no hot form: the eval step reads the COO
    list (and would read a hot form too, were it handed one)."""
    rng = np.random.default_rng(19)
    blocks = [_zipf_keys(rng, tilemm.RSUB)]
    app, shipped, traced = _run(tmp_path, "ev", blocks, monkeypatch, 1024,
                                val_data=str(tmp_path / "ev.crec"))
    assert "ovf_pw" in shipped[0] and "ovf_b" in shipped[-1]
    assert traced.count("spill_margin_rows") == 1     # the eval program
    assert app.timer.totals["overflow_hot_blocks"] == 1
    # handed a hot block, the eval step gives the COO block's margins but
    # for the order of a row's float32 sums
    info = online_info(NNZ, tilemm.RSUB, NB)
    got = app.store.tile_eval_step(shipped[0], info)[5]
    want = app.store.tile_eval_step(shipped[-1], info)[5]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kernel", ["fused", "split"])
def test_zipf_ftrl_run_lands_on_the_oracle(tmp_path, monkeypatch, kernel):
    """Three blocks of Zipf keys at 2**16 buckets, an eighth of the pairs
    listed: through the hot tile the table is the COO path's but for the
    order of float32 sums (five decades under the tile kernels' own
    bfloat16 rounding), and both land on float64 FTRL within the limits
    the tile-path tests hold a run to (test_crec2: rtol 0.05, atol
    5e-3)."""
    rng = np.random.default_rng(23)
    blocks = [_zipf_keys(rng, tilemm.RSUB) for _ in range(3)]
    hot, shipped, _ = _run(tmp_path, "hot", blocks, monkeypatch, 1024,
                           tile_step_kernel=kernel)
    assert hot.timer.totals["overflow_hot_blocks"] == 3
    assert hot.store.step_kernel[0] == kernel
    coo, _, _ = _run(tmp_path, "coo", blocks, monkeypatch,
                     1 << 30, tile_step_kernel=kernel)
    assert coo.timer.totals["overflow_coo_blocks"] == 3
    listed = hot.timer.totals["online_overflow_pairs"]
    assert listed > 0.1 * 3 * tilemm.RSUB * NNZ
    w_hot, w_coo = weights(hot), weights(coo)
    np.testing.assert_allclose(w_hot, w_coo, rtol=2e-5, atol=1e-7)
    w64 = _ftrl64(blocks, NB, alpha=0.1, beta=1.0, l1=0.0, l2=0.0)
    live = (np.abs(w64) > 1e-6) | (np.abs(w_hot) > 1e-6)
    assert live.sum() > 100
    for w in (w_hot, w_coo):
        assert np.allclose(w[live], w64[live], rtol=0.05, atol=5e-3)


# -- FMStore's list through the same pair (ISSUE 48) --------------------------

FM_LISTS = ["skewed", "two_small_hot_tiles", "one_pair", "empty"]


@pytest.fixture(scope="module", params=FM_LISTS)
def fm_helper_case(request):
    """A list of one kind with fifty pairs planted on rows and buckets of
    their own, over ``w`` and ``v`` planes whose lone buckets hold values of
    every class of ``split3``, and both paths' pulls and pushes."""
    from wormhole_tpu.ops.loss import opaque_one
    kind = request.param
    rng = np.random.default_rng(29)
    k = 2
    ovf_b, ovf_r, lone_b, lone_r = _with_lone_pairs(*_list(kind, rng))
    uniq, rank, cell_max = tilemm.hot_ranks(ovf_b, ovf_r, S)
    tiles = -(-len(uniq) // tilemm.TILE)
    assert tiles == (2 if kind == "two_small_hot_tiles" else 1)
    ovf_u, ovf_pw = tilemm.encode_hot(uniq, rank, ovf_r, S, tiles,
                                      crec.hot_vtiles(cell_max))
    # an empty tail: the COO list at a room a third longer than its pairs
    room = len(ovf_b) + len(ovf_b) // 3 + 8
    coo_b, coo_r = tilemm.cap_overflow(ovf_b, ovf_r, room)
    planes = [(rng.standard_normal(NB) * np.exp2(rng.uniform(-20, 20, NB))
               ).astype(np.float32) for _ in range(1 + k)]
    classes = np.concatenate(list(_split_cases().values()))
    classes = classes[np.isfinite(classes)]
    planes[0][lone_b] = rng.choice(classes, len(lone_b))
    # ... a factor's square inside split3's exact range (2**-103 and up)
    mid = classes[(np.abs(classes) > 1e-12) & (np.abs(classes) < 1e18)]
    for v in planes[1:]:
        v[lone_b] = rng.choice(mid, len(lone_b))
    dual = (rng.standard_normal((ROWS, k + 2))
            * np.exp2(rng.uniform(-20, 20, (ROWS, k + 2)))).astype(np.float32)
    dual[lone_r, 0] = rng.choice(classes, len(lone_r))
    dual[:, -1] = rng.random(ROWS) < 0.9          # the count channel: 0 or 1
    push0 = [rng.standard_normal(NB).astype(np.float32) for _ in range(k + 2)]
    dev = jax.device_put
    shape = (NB // tilemm.TILE, tilemm.A_HI, tilemm.B_LO)
    theta = [dev(p.reshape(shape)) for p in planes]
    one = opaque_one(dev(np.ones(3, np.float32)))
    hot_p = tilemm.fm_hot_pull_rows(theta, dev(ovf_u), dev(ovf_pw), SPEC, one)
    coo_p = tilemm.fm_spill_pull_rows(theta, dev(coo_b), dev(coo_r), SPEC,
                                      one)
    push = tuple(dev(p.reshape(shape)) for p in push0)
    hot_g = tilemm.hot_push_scatter_planes(push, dev(dual), dev(ovf_u),
                                           dev(ovf_pw), SPEC)
    coo_g = tilemm.spill_push_scatter_planes(push, dev(dual), dev(coo_b),
                                             dev(coo_r), SPEC)
    q = sum(v.astype(np.float64) ** 2 for v in planes[1:])
    return dict(
        kind=kind, k=k, ovf_b=ovf_b, ovf_r=ovf_r, dual=dual, push0=push0,
        channels=[p.astype(np.float64) for p in planes] + [q],
        hot_p=np.asarray(hot_p), coo_p=np.asarray(coo_p),
        hot_g=[np.asarray(g).reshape(-1) for g in hot_g],
        coo_g=[np.asarray(g).reshape(-1) for g in coo_g])


def test_fm_hot_pulls_are_the_coo_pulls(fm_helper_case):
    """``[w, v, sum v**2]`` a listed pair, summed onto rows: the COO
    helper's bits where a row has one listed pair (so the float32 values
    crossed the hot tile unrounded, whatever their class, and the squares
    were formed from unrounded factors), a few ulps of the summed
    magnitudes where it has many."""
    c = fm_helper_case
    assert c["hot_p"].shape == c["coo_p"].shape == (ROWS, c["k"] + 2)
    per_row = np.bincount(c["ovf_r"], minlength=ROWS)
    assert (per_row == 1).sum() >= 50
    if c["kind"] in ("skewed", "two_small_hot_tiles"):
        assert (per_row > 1).sum() > 100
    one = per_row <= 1
    assert c["hot_p"][one].tobytes() == c["coo_p"][one].tobytes()
    assert c["hot_p"][per_row == 1].any()
    for ch, vals in enumerate(c["channels"]):
        exact, scale = np.zeros(ROWS), np.zeros(ROWS)
        np.add.at(exact, c["ovf_r"], vals[c["ovf_b"]])
        np.add.at(scale, c["ovf_r"], np.abs(vals[c["ovf_b"]]))
        # sum v**2 is itself k rounded float32 additions a pair
        bound = (per_row + c["k"] + 2) * 2.0 ** -24 * scale
        for got in (c["hot_p"], c["coo_p"]):
            assert np.all(np.abs(got[:, ch] - exact) <= bound)


def test_fm_hot_pushes_are_the_coo_pushes(fm_helper_case):
    c = fm_helper_case
    per_bucket = np.bincount(c["ovf_b"], minlength=NB)
    assert (per_bucket == 1).sum() >= 50
    if c["kind"] == "skewed":
        assert per_bucket.max() > 1000        # thousands of pairs a bucket
    one = per_bucket <= 1
    for ch in range(c["k"] + 2):
        hot, coo, g0 = c["hot_g"][ch], c["coo_g"][ch], c["push0"][ch]
        assert hot[one].tobytes() == coo[one].tobytes()
        assert np.array_equal(hot[per_bucket == 0], g0[per_bucket == 0])
        d = c["dual"][c["ovf_r"], ch].astype(np.float64)
        exact, scale = g0.astype(np.float64), np.abs(g0).astype(np.float64)
        np.add.at(exact, c["ovf_b"], d)
        np.add.at(scale, c["ovf_b"], np.abs(d))
        bound = (per_bucket + 4) * 2.0 ** -24 * scale
        for got in (hot, coo):
            assert np.all(np.abs(got - exact) <= bound)


def test_the_ftrl_helpers_are_the_one_channel_case(helper_case):
    """One pair of helpers, parameterised by the channels: FTRL's margins
    and gradient are FM's pulls and pushes of a lone channel, to the bit."""
    c = helper_case
    dev = jax.device_put
    uniq, rank, cell_max = tilemm.hot_ranks(c["ovf_b"], c["ovf_r"], S)
    ovf_u, ovf_pw = tilemm.encode_hot(uniq, rank, c["ovf_r"], S, 1,
                                      crec.hot_vtiles(cell_max))
    tiles, vtiles, hs, _valid, idx = tilemm._hot_dims(dev(ovf_u),
                                                      dev(ovf_pw), SPEC)
    wu = jnp.asarray(c["w"])[idx].reshape(tiles, tilemm.A_HI, tilemm.B_LO)
    pulls = tilemm._hot_pull([wu], dev(ovf_pw), vtiles, hs)
    assert pulls.shape == (ROWS, 1)
    assert np.asarray(pulls)[:, 0].tobytes() == c["hot_m"].tobytes()
    gu = tilemm._hot_push(dev(c["dual"])[:, None], dev(ovf_pw), tiles,
                          vtiles, hs)
    assert gu.shape == (tiles, tilemm.A_HI, 1, tilemm.B_LO)
    got = c["g0"].copy()
    np.add.at(got, np.asarray(idx), np.asarray(gu).reshape(-1))
    listed = np.bincount(c["ovf_b"], minlength=NB) > 0
    assert got[listed].tobytes() == c["hot_g"][listed].tobytes()


@pytest.mark.parametrize("outcome", ["hot", "coo_by_size",
                                     "coo_by_distinct"])
def test_each_outcome_reaches_its_fm_program_and_counter(tmp_path,
                                                         monkeypatch,
                                                         outcome):
    """``HotRoom``'s three outcomes through an ``FMStore`` job: what
    crosses, which helpers its spill step is traced with, and the Timer's
    counts, the store's own among them (the pairs are counted from the COO
    list on the host whichever form crosses)."""
    rng = np.random.default_rng(17)
    n = tilemm.RSUB
    make = _one_tile_keys if outcome == "coo_by_distinct" else _zipf_keys
    blocks = [make(rng, n) for _ in range(2)]
    info = online_info(NNZ, n, NB)
    lists = [crec.encode_tile_pairs(k, NB, info.spec)[1] for k, _l in blocks]
    small = outcome != "coo_by_size"
    app, shipped, traced = _run(tmp_path, outcome, blocks, monkeypatch,
                                1024 if small else crec.HOT_MIN_ROOM,
                                app=_fm_app, helpers=FM_HELPERS)
    t = app.timer.totals
    assert app.timer is app.store.timer
    if outcome == "hot":
        assert all(set(b) == {"pw", "labels", "ovf_u", "ovf_pw"}
                   for b in shipped)
        assert set(traced) == {"fm_hot_pull_rows", "hot_push_scatter_planes"}
        assert t["overflow_hot_blocks"] == 2 and t["overflow_coo_blocks"] == 0
        assert t["overflow_hot_buckets"] == sum(len(np.unique(b))
                                                for b in lists)
    else:
        assert all(set(b) == {"pw", "labels", "ovf_b", "ovf_r"}
                   for b in shipped)
        assert set(traced) == {"fm_spill_pull_rows",
                               "spill_push_scatter_planes"}
        assert t["overflow_hot_blocks"] == 0 and t["overflow_coo_blocks"] == 2
    assert t["fm_spill_blocks"] == 2 and "fm_in_place_blocks" not in t
    assert t["fm_listed_pairs"] == sum(len(b) for b in lists)
    assert t["online_overflow_pairs"] == t["fm_listed_pairs"]
    assert app.timer.counts.get("table_cross", 0) == 0


def test_fm_eval_pass_keeps_the_coo_list(tmp_path, monkeypatch):
    rng = np.random.default_rng(19)
    blocks = [_zipf_keys(rng, tilemm.RSUB)]
    app, shipped, traced = _run(tmp_path, "fmev", blocks, monkeypatch, 1024,
                                app=_fm_app, helpers=FM_HELPERS,
                                val_data=str(tmp_path / "fmev.crec"))
    assert "ovf_pw" in shipped[0] and "ovf_b" not in shipped[0]
    assert set(shipped[-1]) == {"pw", "labels", "ovf_b", "ovf_r"}
    assert traced.count("fm_spill_pull_rows") == 1    # the eval program
    assert traced.count("fm_hot_pull_rows") == 1
    assert app.timer.totals["overflow_hot_blocks"] == 1
    # handed a hot block, the eval step gives the COO block's margins but
    # for the order of a row's float32 sums
    info = online_info(NNZ, tilemm.RSUB, NB)
    got = app.store.tile_eval_step(shipped[0], info)[5]
    want = app.store.tile_eval_step(shipped[-1], info)[5]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def _fm64(blocks, nb, k, cfg):
    """float64 FM with AdaGrad over whole blocks (``FMAdaGrad``'s rule:
    every bucket a block touched, once), from ``FMStore``'s own draw of
    v0: ``(w, v)``."""
    rng = np.random.default_rng(cfg.seed)
    v = (cfg.init_scale * rng.standard_normal((nb, k))).astype(
        np.float32).astype(np.float64)
    w, cg_w, cg_v = np.zeros(nb), np.zeros(nb), np.zeros((nb, k))
    for keys, labels in blocks:
        rr, cc = np.nonzero(keys != crec.SENTINEL_KEY)
        b = fold_keys32(keys[rr, cc], nb).astype(np.int64)
        lin, q = np.zeros(len(keys)), np.zeros(len(keys))
        s = np.zeros((len(keys), k))
        np.add.at(lin, rr, w[b])
        np.add.at(s, rr, v[b])
        np.add.at(q, rr, (v[b] ** 2).sum(axis=1))
        margin = lin + 0.5 * ((s ** 2).sum(axis=1) - q)
        y = 2.0 * labels - 1.0
        dual = -y / (1 + np.exp(y * margin))
        g_w, push = np.zeros(nb), np.zeros((nb, k))
        np.add.at(g_w, b, dual[rr])
        np.add.at(push, b, dual[rr, None] * s[rr])
        touched = np.bincount(b, minlength=nb) > 0
        g_v = push - v * (g_w - cfg.l2_v)[:, None]
        for x, acc, g, t in ((w, cg_w, g_w, touched),
                             (v, cg_v, g_v, touched[:, None])):
            new = np.sqrt(acc * acc + g * g)
            step = cfg.lr_alpha / (cfg.lr_beta + new) * g
            acc[...] = np.where(t, new, acc)
            x[...] = np.where(t, x - step, x)
    return w, v


def _fm_table(app):
    return np.asarray(app.store.slots)[:, :1 + FM_DIM]


def _fm_gap(table, ref, init):
    """How far ``table`` is from ``ref``: the rms of the difference over
    the rms of ``ref``'s change from ``init`` (the benchmark's
    ``state_rel_rms``, over the whole table), and the share of entries
    further than rtol 2e-5, atol 1e-7 (FTRL's run is held to those entry by
    entry; FM's ``dual * s - v * g_w`` cancels at a hot bucket, where the
    order of thousands of float32 additions then shows)."""
    d = np.abs(table - ref)
    return (np.sqrt((d ** 2).mean() / ((ref - init) ** 2).mean()),
            (d > 2e-5 * np.abs(ref) + 1e-7).mean())


@pytest.mark.parametrize("kernel", ["fused", "split"])
def test_zipf_fm_run_lands_on_the_oracle(tmp_path, monkeypatch, kernel):
    """Three blocks of Zipf keys at 2**16 buckets through ``AsyncSGD`` and
    an ``FMStore``: through the hot tile ``w`` and ``v`` are the COO path's
    but for the order of float32 sums (2e-5 of the table's change by rms,
    all but a few entries in 100,000 within rtol 2e-5), both land on
    float64 FM within the limits the tile-path tests hold a step to
    (test_fm_tile: rtol 0.02, atol 2e-3), and a list whose values are
    rounded to bfloat16 on their way to the hot tile (the planted fault:
    ``split3`` keeping the first part alone) is refused by the first
    comparison, tenfold."""
    rng = np.random.default_rng(23)
    blocks = [_zipf_keys(rng, tilemm.RSUB) for _ in range(3)]
    fm = dict(app=_fm_app, helpers=FM_HELPERS, tile_step_kernel=kernel)
    hot, _, traced = _run(tmp_path, "hot", blocks, monkeypatch, 1024, **fm)
    assert hot.timer.totals["overflow_hot_blocks"] == 3
    assert hot.store.step_kernel[0] == kernel
    assert "fm_spill_pull_rows" not in traced
    coo, _, _ = _run(tmp_path, "coo", blocks, monkeypatch, 1 << 30, **fm)
    assert coo.timer.totals["overflow_coo_blocks"] == 3
    listed = hot.timer.totals["fm_listed_pairs"]
    assert listed == coo.timer.totals["fm_listed_pairs"]
    assert listed > 0.1 * 3 * tilemm.RSUB * NNZ
    from wormhole_tpu.models.fm import FMStore
    t_hot, t_coo = _fm_table(hot), _fm_table(coo)
    t_init = np.asarray(FMStore(hot.store.cfg).slots)[:, :1 + FM_DIM]
    rms, far = _fm_gap(t_hot, t_coo, t_init)
    assert rms < 2e-5 and far < 1e-4, (rms, far)
    assert np.abs(t_hot - t_coo).max() < 1e-4
    w64, v64 = _fm64(blocks, NB, FM_DIM, hot.store.cfg)
    t64 = np.concatenate([w64[:, None], v64], axis=1)
    moved = np.abs(t64[:, 0]) > 1e-6
    assert moved.sum() > 100
    for t in (t_hot, t_coo):
        np.testing.assert_allclose(t[moved], t64[moved], rtol=0.02,
                                   atol=2e-3)

    def rounded(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, jnp.zeros_like(hi), jnp.zeros_like(hi)
    monkeypatch.setattr(tilemm, "split3", rounded)
    bad, _, _ = _run(tmp_path, "bad", blocks, monkeypatch, 1024, **fm)
    assert bad.timer.totals["overflow_hot_blocks"] == 3
    rms, far = _fm_gap(_fm_table(bad), t_coo, t_init)
    assert rms > 2e-4 and far > 1e-3, (rms, far)
