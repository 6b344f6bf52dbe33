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
    oracle, and a list rounded to bfloat16 on its way is refused;
  * ``WideDeepStore``'s spill step takes it too (ISSUE 52): its 1 + k
    planes pulled and k + 2 dual channels pushed as they stand, a call a
    part where one call does not admit ``3c`` parts (three calls of 33 and
    of 34 channels at the click-log cell's widths), the push's last step
    the COO helper's own over the hot tiles' sums; a push that leaves a
    channel out is off the oracle and a hot block that loses its list is
    counted.
"""

import contextlib

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
WD_HELPERS = ("plane_hot_pull_rows", "hot_push_scatter_lanes",
              "plane_spill_pull_rows", "spill_push_scatter_lanes")
FM_DIM = 4
WD_DIM = 4


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


def _wd_app(path, hidden=(8,), **over):
    """``AsyncSGD`` over a ``WideDeepStore`` as ``models/wide_deep.build_app``
    builds it, online tile path, one device, a ReLU layer in the tower
    unless ``hidden`` is empty. A block with a list takes the split pair
    whatever the knob says."""
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    from wormhole_tpu.utils.config import Config
    from test_tile_online import single_device_rt
    kw = dict(train_data=str(path), data_format="crec", num_buckets=NB,
              tile_online="on", max_data_pass=1, disp_itv=1e12, max_delay=1,
              pipeline_workers=0)
    kw.update(over)
    rt = single_device_rt()
    store = WideDeepStore(WideDeepConfig(num_buckets=NB, dim=WD_DIM,
                                         hidden=tuple(hidden), seed=3), rt)
    return AsyncSGD(Config(**kw), rt, store=store)


# a store's job, the helpers its two list phases may be traced with (the hot
# push's last step IS the COO helper, over the hot tiles' sums: wide&deep's
# hot program traces both), and its Timer's own counts
STORES = {
    "fm": dict(app=_fm_app, helpers=FM_HELPERS,
               hot={"fm_hot_pull_rows", "hot_push_scatter_planes"},
               coo={"fm_spill_pull_rows", "spill_push_scatter_planes"},
               spill="fm_spill_blocks", pairs="fm_listed_pairs",
               other="fm_in_place_blocks"),
    "wd": dict(app=_wd_app, helpers=WD_HELPERS,
               hot={"plane_hot_pull_rows", "hot_push_scatter_lanes",
                    "spill_push_scatter_lanes"},
               coo={"plane_spill_pull_rows", "spill_push_scatter_lanes"},
               spill="wd_spill_blocks", pairs="wd_listed_pairs",
               other="wd_listless_blocks"),
}


@pytest.fixture(params=list(STORES))
def store(request):
    """``STORES``' entry for FM's job or wide&deep's, the latter with its
    15 and 18 parts cut a call a part as the cell's 99 and 102 are."""
    if request.param == "wd":
        request.getfixturevalue("a_call_a_part")
    return STORES[request.param]


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
# the store whose helpers a case runs: FM's (planes in, planes out, the
# formed channel, ONE call) or wide&deep's (the planes as they stand, the
# pushes tiled, a call a part)
HELPER_CASES = [("fm", kind) for kind in FM_LISTS] + [
    ("wd", "skewed"), ("wd", "two_small_hot_tiles"), ("wd", "one_pair")]
# tiles_step * (ch + 6) of one call (tilemm.MULTI_BUDGET) at which the
# helper cases' 9 and 12 parts (and the app cases' 15 and 18) do not go
# through one call and their 3 to 6 channels do: the cut the click-log
# cell's 99 and 102 parts take at the shipped 128
SMALL_BUDGET = 24


@contextlib.contextmanager
def _small_budget():
    """The hot helpers cut a call a part at the tests' few channels; the
    kernel builders' caches hold nothing built under the other budget."""
    builders = (tilemm._build_fwd_multi, tilemm._build_bwd_multi)
    for b in builders:
        b.cache_clear()
    budget, tilemm.MULTI_BUDGET = tilemm.MULTI_BUDGET, SMALL_BUDGET
    try:
        yield
    finally:
        tilemm.MULTI_BUDGET = budget
        for b in builders:
            b.cache_clear()


@pytest.fixture
def a_call_a_part():
    with _small_budget():
        yield


def _pallas_calls(fn, *args) -> int:
    """The kernel calls ``fn`` traces to, each call site counted."""
    def count(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += count(sub)
        return n
    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.fixture(scope="module", params=HELPER_CASES,
                ids=lambda c: c[1] if c[0] == "fm" else "-".join(c))
def fm_helper_case(request):
    """A list of one kind with fifty pairs planted on rows and buckets of
    their own, over ``w`` and ``v`` planes whose lone buckets hold values of
    every class of ``split3``, and both paths' pulls and pushes, through
    one store's helpers."""
    from wormhole_tpu.ops.loss import opaque_one
    store, kind = request.param
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
    push = tuple(dev(p.reshape(shape)) for p in push0)
    channels = [p.astype(np.float64) for p in planes]
    if store == "fm":
        one = opaque_one(dev(np.ones(3, np.float32)))
        hot_p = tilemm.fm_hot_pull_rows(theta, dev(ovf_u), dev(ovf_pw), SPEC,
                                        one)
        coo_p = tilemm.fm_spill_pull_rows(theta, dev(coo_b), dev(coo_r),
                                          SPEC, one)
        hot_g = tilemm.hot_push_scatter_planes(push, dev(dual), dev(ovf_u),
                                               dev(ovf_pw), SPEC)
        coo_g = tilemm.spill_push_scatter_planes(push, dev(dual), dev(coo_b),
                                                 dev(coo_r), SPEC)
        channels.append(sum(v.astype(np.float64) ** 2 for v in planes[1:]))
    else:
        # wide&deep pulls its 1 + k planes as they stand and adds into the
        # push kernel's own tiled output; 9 and 12 parts, a call a part
        tiled = jnp.concatenate(push, axis=-1)
        hot = (dev(ovf_u), dev(ovf_pw))
        with _small_budget():
            calls = (_pallas_calls(lambda *a: tilemm.plane_hot_pull_rows(
                         *a, SPEC), theta, *hot),
                     _pallas_calls(lambda *a: tilemm.hot_push_scatter_lanes(
                         *a, SPEC), tiled, dev(dual), *hot))
            hot_p = tilemm.plane_hot_pull_rows(theta, *hot, SPEC)
            hot_g = tilemm.hot_push_scatter_lanes(tiled, dev(dual), *hot,
                                                  SPEC)
        assert calls == (tilemm.HOT_CH, tilemm.HOT_CH)
        coo_p = tilemm.plane_spill_pull_rows(theta, dev(coo_b), dev(coo_r),
                                             SPEC)
        coo_g = tilemm.spill_push_scatter_lanes(tiled, dev(dual), dev(coo_b),
                                                dev(coo_r), SPEC)
    return dict(
        kind=kind, k=k, ovf_b=ovf_b, ovf_r=ovf_r, dual=dual, push0=push0,
        channels=channels,
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
    assert c["hot_p"].shape == c["coo_p"].shape == (ROWS,
                                                    len(c["channels"]))
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


@pytest.mark.parametrize("c, calls", [
    (1, 1),             # FTRL's 3 parts
    (10, 1),            # FM's 30 at the cells' dim 8
    (19, 1), (20, 3),   # the last count one call admits, the first it does not
    (33, 3), (34, 3),   # wide&deep's 99 and 102 at the cells' dim 32
])
def test_the_parts_go_through_one_call_or_a_call_a_part(c, calls):
    """The cut is read from the operand's shape: ``3c`` parts against what
    one call of the multi-channel pair admits at the hot spec's two tiles a
    step. What fits stays ONE call each way (the other stores' programs are
    the parent's), what does not is three calls of ``c`` channels."""
    hs = tilemm.hot_spec(8, S)
    assert hs.tiles_step == 2 and tilemm._hot_calls(hs, c) == calls
    sds = jax.ShapeDtypeStruct
    tile = sds((1, tilemm.A_HI, tilemm.B_LO), jnp.float32)
    pw = sds(hs.pairs_shape, jnp.uint32)
    assert _pallas_calls(lambda vals, pw: tilemm._hot_pull(vals, pw, 8, hs),
                         [tile] * c, pw) == calls
    assert _pallas_calls(lambda d, pw: tilemm._hot_push(d, pw, 1, 8, hs),
                         sds((ROWS, c), jnp.float32), pw) == calls


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
                                                         outcome, store):
    """``HotRoom``'s three outcomes through an ``FMStore`` job and through
    a ``WideDeepStore`` job: what crosses, which helpers the spill step is
    traced with, and the Timer's counts, the store's own among them (the
    pairs are counted from the COO list on the host whichever form
    crosses)."""
    rng = np.random.default_rng(17)
    n = tilemm.RSUB
    make = _one_tile_keys if outcome == "coo_by_distinct" else _zipf_keys
    blocks = [make(rng, n) for _ in range(2)]
    info = online_info(NNZ, n, NB)
    lists = [crec.encode_tile_pairs(k, NB, info.spec)[1] for k, _l in blocks]
    small = outcome != "coo_by_size"
    app, shipped, traced = _run(tmp_path, outcome, blocks, monkeypatch,
                                1024 if small else crec.HOT_MIN_ROOM,
                                app=store["app"], helpers=store["helpers"])
    t = app.timer.totals
    assert app.timer is app.store.timer
    if outcome == "hot":
        assert all(set(b) == {"pw", "labels", "ovf_u", "ovf_pw"}
                   for b in shipped)
        assert set(traced) == store["hot"]
        assert t["overflow_hot_blocks"] == 2 and t["overflow_coo_blocks"] == 0
        assert t["overflow_hot_buckets"] == sum(len(np.unique(b))
                                                for b in lists)
    else:
        assert all(set(b) == {"pw", "labels", "ovf_b", "ovf_r"}
                   for b in shipped)
        assert set(traced) == store["coo"]
        assert t["overflow_hot_blocks"] == 0 and t["overflow_coo_blocks"] == 2
    assert t[store["spill"]] == 2 and store["other"] not in t
    assert t[store["pairs"]] == sum(len(b) for b in lists)
    assert t["online_overflow_pairs"] == t[store["pairs"]]
    assert app.timer.counts.get("table_cross", 0) == 0


def test_fm_eval_pass_keeps_the_coo_list(tmp_path, monkeypatch, store):
    rng = np.random.default_rng(19)
    blocks = [_zipf_keys(rng, tilemm.RSUB)]
    app, shipped, traced = _run(tmp_path, "fmev", blocks, monkeypatch, 1024,
                                app=store["app"], helpers=store["helpers"],
                                val_data=str(tmp_path / "fmev.crec"))
    assert "ovf_pw" in shipped[0] and "ovf_b" not in shipped[0]
    assert set(shipped[-1]) == {"pw", "labels", "ovf_b", "ovf_r"}
    pulls = sorted(h for h in store["helpers"] if "pull" in h)
    assert [traced.count(h) for h in pulls] == [1, 1]   # train hot, eval COO
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


# -- WideDeepStore's list through the same pair (ISSUE 52) --------------------

def _bf16(x):
    """float64 values rounded to bfloat16, as the tower rounds its
    operands (``tilemm._tower_mm``)."""
    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32)).astype(np.float64)


def _wd64(blocks, nb, k, cfg, fresh):
    """float64 wide&deep with AdaGrad over whole blocks (the tile step's
    rule: every bucket a block touched, once; weight decay on ``v``), from
    a fresh store's own ``v0`` and tower, the tower's matmul operands
    rounded to bfloat16 as the program states them: ``(w, v)``."""
    table = np.asarray(fresh.slots).astype(np.float64)
    w, v = table[:, 0].copy(), table[:, 1:1 + k].copy()
    cg_w, cg_v = np.zeros(nb), np.zeros((nb, k))
    mlp = {n: np.asarray(p).astype(np.float64) for n, p in fresh.mlp.items()}
    acc = {n: np.zeros_like(p) for n, p in mlp.items()}
    layers = fresh.n_layers
    for keys, labels in blocks:
        rr, cc = np.nonzero(keys != crec.SENTINEL_KEY)
        b = fold_keys32(keys[rr, cc], nb).astype(np.int64)
        wide, pooled = np.zeros(len(keys)), np.zeros((len(keys), k))
        np.add.at(wide, rr, w[b])
        np.add.at(pooled, rr, v[b])
        hs, h = [], pooled
        for i in range(layers):
            hs.append(h)
            h = _bf16(h) @ _bf16(mlp[f"W{i}"]) + mlp[f"b{i}"]
            if i + 1 < layers:
                h = np.maximum(h, 0.0)
        margin = wide + h[:, 0]
        y = 2.0 * labels - 1.0
        dual = -y / (1 + np.exp(y * margin))
        g, g_mlp = dual[:, None], {}
        for i in reversed(range(layers)):
            g_mlp[f"W{i}"] = _bf16(hs[i]).T @ _bf16(g)
            g_mlp[f"b{i}"] = g.sum(axis=0)
            g = _bf16(g) @ _bf16(mlp[f"W{i}"]).T
            if i:
                g = g * (hs[i] > 0)
        g_w, push = np.zeros(nb), np.zeros((nb, k))
        np.add.at(g_w, b, dual[rr])
        np.add.at(push, b, g[rr])
        touched = np.bincount(b, minlength=nb) > 0
        g_v = push + cfg.l2_v * v * touched[:, None]
        for x, a, gx, t in ((w, cg_w, g_w, touched),
                            (v, cg_v, g_v, touched[:, None])):
            new = np.sqrt(a * a + gx * gx)
            step = cfg.lr_alpha / (cfg.lr_beta + new) * gx
            a[...] = np.where(t, new, a)
            x[...] = np.where(t, x - step, x)
        for n in mlp:
            acc[n] = np.sqrt(acc[n] ** 2 + g_mlp[n] ** 2)
            mlp[n] = mlp[n] - cfg.lr_alpha_dense / (cfg.lr_beta
                                                     + acc[n]) * g_mlp[n]
    return w, v


def _wd_table(app):
    return np.asarray(app.store.slots)[:, :1 + WD_DIM]


def test_a_stacked_wide_deep_table_takes_a_hot_list_over_its_planes(
        tmp_path, monkeypatch, a_call_a_part):
    """The hot helpers know planes alone. A stacked ``(nb, 2(1+k))`` array
    assigned to ``slots`` (a restored checkpoint, a seeding hook) that meets
    a hot block is not sent through the ``(nb, ch)`` helpers it takes a COO
    list with: the step reads ``planes_of``'s slices at the kernels' edges,
    so it gives the planar store's table and tower to the bit, and hands
    planes back as any step of a store that keeps them does, with no
    crossing counted. No feed of a cell meets this."""
    from wormhole_tpu.learners import table as tbl
    from wormhole_tpu.models.wide_deep import WideDeepStore
    from wormhole_tpu.ops import overflow
    rng = np.random.default_rng(31)
    app, shipped, _ = _run(tmp_path, "st", [_zipf_keys(rng, tilemm.RSUB)],
                           monkeypatch, 1024, app=_wd_app, helpers=())
    assert overflow.is_hot(shipped[0])
    info = online_info(NNZ, tilemm.RSUB, NB)
    planar, stacked = (WideDeepStore(app.store.cfg) for _ in range(2))
    assert isinstance(planar.slots, tbl.PlaneTable)
    stacked.slots = jnp.asarray(np.asarray(planar.slots))
    for store in (planar, stacked):
        store.tile_train_step(shipped[0], info)
        assert store.step_kernel[0] == "split"
        assert store.timer.counts.get("table_cross", 0) == 0
    assert isinstance(stacked.slots, tbl.PlaneTable)
    assert np.asarray(stacked.slots).tobytes() == np.asarray(
        planar.slots).tobytes()
    assert np.abs(np.asarray(planar.slots)
                  - np.asarray(WideDeepStore(app.store.cfg).slots)).max() > 0
    for name, p in planar.mlp.items():
        assert np.asarray(stacked.mlp[name]).tobytes() == np.asarray(
            p).tobytes()


def test_zipf_wd_run_lands_on_the_oracle(tmp_path, monkeypatch,
                                         a_call_a_part):
    """Three blocks of Zipf keys at 2**16 buckets through ``AsyncSGD`` and a
    ``WideDeepStore`` (a block with a list steps the split pair; the tower
    one linear layer: with a ReLU a unit that the kernels' bfloat16 pulls
    turn on or off moves a bucket's ``v`` by a whole step, 9% of the
    table's change by rms against float64, and no entry-wise limit holds):
    through the hot tiles, a call a part, ``w`` and ``v`` are the COO
    path's but for the order of float32 sums, and both land on float64
    wide&deep within the limits FM's run is held to. Two planted faults are
    each caught: a hot push that leaves the last dual channel of ``v`` out
    (every listed pair of it) is off the oracle in that column, tenfold and
    more; a hot block that loses its list where it crosses is counted
    listless and not as a spill block."""
    from wormhole_tpu.models.wide_deep import WideDeepStore
    from wormhole_tpu.ops import overflow
    rng = np.random.default_rng(23)
    blocks = [_zipf_keys(rng, tilemm.RSUB) for _ in range(3)]
    from functools import partial
    wd = dict(app=partial(_wd_app, hidden=()), helpers=WD_HELPERS)
    hot, _, traced = _run(tmp_path, "hot", blocks, monkeypatch, 1024, **wd)
    assert hot.timer.totals["overflow_hot_blocks"] == 3
    assert hot.store.step_kernel[0] == "split"
    assert "plane_spill_pull_rows" not in traced
    coo, _, _ = _run(tmp_path, "coo", blocks, monkeypatch, 1 << 30, **wd)
    assert coo.timer.totals["overflow_coo_blocks"] == 3
    listed = hot.timer.totals["wd_listed_pairs"]
    assert listed == coo.timer.totals["wd_listed_pairs"]
    assert listed > 0.1 * 3 * tilemm.RSUB * NNZ
    for app in (hot, coo):
        assert app.timer.totals["wd_spill_blocks"] == 3
        assert "wd_listless_blocks" not in app.timer.totals
    t_hot, t_coo = _wd_table(hot), _wd_table(coo)
    fresh = WideDeepStore(hot.store.cfg)
    t_init = np.asarray(fresh.slots)[:, :1 + WD_DIM]
    # 3e-7 here; 2.04e-5 with a ReLU layer, the parts in one call or in
    # three alike: what the order of a hot bucket's float32 sums moves,
    # through the tower
    rms, far = _fm_gap(t_hot, t_coo, t_init)
    assert rms < 2e-5 and far < 1e-4, (rms, far)
    w64, v64 = _wd64(blocks, NB, WD_DIM, hot.store.cfg, fresh)
    t64 = np.concatenate([w64[:, None], v64], axis=1)
    moved = np.abs(t64[:, 0]) > 1e-6
    assert moved.sum() > 100
    for t in (t_hot, t_coo):
        np.testing.assert_allclose(t[moved], t64[moved], rtol=0.02,
                                   atol=2e-3)
    # the listed buckets' last embedding column against the oracle's
    info = online_info(NNZ, tilemm.RSUB, NB)
    listed_b = np.unique(np.concatenate(
        [crec.encode_tile_pairs(k, NB, info.spec)[1] for k, _l in blocks]))

    def last_column_gap(table):
        d = table[listed_b, WD_DIM] - t64[listed_b, WD_DIM]
        change = t64[listed_b, WD_DIM] - t_init[listed_b, WD_DIM]
        return np.sqrt((d ** 2).mean() / (change ** 2).mean())
    sound = last_column_gap(t_hot)
    assert sound < 0.02, sound

    real_push = tilemm._hot_push

    def short(dual_rows, *rest):
        return real_push(dual_rows.at[:, WD_DIM].set(0.0), *rest)
    monkeypatch.setattr(tilemm, "_hot_push", short)
    bad, _, _ = _run(tmp_path, "bad", blocks, monkeypatch, 1024, **wd)
    monkeypatch.setattr(tilemm, "_hot_push", real_push)
    assert bad.timer.totals["overflow_hot_blocks"] == 3
    assert last_column_gap(_wd_table(bad)) > max(10 * sound, 0.2)

    real_crossing = overflow.crossing

    def lost(block, drop_empty):
        return {k: v for k, v in real_crossing(block, drop_empty).items()
                if k not in overflow.HOT}
    monkeypatch.setattr(overflow, "crossing", lost)
    gone, shipped, _ = _run(tmp_path, "gone", blocks, monkeypatch, 1024, **wd)
    assert all(set(b) == {"pw", "labels"} for b in shipped)
    assert gone.timer.totals["overflow_hot_blocks"] == 3      # the feed's
    assert gone.timer.totals["wd_listless_blocks"] == 3       # the store's
    assert "wd_spill_blocks" not in gone.timer.totals
