"""The general traffic generator: the same seed gives the same bytes, the
fields keep their cardinalities, and the work a block makes does not change
with the seed."""

import numpy as np
import pytest

import bm_helpers
from benchmark.generators import fields

STREAM = bm_helpers.load("benchmark/traffic/stream_fields.json")
REPLAY = bm_helpers.load("benchmark/traffic/replay_uniform.json")
ROWS = 16384


def test_same_seed_same_bytes():
    a = fields.make_block(STREAM, 2**31 + 11, 3, ROWS)
    b = fields.make_block(STREAM, 2**31 + 11, 3, ROWS)
    assert a[0].tobytes() == b[0].tobytes()
    assert a[1].tobytes() == b[1].tobytes()
    assert a[0].dtype == np.uint32 and a[0].shape == (ROWS, 39)
    assert a[1].dtype == np.uint8 and set(np.unique(a[1])) <= {0, 1}


def test_other_seed_or_block_other_rows():
    a = fields.make_block(STREAM, 7, 0, ROWS)[0]
    assert not np.array_equal(a, fields.make_block(STREAM, 8, 0, ROWS)[0])
    assert not np.array_equal(a, fields.make_block(STREAM, 7, 1, ROWS)[0])


def test_the_key_fold_is_the_programs():
    from wormhole_tpu.data import hashing
    keys = np.random.default_rng(0).integers(0, 2**32, 10000,
                                             dtype=np.uint64).astype(np.uint32)
    assert np.array_equal(fields.mix32(keys), hashing.mix32_np(keys))
    for nb in (65536, 1 << 25, 1 << 28):
        assert np.array_equal(fields.fold_keys32(keys, nb),
                              hashing.fold_keys32(keys, nb))


def test_no_key_is_the_formats_sentinel():
    keys, _ = fields.make_block(STREAM, 3, 0, ROWS)
    assert not (keys == np.uint32(0xFFFFFFFF)).any()


def test_field_counts_and_cardinalities():
    assert fields.nnz_of(STREAM) == 39 and fields.nnz_of(REPLAY) == 39
    cols = fields._columns(STREAM["fields"])
    vals = fields._draw_values(np.random.default_rng(1), 50000, cols)
    for f, col in enumerate(cols):
        top = col.get("cardinality", col.get("max", 0) + 1)
        assert vals[:, f].min() >= 0 and vals[:, f].max() < top
    # the small categorical fields are seen whole
    for f, col in enumerate(cols):
        if col["dist"] == "zipf" and col["cardinality"] <= 14:
            assert len(np.unique(vals[:, f])) == col["cardinality"]
    # uniform fields: 20,000 values each, all fields distinct keys
    keys, _ = fields.make_block(REPLAY, 1, 0, 98304)
    distinct = len(np.unique(keys))
    assert 0.98 * 39 * 20000 * (1 - np.exp(-98304 / 20000)) < distinct \
        <= 39 * 20000


def test_zipf_head_mass():
    """Rank 1 of a Zipf(1.05) field of 40M values carries about 6%."""
    cols = [{"dist": "zipf", "cardinality": 39884406, "exponent": 1.05}]
    v = fields._draw_values(np.random.default_rng(2), 200000, cols)[:, 0]
    share = (v == 0).mean()
    want = (2 ** -0.05 - 1) / ((39884406 + 1.0) ** -0.05 - 1)
    assert share == pytest.approx(want, rel=0.05)
    assert 0.05 < want < 0.07


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError, match="unknown field distribution"):
        fields._draw_values(np.random.default_rng(0), 4,
                            [{"dist": "bogus", "count": 1}])


@pytest.mark.parametrize("seed", [1, 77, 2**31 + 5])
def test_overflow_pairs_do_not_follow_the_seed(seed):
    """What the seed changes is WHICH ids are hot; the pairs past the
    per-tile cap agree to a percent (1.32M of 3.83M a block at 2**28)."""
    from wormhole_tpu.data.crec import default_cap, encode_tile_block
    from wormhole_tpu.ops.tilemm import make_spec
    nb = 1 << 28
    spec = make_spec(nb, 12, default_cap(39, nb))
    keys, _ = fields.make_block(STREAM, seed, 0, 98304)
    n_ovf = encode_tile_block(keys, nb, spec, STREAM["ovf_cap"])[3]
    assert n_ovf == pytest.approx(1.321e6, rel=0.01)
    assert n_ovf < STREAM["ovf_cap"]


def test_uniform_keys_overflow_nothing():
    from wormhole_tpu.data.crec import default_cap, encode_tile_block
    from wormhole_tpu.ops.tilemm import make_spec
    for nb in (1 << 25, 1 << 28):
        spec = make_spec(nb, 12, default_cap(39, nb))
        keys, _ = fields.make_block(REPLAY, 9, 0, 98304)
        assert encode_tile_block(keys, nb, spec, 1024)[3] <= 1024
