import pytest

from wormhole_tpu.utils.config import Algo, Config, Loss, load_config


def test_defaults_match_reference_schema():
    c = Config()
    # defaults mirror proto/config.proto
    assert c.data_format == "libsvm"
    assert c.loss is Loss.LOGIT
    assert c.algo is Algo.FTRL
    assert c.minibatch == 1000
    assert c.max_data_pass == 10
    assert c.max_delay == 0
    assert c.fixed_bytes == 1 and c.msg_compression is False


def test_cli_overrides(tmp_path):
    conf = tmp_path / "demo.conf"
    conf.write_text(
        "train_data = \"demo/train\"\n"
        "algo = sgd\n"
        "# comment\n"
        "lambda = 1\n"
        "lambda = 0.1\n"
        "minibatch = 500\n")
    c = load_config(str(conf), ["minibatch=900", "lr_eta=0.05", "algo=ftrl"])
    assert c.train_data == "demo/train"
    assert c.minibatch == 900        # CLI wins over file
    assert c.algo is Algo.FTRL
    assert c.lambda_ == [1.0, 0.1]   # repeated field accumulates
    assert c.lr_eta == pytest.approx(0.05)


def test_colon_style_and_bool():
    c = load_config(None, ["msg_compression=true", "loss:square_hinge"])
    assert c.msg_compression is True
    assert c.loss is Loss.SQUARE_HINGE


@pytest.mark.parametrize("token", ["no_such_key=1", "mesh_feed=sync"])
def test_unknown_key_raises(token):
    # mesh_feed went with its fork (PR 32): it is rejected as any
    # unknown key is, not ignored
    with pytest.raises(ValueError):
        load_config(None, [token])


def test_apply_kvs_parses_a_tuple_field():
    """``hidden=1024,512,256``: the one parse of a Tuple[int, ...] field,
    for wide&deep's CLI and the benchmark's hook alike."""
    from wormhole_tpu.models.wide_deep import WideDeepConfig
    from wormhole_tpu.utils.config import apply_kvs
    cfg = WideDeepConfig()
    apply_kvs(cfg, ["hidden=1024,512,256", "dim=32"])
    assert cfg.hidden == (1024, 512, 256) and cfg.dim == 32
    apply_kvs(cfg, ["hidden = 64 32"])
    assert cfg.hidden == (64, 32)
    apply_kvs(cfg, ["hidden="])
    assert cfg.hidden == ()
