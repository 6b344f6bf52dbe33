import numpy as np

from wormhole_tpu.parallel.checkpoint import Checkpointer


def _state(x):
    return {"weights": np.full(5, x, np.float32), "iter": np.int64(x)}


def test_fresh_load_returns_version_zero(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ver, state = ck.load(_state(0))
    assert ver == 0
    assert state["iter"] == 0


def test_save_load_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1))
    ck.save(2, _state(2))
    ver, state = ck.load(_state(0))
    assert ver == 2
    np.testing.assert_array_equal(state["weights"], np.full(5, 2, np.float32))


def test_gc_keeps_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for v in range(1, 6):
        ck.save(v, _state(v))
    import os
    files = sorted(os.listdir(tmp_path))
    assert files == ["ckpt_v4.msgpack", "ckpt_v5.msgpack"]


def test_restart_semantics(tmp_path):
    # kill/restart: a new Checkpointer over the same dir resumes
    ck1 = Checkpointer(str(tmp_path))
    ck1.save(3, _state(3))
    ck2 = Checkpointer(str(tmp_path))
    ver, state = ck2.load(_state(0))
    assert ver == 3 and state["iter"] == 3


def test_mesh_learner_resumes_its_planes_from_a_checkpoint(tmp_path):
    """The learner's own pass-end commit and resume on a ``data:2,model:2``
    mesh, where the linear store keeps its table as planes split over
    MODEL: the run saves after its pass, a fresh learner over the same
    directory resumes at pass 1 with the same table, planes again, each
    where the mesh step wants it."""
    import jax
    from test_mesh_feed import BR, make_app, make_rows, write_file
    from test_table_planes import _is_planes_over_model as planes_over_model
    keys, labels = make_rows(np.random.default_rng(5), 4 * BR)
    path = tmp_path / "c.crec2"
    write_file(path, keys, labels)
    ckpt = str(tmp_path / "ckpt")

    first = make_app(path, "data:2,model:2", checkpoint_dir=ckpt)
    first.run()
    assert planes_over_model(first.store.slots)
    trained = np.asarray(first.store.slots)
    assert np.abs(trained[:, 0]).sum() > 0
    assert Checkpointer(ckpt).latest_version() == 1

    again = make_app(path, "data:2,model:2", checkpoint_dir=ckpt)
    assert not np.asarray(again.store.slots).any()
    again.run()                      # resumes at pass 1 of 1: no step
    assert again.store.t == first.store.t
    assert planes_over_model(again.store.slots)
    np.testing.assert_array_equal(np.asarray(again.store.slots), trained)
    jax.block_until_ready(again.store.slots)
