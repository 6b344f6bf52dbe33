"""Dense text fast path: native chunk -> crec-block assembly feeding the
dense-apply device step (the text ingest path whose Python localize+pad
glue used to cap criteo text).

Pinned two ways: the native assembler must be byte-identical to the
Python spec (key64_to_key32 + sentinel padding, the text2rec crec
semantics), and training directly from criteo TEXT must produce exactly
the same model as training from the text2rec-converted crec file (same
blocks, same steps, f32-identical)."""

import numpy as np
import pytest


@pytest.fixture()
def rng():
    return np.random.default_rng(31)


def _criteo_lines(rng, n, planted=True):
    lines = []
    for _ in range(n):
        y = int(rng.random() < 0.5)
        ints = [str(rng.integers(0, 100)) if rng.random() > 0.2 else ""
                for _ in range(13)]
        cats = [f"{rng.integers(0, 2 ** 32):08x}" if rng.random() > 0.2
                else "" for _ in range(26)]
        if planted:
            cats[0] = "aaaaaaaa" if y else "bbbbbbbb"
        lines.append("\t".join([str(y)] + ints + cats))
    return "\n".join(lines) + "\n"


def test_native_assembler_matches_python_spec(rng):
    from wormhole_tpu.data import native
    from wormhole_tpu.data.crec import _python_crec_assembler
    chunk = _criteo_lines(rng, 300).encode()
    asm_c = native.get_crec_assembler("criteo", 39)
    if asm_c is None:
        pytest.skip("native library unavailable")
    asm_py = _python_crec_assembler("criteo", 39)
    kc, lc = asm_c(chunk)
    kp, lp = asm_py(chunk)
    np.testing.assert_array_equal(kc, kp)
    np.testing.assert_array_equal(lc, lp)


def test_assembler_truncation_and_padding(rng):
    """Rows wider than nnz truncate positionally; narrower rows pad with
    the sentinel — byte-identical between C and Python."""
    from wormhole_tpu.data import native
    from wormhole_tpu.data.crec import _python_crec_assembler
    chunk = (b"1 2:1 5:1 9:1 11:1\n"      # 4 features
             b"0 3:1\n"                    # 1 feature
             b"1 1:1 2:1 3:1\n")
    asm_c = native.get_crec_assembler("libsvm", 2)
    if asm_c is None:
        pytest.skip("native library unavailable")
    kc, lc = asm_c(chunk)
    kp, lp = _python_crec_assembler("libsvm", 2)(chunk)
    np.testing.assert_array_equal(kc, kp)
    np.testing.assert_array_equal(lc, lp)
    assert kc.shape == (3, 2)
    assert (kc[1, 1] == np.uint32(0xFFFFFFFF))   # padded slot


def test_text_dense_training_matches_crec_file(tmp_path, rng):
    """Training straight from criteo TEXT (dense fast path) equals
    training from the text2rec-converted crec v1 file: identical blocks
    -> identical device steps -> identical weights."""
    import jax
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.tools.text2rec import Text2RecConfig, convert
    from wormhole_tpu.utils.config import Config
    n = 3000
    src = tmp_path / "train.criteo"
    src.write_text(_criteo_lines(rng, n))
    crec_path = str(tmp_path / "train.crec")
    br = 1024
    assert convert(Text2RecConfig(input=str(src), output=crec_path,
                                  format="criteo", out_format="crec",
                                  block_rows=br)) == n

    def train(data, fmt):
        cfg = Config(train_data=data, data_format=fmt, num_buckets=1 << 16,
                     lr_eta=0.3, max_data_pass=3, disp_itv=1e12,
                     max_delay=1, text_block_rows=br)
        rt = MeshRuntime.create()
        rt.mesh = make_mesh("data:1", jax.devices()[:1])
        app = AsyncSGD(cfg, rt)
        prog = app.run()
        w = np.asarray(app.store.handle.weights(
            app.store.slots.astype(np.float32)))
        return prog, w

    prog_t, w_t = train(str(src), "criteo")
    prog_c, w_c = train(crec_path, "crec")
    assert prog_t.num_ex == prog_c.num_ex == 3 * n
    np.testing.assert_array_equal(w_t, w_c)
    # and it actually learned the planted feature
    assert prog_t.acc / max(prog_t.count, 1) > 0.8


def test_text_dense_on_mesh(tmp_path, rng):
    """The dense text path rides the mesh dense-apply step on a
    multi-device mesh (grouped blocks, sharded table)."""
    import jax
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    n = 4000
    src = tmp_path / "train.criteo"
    src.write_text(_criteo_lines(rng, n))
    cfg = Config(train_data=str(src), data_format="criteo",
                 num_buckets=1 << 16, lr_eta=0.3, max_data_pass=6,
                 disp_itv=1e12, max_delay=1, text_block_rows=512)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:2,model:2", jax.devices()[:4])
    app = AsyncSGD(cfg, rt)
    prog = app.run()
    assert prog.num_ex == 6 * n
    assert prog.acc / max(prog.count, 1) > 0.8


def test_adfea_dense_path(tmp_path, rng):
    """adfea (the other binary text format) through the dense fast path:
    needs max_nnz as its fixed row width; rows account exactly."""
    import jax
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.parallel.mesh import MeshRuntime, make_mesh
    from wormhole_tpu.utils.config import Config
    n = 1200
    lines = []
    for i in range(n):
        y = int(rng.random() < 0.5)
        feats = rng.choice(100000, size=5, replace=False)
        feats[0] = 7 if y else 8
        toks = " ".join(f"{f}:1" for f in feats)
        # adfea rows: lineid, feature count, label, then feat:group pairs
        lines.append(f"{i} {len(feats)} {y} {toks}")
    src = tmp_path / "t.adfea"
    src.write_text("\n".join(lines) + "\n")
    cfg = Config(train_data=str(src), data_format="adfea",
                 num_buckets=1 << 16, lr_eta=0.3, max_data_pass=4,
                 disp_itv=1e12, max_delay=1, max_nnz=8,
                 text_block_rows=512)
    rt = MeshRuntime.create()
    rt.mesh = make_mesh("data:1", jax.devices()[:1])
    app = AsyncSGD(cfg, rt)
    prog = app.run()
    assert prog.num_ex == 4 * n
    assert prog.acc / max(prog.count, 1) > 0.8
