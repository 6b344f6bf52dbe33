"""chip_smoke.py off the chip: its phases at a tiny size on the CPU, its
refusal to run without a TPU, and where the compile cache goes.

The tiny run goes through ``chip_smoke.run(size, need_tpu=False)`` — a
function argument only this test passes; the script has no option or
environment variable that lets it run without the chip.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from wormhole_tpu.ops import tilemm  # noqa: E402
from wormhole_tpu.parallel import mesh as pmesh  # noqa: E402

TINY = dict(num_buckets=4 * tilemm.TILE, subblocks=2, nnz=8, vocab=5000)


def test_one_chip_phases_pass_at_tiny_size_on_cpu(tmp_path):
    """The one-chip path, with the fused step forced (auto picks split
    on the CPU backend) so the interpreter walks the chip's kernel. In
    a child with ONE host device: conftest.py gives this process eight,
    and the trainer builds its mesh from all it sees."""
    prog = (f"import chip_smoke as cs\n"
            f"cs.run(cs.Size(train_blocks=2, "
            f"conf=('tile_step_kernel=fused',), **{TINY!r}), "
            f"need_tpu=False, workdir={str(tmp_path)!r})\n")
    r = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        cwd=REPO, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "step kernel: fused" in r.stdout
    assert "objective falls pass over pass" in r.stdout
    assert "all phases passed" in r.stdout
    # the result line is main()'s to print, and only on a chip
    assert '"ok"' not in r.stdout


def test_mesh_phases_pass_at_tiny_size_on_cpu(tmp_path, capsys):
    """The --chips 4 path on the eight forced host devices."""
    size = chip_smoke.Size(train_blocks=4, mesh_shape="data:4,model:2",
                           **TINY)
    device = chip_smoke.run(size, chips=4, need_tpu=False,
                            workdir=str(tmp_path))
    assert device["platform"] == "cpu"
    out = capsys.readouterr().out
    assert "every device holds a" in out
    assert "mesh and one chip agree" in out
    assert "all phases passed" in out and '"ok"' not in out


def test_unhooked_script_refuses_the_cpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, cwd=REPO, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
    assert "INTERPRET" not in r.stderr       # it did not interpret either


def test_compile_cache_is_placed_by_the_environment(monkeypatch, tmp_path):
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert pmesh.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing was set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_ignored_path(monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    # this process is held to the CPU backend, which keeps no cache
    assert pmesh.enable_compile_cache() == ""
    assert jax.config.jax_compilation_cache_dir == before
    # ... and one that is not gets the same in-checkout path every time
    monkeypatch.setattr(pmesh, "_held_to_cpu", lambda: False)
    try:
        first = pmesh.enable_compile_cache()
        assert first == pmesh.enable_compile_cache() == \
            os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
