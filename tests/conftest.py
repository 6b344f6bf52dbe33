"""Test fixtures: force a virtual 8-device CPU platform before jax imports.

Mirrors the reference's "distributed tests are local multi-process runs"
strategy (SURVEY.md §4.3) — here, multi-device SPMD on one process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tmp_libsvm(tmp_path, rng):
    """Small libsvm file with values; returns (path, labels, scipy csr)."""
    import scipy.sparse as sp
    n, d = 100, 50
    dense = (rng.random((n, d)) < 0.1) * rng.random((n, d))
    labels = (rng.random(n) < 0.5).astype(np.float32)
    lines = []
    for i in range(n):
        feats = " ".join(f"{j}:{dense[i, j]:.6g}"
                         for j in np.nonzero(dense[i])[0])
        lines.append(f"{int(labels[i])} {feats}")
    path = tmp_path / "data.libsvm"
    path.write_text("\n".join(lines) + "\n")
    return str(path), labels, sp.csr_matrix(dense)
