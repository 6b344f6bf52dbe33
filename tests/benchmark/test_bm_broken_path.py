"""`correct` has to come out false when the timed path is broken underneath:
the harness's look for a chip is skipped, the rest of a run is driven."""

import pytest

import bm_helpers

CELL = "criteo_ftrl.replay_uniform"
STREAM_CELL = "criteo_ftrl.stream_fields"

UNCHANGED_STATE = """
import jax.numpy as jnp
from wormhole_tpu.learners import store as _store
_real = _store.ShardedStore.tile_train_step
def _step(self, block, info, tau=0.0):
    ticket = _real(self, block, info, tau)
    self.slots = jnp.zeros_like(self.slots)   # FTRL starts from zeros:
    return ticket                             # the step left it unchanged
_store.ShardedStore.tile_train_step = _step
"""

HALF_THE_BATCH = """
import numpy as np
from wormhole_tpu.data import crec as _crec
_real = _crec.block2_views
def _views(info, buf):
    v = _real(info, buf)
    labels = v["labels"].copy()
    labels[len(labels) // 2:] = 255            # the format's padded-row mark
    return dict(v, labels=labels)
_crec.block2_views = _views
"""


def _numbers(stdout):
    out = {}
    for line in stdout.splitlines():
        if line.startswith("[bench] check ") and " = " in line:
            name = line.split()[2]
            out[name] = "NOT OK" not in line
    return out


@pytest.mark.parametrize("cell", [CELL, STREAM_CELL])
def test_a_step_that_returns_its_state_unchanged(cell, tmp_path):
    r, result = bm_helpers.run_tiny(cell, tmp_path, prelude=UNCHANGED_STATE)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is False
    ok = _numbers(r.stdout)
    assert ok["change_norm_rel"] is False and ok["grad_norm_rel"] is False


def test_a_part_of_the_batch_left_out(tmp_path):
    r, result = bm_helpers.run_tiny(CELL, tmp_path, prelude=HALF_THE_BATCH)
    # the pass loop counts the rows it trained: the harness sees fewer
    # than it wrote and refuses the step before any number is compared
    assert r.returncode != 0 or result["correct"] is False
    if r.returncode != 0:
        assert "rows" in r.stderr


def test_the_sound_path_is_correct(tmp_path):
    r, result = bm_helpers.run_tiny(CELL, tmp_path, seed=2**31 + 9)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert result["correct"] is True
    assert all(_numbers(r.stdout).values())
