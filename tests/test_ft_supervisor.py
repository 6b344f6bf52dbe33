"""Supervisor-side fault tolerance (wormhole_tpu/ft): dead-rank
detection from heartbeat silence and exit codes, shrink/fixed relaunch
planning, the env-gated SIGTERM drain protocol, deterministic chaos
injection, checkpoint commit durability/retry, world-size resharding
arithmetic, and the default-off pin on every ft/chaos knob."""

import dataclasses
import json
import logging
import os
import signal

import numpy as np
import pytest

from wormhole_tpu.ft import chaos, supervisor
from wormhole_tpu.ft.supervisor import (BYSTANDER_CODES, DeadRankDetector,
                                        Supervisor)
from wormhole_tpu.ft.watchdog import PEER_LOST
from wormhole_tpu.obs.heartbeat import HeartbeatWriter, heartbeat_path
from wormhole_tpu.obs.metrics import Registry


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(supervisor.DRAIN_ENV, raising=False)
    monkeypatch.delenv(chaos.ATTEMPT_ENV, raising=False)
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.reset()
    supervisor.reset_drain()
    yield
    chaos.reset()
    supervisor.reset_drain()


def _write_hb(directory, rank, mono, final=False):
    os.makedirs(directory, exist_ok=True)
    rec = {"ts": 1000.0 + mono, "mono": mono, "rank": rank, "seq": 0,
           "step": 1, "num_ex": 10, "ex_per_sec": 1.0}
    if final:
        rec["final"] = True
    with open(heartbeat_path(directory, rank), "a") as f:
        f.write(json.dumps(rec) + "\n")


# -- dead-rank detection ------------------------------------------------------

def test_detector_declares_silent_rank(tmp_path):
    d = str(tmp_path)
    _write_hb(d, 0, mono=100.0)
    _write_hb(d, 1, mono=95.0)
    det = DeadRankDetector(dead_after_s=10.0)
    assert det.check(d, now=103.0) == []        # both beat recently
    assert det.check(d, now=108.0) == [1]       # rank 1 silent 13s
    assert det.check(d, now=200.0) == [0, 1]


def test_detector_skips_final_and_missing(tmp_path):
    d = str(tmp_path)
    _write_hb(d, 0, mono=10.0, final=True)      # deliberate exit
    det = DeadRankDetector(dead_after_s=5.0)
    assert det.check(d, now=1000.0) == []
    # a rank that never wrote a beat is never declared by silence
    assert det.check(str(tmp_path / "empty"), now=1000.0) == []
    # disabled detector never declares
    assert DeadRankDetector(0.0).check(d, now=1000.0) == []


def test_supervisor_exit_code_classes():
    sup = Supervisor(world=4)
    for code in BYSTANDER_CODES:
        sup.record_exit(0, code)
    assert sup.dead == set()
    sup.record_exit(1, -signal.SIGKILL)         # chaos kill
    sup.record_exit(2, 17)                      # app crash
    sup.record_exit(3, PEER_LOST)               # watchdog victim: bystander
    assert sup.dead == {1, 2}


def test_supervisor_shrink_and_fixed_planning():
    sup = Supervisor(world=4, elastic="shrink")
    sup.record_exit(1, -signal.SIGKILL)
    assert sup.next_world() == 3
    assert sup.plan_relaunch() == 3
    assert sup.dead == set() and sup.exit_codes == {}
    # floor at MIN_WORLD: the single-process path can't read sharded state
    sup.record_dead([0, 1, 2])
    assert sup.next_world() == Supervisor.MIN_WORLD

    fixed = Supervisor(world=4, elastic="fixed")
    fixed.record_exit(2, -signal.SIGKILL)
    assert fixed.next_world() == 4
    with pytest.raises(ValueError):
        Supervisor(world=4, elastic="bogus")


def test_supervisor_scan_heartbeats_records_once(tmp_path):
    d = str(tmp_path)
    _write_hb(d, 0, mono=100.0)
    _write_hb(d, 1, mono=10.0)
    sup = Supervisor(world=2, dead_after_s=5.0)
    assert sup.scan_heartbeats(d, now=100.0) == [1]
    assert sup.dead == {1}
    # already-known dead ranks are not re-reported to the kill loop
    assert sup.scan_heartbeats(d, now=100.0) == []


# -- drain protocol -----------------------------------------------------------

def test_drain_handler_gated_on_env(monkeypatch):
    monkeypatch.delenv(supervisor.DRAIN_ENV, raising=False)
    assert supervisor.install_drain_handler() is False
    assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL


def test_drain_sigterm_sets_flag(monkeypatch):
    monkeypatch.setenv(supervisor.DRAIN_ENV, "1")
    assert supervisor.install_drain_handler() is True
    assert not supervisor.drain_requested()
    os.kill(os.getpid(), signal.SIGTERM)        # handled, not fatal
    assert supervisor.drain_requested()
    supervisor.reset_drain()
    assert not supervisor.drain_requested()
    assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL


# -- chaos injection ----------------------------------------------------------

def test_chaos_inert_by_default():
    assert chaos.install({}, rank=0) is False
    assert not chaos.active()
    chaos.tick_block(100)                       # no plan: all hooks no-op
    chaos.on_collective("x")
    chaos.on_heartbeat()
    chaos.ckpt_fault("/p")


def test_chaos_arms_only_on_attempt_zero(monkeypatch):
    assert chaos.install({"kill_rank": 1}, rank=0) is True
    assert chaos.active()
    monkeypatch.setenv(chaos.ATTEMPT_ENV, "1")
    assert chaos.install({"kill_rank": 1}, rank=0) is False
    assert not chaos.active()


def test_chaos_env_plan(monkeypatch):
    monkeypatch.setenv(chaos.CHAOS_ENV, "ckpt_errors=2,delay_rank=0")
    assert chaos.install({}, rank=0) is True
    with pytest.raises(OSError, match="chaos"):
        chaos.ckpt_fault("/a")
    with pytest.raises(OSError, match="chaos"):
        chaos.ckpt_fault("/b")
    chaos.ckpt_fault("/c")                      # budget spent: clean


def test_chaos_config_knobs_default_off():
    """lint_knobs-style pin: every ft/chaos knob defaults to its inert
    value, so an untouched config can never arm the subsystem."""
    from wormhole_tpu.utils.config import Config
    inert = {"comm_timeout_s": 0.0, "ft_dead_after_s": 0.0,
             "ft_elastic": "fixed", "chaos_kill_rank": -1,
             "chaos_kill_block": 0, "chaos_delay_rank": -1,
             "chaos_collective_delay_s": 0.0,
             "chaos_heartbeat_delay_s": 0.0, "chaos_ckpt_errors": 0}
    fields = {f.name: f.default for f in dataclasses.fields(Config)
              if f.name in inert}
    assert fields == inert
    assert chaos.install_from_config(Config(), rank=0) is False


# -- checkpoint durability / retry / resharding -------------------------------

def test_commit_bytes_retries_transient_error(tmp_path, caplog):
    from wormhole_tpu.parallel.checkpoint import _commit_bytes
    chaos.install({"ckpt_errors": 1}, rank=0)
    p = str(tmp_path / "blob")
    with caplog.at_level(logging.WARNING):
        _commit_bytes(p, b"payload")
    assert open(p, "rb").read() == b"payload"
    assert "transient checkpoint IO error" in caplog.text
    # two consecutive faults exhaust the single retry
    chaos.install({"ckpt_errors": 2}, rank=0)
    with pytest.raises(OSError, match="chaos"):
        _commit_bytes(str(tmp_path / "blob2"), b"x")


def test_shard_checkpointer_survives_transient_fault(tmp_path):
    from wormhole_tpu.parallel.checkpoint import ShardCheckpointer
    chaos.install({"ckpt_errors": 1}, rank=0)
    ck = ShardCheckpointer(str(tmp_path))
    state = {"w": np.arange(8, dtype=np.float32)}
    ck.save(3, state)
    assert ck.latest_version() == 3
    assert os.path.exists(tmp_path / "rank0" / "ckpt_v3.ok")
    ver, loaded = ck.load({"w": np.zeros(8, np.float32)})
    assert ver == 3
    np.testing.assert_array_equal(loaded["w"], state["w"])


def test_reassemble_rows_layouts():
    from wormhole_tpu.parallel.checkpoint import reassemble_rows
    a = np.arange(6).reshape(3, 2)
    b = np.arange(6, 16).reshape(5, 2)
    # partitioned: disjoint row ranges concatenate in rank order
    np.testing.assert_array_equal(reassemble_rows([a, b], 8),
                                  np.concatenate([a, b]))
    # replicated: every rank wrote the full array; any copy is the array
    np.testing.assert_array_equal(reassemble_rows([a, a.copy()], 3), a)
    # anything else is a layout bug, not a guess
    with pytest.raises(ValueError, match="cannot reshard"):
        reassemble_rows([a, b], 11)


# -- heartbeat write-failure satellite ---------------------------------------

def test_heartbeat_write_failure_one_shot(tmp_path, caplog):
    reg = Registry()
    hb = HeartbeatWriter(str(tmp_path), rank=3, interval=0.0,
                         registry=reg)
    # make the append fail: the heartbeat path is a directory
    os.makedirs(hb.path)
    with caplog.at_level(logging.WARNING, logger="wormhole.obs"):
        assert hb.beat(step=1, num_ex=10) is False
        assert hb.beat(step=2, num_ex=20) is False
    assert caplog.text.count("heartbeat write") == 1     # one-shot warning
    assert reg.counter("heartbeat/write_errors").value == 1.0
