"""Fleet serving (wormhole_tpu/serve/fleet.py + router.py) and the
deadline-aware shed path (frontend.py).

Contracts pinned here:
- consistent-hash routing balances 10k keys within a bound across
  N ∈ {2, 4, 8} replicas, is deterministic across router instances,
  and the spill policy drains traffic off an artificially-stalled
  replica;
- delta snapshot shipping is bit-parity with the disk-poll swap per
  store flavor (full frames, the exact path), and quantized deltas
  keep every replica bitwise equal to the publisher base with a
  bounded error vs the true state;
- a version gap (missed frame) triggers a full resync instead of a
  corrupt apply;
- priority classes flush high-first; overload sheds ONLY sheddable
  classes, fails their futures with ServeShedError, counts them, and
  a shed storm triggers one FlightRecorder dump;
- SnapshotPoller backs off exponentially on repeated torn-file loads
  and counts retries.
"""

import time
from collections import Counter, deque

import numpy as np
import pytest

import jax

from wormhole_tpu.learners.handles import FTRLHandle, LearnRate
from wormhole_tpu.learners.store import ShardedStore, StoreConfig
from wormhole_tpu.obs import flight
from wormhole_tpu.obs.metrics import Registry
from wormhole_tpu.obs.slo import Objective
from wormhole_tpu.ops.penalty import L1L2
from wormhole_tpu.parallel.checkpoint import Checkpointer
from wormhole_tpu.serve import (ForwardStep, Router, ServeFleet,
                                ServeFrontend, ServeShedError, ShedPolicy,
                                SnapshotPoller, request_key)

NB = 1024


def _linear_store(rng, nb=NB):
    store = ShardedStore(StoreConfig(num_buckets=nb, loss="logit"),
                         FTRLHandle(penalty=L1L2(1.0, 0.1),
                                    lr=LearnRate(0.1, 1.0)))
    store.slots = store.slots.at[:, 0].set(
        jax.numpy.asarray(rng.standard_normal(nb).astype(np.float32)))
    return store


def _owned_forwards(store, n):
    """n ForwardSteps serving OWNED copies of the store's current
    params (fleet replicas must not alias donated training buffers)."""
    fwds = [ForwardStep.from_store(store) for _ in range(n)]
    base = jax.tree.map(lambda x: np.array(x), fwds[0].params)
    for f in fwds:
        f.swap(jax.tree.map(jax.numpy.asarray, base))
    return fwds


def _wait_versions(fleet, ver, timeout=15.0):
    """Until every replica AND the publisher report ``ver``: a replica has
    the version once it decodes the frame, the publisher (its base, its
    frame counts) only when the broadcast that landed it returns."""
    deadline = time.monotonic() + timeout
    while ((any(v < ver for v in fleet.versions())
            or fleet.publisher.version < ver)
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert fleet.versions() == [ver] * fleet.n, fleet.versions()
    assert fleet.publisher.version == ver


def _leaves_equal(a, b):
    return all(np.array_equal(x, y)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# -- router ---------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 4, 8])
def test_router_balance_bound_10k_keys(n):
    r = Router(n, policy="hash", vnodes=128)
    rng = np.random.default_rng(7)
    counts = Counter(
        r.route(request_key(rng.choice(1 << 20, size=6, replace=False)))
        for _ in range(10_000))
    assert set(counts) == set(range(n))      # every replica owns keys
    mean = 10_000 / n
    # 128 vnodes/replica keeps the ring well-mixed: each replica's
    # share stays within ±50% of uniform (loose enough to be stable
    # across blake2b, tight enough to catch a broken ring)
    for rep, c in counts.items():
        assert 0.5 * mean <= c <= 1.5 * mean, (rep, c, counts)


def test_router_deterministic_across_instances():
    keys = [request_key([k, k + 3, k * 7 % 997]) for k in range(200)]
    a = [Router(4, policy="hash").route(k) for k in keys]
    b = [Router(4, policy="hash").route(k) for k in keys]
    assert a == b
    # permutations of the same feature set are the same request
    assert request_key([5, 9, 31]) == request_key([31, 5, 9])


def test_router_spill_drains_stalled_replica():
    depths = [500, 1, 1, 1]                   # replica 0 is wedged
    r = Router(4, policy="spill", spill_frac=2.0, spill_min=8,
               depth_fn=lambda i: depths[i])
    rng = np.random.default_rng(3)
    landed = Counter()
    owners = Counter()
    for _ in range(2000):
        k = request_key(rng.choice(1 << 20, size=5, replace=False))
        owners[r.owner(k)] += 1
        landed[r.route(k)] += 1
    assert owners[0] > 0                      # hash does assign it keys
    assert landed[0] == 0                     # ...but spill diverts all
    assert r.spilled == owners[0]
    st = r.stats()
    assert st["spilled"] == owners[0] and st["routed"] == 2000
    # healthy fleet never spills
    r2 = Router(4, policy="spill", depth_fn=lambda i: 3)
    for _ in range(500):
        r2.route(request_key(rng.choice(1 << 20, size=5, replace=False)))
    assert r2.spilled == 0


def test_router_hash_policy_ignores_depths():
    r = Router(4, policy="hash", depth_fn=lambda i: 10_000 if i == 0 else 0)
    k = request_key([1, 2, 3])
    assert r.route(k) == r.owner(k)


def test_router_validation():
    with pytest.raises(ValueError):
        Router(0)
    with pytest.raises(ValueError):
        Router(2, policy="roulette")
    with pytest.raises(ValueError):
        Router(2, vnodes=0)


# -- delta shipping vs disk poll -----------------------------------------


def _store_flavors(rng):
    from wormhole_tpu.models.fm import FMConfig, FMStore
    from wormhole_tpu.models.wide_deep import WideDeepConfig, WideDeepStore
    return {
        "linear": _linear_store(rng),
        "fm": FMStore(FMConfig(num_buckets=NB, dim=4, init_scale=0.3,
                               seed=3)),
        "wide_deep": WideDeepStore(WideDeepConfig(num_buckets=NB, dim=4,
                                                  hidden=(8,),
                                                  init_scale=0.3, seed=3)),
    }


@pytest.mark.parametrize("flavor", ["linear", "fm", "wide_deep"])
def test_delta_ship_bit_parity_with_disk_poll(rng, tmp_path, flavor):
    """Full-frame shipping (full_every=1, the exact path) must land the
    SAME bits the SnapshotPoller's disk poll lands, for every store
    flavor — both sides read the identical checkpoint file."""
    store = _store_flavors(rng)[flavor]
    template = jax.tree.map(np.asarray, store.state_pytree())
    ckpt = Checkpointer(str(tmp_path), is_writer=True)
    ckpt.save(1, store.state_pytree())

    fwd_poll = ForwardStep.from_store(store)
    poller = SnapshotPoller(ckpt, template, fwd_poll, poll_itv=0.02)
    assert poller.poll_once() is True and poller.version == 1

    (fwd_fleet,) = _owned_forwards(store, 1)
    fleet = ServeFleet([fwd_fleet], batch_rows=4, max_nnz=4,
                       full_every=1, poll_itv=0.02,
                       ckpt=ckpt, template_state=template)
    try:
        _wait_versions(fleet, 1)
        assert _leaves_equal(fwd_poll.params, fwd_fleet.params)
        assert fleet.publisher.full_frames >= 1
        assert fleet.publisher.delta_frames == 0
    finally:
        fleet.close()


def test_quantized_deltas_keep_fleet_bitwise_uniform(rng):
    """full_every=0: every frame is a quantized delta. Replicas must
    stay bitwise equal to the publisher base (they all decode the same
    wire bytes), and the base must track the true state within one
    quantization step per shipped delta (error feedback carries the
    remainder forward)."""
    store = _linear_store(rng)
    fwds = _owned_forwards(store, 2)
    base = jax.tree.map(lambda x: np.array(x), fwds[0].params)
    fleet = ServeFleet(fwds, batch_rows=4, max_nnz=4,
                       full_every=0, poll_itv=0.02)
    try:
        true = base
        for v in range(1, 4):
            true = jax.tree.map(
                lambda x: x + rng.normal(0, 0.05, x.shape)
                .astype(x.dtype), true)
            fleet.publish(true, v)
            _wait_versions(fleet, v)
        st = fleet.stats()["snapshot"]
        assert st["delta_frames"] == 3 and st["full_frames"] == 0
        assert st["bytes_wire"] > 0
        pub_base = fleet.publisher._base
        for sub in fleet.subscribers:
            assert _leaves_equal(pub_base, sub._base)
        # lossy, but bounded: one quant8 step of the last delta's range
        for t, b in zip(jax.tree.leaves(true), jax.tree.leaves(pub_base)):
            step = np.ptp(t - b + 0.0) if t.size else 0.0
            err = float(np.max(np.abs(t - b))) if t.size else 0.0
            assert err <= max(0.3 / 255 * 4, 1e-6) or err <= step, err
    finally:
        fleet.close()


def test_version_gap_triggers_full_resync(rng):
    store = _linear_store(rng)
    fwds = _owned_forwards(store, 2)
    base = jax.tree.map(lambda x: np.array(x), fwds[0].params)
    fleet = ServeFleet(fwds, batch_rows=4, max_nnz=4,
                       full_every=0, poll_itv=0.02)
    try:
        # replica 1 silently diverges (as if it missed a frame)
        fleet.subscribers[1].version = 99
        new = jax.tree.map(lambda x: x + np.float32(0.25), base)
        fleet.publish(new, 1)
        deadline = time.monotonic() + 15
        while (fleet.subscribers[1].version != 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert fleet.subscribers[1].version == 1
        assert fleet.subscribers[1].gaps >= 1
        assert fleet.publisher.resyncs >= 1
        assert fleet.publisher.full_frames >= 1
        # after the resync both replicas are bitwise the publisher base
        for sub in fleet.subscribers:
            assert _leaves_equal(fleet.publisher._base, sub._base)
    finally:
        fleet.close()


def test_fleet_serves_bit_equal_pull_oracle(rng):
    """Routed fleet answers match the host pull oracle on whichever
    replica they land (all replicas serve the same version)."""
    store = _linear_store(rng)
    fwds = _owned_forwards(store, 2)
    reg = Registry()
    fleet = ServeFleet(fwds, batch_rows=8, max_nnz=8,
                       deadline_ms=10.0, registry=reg, poll_itv=0.05)
    try:
        reqs = []
        for _ in range(30):
            keys = rng.choice(NB, size=rng.integers(1, 8), replace=False)
            vals = rng.random(len(keys)).astype(np.float32)
            reqs.append((keys, vals, fleet.submit(keys, vals)))
        for keys, vals, r in reqs:
            pred = r.result(timeout=15)
            oracle = float(store.pull(keys.astype(np.int64)) @ vals)
            assert abs(r.margin - oracle) < 1e-5
            assert abs(pred - 1 / (1 + np.exp(-oracle))) < 1e-6
        st = fleet.stats()
        assert st["aggregate"]["requests"] == 30
        assert st["router"]["routed"] == 30
        assert reg.get("serve/requests").value == 30
    finally:
        fleet.close()


# -- priority classes + load shedding ------------------------------------


def _stub_frontend(flush_s, **kw):
    """A frontend over a stub forward with a controlled flush time —
    the service rate is the knob the shed projection divides by."""
    def forward(batch):
        time.sleep(flush_s)
        n = batch.cols.shape[0]
        return np.zeros(n, np.float32), np.full(n, 0.5, np.float32)
    return ServeFrontend(forward, **kw)


def test_take_group_priority_order():
    fe = _stub_frontend(0.0, batch_rows=4, max_nnz=4, deadline_ms=1.0)
    try:
        mk = lambda p: type("R", (), {"priority": p})()
        pending = {1: deque([mk(1), mk(1), mk(1)]),
                   0: deque([mk(0), mk(0)])}
        group, left = fe._take_group(pending, 5)
        assert [r.priority for r in group] == [0, 0, 1, 1]
        assert left == 1 and [r.priority for r in pending[1]] == [1]
    finally:
        fe.close()


def test_shed_drops_only_low_priority_and_counts(rng):
    reg = Registry()
    pol = ShedPolicy(objective=None, engage_frac=0.0,   # always armed
                     storm_n=4, storm_window_s=60.0)
    fe = _stub_frontend(0.05, batch_rows=8, max_nnz=8,
                        deadline_ms=75.0, registry=reg, shed=pol)
    try:
        # one warm-up flush establishes the EWMA service rate
        fe.submit([1, 2, 3]).result(timeout=10)
        high, low = [], []
        for i in range(60):
            keys = rng.choice(NB, size=4, replace=False)
            (high if i % 3 == 0 else low).append(
                fe.submit(keys, priority=0 if i % 3 == 0 else 1))
        shed = served = 0
        for r in high:
            r.result(timeout=30)              # class 0 NEVER sheds
        for r in low:
            try:
                r.result(timeout=30)
                served += 1
            except ServeShedError:
                shed += 1
        assert shed > 0, "overload must shed some low-priority work"
        st = fe.stats()
        assert st["shed"] == shed
        assert reg.get("serve/shed").value == shed
        assert st["shed_storms"] >= 1         # storm_n=4 trips quickly
        assert reg.get("serve/shed_storms").value == st["shed_storms"]
    finally:
        fe.close()


def test_shed_storm_triggers_flight_dump(rng, tmp_path):
    rec = flight.FlightRecorder(str(tmp_path), registry=Registry())
    flight.install(rec)
    try:
        pol = ShedPolicy(objective=None, engage_frac=0.0,
                         storm_n=2, storm_window_s=60.0)
        fe = _stub_frontend(0.05, batch_rows=4, max_nnz=4,
                            deadline_ms=60.0, shed=pol)
        try:
            fe.submit([1, 2]).result(timeout=10)
            pend = [fe.submit(rng.choice(NB, size=3, replace=False),
                              priority=1) for _ in range(40)]
            for r in pend:
                try:
                    r.result(timeout=30)
                except ServeShedError:
                    pass
            assert fe.stats()["shed_storms"] >= 1
        finally:
            fe.close()
        dumps = [p for p in tmp_path.iterdir() if p.is_dir()]
        assert dumps, "storm must write one flight bundle"
        assert any("serve_shed_storm" in p.name for p in dumps)
    finally:
        flight.uninstall()


def test_slo_gate_holds_shedding_below_engage_band(rng):
    """With a ceiling objective and the rolling p99 far below the
    engage band, projected-wait overload must NOT shed — the SLO gate
    keeps bursts unshed while the tail is healthy."""
    pol = ShedPolicy(objective=Objective("serve_p99", "serve/p99_ms",
                                         bound=1e9, kind="ceiling"),
                     engage_frac=0.8, storm_n=1 << 30)
    fe = _stub_frontend(0.05, batch_rows=8, max_nnz=8,
                        deadline_ms=75.0, shed=pol)
    try:
        fe.submit([1, 2, 3]).result(timeout=10)
        pend = [fe.submit(rng.choice(NB, size=3, replace=False),
                          priority=1) for _ in range(40)]
        for r in pend:
            r.result(timeout=60)              # nothing shed
        assert fe.stats()["shed"] == 0
    finally:
        fe.close()


def test_priority_validation_and_defaults(rng):
    fe = _stub_frontend(0.0, batch_rows=4, max_nnz=4, deadline_ms=5.0)
    try:
        with pytest.raises(ValueError):
            fe.submit([1, 2], priority=-1)
        assert fe.submit([1, 2]).result(timeout=10) == 0.5
    finally:
        fe.close()


# -- SnapshotPoller backoff (satellite 2) --------------------------------


def test_poller_backs_off_on_repeated_garbage(rng, tmp_path):
    store = _linear_store(rng)
    fwd = ForwardStep.from_store(store)
    ckpt = Checkpointer(str(tmp_path), is_writer=True)
    template = jax.tree.map(np.asarray, store.state_pytree())
    reg = Registry()
    poller = SnapshotPoller(ckpt, template, fwd, poll_itv=0.5,
                            registry=reg)
    assert poller.wait_s() == 0.5             # healthy: base cadence
    (tmp_path / "ckpt_v1.msgpack").write_bytes(b"\x00garbage")
    for k in range(1, 4):
        assert poller.poll_once() is False
        assert poller.retries == k
        assert poller.wait_s() == 0.5 * (1 << k)
    assert reg.get("serve/snapshot_retries").value == 3
    # the backoff multiplier is capped (wedged store != infinite sleep)
    for _ in range(20):
        poller.poll_once()
    assert poller.wait_s() == 0.5 * (1 << 6)
    # a good save recovers AND resets the streak
    ckpt.save(2, store.state_pytree())
    assert poller.poll_once() is True
    assert poller.version == 2
    assert poller.wait_s() == 0.5
