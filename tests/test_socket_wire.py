"""The TCP wire (parallel/socket_wire.py): frame codec over torn
streams, file/port rendezvous, full-mesh collectives against the
BusWire byte-semantics oracle, disconnect surfacing through the
watchdog exit-code scheme (PEER_LOST), the rejoin side channel, and the
FilterChain transport stack riding on top bit-identically."""

import hashlib
import json
import os
import threading
import time

import numpy as np
import pytest

from wormhole_tpu.ft import watchdog as ft_watchdog
from wormhole_tpu.parallel import transport
from wormhole_tpu.parallel.filters import FilterChain
from wormhole_tpu.parallel.socket_wire import (
    FrameError, FrameParser, PeerLostError, Rendezvous, SocketWire,
    K_CTL, K_GATHER, MAX_FRAME, pack_frame)
from wormhole_tpu.parallel.transport import (BusWire, SimBus,
                                             TransportStack)


@pytest.fixture(autouse=True)
def _no_watchdog():
    """Tests install their own recorders; never leak a real watchdog
    (its default exit path is os._exit)."""
    ft_watchdog.shutdown()
    yield
    ft_watchdog.shutdown()


def _par(fns, timeout=60.0):
    """Run one callable per rank concurrently (socket collectives block
    until every rank participates); re-raise the first failure."""
    out = [None] * len(fns)
    errs = []

    def call(i):
        try:
            out[i] = fns[i]()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [threading.Thread(target=call, args=(i,), daemon=True)
          for i in range(len(fns))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
    if errs:
        raise errs[0]
    assert all(not t.is_alive() for t in ts), "rank thread hung"
    return out


def _mesh(tmp_path, world, **kw):
    """Build a full SocketWire mesh on loopback (concurrent: each
    constructor blocks in rendezvous + connect until all arrive)."""
    rdv = str(tmp_path / "rdv")
    return _par([lambda r=r: SocketWire(rank=r, world=world,
                                        rendezvous=rdv, **kw)
                 for r in range(world)])


def _close_all(wires):
    for w in wires:
        w.close()


# -- frame codec -------------------------------------------------------------

def test_frame_parser_reassembles_torn_stream():
    payloads = [b"", b"x", os.urandom(3000), b"tail"]
    stream = b"".join(pack_frame(K_GATHER, i, p)
                      for i, p in enumerate(payloads))
    parser = FrameParser()
    got = []
    for i in range(len(stream)):          # worst case: 1 byte per recv
        got.extend(parser.feed(stream[i:i + 1]))
    assert [(k, s) for k, s, _ in got] == [(K_GATHER, i)
                                           for i in range(len(payloads))]
    assert [p for _, _, p in got] == payloads
    assert parser.pending() == 0


def test_frame_parser_short_frame_stays_buffered():
    frame = pack_frame(K_CTL, 7, b"abcdef")
    parser = FrameParser()
    assert parser.feed(frame[:-1]) == []   # one byte short: nothing out
    assert parser.pending() == len(frame) - 1
    assert parser.feed(frame[-1:]) == [(K_CTL, 7, b"abcdef")]


def test_frame_parser_rejects_oversized_length_prefix():
    parser = FrameParser(max_frame=1024)
    ok = pack_frame(K_GATHER, 0, b"a" * 1024)   # at the bound: fine
    assert parser.feed(ok)[0][2] == b"a" * 1024
    bad = pack_frame(K_GATHER, 1, b"")[:9] + (2048).to_bytes(4, "little")
    with pytest.raises(FrameError, match="exceeds max_frame"):
        parser.feed(bad)
    # garbage read as a length prefix must not drive an allocation:
    # a header whose u32 length field claims 4 GiB tears the stream down
    parser2 = FrameParser()
    junk = pack_frame(K_GATHER, 2, b"")[:9] + b"\xff\xff\xff\xff"
    with pytest.raises(FrameError):
        parser2.feed(junk)


# -- rendezvous --------------------------------------------------------------

def test_rendezvous_publish_and_table(tmp_path):
    d = str(tmp_path / "rdv")
    rdvs = [Rendezvous(d, r, 2, timeout_s=10.0) for r in range(2)]
    rdvs[0].publish("127.0.0.1", 7001)
    rdvs[1].publish("127.0.0.1", 7002)
    tables = _par([r.table for r in rdvs])
    assert tables[0] == tables[1] == [("127.0.0.1", 7001),
                                      ("127.0.0.1", 7002)]
    # the committed table is valid JSON (atomic commit, never torn)
    doc = json.load(open(os.path.join(d, Rendezvous.TABLE)))
    assert doc["world"] == 2 and len(doc["peers"]) == 2


def test_rendezvous_timeout_names_missing_ranks(tmp_path):
    rdv = Rendezvous(str(tmp_path / "rdv"), 0, 2, timeout_s=0.2)
    rdv.publish("127.0.0.1", 7001)       # rank 1 never shows up
    with pytest.raises(TimeoutError, match=r"waiting on \[1\]"):
        rdv.table()


# -- collectives: BusWire byte-semantics oracle ------------------------------

def _collective_program(wire):
    """The same program every Wire implementation must answer alike:
    true-length byte gathers (empty buffers included), non-zero-root
    broadcast, array gather, tree broadcast, named barriers."""
    r, w = wire.rank(), wire.world_size()
    out = {}
    out["gather"] = wire.gather_bytes(b"r%d" % r * (r * 3))  # len varies
    out["gather_empty"] = wire.gather_bytes(b"" if r == 0 else b"x%d" % r)
    out["bcast"] = wire.bcast_bytes(
        b"root-payload" if r == w - 1 else b"IGNORED", root=w - 1)
    arr = np.arange(6, dtype=np.float32).reshape(2, 3) + r
    out["gather_array"] = wire.gather_array(arr)
    out["tree"] = wire.bcast_tree(
        {"a": [1, 2], "b": "z"} if r == 0 else None, root=0)
    wire.sync("epoch0")
    out["gather2"] = wire.gather_bytes(bytes([r]) * 5)
    return out


def test_socket_collectives_match_buswire_oracle(tmp_path):
    world = 3
    wires = _mesh(tmp_path, world)
    try:
        got = _par([lambda w=w: _collective_program(w) for w in wires])
    finally:
        _close_all(wires)
    bus = SimBus(world)
    want = _par([lambda h=h: _collective_program(BusWire(bus, h))
                 for h in range(world)])
    for r in range(world):
        assert got[r]["gather"] == want[r]["gather"]
        assert got[r]["gather_empty"] == want[r]["gather_empty"]
        assert got[r]["bcast"] == want[r]["bcast"] == b"root-payload"
        assert np.array_equal(got[r]["gather_array"],
                              want[r]["gather_array"])
        assert got[r]["tree"] == {"a": [1, 2], "b": "z"}
        assert got[r]["gather2"] == want[r]["gather2"]
    # the wire actually moved measured bytes, with coalescing live
    for w in wires:
        assert w.stats["frames_sent"] > 0
        assert w.stats["bytes_sent"] > 0
        assert w.stats["bytes_recv"] > 0


def test_single_rank_wire_needs_no_rendezvous():
    with SocketWire(rank=0, world=1) as w:
        assert w.gather_bytes(b"solo") == [b"solo"]
        assert w.bcast_bytes(b"b", root=0) == b"b"
        w.sync("noop")


def test_sync_tag_mismatch_surfaces_divergence(tmp_path):
    wires = _mesh(tmp_path, 2)
    try:
        with pytest.raises(RuntimeError, match="programs diverged"):
            _par([lambda: wires[0].sync("pass3"),
                  lambda: wires[1].sync("pass4")])
    finally:
        _close_all(wires)


# -- disconnect surfacing ----------------------------------------------------

def _kill_peer(victim):
    """Tear the victim's connections down WITHOUT marking it closed —
    from every other rank this is indistinguishable from the process
    dying mid-collective (shutdown(SHUT_RDWR) propagates immediately
    even to a thread parked in recv)."""
    for peer in list(victim._peers.values()):
        peer.close()


def test_disconnect_raises_peer_lost_without_watchdog(tmp_path):
    wires = _mesh(tmp_path, 2)
    try:
        _kill_peer(wires[1])
        with pytest.raises(PeerLostError, match="peer rank 1 lost"):
            wires[0].gather_bytes(b"never answered")
        assert PeerLostError.exit_code == ft_watchdog.PEER_LOST == 117
    finally:
        _close_all(wires)


def test_disconnect_trips_watchdog_exit_classes(tmp_path):
    """With a watchdog installed, a detected disconnect takes the SAME
    exit path a timed-out collective would — immediately, without
    waiting out the timeout (the trip() fast path)."""
    fired = []
    ft_watchdog.configure(30.0, exit_fn=fired.append)
    wires = _mesh(tmp_path, 2)
    t0 = time.monotonic()
    try:
        _kill_peer(wires[1])
        # the recorder returns (tests), so the error still propagates
        with pytest.raises(PeerLostError):
            wires[0].gather_bytes(b"x")
    finally:
        _close_all(wires)
    assert fired and "peer1" in fired[0], fired
    assert time.monotonic() - t0 < 15.0   # detected, not timed out
    assert ft_watchdog.get().fired_site == fired[0]


def test_orderly_close_is_not_peer_loss(tmp_path):
    """close() must not manufacture PEER_LOST: the closing wire ignores
    its own teardown EOFs, nothing is left waiting, and close is
    idempotent — so an installed watchdog never fires."""
    fired = []
    ft_watchdog.configure(30.0, exit_fn=fired.append)
    wires = _mesh(tmp_path, 2)
    _par([lambda w=w: w.gather_bytes(b"ok") for w in wires])
    _par([lambda w=w: w.close() for w in wires])
    time.sleep(0.1)                       # let recv threads drain EOFs
    _close_all(wires)                     # second close: no-op
    assert fired == []
    # a wire that closed ITSELF never marks peers dead (EOFs arriving
    # after _closed is set are orderly teardown, not peer loss)
    assert all(w._dead == {} or w._closed for w in wires)


def test_slow_peer_hits_wire_timeout(tmp_path):
    wires = _mesh(tmp_path, 2, timeout_s=0.3)
    try:
        with pytest.raises(TimeoutError, match="waited"):
            wires[0].gather_bytes(b"alone")   # rank 1 never calls
    finally:
        _close_all(wires)


# -- rejoin side channel -----------------------------------------------------

def test_rejoin_channel_roundtrip(tmp_path):
    wires = _mesh(tmp_path, 2)
    try:
        seen = []

        def provider(rank, have_idx):
            seen.append((rank, have_idx))
            return 5, [(3, b"delta3"), (4, b"delta4")]

        wires[0].serve_rejoin(provider)
        host, port = wires[1].peer_addr(0)
        join_idx, entries = SocketWire.request_rejoin(host, port,
                                                      rank=7, have_idx=3)
        assert (join_idx, entries) == (5, [(3, b"delta3"), (4, b"delta4")])
        assert seen == [(7, 3)]
        # the mesh stays usable after serving a rejoin connection
        res = _par([lambda w=w: w.gather_bytes(b"after") for w in wires])
        assert res[0] == res[1] == [b"after", b"after"]
    finally:
        _close_all(wires)


def test_rejoin_without_provider_is_refused(tmp_path):
    wires = _mesh(tmp_path, 2)
    try:
        host, port = wires[0].peer_addr(1)   # rank 1 never armed one
        with pytest.raises(RuntimeError, match="rejoin refused"):
            SocketWire.request_rejoin(host, port, rank=9, have_idx=0)
    finally:
        _close_all(wires)


# -- FilterChain stack parity: socket vs SimBus, fuzzed ----------------------

def _chain():
    return FilterChain(filters={"key_caching", "fixing_float",
                                "compressing"},
                       quant_bits=8, min_bytes=0)


def _stack_program(stack, rank, seed):
    """Randomized exchange mix through the full layer stack: lossy
    allreduces on an allowlisted site, exact allreduces elsewhere,
    quantized snapshot broadcasts, and an allgather — digested so
    socket-vs-sim comparison is a single bitwise witness per rank."""
    shape_rng = np.random.default_rng(seed)         # same on every rank
    rng = np.random.default_rng(seed * 100 + rank + 1)  # rank-local data
    h = hashlib.sha256()
    for i in range(6):
        n = int(shape_rng.integers(1, 2048))
        delta = rng.standard_normal(n).astype(np.float32)
        out = stack.allreduce(delta, None, op="sum", site="hier/delta")
        h.update(np.ascontiguousarray(out).tobytes())
        exact = rng.standard_normal(
            int(shape_rng.integers(1, 64))).astype(np.float64)
        out2 = stack.allreduce(exact, None, op="sum", site="ctl/exact")
        h.update(np.ascontiguousarray(out2).tobytes())
        if i % 2 == 0:
            snap = np.asarray(
                rng.standard_normal(512), np.float32)
            got = stack.broadcast(snap, None, root=0,
                                  site="serve/snapshot", op="sum")
            h.update(np.ascontiguousarray(got).tobytes())
    g = stack.allgather(np.arange(4, dtype=np.int64) * (rank + 1),
                        site="ctl/gather")
    h.update(np.ascontiguousarray(g).tobytes())
    stack.sync("fuzz_end")
    return h.hexdigest()


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_filterchain_parity_socket_vs_sim(tmp_path, seed):
    """tau=0 parity witness: the identical randomized FilterChain
    program over real TCP and over the SimBus oracle must be BITWISE
    identical on every rank — framing, coalescing and thread handoff
    may not perturb a single codec byte."""
    world = 2
    transport.reset_site_seq()
    wires = _mesh(tmp_path, world)
    try:
        sock_digests = _par([
            lambda w=w: _stack_program(
                TransportStack(wire=w, chain=_chain()), w.rank(), seed)
            for w in wires])
        for w in wires:
            assert w.stats["bytes_sent"] > 0
    finally:
        _close_all(wires)
    transport.reset_site_seq()
    bus = SimBus(world)
    sim_digests = _par([
        lambda h=h: _stack_program(
            TransportStack(wire=BusWire(bus, h), chain=_chain()), h, seed)
        for h in range(world)])
    assert sock_digests == sim_digests
    assert len(set(sock_digests)) == 1    # reduced state agrees fleet-wide
