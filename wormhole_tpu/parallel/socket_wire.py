"""TCP implementation of the :class:`~wormhole_tpu.parallel.transport.Wire`
seam: real cross-host bytes through real sockets.

Every other wire in the tree either simulates the cross-host hop in
process (``SimBus``) or delegates it to ``jax.distributed``'s static
coordinator (``ProcessWire``). :class:`SocketWire` is the repo-owned
hop — the ps-lite ``van.cc`` analogue — so the hierarchy, delta-snapshot
and rejoin paths can be measured over a kernel boundary, and CPU serve
replicas can peer with TPU trainers outside the jax process mesh.

Design:

- **Frames.** Length-prefixed: ``kind:u8 | seq:u64 | len:u32`` then
  ``len`` payload bytes, carried verbatim (the FilterChain codec buffer
  IS the payload — no re-framing, no copy). A length above
  ``max_frame`` is a protocol violation and tears the connection down
  (a torn/garbage stream must not drive a multi-GB allocation).
- **Rendezvous.** Tiny file/port discovery under one shared directory:
  every rank binds ``127.0.0.1:0``, commits ``advert_r<rank>.json``
  with the same tmp+fsync+``os.replace`` discipline the checkpointer
  uses (parallel/checkpoint.py ``_commit_bytes``), rank 0 polls the
  adverts and commits the consolidated ``peers.json`` peer table, and
  everyone else polls that. Readers never see a torn table.
- **Topology.** Full mesh: rank j dials every rank i < j (a HELLO
  frame carries the dialer's rank in the seq field); rank i accepts
  the rest. The acceptor keeps listening after the mesh is up so a
  rejoiner can reach a survivor's :meth:`SocketWire.serve_rejoin`
  port (the handshake + replay leg of ft/rejoin.py over TCP).
- **Overlap.** Each peer gets a send thread draining a BOUNDED outbox
  (``outbox_depth`` frames) and a recv thread parsing frames into a
  shared inbox. Callers enqueue and return, so the FilterChain encode
  (quant8+zlib) of the next window overlaps this window's socket I/O
  instead of serializing behind ``sendall``. The sender drains every
  queued frame it can and concatenates small ones into a single
  ``sendall`` — the seq/ctl/handshake messages that would otherwise
  pay a syscall each ride along with the data frames (TCP_NODELAY is
  on; coalescing is ours, not Nagle's).
- **Collective matching.** Every rank executes the same collective
  program in the same order, so a per-wire monotonic op counter IS the
  collective identity: frame ``seq`` from peer r matches this rank's
  own op number. TCP is FIFO per connection, so no reordering window
  is needed.
- **Fault surface.** Blocking waits sit under the stack's
  ``WatchdogLayer`` like every other wire. A disconnect is detected
  immediately by the peer's recv thread; a caller blocked on that peer
  then takes the SAME exit path the supervisor already handles — the
  installed watchdog's exit path (flight record + ``PEER_LOST`` 117)
  when one is configured, else :class:`PeerLostError`.

This module is the single home of raw ``socket`` imports in the
package (analysis/checkers rule WH-SOCKET); the launcher's free-port
helper lives here for that reason.
"""

from __future__ import annotations

import json
import os
import pickle
import queue
import socket
import struct
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from wormhole_tpu.ft import watchdog as _watchdog
from wormhole_tpu.parallel.transport import Wire

__all__ = [
    "SocketWire", "Rendezvous", "FrameParser", "FrameError",
    "PeerLostError", "pack_frame", "free_port",
    "MAX_FRAME", "RENDEZVOUS_ENV",
    "K_HELLO", "K_GATHER", "K_BCAST", "K_SYNC", "K_CTL",
    "K_REJOIN", "K_REJOIN_REPLY",
]

# Env fallbacks: the supervised launcher already exports PROCESS_ID /
# NUM_PROCESSES to every child; the rendezvous dir rides its own var so
# a worker can build a wire without a Config in hand.
RENDEZVOUS_ENV = "WORMHOLE_WIRE_RENDEZVOUS"

# frame kinds
K_HELLO = 0         # mesh join: seq field carries the dialer's rank
K_GATHER = 1        # one rank's contribution to an all-gather op
K_BCAST = 2         # root's payload of a broadcast op
K_SYNC = 3          # named barrier (payload = tag bytes, cross-checked)
K_CTL = 4           # small control payloads (reserved for callers)
K_REJOIN = 5        # rejoiner -> survivor: pickled {rank, have}
K_REJOIN_REPLY = 6  # survivor -> rejoiner: pickled (join_idx, entries)

_HDR = struct.Struct("<BQI")     # kind, seq, payload length

# Reject anything claiming more than this before allocating: a torn or
# hostile stream read as a length prefix must not OOM the process.
MAX_FRAME = 1 << 30

# sender-side coalescing bound: keep concatenating queued frames into
# one sendall until the batch passes this many bytes
_COALESCE_BYTES = 1 << 16
_RECV_CHUNK = 1 << 16
# what an orderly close gives a peer's send thread to empty its outbox
_CLOSE_FLUSH_S = 2.0


def free_port() -> int:
    """An OS-assigned free loopback port (bind-to-0 probe). Shared by
    the mp launcher's coordinator setup — the one other place in the
    tree that needs a port without owning a socket lifetime."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class FrameError(ValueError):
    """A malformed frame on the stream (oversized length prefix)."""


class PeerLostError(RuntimeError):
    """A peer's connection died while a collective was waiting on it.
    ``exit_code`` mirrors the watchdog's exit codes so callers that map
    errors to process exits use the code the supervisor expects."""

    exit_code = _watchdog.PEER_LOST


def pack_frame(kind: int, seq: int, payload: bytes) -> bytes:
    """One wire frame: header + payload bytes, ready for sendall."""
    return _HDR.pack(kind, seq, len(payload)) + payload


class FrameParser:
    """Incremental frame decoder over an arbitrary chunking of the
    stream. ``feed`` buffers partial (torn) frames until the rest
    arrives and raises :class:`FrameError` on an oversized length
    prefix — the connection is unrecoverable past that point because
    the stream offset is garbage."""

    def __init__(self, max_frame: int = MAX_FRAME) -> None:
        self.max_frame = int(max_frame)
        self._buf = bytearray()

    def feed(self, data: bytes) -> List[Tuple[int, int, bytes]]:
        self._buf += data
        frames: List[Tuple[int, int, bytes]] = []
        while len(self._buf) >= _HDR.size:
            kind, seq, ln = _HDR.unpack_from(self._buf, 0)
            if ln > self.max_frame:
                raise FrameError(
                    f"frame length {ln} exceeds max_frame "
                    f"{self.max_frame} (kind={kind}, seq={seq}) — "
                    f"stream torn or not a wire peer")
            end = _HDR.size + ln
            if len(self._buf) < end:
                break
            frames.append((kind, seq, bytes(self._buf[_HDR.size:end])))
            del self._buf[:end]
        return frames

    def pending(self) -> int:
        """Bytes of an incomplete frame currently buffered."""
        return len(self._buf)


# ---------------------------------------------------------------------------
# rendezvous: file/port discovery with the checkpointer's commit discipline
# ---------------------------------------------------------------------------

def _commit_bytes(path: str, data: bytes) -> None:
    """tmp + fsync + os.replace, the same durable-atomic commit the
    checkpointer uses: a poller never reads a torn advert or table."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class Rendezvous:
    """File/port peer discovery under one shared directory.

    Every rank commits ``advert_r<rank>.json`` with its bound address;
    rank 0 polls until all ``world`` adverts exist and commits the
    consolidated ``peers.json`` table; ranks > 0 poll the table. Both
    files are committed atomically, so polling readers either see a
    complete document or none."""

    TABLE = "peers.json"

    def __init__(self, directory: str, rank: int, world: int,
                 timeout_s: float = 60.0, poll_itv: float = 0.02) -> None:
        if not directory:
            raise ValueError("SocketWire rendezvous directory is empty "
                             f"(pass rendezvous= or set {RENDEZVOUS_ENV})")
        self.dir = directory
        self.rank = int(rank)
        self.world = int(world)
        self.timeout_s = float(timeout_s)
        self.poll_itv = float(poll_itv)
        os.makedirs(self.dir, exist_ok=True)

    def _advert(self, rank: int) -> str:
        return os.path.join(self.dir, f"advert_r{rank}.json")

    def publish(self, host: str, port: int) -> None:
        _commit_bytes(self._advert(self.rank), json.dumps(
            {"rank": self.rank, "host": host, "port": int(port),
             "pid": os.getpid()}).encode())

    def _read_json(self, path: str) -> Optional[dict]:
        try:
            with open(path, "rb") as f:
                return json.loads(f.read().decode())
        except (OSError, ValueError):
            return None

    def table(self) -> List[Tuple[str, int]]:
        """Block until the full peer table exists; return rank-ordered
        ``(host, port)``. Rank 0 assembles and commits it; the rest
        poll the committed file."""
        deadline = time.monotonic() + self.timeout_s
        path = os.path.join(self.dir, self.TABLE)
        while True:
            if self.rank == 0:
                ads = [self._read_json(self._advert(r))
                       for r in range(self.world)]
                if all(a is not None for a in ads):
                    _commit_bytes(path, json.dumps(
                        {"world": self.world,
                         "peers": [{"rank": a["rank"], "host": a["host"],
                                    "port": a["port"]} for a in ads]}
                    ).encode())
                    return [(a["host"], int(a["port"])) for a in ads]
                missing = [r for r, a in enumerate(ads) if a is None]
            else:
                doc = self._read_json(path)
                if doc is not None and doc.get("world") == self.world:
                    peers = sorted(doc["peers"], key=lambda p: p["rank"])
                    return [(p["host"], int(p["port"])) for p in peers]
                missing = ["table"]
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"wire rendezvous timed out after {self.timeout_s}s "
                    f"in {self.dir} (rank {self.rank} waiting on "
                    f"{missing})")
            time.sleep(self.poll_itv)


# ---------------------------------------------------------------------------
# the wire
# ---------------------------------------------------------------------------

class _Peer:
    """One established connection: a bounded outbox drained by a send
    thread (coalescing), and a recv thread parsing frames into the
    wire's shared inbox."""

    def __init__(self, wire: "SocketWire", rank: int,
                 sock: socket.socket, parser: FrameParser) -> None:
        self.wire = wire
        self.rank = rank
        self.sock = sock
        self.parser = parser
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.outbox: "queue.Queue[Optional[bytes]]" = queue.Queue(
            maxsize=wire.outbox_depth)
        self._sender = threading.Thread(
            target=self._send_loop, daemon=True,
            name=f"wire-send-r{wire._rank}-to-r{rank}")
        self._recver = threading.Thread(
            target=self._recv_loop, daemon=True,
            name=f"wire-recv-r{wire._rank}-from-r{rank}")
        self._sender.start()
        self._recver.start()

    def put(self, frame: bytes) -> None:
        """Enqueue one frame (blocks on a full outbox — backpressure,
        not unbounded memory). A dead peer drains to nowhere rather
        than wedging the sender: the RECV side is where loss must
        surface, on the rank that actually waits for the peer."""
        while True:
            if self.rank in self.wire._dead:
                return
            try:
                self.outbox.put(frame, timeout=0.2)
                return
            except queue.Full:
                continue

    def _send_loop(self) -> None:
        w = self.wire
        while True:
            item = self.outbox.get()
            if item is None:
                return
            chunks = [item]
            total = len(item)
            stop = False
            # coalesce whatever else is already queued: small ctl/sync
            # frames ride one sendall instead of a syscall each
            while total < _COALESCE_BYTES:
                try:
                    nxt = self.outbox.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:
                    stop = True
                    break
                chunks.append(nxt)
                total += len(nxt)
            t0 = time.perf_counter()
            try:
                self.sock.sendall(b"".join(chunks))
            except OSError as e:
                self.wire._mark_dead(self.rank, f"send failed: {e}")
                return
            with w._stats_lock:
                w.stats["sends"] += 1
                w.stats["frames_sent"] += len(chunks)
                w.stats["coalesced_frames"] += len(chunks) - 1
                w.stats["bytes_sent"] += total
                w.stats["send_s"] += time.perf_counter() - t0
            if stop:
                return

    def _recv_loop(self) -> None:
        w = self.wire
        while True:
            try:
                data = self.sock.recv(_RECV_CHUNK)
            except OSError as e:
                w._mark_dead(self.rank, f"recv failed: {e}")
                return
            if not data:
                w._mark_dead(self.rank, "connection closed")
                return
            try:
                frames = self.parser.feed(data)
            except FrameError as e:
                w._mark_dead(self.rank, str(e))
                return
            with w._stats_lock:
                w.stats["bytes_recv"] += len(data)
                w.stats["frames_recv"] += len(frames)
            if not frames:
                continue
            with w._cv:
                for kind, seq, payload in frames:
                    w._inbox[(self.rank, kind, seq)] = payload
                w._cv.notify_all()

    def close(self) -> None:
        """Orderly close: the frames already in the outbox go out first.
        A rank that leaves its last collective a moment before its peer
        has only ENQUEUED its half of it; shutting the socket down under
        the send thread dropped that frame, and the peer then read the
        close as a loss mid-collective (seen on a loaded host). So the
        sentinel is queued behind the frames and the send thread is
        given ``_CLOSE_FLUSH_S`` to reach it; a peer already marked dead
        is not waited for."""
        try:
            self.outbox.put(None, timeout=_CLOSE_FLUSH_S)
        except queue.Full:
            pass
        if self.rank not in self.wire._dead:
            self._sender.join(_CLOSE_FLUSH_S)
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class SocketWire(Wire):
    """TCP full-mesh :class:`Wire`: byte semantics mirror BusWire /
    ProcessWire exactly (``gather_bytes`` returns TRUE-length per-rank
    buffers in rank order; ``bcast_bytes`` returns the root's buffer on
    every rank including the root), so the layer stack, FilterChain
    codec and tau=0 parity oracles compose unchanged on top."""

    def __init__(self, rank: Optional[int] = None,
                 world: Optional[int] = None,
                 rendezvous: Optional[str] = None, *,
                 outbox_depth: int = 8,
                 timeout_s: float = 120.0,
                 connect_timeout_s: float = 60.0,
                 max_frame: int = MAX_FRAME,
                 host: str = "127.0.0.1") -> None:
        if rank is None:
            rank = int(os.environ.get("PROCESS_ID", "0"))
        if world is None:
            world = int(os.environ.get("NUM_PROCESSES", "1"))
        if rendezvous is None:
            rendezvous = os.environ.get(RENDEZVOUS_ENV, "")
        if not 0 <= rank < world:
            raise ValueError(f"rank {rank} outside world {world}")
        self._rank = int(rank)
        self._world = int(world)
        self.outbox_depth = max(1, int(outbox_depth))
        self.timeout_s = float(timeout_s)
        self.max_frame = int(max_frame)
        self._cv = threading.Condition()
        self._inbox: Dict[Tuple[int, int, int], bytes] = {}
        self._dead: Dict[int, str] = {}
        self._peers: Dict[int, _Peer] = {}
        self._closed = False
        self._oplock = threading.Lock()
        self._opseq = 0
        self._stats_lock = threading.Lock()
        self.stats: Dict[str, float] = {
            "bytes_sent": 0, "bytes_recv": 0, "frames_sent": 0,
            "frames_recv": 0, "sends": 0, "coalesced_frames": 0,
            "send_s": 0.0, "recv_wait_s": 0.0}
        self._rejoin_provider: Optional[Callable] = None
        self._listener = socket.socket()
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind((host, 0))
        self._listener.listen(self._world + 2)
        self.port = self._listener.getsockname()[1]
        self._acceptor = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"wire-accept-r{self._rank}")
        self._acceptor.start()
        if self._world > 1:
            rdv = Rendezvous(rendezvous, self._rank, self._world,
                             timeout_s=connect_timeout_s)
            rdv.publish(host, self.port)
            self._table = rdv.table()
            self._connect_mesh(connect_timeout_s)
        else:
            self._table = [(host, self.port)]

    # -- mesh setup ---------------------------------------------------

    def _connect_mesh(self, timeout_s: float) -> None:
        # dial every lower rank; the acceptor collects the higher ones
        for r in range(self._rank):
            h, p = self._table[r]
            deadline = time.monotonic() + timeout_s
            while True:
                try:
                    s = socket.create_connection((h, p), timeout=5.0)
                    break
                except OSError:
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            f"rank {self._rank} could not dial rank {r} "
                            f"at {h}:{p} within {timeout_s}s")
                    time.sleep(0.02)
            s.sendall(pack_frame(K_HELLO, self._rank, b""))
            with self._cv:
                self._peers[r] = _Peer(self, r, s, FrameParser(
                    self.max_frame))
                self._cv.notify_all()
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while len(self._peers) < self._world - 1:
                left = deadline - time.monotonic()
                if left <= 0:
                    have = sorted(self._peers)
                    raise TimeoutError(
                        f"rank {self._rank} mesh incomplete after "
                        f"{timeout_s}s: connected {have} of "
                        f"{self._world - 1} peers")
                self._cv.wait(left)

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                s, _addr = self._listener.accept()
            except OSError:
                return  # listener closed
            threading.Thread(target=self._admit, args=(s,), daemon=True,
                             name=f"wire-admit-r{self._rank}").start()

    def _admit(self, s: socket.socket) -> None:
        """Read the first frame of a fresh connection: HELLO joins the
        mesh (any bytes already past the hello stay in the parser and
        flow to the recv thread); REJOIN serves the handshake+replay
        request and closes."""
        parser = FrameParser(self.max_frame)
        s.settimeout(30.0)
        frames: List[Tuple[int, int, bytes]] = []
        try:
            while not frames:
                data = s.recv(_RECV_CHUNK)
                if not data:
                    s.close()
                    return
                frames = parser.feed(data)
        except (OSError, FrameError):
            s.close()
            return
        kind, seq, payload = frames[0]
        if kind == K_HELLO:
            peer_rank = int(seq)
            s.settimeout(None)
            with self._cv:
                peer = _Peer(self, peer_rank, s, parser)
                self._peers[peer_rank] = peer
                # frames that rode in behind the hello
                for k, sq, p in frames[1:]:
                    self._inbox[(peer_rank, k, sq)] = p
                self._cv.notify_all()
            return
        if kind == K_REJOIN:
            self._serve_rejoin_conn(s, payload)
            return
        s.close()

    # -- Wire surface -------------------------------------------------

    def world_size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    def _next_op(self) -> int:
        with self._oplock:
            n = self._opseq
            self._opseq += 1
            return n

    def _peer_ranks(self) -> List[int]:
        return [r for r in range(self._world) if r != self._rank]

    def _take(self, rank: int, kind: int, seq: int,
              site: Optional[str] = None) -> bytes:
        key = (rank, kind, seq)
        deadline = time.monotonic() + self.timeout_s
        t0 = time.perf_counter()
        with self._cv:
            while key not in self._inbox:
                if rank in self._dead:
                    self._peer_lost(rank, site)
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"socket wire: rank {self._rank} waited "
                        f"{self.timeout_s:.0f}s for rank {rank} "
                        f"(kind={kind}, op={seq})")
                self._cv.wait(left)
            out = self._inbox.pop(key)
        with self._stats_lock:
            self.stats["recv_wait_s"] += time.perf_counter() - t0
        return out

    def _mark_dead(self, rank: int, why: str) -> None:
        if self._closed:
            return  # orderly teardown, not a lost peer
        with self._cv:
            self._dead.setdefault(rank, why)
            self._cv.notify_all()

    def _peer_lost(self, rank: int, site: Optional[str]) -> None:
        """Surface a disconnect with the exit-code scheme the supervisor
        already handles: the installed watchdog's exit path (flight
        record + PEER_LOST exit) when one is configured — a disconnect
        is a *detected* peer loss, there is nothing to wait out — else
        a :class:`PeerLostError` carrying the same code."""
        why = self._dead.get(rank, "lost")
        label = f"{site or 'socket'}:peer{rank}"
        msg = (f"socket wire: peer rank {rank} lost mid-collective "
               f"({why})")
        wd = _watchdog.get()
        if wd is not None:
            sys.stderr.write(f"[wire] {msg}\n")
            sys.stderr.flush()
            wd.trip(label)
        raise PeerLostError(msg)

    def gather_bytes(self, buf: bytes) -> List[bytes]:
        buf = bytes(buf)
        op = self._next_op()
        frame = pack_frame(K_GATHER, op, buf)
        for r in self._peer_ranks():
            self._peers[r].put(frame)
        out: List[Optional[bytes]] = [None] * self._world
        out[self._rank] = buf
        for r in self._peer_ranks():
            out[r] = self._take(r, K_GATHER, op)
        return out  # type: ignore[return-value]

    def gather_array(self, x):
        x = np.ascontiguousarray(np.asarray(x))
        rows = self.gather_bytes(pickle.dumps(
            (x.dtype.str, x.shape, x.tobytes())))
        parts = [pickle.loads(b) for b in rows]
        return np.stack([np.frombuffer(b, np.dtype(dt)).reshape(shp)
                         for dt, shp, b in parts])

    def bcast_bytes(self, buf: bytes, root: int) -> bytes:
        op = self._next_op()
        if self._rank == root:
            buf = bytes(buf)
            frame = pack_frame(K_BCAST, op, buf)
            for r in self._peer_ranks():
                self._peers[r].put(frame)
            return buf
        return self._take(root, K_BCAST, op)

    def bcast_tree(self, tree, root: int):
        return pickle.loads(self.bcast_bytes(
            pickle.dumps(tree) if self._rank == root else b"", root))

    def sync(self, tag: str) -> None:
        op = self._next_op()
        payload = tag.encode()
        frame = pack_frame(K_SYNC, op, payload)
        for r in self._peer_ranks():
            self._peers[r].put(frame)
        for r in self._peer_ranks():
            got = self._take(r, K_SYNC, op, site=f"sync:{tag}")
            if got != payload:
                raise RuntimeError(
                    f"socket wire: barrier tag mismatch at op {op}: "
                    f"rank {self._rank} has {tag!r}, rank {r} has "
                    f"{got.decode(errors='replace')!r} — collective "
                    f"programs diverged")

    # -- rejoin port --------------------------------------------------

    def serve_rejoin(self, provider: Callable[[int, int],
                                              Tuple[int, list]]) -> None:
        """Arm this wire's listener as a survivor-side rejoin port:
        ``provider(rank, have_idx)`` runs the in-process handshake
        (``group.attach`` + ``replay.fetch``) and its ``(join_idx,
        entries)`` result ships back over the connection."""
        self._rejoin_provider = provider

    def _serve_rejoin_conn(self, s: socket.socket, payload: bytes) -> None:
        try:
            req = pickle.loads(payload)
            if self._rejoin_provider is None:
                reply = {"error": "no rejoin provider armed"}
            else:
                join_idx, entries = self._rejoin_provider(
                    int(req["rank"]), int(req["have"]))
                reply = {"join_idx": join_idx, "entries": entries}
            s.sendall(pack_frame(K_REJOIN_REPLY, 0, pickle.dumps(reply)))
        except (OSError, pickle.PickleError, KeyError, ValueError) as e:
            try:
                s.sendall(pack_frame(K_REJOIN_REPLY, 0,
                                     pickle.dumps({"error": repr(e)})))
            except OSError:
                pass
        finally:
            s.close()

    @staticmethod
    def request_rejoin(host: str, port: int, rank: int, have_idx: int,
                       timeout_s: float = 30.0,
                       max_frame: int = MAX_FRAME) -> Tuple[int, list]:
        """Rejoiner side: dial a survivor's wire port, send the
        handshake request, return ``(join_idx, entries)`` to replay."""
        with socket.create_connection((host, port),
                                      timeout=timeout_s) as s:
            s.settimeout(timeout_s)
            s.sendall(pack_frame(K_REJOIN, 0, pickle.dumps(
                {"rank": int(rank), "have": int(have_idx)})))
            parser = FrameParser(max_frame)
            frames: List[Tuple[int, int, bytes]] = []
            while not frames:
                data = s.recv(_RECV_CHUNK)
                if not data:
                    raise PeerLostError(
                        "rejoin survivor closed before replying")
                frames = parser.feed(data)
            kind, _, payload = frames[0]
            if kind != K_REJOIN_REPLY:
                raise FrameError(f"expected REJOIN_REPLY, got kind {kind}")
            reply = pickle.loads(payload)
            if "error" in reply:
                raise RuntimeError(f"rejoin refused: {reply['error']}")
            return int(reply["join_idx"]), list(reply["entries"])

    # -- lifecycle ----------------------------------------------------

    def peer_addr(self, rank: int) -> Tuple[str, int]:
        """The rendezvous-advertised ``(host, port)`` of ``rank``."""
        return self._table[rank]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._listener.close()
        except OSError:
            pass
        for peer in list(self._peers.values()):
            peer.close()

    def __enter__(self) -> "SocketWire":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
