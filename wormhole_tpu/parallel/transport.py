"""Unified transport: one layered stack under every exchange path.

Before this module the repo moved state through three disjoint paths —
the BSP host tree collectives (collectives.py), the bounded-staleness
``ExchangeEngine`` drain thread (ps/engine.py), and the in-jit
``shard_map`` collectives (mesh.py) — each re-porting its own
site-id/seq stamping, FilterChain routing, watchdog arming and wire
accounting. Here those cross-cutting concerns are composable
:class:`Layer` objects folded around a raw :class:`Wire`, so every
path shares ONE implementation of each concern:

    SeqLayer        per-site call counters ((site, seq) span identity;
                    obs/merge.py matches spans across ranks by it)
    SpanLayer       the ``collective:*`` trace spans
    LocalLayer      single-process fast path (span still recorded;
                    everything below skipped)
    ChaosLayer      ft/chaos straggler injection
    WatchdogLayer   ft/watchdog arming (PEER_LOST escape hatch)
    FilterLayer     resolves the process-global FilterChain
    AccountingLayer books bytes_raw/bytes_wire deltas onto span args
    -- base --      encode/exchange/decode against the Wire

The :class:`Wire` is the only seam that differs per deployment:
:class:`ProcessWire` is the real DCN hop (the ONLY place in the tree
allowed to call ``jax.experimental.multihost_utils`` — enforced by
scripts/lint_collectives.py rule 1); :class:`BusWire` is an in-process
simulated host endpoint on a :class:`SimBus` (tests and the bench
``hierarchy`` phase run H fake hosts in one process, each with its own
FilterChain, exchanging real encoded bytes).

On top of the stack sit the two composite transports:

- :class:`MeshTransport` — the intra-host leg. ``shard_map`` psums
  lower onto ICI inside the compiled step, so they can never route
  through the host wire or the filter chain; what CAN apply uniformly
  is stamped here: site/seq, the ``collective:mesh`` span, watchdog
  arming, chaos, and ICI byte accounting (``comm/bytes_ici``, modeled
  from the step's known psum payload shapes via :func:`ici_ring_bytes`).
- :class:`HierarchicalTransport` — the 2D topology: each host reduces
  over its own ``(data, model)`` mesh via the MeshTransport leg and
  ships only the host-level bucket-space delta cross-host through the
  filtered wire, optionally through an ``ExchangeEngine`` so up to
  ``staleness_tau`` deltas overlap compute. At tau=0 the engine path
  degenerates to submit-then-wait and is bit-identical to the direct
  BSP exchange (the parity oracle tests/test_transport.py pins).
"""

from __future__ import annotations

import os
import pickle
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.ft import chaos as _chaos
from wormhole_tpu.ft import watchdog as _watchdog
from wormhole_tpu.obs import trace

__all__ = [
    "Exchange", "Layer", "SeqLayer", "SpanLayer", "LocalLayer",
    "ChaosLayer", "WatchdogLayer", "FilterLayer", "AccountingLayer",
    "Wire", "ProcessWire", "SimBus", "BusWire",
    "TransportStack", "default_stack", "set_default_stack",
    "install_wire_from_config",
    "default_layers", "validate_layers", "reset_site_seq",
    "MeshTransport", "HierarchicalTransport", "ici_ring_bytes",
]


# ---------------------------------------------------------------------------
# per-site sequence counters (shared by every path)
# ---------------------------------------------------------------------------
#
# Every rank executes the same collective program, so the Nth call at a
# site is the SAME logical collective on every rank — obs/merge.py
# matches spans across rank trace files by (site, seq) to compute
# arrival skew. The counter advances whether or not tracing is on (a
# late-enabled trace must not desynchronize the numbering), and one
# counter covers all exchange kinds at a site (call order, not kind,
# is the identity). Mesh dispatches share the same counter space.

_SITE_SEQ: Dict[str, int] = {}


def _next_seq(site: str) -> int:
    n = _SITE_SEQ.get(site, 0)
    _SITE_SEQ[site] = n + 1
    return n


def reset_site_seq() -> None:
    """Forget per-site sequence numbers (tests / fresh logical runs)."""
    _SITE_SEQ.clear()


# ---------------------------------------------------------------------------
# exchange description
# ---------------------------------------------------------------------------

@dataclass
class Exchange:
    """One host-level exchange moving through the layer stack. Layers
    communicate by mutating this record (attrs, chain) on the way down;
    the base exchange consumes it against the wire."""

    kind: str                      # "allreduce" | "allgather" | "broadcast"
    tree: Any
    op: str = "sum"
    site: Optional[str] = None
    root: int = 0
    mesh: Any = None               # carried for API symmetry; unused by wires
    compress: bool = False         # legacy pre-filter-chain zlib knob
    attrs: Optional[dict] = None   # span args (seq, byte accounting)
    chain: Any = None              # resolved FilterChain (FilterLayer)
    chain_override: Any = None     # stack-pinned chain (simulated hosts)
    wire: Any = None               # set by TransportStack.execute

    def span_name(self) -> str:
        if self.kind == "allreduce":
            return f"collective:allreduce_{self.op}"
        return f"collective:{self.kind}"

    def guard_site(self) -> str:
        """Watchdog slot label: the site id, else the kind."""
        if self.site:
            return self.site
        if self.kind == "allreduce":
            return f"allreduce_{self.op}"
        return self.kind


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

class Layer:
    """One cross-cutting concern wrapped around the exchange.

    ``requires`` names layers that must sit OUTSIDE (before) this one;
    :func:`validate_layers` enforces it. Everything not constrained
    commutes — tests/test_transport.py pins result invariance under
    permutation of the commuting suffix."""

    name = "layer"
    requires: Tuple[str, ...] = ()

    def run(self, ex: Exchange, inner: Callable[[Exchange], Any]) -> Any:
        return inner(ex)


class SeqLayer(Layer):
    """Owns ordering: stamps (site, seq) into the span attrs. Must be
    outermost of the attrs-touching layers — the span snapshots the
    dict it is handed, and the fast path must still advance counters."""

    name = "seq"

    def run(self, ex, inner):
        if ex.site is not None and ex.attrs is None:
            ex.attrs = {"site": ex.site}
        if ex.attrs is not None:
            ex.attrs["seq"] = _next_seq(ex.attrs["site"])
        return inner(ex)


class SpanLayer(Layer):
    """Owns telemetry: the ``collective:*`` span, recorded on the
    single-process fast path too — the boundary is where the sync
    would be, which is what a trace reader looks for."""

    name = "span"
    requires = ("seq",)

    def run(self, ex, inner):
        with trace.span(ex.span_name(), cat="collective", args=ex.attrs):
            return inner(ex)


class LocalLayer(Layer):
    """Single-process fast path: seq advanced and span recorded above,
    everything below (chaos, watchdog, filters, wire) skipped so the
    per-call cost stays a few dict ops."""

    name = "local"
    requires = ("seq", "span")

    def run(self, ex, inner):
        if ex.wire.world_size() == 1:
            if ex.kind == "allgather":
                return jax.tree.map(lambda x: np.asarray(x)[None], ex.tree)
            return ex.tree  # allreduce: one logical copy; broadcast: root
        return inner(ex)


class ChaosLayer(Layer):
    """FT test hook: injected straggler delay (ft/chaos)."""

    name = "chaos"
    requires = ("local",)

    def run(self, ex, inner):
        _chaos.on_collective(ex.site)
        return inner(ex)


class WatchdogLayer(Layer):
    """Owns FT arming: the CollectiveWatchdog slot around the blocking
    wire call (ft/watchdog — PEER_LOST escape from a dead peer)."""

    name = "watchdog"
    requires = ("local",)

    def run(self, ex, inner):
        with _watchdog.guard(ex.guard_site()):
            return inner(ex)


class FilterLayer(Layer):
    """Owns codec selection: resolves the process-global FilterChain
    (parallel/filters.py), else the compression-only fallback for
    legacy ``compress=True`` callers, else None (raw wire)."""

    name = "filter"
    requires = ("local",)

    def run(self, ex, inner):
        if ex.chain_override is not None:
            # a stack-pinned chain (one per simulated host) never falls
            # back to the process-global: H fake hosts in one process
            # must not share key caches or EF residuals
            ch = ex.chain_override
            ex.chain = ch if ch.active_for(ex.site) else None
        else:
            ex.chain = _resolve_chain(ex.site, ex.compress)
        return inner(ex)


class AccountingLayer(Layer):
    """Owns wire accounting: books this exchange's bytes_raw/bytes_wire
    deltas (the chain's cumulative stats, diffed around the exchange)
    onto the span args. The Registry counters themselves are advanced
    by the chain's codec (filters.FilterChain._account)."""

    name = "accounting"
    requires = ("filter",)

    def run(self, ex, inner):
        ch = ex.chain
        if ch is None or ex.attrs is None:
            return inner(ex)
        raw0, wire0 = ch.stats["bytes_raw"], ch.stats["bytes_wire"]
        out = inner(ex)
        ex.attrs["bytes_raw"] = ch.stats["bytes_raw"] - raw0
        ex.attrs["bytes_wire"] = ch.stats["bytes_wire"] - wire0
        return out


def default_layers() -> List[Layer]:
    """The canonical stack, outermost first."""
    return [SeqLayer(), SpanLayer(), LocalLayer(), ChaosLayer(),
            WatchdogLayer(), FilterLayer(), AccountingLayer()]


def validate_layers(layers) -> None:
    """Enforce each layer's ``requires`` ordering constraints."""
    seen = set()
    for l in layers:
        missing = [r for r in l.requires if r not in seen]
        if missing:
            raise ValueError(
                f"transport layer {l.name!r} requires {missing} "
                f"outside it (have {sorted(seen)}); canonical order is "
                f"{[x.name for x in default_layers()]}")
        seen.add(l.name)


# ---------------------------------------------------------------------------
# filter-chain resolution (shared with the legacy compress knob)
# ---------------------------------------------------------------------------

_LEGACY_Z = None


def _resolve_chain(site, compress: bool):
    """The chain this call should route through: the installed global
    chain when active, else a compression-only fallback for legacy
    ``compress=True`` callers (the pre-filters zlib leaf codec)."""
    from wormhole_tpu.parallel import filters
    chain = filters.get_chain()
    if chain is not None and chain.active_for(site):
        return chain
    if compress:
        global _LEGACY_Z
        if _LEGACY_Z is None:
            _LEGACY_Z = filters.FilterChain(filters={"compressing"},
                                            min_bytes=0)
        return _LEGACY_Z
    return None


# ---------------------------------------------------------------------------
# wires
# ---------------------------------------------------------------------------

class Wire:
    """Raw exchange primitives under the layer stack. A wire knows how
    to move bytes/arrays between participants and nothing else — no
    filters, no spans, no FT. Byte gathers return each participant's
    TRUE-length buffer (padding needed for fixed-shape transports never
    leaks to the codec)."""

    def world_size(self) -> int:
        raise NotImplementedError

    def rank(self) -> int:
        raise NotImplementedError

    def gather_bytes(self, buf: bytes) -> List[bytes]:
        raise NotImplementedError

    def gather_array(self, x):
        raise NotImplementedError

    def bcast_bytes(self, buf: bytes, root: int) -> bytes:
        raise NotImplementedError

    def bcast_tree(self, tree, root: int):
        raise NotImplementedError

    def sync(self, tag: str) -> None:
        raise NotImplementedError


class ProcessWire(Wire):
    """The real DCN hop: JAX multi-controller collectives. This class
    is the single home of raw ``multihost_utils`` calls (lint rule 1);
    everything else in the tree reaches the wire through the stack."""

    def world_size(self) -> int:
        return jax.process_count()

    def rank(self) -> int:
        return jax.process_index()

    def gather_bytes(self, buf: bytes) -> List[bytes]:
        """Padded fixed-shape allgather: one int64 length exchange, pad
        every buffer to the max wire length, slice each rank's chunk
        back to the sender's true length."""
        from jax.experimental import multihost_utils
        lens = np.asarray(multihost_utils.process_allgather(
            np.int64(len(buf))))
        pad = np.zeros(int(lens.max()), np.uint8)
        pad[:len(buf)] = np.frombuffer(buf, np.uint8)
        g = np.asarray(multihost_utils.process_allgather(pad))
        return [g[r, :int(lens[r])].tobytes() for r in range(g.shape[0])]

    def gather_array(self, x):
        from jax.experimental import multihost_utils
        return multihost_utils.process_allgather(jnp.asarray(x))

    def bcast_bytes(self, buf: bytes, root: int) -> bytes:
        from jax.experimental import multihost_utils
        src = jax.process_index() == root
        n = int(np.asarray(multihost_utils.broadcast_one_to_all(
            np.int64(len(buf)), is_source=src)))
        pad = np.zeros(n, np.uint8)
        if src:
            pad[:len(buf)] = np.frombuffer(buf, np.uint8)
        g = np.asarray(multihost_utils.broadcast_one_to_all(
            pad, is_source=src))
        return g.tobytes()

    def bcast_tree(self, tree, root: int):
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(
            tree, is_source=jax.process_index() == root)

    def host_local_to_global(self, tree, mesh, pspec):
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            tree, mesh, pspec)

    def sync(self, tag: str) -> None:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)


class SimBus:
    """In-process rendezvous for N simulated hosts (tests and the bench
    ``hierarchy`` phase; production rides :class:`ProcessWire`). Each
    round is an all-to-all: host h deposits its payload and blocks
    until all N have, then every host reads the same ordered row.
    Thread-per-host or engine-drain-thread callers both work — the
    rendezvous is keyed by each host's own round cursor, so hosts may
    be a round apart without cross-talk."""

    def __init__(self, hosts: int, timeout_s: float = 120.0) -> None:
        if hosts < 1:
            raise ValueError(f"SimBus needs >= 1 host, got {hosts}")
        self.hosts = int(hosts)
        self.timeout_s = float(timeout_s)
        self._cv = threading.Condition()
        self._cursor = [0] * self.hosts      # per-host round counter
        self._slots: Dict[int, dict] = {}    # round -> {host: payload}
        self._rows: Dict[int, list] = {}     # round -> ordered payloads
        self._read: Dict[int, int] = {}      # round -> hosts done reading

    def exchange(self, host: int, payload) -> list:
        with self._cv:
            r = self._cursor[host]
            self._cursor[host] = r + 1
            self._slots.setdefault(r, {})[host] = payload
            if len(self._slots[r]) == self.hosts:
                row = self._slots.pop(r)
                self._rows[r] = [row[h] for h in range(self.hosts)]
                self._read[r] = 0
                self._cv.notify_all()
            else:
                while r not in self._rows:
                    if not self._cv.wait(timeout=self.timeout_s):
                        raise RuntimeError(
                            f"SimBus rendezvous timed out: host {host} "
                            f"round {r} has {len(self._slots.get(r, {}))}"
                            f"/{self.hosts} participants")
            out = self._rows[r]
            self._read[r] += 1
            if self._read[r] == self.hosts:
                del self._rows[r], self._read[r]
            return out


class BusWire(Wire):
    """One simulated host's endpoint on a :class:`SimBus`. Payload
    semantics mirror ProcessWire at the byte level: ``gather_bytes``
    returns true-length per-host buffers in host order."""

    def __init__(self, bus: SimBus, host: int) -> None:
        self.bus = bus
        self.host = int(host)

    def world_size(self) -> int:
        return self.bus.hosts

    def rank(self) -> int:
        return self.host

    def gather_bytes(self, buf: bytes) -> List[bytes]:
        return self.bus.exchange(self.host, bytes(buf))

    def gather_array(self, x):
        x = np.ascontiguousarray(np.asarray(x))
        rows = self.bus.exchange(
            self.host, (x.dtype.str, x.shape, x.tobytes()))
        return np.stack([np.frombuffer(b, np.dtype(dt)).reshape(shp)
                         for dt, shp, b in rows])

    def bcast_bytes(self, buf: bytes, root: int) -> bytes:
        return self.bus.exchange(self.host, bytes(buf))[root]

    def bcast_tree(self, tree, root: int):
        return pickle.loads(
            self.bus.exchange(self.host, pickle.dumps(tree))[root])

    def sync(self, tag: str) -> None:
        self.bus.exchange(self.host, None)


# ---------------------------------------------------------------------------
# base exchange: codec against the wire
# ---------------------------------------------------------------------------

def _exchange_leaf(wire, chain, site, idx, x, op) -> list:
    """Ship one encoded leaf through the wire's byte gather and decode
    every participant's contribution at its true length."""
    buf = chain.encode_leaf(site, idx, x, op)
    return [chain.decode_leaf(site, idx, b)
            for b in wire.gather_bytes(buf)]


def _base_exchange(ex: Exchange):
    wire = ex.wire
    if ex.kind == "allreduce":
        if ex.chain is not None:
            npfn = {"sum": np.sum, "max": np.max, "min": np.min}[ex.op]
            leaves, treedef = jax.tree.flatten(ex.tree)
            out = [npfn(np.stack(_exchange_leaf(
                       wire, ex.chain, ex.site, i, x, ex.op)), axis=0)
                   for i, x in enumerate(leaves)]
            return jax.tree.unflatten(treedef, out)
        fn = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[ex.op]
        return jax.tree.map(
            lambda x: np.asarray(fn(wire.gather_array(x), axis=0)),
            ex.tree)
    if ex.kind == "allgather":
        if ex.chain is not None:
            leaves, treedef = jax.tree.flatten(ex.tree)
            out = [np.stack(_exchange_leaf(
                       wire, ex.chain, ex.site, i, x, "gather"))
                   for i, x in enumerate(leaves)]
            return jax.tree.unflatten(treedef, out)
        return jax.tree.map(
            lambda x: np.asarray(wire.gather_array(x)), ex.tree)
    if ex.kind == "broadcast":
        if ex.chain is not None:
            src = wire.rank() == ex.root
            leaves, treedef = jax.tree.flatten(ex.tree)
            out = []
            for i, x in enumerate(leaves):
                # The broadcast op is the CODEC op: "bcast" stays exact
                # (zlib only), while publishers that fan out residual
                # deltas pass op="sum" so the chain's lossy gate
                # (quant8 + error feedback on allowlisted sites) applies
                # to the one encode the root performs. Every receiver —
                # root included — decodes the same wire bytes, so the
                # return value is bitwise identical fleet-wide and the
                # root can adopt it as the new shipped base.
                buf = (ex.chain.encode_leaf(ex.site, i, x, ex.op)
                       if src else b"")
                out.append(ex.chain.decode_leaf(
                    ex.site, i, wire.bcast_bytes(buf, ex.root)))
            return jax.tree.unflatten(treedef, out)
        return wire.bcast_tree(ex.tree, ex.root)
    raise ValueError(f"unknown exchange kind {ex.kind!r}")


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

class TransportStack:
    """A wire plus an ordered layer list; every exchange folds through
    the layers into the base codec. The process-default stack (a
    ProcessWire under the canonical layers) is what collectives.py's
    public wrappers delegate to; tests and the hierarchy sim build
    their own stacks over BusWires."""

    def __init__(self, wire: Optional[Wire] = None,
                 layers: Optional[List[Layer]] = None,
                 chain=None) -> None:
        self.wire = wire if wire is not None else ProcessWire()
        self.layers = (list(layers) if layers is not None
                       else default_layers())
        # a stack-pinned FilterChain: simulated hosts pin one chain per
        # stack so the process-global chain (one host's view) is never
        # shared across fake hosts
        self.chain = chain
        validate_layers(self.layers)

    def execute(self, ex: Exchange):
        ex.wire = self.wire
        ex.chain_override = self.chain
        layers = self.layers

        def call(i: int, e: Exchange):
            if i == len(layers):
                return _base_exchange(e)
            return layers[i].run(e, lambda e2: call(i + 1, e2))

        return call(0, ex)

    # -- the three exchange kinds ------------------------------------

    def allreduce(self, tree, mesh=None, op: str = "sum",
                  compress: bool = False, site: Optional[str] = None):
        return self.execute(Exchange("allreduce", tree, op=op, site=site,
                                     mesh=mesh, compress=compress))

    def allgather(self, tree, mesh=None, site: Optional[str] = None):
        return self.execute(Exchange("allgather", tree, site=site,
                                     mesh=mesh))

    def broadcast(self, tree, mesh=None, root: int = 0,
                  site: Optional[str] = None, op: str = "bcast"):
        """One-to-all. ``op`` selects the codec path: the default
        ``"bcast"`` is exact end-to-end; ``op="sum"`` routes the root's
        encode through the chain's lossy gate, which fires only on
        allowlisted sites — how the serve fleet ships quantized
        snapshot deltas (site ``serve/snapshot``) while every other
        broadcast stays bit-exact."""
        return self.execute(Exchange("broadcast", tree, op=op, root=root,
                                     site=site, mesh=mesh))

    # -- non-layered wire passthroughs -------------------------------

    def host_local_to_global(self, tree, mesh, pspec):
        """Device-feed assembly (no filtering: bytes move host→device,
        not across the DCN)."""
        return self.wire.host_local_to_global(tree, mesh, pspec)

    def sync(self, tag: str, site: Optional[str] = None) -> None:
        """Named cross-process barrier (checkpoint commit fences),
        watchdog-armed like every other blocking wire call."""
        if self.wire.world_size() == 1:
            return
        with _watchdog.guard(site or f"sync:{tag}"):
            self.wire.sync(tag)


_DEFAULT: Optional[TransportStack] = None


def default_stack() -> TransportStack:
    """The process-global stack over the real wire (lazily built)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = TransportStack()
    return _DEFAULT


def set_default_stack(stack: Optional[TransportStack]):
    """Swap the process-default stack (tests); returns the previous."""
    global _DEFAULT
    prev, _DEFAULT = _DEFAULT, stack
    return prev


def install_wire_from_config(cfg) -> Optional[TransportStack]:
    """Route the cross-host leg per the ``wire`` knob.

    Only the HOST wire is selected here — every consumer of the default
    stack (``hier/delta`` deltas, snapshot fan-out, checkpoint fences,
    rejoin ctl) picks the change up through ``default_stack()``, and the
    intra-host ICI leg is untouched either way:

    - ``"process"``: the existing jax.distributed wire; nothing to do
      (the lazy default builds ProcessWire/LocalWire itself).
    - ``"socket"``: the repo-owned TCP wire (parallel/socket_wire.py),
      discovered through ``cfg.wire_rendezvous`` (or the env fallback).
    - ``"sim"``: the in-process SimBus oracle. Only coherent inside one
      process — a multi-process run selecting it would silently stop
      exchanging, so world > 1 is an error.
    """
    from wormhole_tpu.utils.config import check_choice
    choice = check_choice("wire", cfg.wire, ("process", "socket", "sim"))
    if choice == "process":
        return None
    if choice == "sim":
        world = int(os.environ.get("NUM_PROCESSES", "1"))
        if world > 1:
            raise ValueError(
                "wire=sim is the single-process deterministic oracle; "
                f"this run has NUM_PROCESSES={world} — use wire=socket "
                "(or wire=process) for real multi-process exchange")
        bus = SimBus(1)
        stack = TransportStack(wire=BusWire(bus, 0))
    else:
        from wormhole_tpu.parallel.socket_wire import SocketWire
        stack = TransportStack(wire=SocketWire(
            rendezvous=cfg.wire_rendezvous or None,
            outbox_depth=cfg.wire_outbox_depth,
            timeout_s=cfg.comm_timeout_s or 120.0))
    set_default_stack(stack)
    return stack


# ---------------------------------------------------------------------------
# mesh (ICI) leg
# ---------------------------------------------------------------------------

def _ici_counter():
    """Single declaration site (lint_knobs contract) for the ICI byte
    counter; fetched per call so a replaced default registry can never
    strand a stale Counter."""
    try:
        from wormhole_tpu.obs.metrics import default_registry
    except Exception:
        return None
    return default_registry().counter(
        "comm/bytes_ici",
        help="in-mesh collective payload bytes moved over ICI "
             "(modeled from the dispatched step's psum shapes)")


def ici_ring_bytes(payload_nbytes: int, axis_size: int) -> int:
    """Bytes one participant moves for a ring all-reduce of an
    ``payload_nbytes`` buffer over ``axis_size`` devices: the standard
    2(k-1)/k · n (reduce-scatter + allgather halves). Zero when the
    axis is trivial — XLA elides the collective entirely."""
    k = int(axis_size)
    if k <= 1:
        return 0
    return int(round(2.0 * (k - 1) / k * float(payload_nbytes)))


class MeshTransport:
    """The intra-host (ICI) leg of the stack.

    ``shard_map`` collectives live INSIDE the compiled step — XLA
    lowers ``lax.psum`` onto ICI rings — so the host wire and the
    filter chain structurally cannot see them. What the unified
    transport can still own is everything around the dispatch: site-id
    and seq stamping (same counter space as the host wire, so traces
    interleave coherently), the ``collective:mesh`` span, chaos
    injection, watchdog arming, and ICI byte accounting
    (``comm/bytes_ici``) modeled from the step's known psum payload
    sizes — distinct from ``comm/bytes_wire`` so hierarchy runs show
    both legs."""

    def __init__(self, site: str = "mesh/step",
                 ici_bytes_per_call: int = 0) -> None:
        self.site = str(site)
        self.ici_bytes_per_call = int(ici_bytes_per_call)
        # this leg's own running totals (the registry's comm/bytes_ici is
        # process-wide): a pass loop takes their change over a part into
        # its Timer, where a reader finds it beside the part's seconds
        self.dispatches = 0
        self.bytes_ici = 0

    def dispatch(self, fn: Callable, *args,
                 ici_bytes: Optional[int] = None):
        """Run one compiled mesh step under the transport concerns."""
        b = (self.ici_bytes_per_call if ici_bytes is None
             else int(ici_bytes))
        attrs = {"site": self.site, "seq": _next_seq(self.site)}
        if b:
            attrs["bytes_ici"] = b
        with trace.span("collective:mesh", cat="collective", args=attrs):
            _chaos.on_collective(self.site)
            with _watchdog.guard(self.site):
                out = fn(*args)
        self.dispatches += 1
        self.bytes_ici += b
        if b:
            c = _ici_counter()
            if c is not None:
                c.inc(b)
        return out


# ---------------------------------------------------------------------------
# 2D hierarchy: mesh-over-ICI × filtered cross-host deltas
# ---------------------------------------------------------------------------

class _Done:
    """Ticket-shaped handle for an exchange that already completed
    (the engine-less tau=0 path)."""

    __slots__ = ("result", "error")

    def __init__(self, result) -> None:
        self.result = result
        self.error = None

    def done(self) -> bool:
        return True


class HierarchicalTransport:
    """Compose the two legs into the 2D topology: each host runs a
    ``(data, model)`` mesh over ICI (``local`` — in-mesh psum reduces
    the intra-host contribution inside the step) while hosts exchange
    only the host-level bucket-space delta through the filtered wire
    (``stack`` — quant8+zlib on the cross-host leg), optionally routed
    through an :class:`~wormhole_tpu.ps.engine.ExchangeEngine` so up
    to ``staleness_tau`` deltas stay in flight.

    Without an engine (or at tau=0) :meth:`submit_delta` degenerates
    to exchange-then-return — bit-identical to calling the BSP
    collective inline, which is the parity oracle the tests pin."""

    def __init__(self, local: MeshTransport, stack: TransportStack,
                 engine=None, site: str = "hier/delta",
                 op: str = "sum") -> None:
        self.local = local
        self.stack = stack
        self.engine = engine
        self.site = str(site)
        self.op = str(op)

    # -- intra-host leg ----------------------------------------------

    def local_dispatch(self, fn: Callable, *args,
                       ici_bytes: Optional[int] = None):
        return self.local.dispatch(fn, *args, ici_bytes=ici_bytes)

    # -- cross-host leg ----------------------------------------------

    def exchange_delta(self, tree):
        """Synchronous cross-host delta reduce (the tau=0 wire hop)."""
        return self.stack.allreduce(tree, None, op=self.op,
                                    site=self.site)

    def submit_delta(self, tree):
        """Queue the cross-host reduce; returns a ticket whose
        ``.result`` is the summed delta once done. Engine-less
        transports exchange inline and return a completed ticket."""
        if self.engine is None:
            return _Done(self.exchange_delta(tree))
        return self.engine.submit(lambda t=tree: self.stack.allreduce(
            t, None, op=self.op, site=self.site))

    def gate(self) -> list:
        """Collect deltas past the staleness bound (oldest first)."""
        if self.engine is None:
            return []
        return self.engine.gate()

    def quiesce(self) -> list:
        """Collect every in-flight delta (pass end / drain)."""
        if self.engine is None:
            return []
        return self.engine.quiesce()

    def stop(self) -> None:
        if self.engine is not None:
            self.engine.stop()
