"""Fleet-scale serving: N pull-only frontends + delta snapshot shipping.

The reference serves Criteo-TB by fanning pulls across ~100 ps-lite
servers; model freshness is whatever the servers hold. Our fleet keeps
the pull-only discipline — every replica is a plain
:class:`~wormhole_tpu.serve.frontend.ServeFrontend` that never writes
model state — and makes freshness an explicit publisher/subscriber
protocol over the transport layer instead of N independent disk polls:

- **Routing** (:mod:`~wormhole_tpu.serve.router`): consistent-hash over
  the request's feature buckets with a least-loaded spill valve fed by
  the per-replica queue-depth gauges.
- **Freshness**: one :class:`SnapshotPublisher` (the only disk reader)
  fans out base-version-tagged frames through a ``'serve/snapshot'``
  FilterChain stack — deltas against the last shipped base ride the
  lossy path (quant8 + error feedback + zlib, op="sum"), periodic and
  on-demand full frames ride exact (op="bcast"). Each
  :class:`SnapshotSubscriber` applies frames to a host-side standby
  pytree and atomically ``swap()``s its forward; a version gap (missed
  delta) makes the replica request a full resync on the next control
  round instead of applying garbage.
- **Overload**: the frontends' deadline-aware shed policy (see
  frontend.py) keeps per-replica p99 inside the SLO ceiling while the
  router keeps the fleet balanced.

The wire protocol is two collectives per round on any
:class:`~wormhole_tpu.parallel.transport.TransportStack` (host 0 =
publisher, hosts 1..N = replicas):

1. control: an exact int64 ``allreduce(op="max")`` of
   ``[need_full, frame_kind, stop]`` — replicas raise ``need_full``,
   the publisher announces the pending frame kind (0 none / 1 delta /
   2 full) and the stop flag.
2. frame (only when ``frame_kind > 0``): a ``broadcast`` of
   ``{"meta": int64 [kind, base_version, version], "params": pytree}``
   at site ``serve/snapshot`` — op="sum" for deltas (lossy gate fires),
   op="bcast" for fulls (exact).

The publisher adopts the DECODED broadcast return as its new base, so
publisher and replicas hold bitwise-identical state after every frame;
the chain's error-feedback residual absorbs quantization drift against
the true checkpoint across subsequent deltas. Idle rounds (kind 0)
double as heartbeats so no host ever blocks longer than the publish
cadence. :class:`ServeFleet` wires all of it over an in-process
``SimBus`` (one subscriber thread per replica); live multi-host
deployments run the same publisher/subscriber pair over each process's
``ProcessWire`` stack instead.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Sequence

import jax
import numpy as np

from wormhole_tpu.obs import trace
from wormhole_tpu.parallel.filters import FilterChain
from wormhole_tpu.parallel.transport import BusWire, SimBus, TransportStack
from wormhole_tpu.serve.frontend import ServeFrontend, ShedPolicy
from wormhole_tpu.serve.router import Router, request_key
from wormhole_tpu.utils.logging import get_logger

log = get_logger("serve")

__all__ = ["ServeFleet", "SnapshotPublisher", "SnapshotSubscriber",
           "SNAPSHOT_SITE", "fleet_metrics"]

# frame broadcast site — MUST stay in filters.DEFAULT_LOSSY_SITES (the
# lint_serve single-declaration check pins this) so delta frames hit
# the quant8 + error-feedback path
SNAPSHOT_SITE = "serve/snapshot"
# control-round site: int64 flags, never lossy (not allowlisted, and
# op="max" bypasses the quant gate anyway)
_CTL_SITE = "serve/snapshot_ctl"

_K_NONE, _K_DELTA, _K_FULL = 0, 1, 2


def fleet_metrics(reg):
    """Single declaration site for the fleet metric names: (snapshot
    frames counter, shipped-version gauge, spill counter)."""
    return (reg.counter("serve/snapshot_frames",
                        help="snapshot frames fanned out by the "
                             "publisher (delta + full)"),
            reg.gauge("serve/snapshot_version",
                      help="latest model version shipped to the fleet"),
            reg.counter("serve/fleet_spill",
                        help="requests diverted off their hash owner "
                             "by the least-loaded spill policy"))


def _host_params(tree):
    """Pull a params pytree to host numpy (publisher/subscriber bases
    live host-side; device placement happens only at swap)."""
    # host-sync: snapshot bases are host-resident by design
    return jax.tree.map(lambda x: np.asarray(x), tree)


class SnapshotPublisher:
    """Host 0 of the snapshot protocol: the fleet's only disk reader.

    ``base_params`` is the params pytree every replica currently serves
    (the synced starting point). New versions arrive either through
    :meth:`publish` (trainer pushes its post-step params) or from
    ``ckpt`` polling (one reader replacing N replica disk polls); each
    becomes one frame on the next round. Every ``full_every``-th frame
    ships full; the rest ship as deltas against the last shipped base.
    ``full_every=1`` disables deltas entirely (bit-exact shipping),
    ``full_every=0`` ships fulls only on replica demand (version gap).
    """

    def __init__(self, stack: TransportStack, base_params: Any, *,
                 start_version: int = 0, full_every: int = 16,
                 poll_itv: float = 0.25, ckpt=None,
                 template_state: Any = None,
                 param_keys: Optional[Sequence[str]] = None,
                 registry=None) -> None:
        if ckpt is not None and template_state is None:
            raise ValueError("ckpt polling needs template_state")
        self.stack = stack
        self.full_every = int(full_every)
        self.poll_itv = float(poll_itv)
        self.ckpt = ckpt
        self.template = template_state
        self.param_keys = list(param_keys) if param_keys else None
        self.version = int(start_version)  # owner-thread: fleet-pub
        self.frames = 0  # owner-thread: fleet-pub
        self.full_frames = 0  # owner-thread: fleet-pub
        self.delta_frames = 0  # owner-thread: fleet-pub
        self.resyncs = 0  # owner-thread: fleet-pub
        self._base = _host_params(base_params)  # owner-thread: fleet-pub
        self._want_full = False  # owner-thread: fleet-pub
        self._metrics = None if registry is None else fleet_metrics(registry)
        self._pending = None  # (version, params)  guarded-by: _lock
        self._lock = threading.Lock()
        self._kick = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- feeding the publisher ----------------------------------------------

    def publish(self, params: Any, version: int) -> None:
        """Hand the publisher a new model version (host or device
        arrays; same treedef as the base). Latest pending wins — the
        fleet serves versions, not a version history."""
        with self._lock:
            self._pending = (int(version), _host_params(params))
        self._kick.set()

    def _maybe_poll_ckpt(self) -> None:
        if self.ckpt is None:
            return
        try:
            ver = self.ckpt.latest_version()
            if ver <= self.version:
                return
            ver, state = self.ckpt.load(self.template, version=ver)
        except (OSError, KeyError, ValueError) as exc:
            log.warning("publisher snapshot v? load failed (%s); "
                        "retrying next round", exc)
            return
        keys = self.param_keys or list(self._base)
        self.publish({k: state[k] for k in keys}, ver)

    # -- the round -----------------------------------------------------------

    def _round(self) -> bool:
        """One control round + optional frame fan-out. Returns False
        once the stop flag has been announced (the fleet's last round).
        """
        stopping = self._stop.is_set()
        kind, frame = _K_NONE, None
        if not stopping:
            self._maybe_poll_ckpt()
            with self._lock:
                pub, self._pending = self._pending, None
            if pub is None and self._want_full:
                # a replica gapped: resync it from the current base at
                # the current version, no fresh publish required
                pub = (self.version, self._base)
                self.resyncs += 1
            if pub is not None:
                ver, params = pub
                full = (self._want_full
                        or self.full_every == 1
                        or (self.full_every > 1
                            and self.frames % self.full_every == 0))
                if full:
                    kind, payload = _K_FULL, params
                else:
                    kind = _K_DELTA
                    payload = jax.tree.map(
                        lambda new, base: (new - base).astype(new.dtype),
                        params, self._base)
                frame = {"meta": np.array([kind, self.version, ver],
                                          np.int64),
                         "params": payload}
        ctl = self.stack.allreduce(
            np.array([0, kind, 1 if stopping else 0], np.int64),
            op="max", site=_CTL_SITE)
        if kind != _K_NONE:
            out = self.stack.broadcast(
                frame, root=0, site=SNAPSHOT_SITE,
                op="sum" if kind == _K_DELTA else "bcast")
            # adopt the decoded return as the new base: it is exactly
            # what every replica decoded, so fleet state stays bitwise
            # uniform even though the delta encode was lossy
            if kind == _K_DELTA:
                self._base = jax.tree.map(
                    lambda b, d: (b + d).astype(b.dtype),
                    self._base, out["params"])
                self.delta_frames += 1
            else:
                self._base = out["params"]
                self.full_frames += 1
            self.frames += 1
            # last: a reader that sees the version sees the base and
            # the counts of the frame that brought it (the replicas
            # report it as soon as they decode, before the broadcast
            # returns here)
            self.version = int(frame["meta"][2])
            if self._metrics is not None:
                self._metrics[0].inc()
                self._metrics[1].set(self.version)
        self._want_full = bool(int(np.asarray(ctl)[0]) > 0)
        return not stopping

    def _loop(self) -> None:
        try:
            while self._round():
                self._kick.wait(self.poll_itv)
                self._kick.clear()
        except Exception as exc:  # noqa: BLE001 — surface, don't hang
            log.error("snapshot publisher died: %s", exc)

    def start(self) -> "SnapshotPublisher":
        if self._thread is not None:
            raise RuntimeError("publisher already started")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="fleet-pub")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._kick.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def wire_stats(self) -> dict:
        """Publisher-side chain accounting: only the root encodes in a
        broadcast, so these ARE the per-link snapshot wire bytes."""
        s = dict(self.stack.chain.stats) if self.stack.chain else {}
        raw, wire = s.get("bytes_raw", 0), s.get("bytes_wire", 0)
        return {"bytes_raw": raw, "bytes_wire": wire,
                "wire_ratio": (raw / wire) if wire else 0.0}


class SnapshotSubscriber:
    """One replica's end of the snapshot protocol: participate in every
    control round, decode frames, apply to the host-side standby base,
    device-place and atomically swap the forward between batches."""

    def __init__(self, stack: TransportStack, forward, *,
                 start_version: int = 0, name: str = "sub") -> None:
        self.stack = stack
        self.forward = forward
        self.name = name
        self.version = int(start_version)  # owner-thread: fleet-sub
        self.swaps = 0  # owner-thread: fleet-sub
        self.gaps = 0  # owner-thread: fleet-sub
        self._base = _host_params(forward.params)  # owner-thread: fleet-sub
        self._need_full = 0  # owner-thread: fleet-sub
        self._thread: Optional[threading.Thread] = None

    def _apply(self, new_base: Any, version: int) -> None:
        from wormhole_tpu.learners.store import put_like
        cur = self.forward.params
        placed = jax.tree.map(put_like, cur, new_base)
        with trace.span("serve:swap", cat="serve",
                        args={"version": int(version)}):
            self.forward.swap(placed)
        self._base = new_base
        self.version = int(version)
        self.swaps += 1
        self._need_full = 0

    def _round(self) -> bool:
        ctl = self.stack.allreduce(
            np.array([self._need_full, 0, 0], np.int64),
            op="max", site=_CTL_SITE)
        ctl = np.asarray(ctl)
        kind, stop = int(ctl[1]), int(ctl[2])
        if kind != _K_NONE:
            template = {"meta": np.zeros(3, np.int64),
                        "params": self._base}
            out = self.stack.broadcast(
                template, root=0, site=SNAPSHOT_SITE,
                op="sum" if kind == _K_DELTA else "bcast")
            meta = np.asarray(out["meta"])
            base_ver, ver = int(meta[1]), int(meta[2])
            if kind == _K_FULL:
                self._apply(out["params"], ver)
            elif base_ver != self.version:
                # missed a frame (or joined late): applying this delta
                # would corrupt the standby — ask for a full instead
                self.gaps += 1
                self._need_full = 1
                log.warning("%s: snapshot gap (have v%d, delta base "
                            "v%d); requesting full resync", self.name,
                            self.version, base_ver)
            else:
                new = jax.tree.map(lambda b, d: (b + d).astype(b.dtype),
                                   self._base, out["params"])
                self._apply(new, ver)
        return stop == 0

    def _loop(self) -> None:
        while True:
            try:
                if not self._round():
                    return
            except Exception as exc:  # noqa: BLE001
                # a dead subscriber would stall the whole bus at the
                # next rendezvous; log loudly and bail instead of
                # half-participating
                log.error("%s: snapshot subscriber died: %s",
                          self.name, exc)
                return

    def start(self) -> "SnapshotSubscriber":
        if self._thread is not None:
            raise RuntimeError("subscriber already started")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=self.name)
        self._thread.start()
        return self

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None


class ServeFleet:
    """N pull-only serve replicas behind a router, kept fresh by one
    snapshot publisher over an in-process transport bus.

    ``forwards`` is one ForwardStep per replica, all serving the SAME
    initial params (the publisher's starting base — replica state is
    publisher state by protocol invariant). The fleet owns frontends,
    router, bus, publisher, and subscriber threads; ``close()`` tears
    all of it down in dependency order.
    """

    def __init__(self, forwards: Sequence, *,
                 batch_rows: int = 256, max_nnz: int = 64,
                 key_pad: int = 0, deadline_ms: float = 5.0,
                 registry=None, shed: Optional[ShedPolicy] = None,
                 router_policy: str = "spill", vnodes: int = 128,
                 spill_frac: float = 2.0,
                 spill_min: Optional[int] = None,
                 full_every: int = 16, poll_itv: float = 0.25,
                 quant_bits: int = 8, start_version: int = 0,
                 ckpt=None, template_state: Any = None,
                 bus_timeout_s: float = 120.0,
                 name: str = "fleet") -> None:
        if not forwards:
            raise ValueError("ServeFleet needs >= 1 forward")
        self.n = len(forwards)
        self.name = name
        self.frontends: List[ServeFrontend] = [
            ServeFrontend(fwd, batch_rows=batch_rows, max_nnz=max_nnz,
                          key_pad=key_pad, deadline_ms=deadline_ms,
                          registry=registry, shed=shed,
                          name=f"{name}-r{r}")
            for r, fwd in enumerate(forwards)]
        # the spill floor must sit ABOVE normal batch-fill depth: a
        # replica with < 2 batches queued is just collecting rows, and
        # diverting those bursts off their hash owner churns the very
        # affinity the ring exists for (measured as p99 spikes)
        if spill_min is None:
            spill_min = 2 * batch_rows
        self.router = Router(self.n, policy=router_policy, vnodes=vnodes,
                             spill_frac=spill_frac, spill_min=spill_min,
                             depth_fn=lambda r: self.frontends[r]
                             .queue_depth())
        if registry is not None:
            spill_counter = fleet_metrics(registry)[2]
            self.router.on_spill = lambda: spill_counter.inc()
        # snapshot plane: hosts 0..N on one bus, one pinned FilterChain
        # per host (simulated hosts must never share EF residuals or
        # key caches — chain state is one host's view)
        self._bus = SimBus(self.n + 1, timeout_s=bus_timeout_s)
        self._stacks = [
            TransportStack(
                wire=BusWire(self._bus, h),
                chain=FilterChain(
                    filters={"key_caching", "fixing_float",
                             "compressing"},
                    quant_bits=quant_bits, min_bytes=0))
            for h in range(self.n + 1)]
        self.publisher = SnapshotPublisher(
            self._stacks[0], forwards[0].params,
            start_version=start_version, full_every=full_every,
            poll_itv=poll_itv, ckpt=ckpt, template_state=template_state,
            param_keys=list(forwards[0].param_keys()),
            registry=registry)
        self.subscribers = [
            SnapshotSubscriber(self._stacks[r + 1], fwd,
                               start_version=start_version,
                               name=f"{name}-sub{r}")
            for r, fwd in enumerate(forwards)]
        for sub in self.subscribers:
            sub.start()
        self.publisher.start()
        self._closed = False

    # -- client surface ------------------------------------------------------

    def submit(self, keys, vals=None, priority: int = 0):
        """Route one request by its feature buckets and enqueue it on
        the chosen replica. Returns the frontend's ServeResult."""
        r = self.router.route(request_key(keys))
        return self.frontends[r].submit(keys, vals, priority=priority)

    def publish(self, params: Any, version: int) -> None:
        """Ship a new model version to every replica (see
        :meth:`SnapshotPublisher.publish`)."""
        self.publisher.publish(params, version)

    def versions(self) -> List[int]:
        """Per-replica served model versions (freshness probe)."""
        return [sub.version for sub in self.subscribers]

    def stats(self) -> dict:
        fronts = [f.stats() for f in self.frontends]
        agg = {k: sum(f.get(k, 0) for f in fronts)
               for k in ("requests", "batches", "shed")}
        # fleet-wide percentiles from the MERGED reservoirs: averaging
        # per-replica p99s would hide a single slow replica's tail
        lat = np.concatenate([f.latencies_s() for f in self.frontends]) \
            if self.frontends else np.empty(0)
        if lat.size:
            agg["p50_ms"] = float(np.percentile(lat, 50) * 1e3)
            agg["p99_ms"] = float(np.percentile(lat, 99) * 1e3)
        return {"replicas": self.n,
                "router": self.router.stats(),
                "frontends": fronts,
                "aggregate": agg,
                "snapshot": {
                    "version": self.publisher.version,
                    "frames": self.publisher.frames,
                    "full_frames": self.publisher.full_frames,
                    "delta_frames": self.publisher.delta_frames,
                    "resyncs": self.publisher.resyncs,
                    "replica_versions": self.versions(),
                    "replica_swaps": [s.swaps for s in self.subscribers],
                    "replica_gaps": [s.gaps for s in self.subscribers],
                    **self.publisher.wire_stats()}}

    def close(self) -> None:
        """Stop publishing (the stop flag releases every subscriber),
        then drain and close the frontends."""
        if self._closed:
            return
        self._closed = True
        self.publisher.stop()
        for sub in self.subscribers:
            sub.join(timeout=30)
        for f in self.frontends:
            f.close()

    def __enter__(self) -> "ServeFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
