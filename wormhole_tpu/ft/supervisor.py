"""Dead-rank supervision and the SIGTERM drain protocol.

Two halves, one protocol:

Launcher side — :class:`DeadRankDetector` reads the heartbeat files the
workers already write (obs/heartbeat.py) and declares a rank *dead*
after ``ft_dead_after_s`` of silence; this is deliberately distinct
from the StragglerDetector's relative-rate warning (a straggler is
slow, a dead rank is gone). :class:`Supervisor` accumulates dead ranks
(from heartbeat silence and from child exit codes) across one attempt
and computes the relaunch geometry: ``fixed`` keeps the world size,
``shrink`` drops to the survivors (floor 2 — the single-process path
uses the unsharded Checkpointer and cannot read sharded state).

Learner side — the supervised launcher exports :data:`DRAIN_ENV` and
SIGTERMs survivors; :func:`install_drain_handler` (called by the
learner, a no-op unless the env var is set so unsupervised runs keep
default SIGTERM semantics) turns that into a flag the training loops
poll at block boundaries. A multihost pass raises
:class:`DrainInterrupt`; ``run_multihost`` catches it, commits a
barrier-free checkpoint (the resume-version allreduce-min is the
cross-rank agreement, so no peer sync is needed while peers may be
dying), and returns cleanly.

Exit-code classes used to tell a *dead* rank from a *bystander*:
0 (done), -15 (SIGTERMed by us), and PEER_LOST (watchdog abandoned a
collective) are bystanders; anything else marks the rank dead.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Dict, Iterable, List, Optional, Set

from .watchdog import PEER_LOST

DRAIN_ENV = "WORMHOLE_FT_DRAIN"
# set on a child respawned into a live world (elastic="rejoin"): the
# learner takes the checkpoint-restore + handshake + replay path
# instead of a cold start
REJOIN_ENV = "WORMHOLE_REJOIN_RANK"

# waitpid codes that do NOT mean "this rank caused the failure"
BYSTANDER_CODES = (0, -signal.SIGTERM, PEER_LOST)


class DrainInterrupt(Exception):
    """Raised at a block boundary when a SIGTERM drain was requested."""


_drain_flag = threading.Event()
_handler_installed = False


def drain_enabled() -> bool:
    return bool(os.environ.get(DRAIN_ENV, ""))


def install_drain_handler() -> bool:
    """Install the SIGTERM→drain handler; returns True when installed.

    Only acts under a supervised launcher (:data:`DRAIN_ENV` set): an
    unconditional handler would make any SIGTERMed learner linger
    through a full drain, surprising plain ``kill`` users and adding
    the launcher's kill-timeout to every crash-cleanup path.
    """
    global _handler_installed
    if not drain_enabled():
        return False
    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:  # not the main thread
        return False
    _handler_installed = True
    return True


def _on_sigterm(signum, frame) -> None:
    _drain_flag.set()


def drain_requested() -> bool:
    return _drain_flag.is_set()


def request_drain() -> None:
    """Programmatic drain (tests)."""
    _drain_flag.set()


def reset_drain() -> None:
    global _handler_installed
    _drain_flag.clear()
    if _handler_installed:
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        _handler_installed = False


class DeadRankDetector:
    """Declare ranks dead after ``dead_after_s`` of heartbeat silence.

    Heartbeat records carry a monotonic stamp (``mono``); launcher and
    workers share one machine per host, so the launcher's own monotonic
    clock is directly comparable. A rank with no heartbeat yet is never
    declared (nothing to age against — startup hangs are the watchdog's
    and the poll loop's job), and a rank whose last record is marked
    ``final`` exited deliberately.
    """

    def __init__(self, dead_after_s: float) -> None:
        self.dead_after_s = float(dead_after_s)

    def check(self, heartbeat_dir: str,
              now: Optional[float] = None) -> List[int]:
        if self.dead_after_s <= 0 or not heartbeat_dir:
            return []
        from wormhole_tpu.obs.heartbeat import read_heartbeats
        now = time.monotonic() if now is None else now
        dead = []
        for rank, recs in read_heartbeats(heartbeat_dir).items():
            last = recs[-1]
            if last.get("final"):
                continue
            if now - float(last.get("mono", now)) > self.dead_after_s:
                dead.append(rank)
        return sorted(dead)


class Supervisor:
    """Relaunch policy state for one supervised ``launch_mp`` job."""

    MIN_WORLD = 2

    def __init__(self, world: int, elastic: str = "fixed",
                 dead_after_s: float = 0.0) -> None:
        if elastic not in ("fixed", "shrink", "rejoin"):
            raise ValueError(f"ft_elastic must be fixed|shrink|rejoin, "
                             f"got {elastic!r}")
        self.world = int(world)
        self.elastic = elastic
        self.detector = DeadRankDetector(dead_after_s)
        self.dead: Set[int] = set()
        self.exit_codes: Dict[int, int] = {}
        # membership epoch: bumped on every death and every rejoin so
        # survivors (and telemetry) can order membership changes
        self.epoch = 0

    def record_exit(self, rank: int, code: int) -> None:
        self.exit_codes[rank] = code
        if code not in BYSTANDER_CODES:
            self.dead.add(rank)
            self.epoch += 1
            self._flight(f"rank{rank}_rc{code}")

    def record_dead(self, ranks: Iterable[int]) -> None:
        fresh = {int(r) for r in ranks} - self.dead
        self.dead.update(fresh)
        self.epoch += len(fresh)
        for r in sorted(fresh):
            self._flight(f"dead_rank{r}")

    @staticmethod
    def _flight(reason: str) -> None:
        """Supervisor-observed deaths are a failure edge the dead child
        can't report itself — dump the observer's flight bundle."""
        try:
            from ..obs import flight
            flight.record(reason)
        except BaseException:
            pass

    def scan_heartbeats(self, heartbeat_dir: str,
                        now: Optional[float] = None) -> List[int]:
        """Heartbeat-silent ranks not yet known dead (for the poll loop
        to SIGKILL — a hung rank never exits on its own)."""
        fresh = [r for r in self.detector.check(heartbeat_dir, now=now)
                 if r not in self.dead]
        self.record_dead(fresh)
        return fresh

    def next_world(self) -> int:
        if self.elastic == "shrink" and self.dead:
            return max(self.MIN_WORLD, self.world - len(self.dead))
        # "fixed" and "rejoin" keep the world size: fixed relaunches
        # everyone at it, rejoin keeps the survivors running and refills
        # the dead slots in place
        return self.world

    def plan_relaunch(self) -> int:
        """Commit the next attempt's geometry and clear per-attempt state."""
        self.world = self.next_world()
        self.dead.clear()
        self.exit_codes.clear()
        return self.world

    # -- live rejoin (elastic="rejoin") -------------------------------

    def rejoinable(self, rank: int) -> bool:
        """Should the launcher respawn just ``rank`` instead of folding
        its death into a whole-world relaunch?"""
        return self.elastic == "rejoin" and rank in self.dead

    def note_rejoined(self, rank: int) -> int:
        """A respawned rank completed its handshake (or at least came
        back up): drop it from the dead set so heartbeat scans age its
        FRESH records instead of instantly re-declaring it, and bump
        the membership epoch. Returns the new epoch."""
        self.dead.discard(rank)
        self.exit_codes.pop(rank, None)
        self.epoch += 1
        return self.epoch
