"""Collective watchdog: turn a hang on a dead peer into a clean exit.

JAX multi-controller collectives (``process_allgather``,
``sync_global_devices``, and everything built on them) block inside C
until *every* process arrives. When a peer is SIGKILLed mid-step the
survivors wait forever — Python signal handlers cannot run while the
interpreter is parked in a C call, so even SIGTERM cannot drain them.
The watchdog is the escape hatch: a single daemon thread holds one
armed deadline; each blocking collective arms it on entry and disarms
on return. If the deadline passes while still armed, the thread prints
one diagnostic line and ``os._exit``\\ s the process with the
distinguished :data:`PEER_LOST` code, which the supervised launcher
treats as "bystander of someone else's failure", not a crash.

Off by default: ``configure(0)`` (the default knob) installs nothing —
no thread exists and :func:`guard` returns one shared no-op context, so
the per-collective cost is a function call and a global load.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Callable, Optional

# Exit code of a survivor that abandoned a collective because a peer was
# presumed lost. Chosen outside the bash/errno conventions (and far from
# signal-death codes, which the launcher sees as negative waitpid codes).
PEER_LOST = 117

# Env fallback for processes that never build a Config (exported by the
# supervised launcher so every child inherits the timeout).
COMM_TIMEOUT_ENV = "WORMHOLE_COMM_TIMEOUT_S"


class CollectiveWatchdog:
    """One monitor thread, armed/disarmed around blocking collectives.

    One armed slot PER CALLING THREAD: the ps exchange engine runs its
    collectives on its own thread while the training loop still arms
    around the control-plane exchanges, so arm/disarm must not clobber
    across threads. Each ``arm`` replaces only the calling thread's
    slot (re-arm resets that slot's deadline); ``disarm`` clears it.
    The monitor fires on the earliest expired slot of any thread —
    recomputing deadlines from the live slot map on every wakeup, so a
    stale wakeup (scheduled before a disarm, delivered after a re-arm)
    can never fire against the wrong collective.
    """

    def __init__(self, timeout_s: float,
                 exit_fn: Optional[Callable[[str], None]] = None) -> None:
        self.timeout_s = float(timeout_s)
        self._exit = exit_fn if exit_fn is not None else self._default_exit
        self._cv = threading.Condition()
        # thread ident -> (site, deadline); presence in the map IS the
        # armed state, so removal doubles as the stale-wakeup guard
        self._armed: dict = {}
        self._stopped = False
        self.fired_site: Optional[str] = None
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ft-watchdog")
        self._thread.start()

    def _default_exit(self, site: str) -> None:
        sys.stderr.write(
            f"[ft] watchdog: collective {site!r} blocked > "
            f"{self.timeout_s:.1f}s — peer presumed lost; "
            f"exiting with PEER_LOST ({PEER_LOST})\n")
        sys.stderr.flush()
        try:    # os._exit skips every exporter: flight-record first
            from ..obs import flight
            flight.record(f"peer_lost_{site}")
        except BaseException:
            pass
        os._exit(PEER_LOST)

    def arm(self, site: str) -> None:
        with self._cv:
            self._armed[threading.get_ident()] = (
                str(site), time.monotonic() + self.timeout_s)
            self._cv.notify()

    def disarm(self) -> None:
        with self._cv:
            self._armed.pop(threading.get_ident(), None)
            self._cv.notify()

    @contextlib.contextmanager
    def armed(self, site: str):
        self.arm(site)
        try:
            yield
        finally:
            self.disarm()

    def trip(self, site: str) -> None:
        """Fire the exit path immediately, without waiting out the
        timeout. For callers that positively *detect* peer loss (the
        socket wire sees the connection drop) rather than infer it from
        silence — the exit path (flight record + PEER_LOST exit, or
        the injected test recorder) stays identical either way."""
        self.fired_site = str(site)
        self._exit(str(site))

    def stop(self) -> None:
        """Shut the monitor thread down (tests; production exits instead)."""
        with self._cv:
            self._stopped = True
            self._armed.clear()
            self._cv.notify()
        self._thread.join(timeout=5.0)

    def _loop(self) -> None:
        with self._cv:
            while not self._stopped:
                if not self._armed:
                    self._cv.wait()
                    continue
                now = time.monotonic()
                expired = [(dl, tid, site)
                           for tid, (site, dl) in self._armed.items()
                           if dl <= now]
                if not expired:
                    nxt = min(dl for _, dl in self._armed.values())
                    self._cv.wait(timeout=nxt - now)
                    continue
                _, tid, site = min(expired)
                del self._armed[tid]
                self.fired_site = site
                # exit_fn normally never returns (os._exit); tests inject
                # a recorder, in which case keep monitoring
                self._exit(site)


_WATCHDOG: Optional[CollectiveWatchdog] = None
# shared no-op context handed out when no watchdog is installed —
# nullcontext is reentrant, so one instance serves every call site
_OFF = contextlib.nullcontext()


def configure(timeout_s: float = 0.0,
              exit_fn: Optional[Callable[[str], None]] = None,
              ) -> Optional[CollectiveWatchdog]:
    """Install (effective timeout > 0) or remove (== 0) the watchdog.

    A zero ``timeout_s`` falls back to the :data:`COMM_TIMEOUT_ENV`
    env var (the supervised launcher's export); zero both ways means
    no watchdog at all. Re-configuring stops any previous instance.
    """
    global _WATCHDOG
    eff = float(timeout_s)
    if eff <= 0:
        try:
            eff = float(os.environ.get(COMM_TIMEOUT_ENV, "0") or "0")
        except ValueError:
            eff = 0.0
    if _WATCHDOG is not None:
        _WATCHDOG.stop()
        _WATCHDOG = None
    if eff > 0:
        _WATCHDOG = CollectiveWatchdog(eff, exit_fn=exit_fn)
    return _WATCHDOG


def shutdown() -> None:
    """Remove the watchdog regardless of env (test teardown)."""
    global _WATCHDOG
    if _WATCHDOG is not None:
        _WATCHDOG.stop()
        _WATCHDOG = None


def get() -> Optional[CollectiveWatchdog]:
    return _WATCHDOG


def guard(site: str):
    """Context manager arming the watchdog around one blocking collective;
    the shared no-op when none is installed."""
    w = _WATCHDOG
    return w.armed(site) if w is not None else _OFF
