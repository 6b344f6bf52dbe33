"""Wall-clock timers (reference dmlc/timer.h usage, SURVEY.md §5.1)."""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Iterator

from wormhole_tpu.obs import trace


def get_time() -> float:
    return time.monotonic()


class Timer:
    """Accumulating named timer; `with timer.scope("parse"): ...`."""

    def __init__(self) -> None:
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    @contextmanager
    def scope(self, name: str) -> Iterator[None]:
        t0 = time.monotonic()
        try:
            # every timer scope is a trace span, opened and closed here
            with trace.span(name, cat="timer"):
                yield
        finally:
            dt = time.monotonic() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def add(self, name: str, seconds: float, calls: int = 1) -> None:
        """Merge externally-measured time (e.g. from a feed thread)."""
        self.totals[name] = self.totals.get(name, 0.0) + seconds
        self.counts[name] = self.counts.get(name, 0) + calls

    def report(self) -> str:
        rows = [
            f"{name}: {self.totals[name]:.3f}s / {self.counts[name]} calls"
            for name in sorted(self.totals)
        ]
        return "\n".join(rows)
