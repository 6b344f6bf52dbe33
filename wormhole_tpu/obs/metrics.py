"""Metrics registry: named counters / gauges / histograms, one place.

The repo grew three metric surfaces PR by PR — the accumulating
``Timer``, the fixed-layout ``Progress`` POD slots, and
``DeviceFeed.drain_stats`` dicts. This registry subsumes them behind one
namespace (adapters below import each one), with two exporters:

- **JSON-lines heartbeat records** (:meth:`Registry.record`) — one dict
  per emission, appended per host (obs/heartbeat.py owns the file and
  the rate limit);
- **Prometheus text exposition** (:meth:`Registry.prometheus_text`) —
  a scrape-ready dump written at run end (or served by whatever wraps
  it).

Cross-host semantics mirror the ``Progress`` POD: a registry snapshot is
a flat dict that merges slot-wise (:func:`merge_snapshots` — counters
and histogram bins add, gauges take their declared aggregation), and
:meth:`Registry.allreduce` ships the value vector over the existing
Progress psum/queue side channel (``parallel.collectives.allreduce_tree``)
so every host ends with the global view.

Metric *kinds* follow the Prometheus model: a Counter only goes up, a
Gauge is a point-in-time value with an explicit cross-host aggregation
("sum", "max", "min" or "last"), a Histogram is fixed bucket counts +
count/sum (mergeable by addition, like the AUC margin histograms).
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Optional, Sequence

__all__ = ["Counter", "Gauge", "Histogram", "Registry",
           "default_registry", "merge_snapshots"]

_DEF_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0,
                50.0, 100.0)


class Counter:
    """Monotone accumulator (merge = sum)."""

    kind = "counter"

    __slots__ = ("name", "help", "value")

    def __init__(self, name: str, help: str = "") -> None:
        self.name = name
        self.help = help
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        if v < 0:
            raise ValueError(f"counter {self.name}: inc by {v} < 0")
        self.value += v

    def snapshot(self):
        return self.value

    def restore(self, v) -> None:
        self.value = float(v)


class Gauge:
    """Point-in-time value; ``agg`` names the cross-host merge."""

    kind = "gauge"

    __slots__ = ("name", "help", "value", "agg")

    def __init__(self, name: str, help: str = "",
                 agg: str = "last") -> None:
        if agg not in ("sum", "max", "min", "last"):
            raise ValueError(f"gauge {name}: unknown agg {agg!r}")
        self.name = name
        self.help = help
        self.agg = agg
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def max(self, v: float) -> None:
        self.value = max(self.value, float(v))

    def snapshot(self):
        return self.value

    def restore(self, v) -> None:
        self.value = float(v)


class Histogram:
    """Fixed cumulative-bucket histogram (Prometheus ``le`` semantics):
    ``bins[i]`` counts observations <= ``buckets[i]``; the implicit
    +Inf bucket is ``count``. Mergeable by elementwise add."""

    kind = "histogram"

    __slots__ = ("name", "help", "buckets", "bins", "count", "sum")

    def __init__(self, name: str, help: str = "",
                 buckets: Sequence[float] = _DEF_BUCKETS) -> None:
        self.name = name
        self.help = help
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError(f"histogram {name}: empty buckets")
        self.bins = [0] * len(self.buckets)
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.buckets, v)
        if i < len(self.bins):
            self.bins[i] += 1
        self.count += 1
        self.sum += v

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile (0 <= q <= 1) by linear interpolation
        over the bucket bounds — the histogram_quantile() model, so the
        estimate stays mergeable across ranks (unlike an exact
        reservoir). Returns NaN when empty; observations past the last
        finite bound clamp to it, as Prometheus does for +Inf."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile {q}: want 0 <= q <= 1")
        if self.count == 0:
            return float("nan")
        target = q * self.count
        cum = 0
        for i, upper in enumerate(self.buckets):
            prev_cum, cum = cum, cum + self.bins[i]
            if cum >= target:
                lower = self.buckets[i - 1] if i else 0.0
                if self.bins[i] == 0:
                    return upper
                frac = (target - prev_cum) / self.bins[i]
                return lower + (upper - lower) * frac
        return self.buckets[-1]

    def snapshot(self):
        return {"buckets": list(self.buckets), "bins": list(self.bins),
                "count": self.count, "sum": self.sum}

    def restore(self, snap) -> None:
        self.bins = [int(b) for b in snap["bins"]]
        self.count = int(snap["count"])
        self.sum = float(snap["sum"])


class Registry:
    """Named metric namespace. Re-declaring an existing name returns the
    existing metric when the kind matches and raises when it does not —
    the runtime arm of scripts/lint_knobs.py's unique-name rule."""

    def __init__(self) -> None:
        # Mutated by the learner thread (merge of remote snapshots) and
        # the timeline sampler thread (counter/gauge declares) alike.
        self._metrics: Dict[str, object] = {}  # guarded-by: _lock
        # RLock: merge() holds it across the whole fold while calling
        # counter()/gauge()/histogram(), which re-enter via _declare().
        self._lock = threading.RLock()

    def _declare(self, cls, name: str, *args, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if m.kind != cls.kind:
                    raise ValueError(
                        f"metric {name!r} already registered as {m.kind}, "
                        f"cannot re-register as {cls.kind}")
                return m
            m = cls(name, *args, **kwargs)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._declare(Counter, name, help)

    def gauge(self, name: str, help: str = "",
              agg: str = "last") -> Gauge:
        return self._declare(Gauge, name, help, agg)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = _DEF_BUCKETS) -> Histogram:
        return self._declare(Histogram, name, help, buckets)

    def names(self) -> List[str]:
        return sorted(self._metrics)

    def get(self, name: str):
        return self._metrics.get(name)

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()

    # -- snapshots & merge ---------------------------------------------------

    def snapshot(self) -> dict:
        """Flat mergeable view: name -> {kind, agg?, value-or-hist}."""
        out = {}
        for name in self.names():
            m = self._metrics[name]
            row = {"kind": m.kind, "value": m.snapshot()}
            if m.kind == "gauge":
                row["agg"] = m.agg
            out[name] = row
        return out

    def merge(self, snap: dict) -> None:
        """Fold another host's snapshot into this registry (Progress
        POD merge semantics, per metric kind).

        The whole fold runs under ``_lock``: ``value += v`` and the
        bin-wise histogram adds are read-modify-write sequences, and a
        concurrent ``inc()`` from the timeline sampler thread between
        the read and the write would be silently dropped."""
        with self._lock:
            for name, row in snap.items():
                kind = row["kind"]
                if kind == "counter":
                    self.counter(name).value += float(row["value"])
                elif kind == "gauge":
                    fresh = name not in self._metrics
                    g = self.gauge(name, agg=row.get("agg", "last"))
                    v = float(row["value"])
                    if fresh:
                        # first contribution: adopt it outright — folding
                        # against the fresh gauge's 0.0 would corrupt min
                        # aggregation (min(0, v)) and negative-valued max
                        g.value = v
                    elif g.agg == "sum":
                        g.value += v
                    elif g.agg == "max":
                        g.value = max(g.value, v)
                    elif g.agg == "min":
                        g.value = min(g.value, v)
                    else:
                        g.value = v
                elif kind == "histogram":
                    sv = row["value"]
                    h = self.histogram(name, buckets=sv["buckets"])
                    if list(h.buckets) != [float(b) for b in sv["buckets"]]:
                        raise ValueError(
                            f"histogram {name}: bucket layouts differ")
                    h.bins = [a + int(b)
                              for a, b in zip(h.bins, sv["bins"])]
                    h.count += int(sv["count"])
                    h.sum += float(sv["sum"])
                else:
                    raise ValueError(
                        f"metric {name}: unknown kind {kind!r}")

    def allreduce(self, mesh) -> None:
        """Merge this registry across hosts over the existing Progress
        side channel (one allreduce of the scalar vector + one per
        histogram). No-op on a single process."""
        import numpy as np
        from wormhole_tpu.parallel.collectives import allreduce_tree
        names = self.names()
        scalars = [n for n in names
                   if self._metrics[n].kind in ("counter", "gauge")]
        sums = np.array(
            [self._metrics[n].value if self._metrics[n].kind == "counter"
             or self._metrics[n].agg == "sum" else 0.0
             for n in scalars], np.float64)
        maxs = np.array(
            [self._metrics[n].value
             if getattr(self._metrics[n], "agg", "") in ("max", "last")
             else -np.inf for n in scalars], np.float64)
        mins = np.array(
            [self._metrics[n].value
             if getattr(self._metrics[n], "agg", "") == "min" else np.inf
             for n in scalars], np.float64)
        # site "obs/registry" is NOT in the lossy allowlist: metric
        # counters merge bit-exact (docs/comm.md's exact-semantics rule).
        # All registry merges are `transport: direct`: metrics windows
        # run with the engine quiesced (collective:metrics_window).
        # transport: direct — engine quiesced around the window
        sums = np.asarray(allreduce_tree(sums, mesh, "sum",
                                         site="obs/registry"))
        # transport: direct — engine quiesced around the window
        maxs = np.asarray(allreduce_tree(maxs, mesh, "max",
                                         site="obs/registry"))
        # transport: direct — engine quiesced around the window
        mins = np.asarray(allreduce_tree(mins, mesh, "min",
                                         site="obs/registry"))
        for i, n in enumerate(scalars):
            m = self._metrics[n]
            if m.kind == "counter" or getattr(m, "agg", "") == "sum":
                m.value = float(sums[i])
            elif m.agg in ("max", "last"):
                m.value = float(maxs[i])
            else:
                m.value = float(mins[i])
        for n in names:
            m = self._metrics[n]
            if m.kind != "histogram":
                continue
            vec = np.array(m.bins + [m.count], np.float64)
            # transport: direct — engine quiesced around the window
            vec = np.asarray(allreduce_tree(vec, mesh, "sum",
                                            site="obs/registry"))
            m.bins = [int(v) for v in vec[:-1]]
            m.count = int(vec[-1])
            # transport: direct — engine quiesced around the window
            m.sum = float(np.asarray(
                allreduce_tree(np.float64(m.sum), mesh, "sum",
                               site="obs/registry")))

    # -- adapters: the legacy metric surfaces --------------------------------

    def from_timer(self, timer, prefix: str = "timer_") -> None:
        """Import Timer totals/counts as counters (idempotent set: the
        timer itself is the accumulator, the registry mirrors it)."""
        for name, total in timer.totals.items():
            key = prefix + name
            self.counter(key + "_seconds").value = float(total)
            self.counter(key + "_calls").value = float(
                timer.counts.get(name, 0))

    def from_progress(self, prog, prefix: str = "progress_") -> None:
        """Mirror the fixed-layout Progress POD through its names()
        introspection (utils/progress.py) — every slot becomes a gauge
        with sum aggregation, same merge semantics as the POD."""
        fnames, inames = type(prog).names()
        for i, n in enumerate(fnames):
            self.gauge(prefix + n, agg="sum").value = float(prog.fvec[i])
        for i, n in enumerate(inames):
            self.gauge(prefix + n, agg="sum").value = float(prog.ivec[i])

    def ingest_feed(self, snap: dict, prefix: str = "feed_") -> None:
        """Fold a DeviceFeed stats()/drain_stats() snapshot in: stage
        seconds and batch counts accumulate, ring_max maxes."""
        for k, v in snap.items():
            if k == "ring_max":
                self.gauge(prefix + "ring_max", agg="max").max(float(v))
            elif k == "batches":
                self.counter(prefix + "batches").inc(float(v))
            else:
                self.counter(prefix + k + "_seconds").inc(float(v))

    # -- exporters -----------------------------------------------------------

    def record(self, **extra) -> dict:
        """One JSON-lines heartbeat record: flat name->value dict (hist
        as count/sum) plus caller extras (rank, step, rates...). Carries
        both wall ``ts`` and monotonic ``mono`` so obs/merge.py's clock
        model (offset = median(ts - mono)) can align records cross-rank;
        caller extras override those stamps (heartbeat passes its own
        ts/mono pair, sampled together), while registry metric values
        are written last and win over a same-named extra."""
        out = {"ts": round(time.time(), 3),
               "mono": round(time.monotonic(), 4)}
        out.update(extra)
        for name in self.names():
            m = self._metrics[name]
            if m.kind == "histogram":
                out[name + "_count"] = m.count
                out[name + "_sum"] = round(m.sum, 6)
            else:
                out[name] = (round(m.value, 6)
                             if isinstance(m.value, float) else m.value)
        return out

    def prometheus_text(self, labels: Optional[dict] = None) -> str:
        """Prometheus text exposition (version 0.0.4): a ``# HELP`` and
        ``# TYPE`` header per family (HELP from the declaration-site
        help string, falling back to the metric name so a strict scraper
        always sees both lines), then one sample per scalar and the
        cumulative ``_bucket`` series + ``_count``/``_sum`` per
        histogram. HELP text and label values are escaped per the
        exposition-format rules."""
        lab = ""
        if labels:
            inner = ",".join(
                f'{k}="{_esc_label(str(v))}"'
                for k, v in sorted(labels.items()))
            lab = "{" + inner + "}"

        def _san(name: str) -> str:
            return "".join(c if c.isalnum() or c == "_" else "_"
                           for c in name)

        lines = []
        for name in self.names():
            m = self._metrics[name]
            pname = _san(name)
            lines.append(f"# HELP {pname} {_esc_help(m.help or name)}")
            lines.append(f"# TYPE {pname} {m.kind}")
            if m.kind == "histogram":
                cum = 0
                for le, b in zip(m.buckets, m.bins):
                    cum += b
                    ll = (lab[:-1] + "," if lab else "{") + f'le="{le}"' + "}"
                    lines.append(f"{pname}_bucket{ll} {cum}")
                ll = (lab[:-1] + "," if lab else "{") + 'le="+Inf"' + "}"
                lines.append(f"{pname}_bucket{ll} {m.count}")
                lines.append(f"{pname}_sum{lab} {m.sum}")
                lines.append(f"{pname}_count{lab} {m.count}")
            else:
                lines.append(f"{pname}{lab} {m.value}")
        return "\n".join(lines) + "\n"


def _esc_help(text: str) -> str:
    """Exposition-format HELP escaping: backslash and newline only."""
    return text.replace("\\", "\\\\").replace("\n", "\\n")


def _esc_label(text: str) -> str:
    """Exposition-format label-value escaping: backslash, quote, LF."""
    return (text.replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def merge_snapshots(snaps: Sequence[dict]) -> Registry:
    """Merge per-host snapshots into one registry — the serial oracle
    for the cross-host path (tests assert merge == serial totals)."""
    reg = Registry()
    for s in snaps:
        reg.merge(s)
    return reg


_DEFAULT: Optional[Registry] = None


def default_registry() -> Registry:
    """The process-wide registry (apps and the bench share it)."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Registry()
    return _DEFAULT


def encode_counters(reg: Optional[Registry] = None):
    """The online tile-encode stage counters — single declaration site
    (lint_knobs uniqueness contract), fetched per call so a cleared
    default registry never strands stale Counter objects: seconds the
    stream waited on the encode workers (beside the PR 1 feed stall
    counters), and ``feed/tile_fallback_blocks``, which nothing counts
    any more: the scatter step that online blocks past the overflow room
    fell to is gone (the room is sized to the data), and the name stays
    at 0 only because ``benchmark/configs/criteo_ftrl_text`` reads it
    and states that it is 0; it goes with that file's next edit."""
    reg = reg if reg is not None else default_registry()
    return (reg.counter("feed/encode_stall",
                        help="seconds the stream waited on the online "
                             "tile-encode workers"),
            reg.counter("feed/tile_fallback_blocks",
                        help="always 0: online-encoded blocks no longer "
                             "leave the tile path"))


def encode_native_counter(reg: Optional[Registry] = None):
    """Blocks the online tile-encode stage put through the NATIVE encoder
    (native/tile_encode.cc behind data/crec.encode_tile_pairs) — single
    declaration site, fetched per call like :func:`encode_counters`.
    Which encoder runs is decided by what the process can load, so a
    stream whose count is 0 ran the numpy encoder for every block."""
    reg = reg if reg is not None else default_registry()
    return reg.counter("feed/encode_native_blocks",
                       help="online-encoded blocks the native tile "
                            "encoder took (0: the numpy encoder is live)")


def online_overflow_metrics(reg: Optional[Registry] = None):
    """What the online tile encoder puts on the blocks' COO overflow
    lists — single declaration site, fetched per call like
    :func:`encode_counters`: the pairs past the per-tile cap, the room in
    force for a list (data/crec.OverflowRoom) and how often a block
    passed it (each time is one more compile of the spill step)."""
    reg = reg if reg is not None else default_registry()
    return (reg.counter("feed/online_overflow_pairs",
                        help="pairs past the per-tile cap that online-"
                             "encoded blocks carry on their overflow "
                             "lists"),
            reg.gauge("feed/online_overflow_room",
                      help="slots of an online block's overflow list, "
                           "sized to the counts seen", agg="max"),
            reg.counter("feed/online_room_grown",
                        help="times a block's overflow count passed the "
                             "room and the room grew"))


def overflow_hot_metrics(reg: Optional[Registry] = None):
    """Which form the blocks' overflow lists took on their way to the
    one-device train step (data/crec.HotRoom) — single declaration site,
    fetched per call like :func:`encode_counters`."""
    reg = reg if reg is not None else default_registry()
    return (reg.counter("feed/overflow_hot_blocks",
                        help="blocks whose overflow list rode in its hot "
                             "form (distinct buckets + packed pair words)"),
            reg.counter("feed/overflow_coo_blocks",
                        help="blocks with an overflow list that kept the "
                             "COO path (short, or its buckets mostly "
                             "distinct; the reason is logged once)"),
            reg.counter("feed/overflow_hot_buckets",
                        help="distinct buckets the hot lists named, "
                             "summed over their blocks"),
            reg.gauge("feed/overflow_hot_room",
                      help="slots of a hot list's pair words at the room "
                           "in force", agg="max"))


def mesh_overflow_metrics(reg: Optional[Registry] = None):
    """What the sharded mesh feed ships of the blocks' overflow lists
    (data/crec.MeshGroupFeed) — single declaration site, fetched per
    call like :func:`encode_counters`: a group's lists cross as one lane
    at the widest member's width, every chip handed its DATA member's
    whole list."""
    reg = reg if reg is not None else default_registry()
    return (reg.counter("mesh/overflow_slots",
                        help="slots of the groups' stacked overflow list "
                             "lanes as they crossed to the chips, after "
                             "widening"),
            reg.counter("mesh/widened_groups",
                        help="groups in which a member's overflow list "
                             "was widened to the group's widest"))


def fm_step_metrics(reg: Optional[Registry] = None):
    """Which variant of the one-device FM tile train step each block took
    (models/fm.FMStore.tile_train_step) — single declaration site,
    fetched per call like :func:`encode_counters`. A block that brings an
    overflow list takes the spill step (the gradient-writing kernel, the
    COO pull and scatter, one update pass in XLA), one without a list the
    in-place kernel; a resident click-log shard whose in-place count
    moves is stepping blocks that lost their lists."""
    reg = reg if reg is not None else default_registry()
    return (reg.counter("step/fm_spill_blocks",
                        help="train blocks that took FM's spill step "
                             "(the block brought an overflow list)"),
            reg.counter("step/fm_in_place_blocks",
                        help="train blocks that took FM's in-place "
                             "kernel (no overflow list)"),
            reg.counter("step/fm_listed_pairs",
                        help="pairs on the overflow lists of the blocks "
                             "that took FM's spill step"))


def wd_step_metrics(reg: Optional[Registry] = None):
    """Whether each train block of the one-device wide&deep tile step
    brought an overflow list (models/wide_deep.WideDeepStore) — single
    declaration site, fetched per call like :func:`encode_counters`. A
    block with a list takes the spill step's two list phases beside the
    kernel pair (``wd_ovf_pull``, ``wd_ovf_scatter``: a gather and a
    scatter-add a slot a plane); a resident click-log shard whose
    listless count moves is stepping blocks that lost their lists."""
    reg = reg if reg is not None else default_registry()
    return (reg.counter("step/wd_spill_blocks",
                        help="train blocks that took wide&deep's spill "
                             "step (the block brought an overflow list)"),
            reg.counter("step/wd_listed_pairs",
                        help="pairs on the overflow lists of the blocks "
                             "that took wide&deep's spill step"),
            reg.counter("step/wd_listless_blocks",
                        help="train blocks wide&deep stepped without an "
                             "overflow list"))


def mesh_feed_gauges(reg: Optional[Registry] = None):
    """The sharded mesh-feed (data/crec.MeshGroupFeed) telemetry —
    single declaration site (lint_knobs uniqueness contract), fetched
    per call like :func:`encode_counters`. Skew is the arrival-time
    spread between the first and last block of a data-axis group on the
    feed dispatcher — the per-device straggler signal: one slow block
    delays its whole group's dispatch by exactly this much."""
    reg = reg if reg is not None else default_registry()
    return (reg.gauge("mesh/dispatch_skew_ms",
                      help="mean per-group block arrival skew on the "
                           "mesh feed dispatcher, milliseconds"),
            reg.gauge("mesh/dispatch_skew_ms_max",
                      help="worst per-group block arrival skew, "
                           "milliseconds", agg="max"),
            reg.counter("mesh/feed_groups",
                        help="data-axis block groups dispatched through "
                             "the sharded mesh feed"),
            reg.counter("mesh/pad_blocks",
                        help="all-PAD filler blocks standing in for the "
                             "missing members of short tail groups"))
