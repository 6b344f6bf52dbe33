"""The crec pass's deferred metric window (learners/async_sgd.py).

A crec pass never fetches a step's metrics as it dispatches it: a
per-step ``float(np.asarray(...))`` costs one blocking round trip and
drains the async dispatch pipeline. What a pass defers rides one of
two lists until a drain:

- the on-device accumulator's async tickets (``MetricAccumulator``):
  tile and mesh TRAIN steps add their packed metric row into one device
  buffer (``store.fetch_metrics_async``), so the host only counts the
  steps and fetches one buffer a window;
- per-step metric vectors (eval steps, the crec v1 dense steps).

This module is also the one place that knows the two metric-row layouts
of the stores' steps by name (:func:`fold_row`) and that label 255 is a
PAD row (:func:`pool_margins`).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from wormhole_tpu import obs
from wormhole_tpu.data.crec import PAD_LABEL
from wormhole_tpu.ops.metrics import auc_from_hist
from wormhole_tpu.sched.workload_pool import TRAIN
from wormhole_tpu.utils.progress import Progress


def fold_row(local: Progress, row, layout: str, kind: str):
    """Fold one step's fetched metric row into ``local`` (one count).

    ``layout`` names what the step returned: ``"sparse"`` is
    ``[objv, num_ex, auc, acc, wdelta2|margin]`` (the sparse step and
    the one-device crec v1 dense steps);
    ``"tile"`` is ``[objv, num_ex, acc, pos, neg, wdelta2|margin]`` with
    the AUC in margin histograms (the tile steps and every mesh step).
    The last slot, where a step has one, is Σ(Δw)² on a TRAIN pass and
    the rows' margins otherwise: the margins are returned for
    :func:`pool_margins`, None on a TRAIN pass."""
    local.objv += float(row[0])
    local.num_ex += int(row[1])
    local.count += 1
    if layout == "tile":
        local.acc += float(row[2])
        local.auc += auc_from_hist(row[3], row[4])
        tail = 5
    else:
        local.auc += float(row[2])
        local.acc += float(row[3])
        tail = 4
    if len(row) <= tail:
        return None
    if kind == TRAIN:
        local.wdelta2 += float(row[tail])
        return None
    return row[tail]


def pool_margins(pooled: list, margin, labels_u8: np.ndarray) -> None:
    """Append the real rows of one step to an eval pass's ``pooled``
    ``(margin, label, weight)`` triples: a crec label lane carries PAD
    rows as 255 and any other non-zero byte as a positive."""
    real = labels_u8 != PAD_LABEL
    pooled.append((margin[real],
                   np.minimum(labels_u8[real], 1).astype(np.float32),
                   np.ones(int(real.sum()), np.float32)))


class MetricAccumulator:
    """Host side of the on-device metric accumulator: the steps
    dispatched since the last fetch, the async reads in flight (each
    with its step count), and the running margin histograms the
    displayed AUC comes from. The app's own survives parts and passes
    (one-device tile TRAIN; ``AsyncSGD.flush_metrics`` drains it); a
    mesh or multihost part makes one and drains it at the part's end."""

    def __init__(self):
        self.count = 0
        self.tickets: list = []
        self.hist = [np.zeros(512), np.zeros(512)]


class MetricWindow:
    """What one crec part has dispatched and not yet folded into its
    ``local`` Progress. ``acc`` is the accumulator this part's TRAIN
    steps count into (None: every step returns its own metric vector).
    ``bounded`` is the mesh rule: a mesh or multihost part gates on no
    step, so the window bounds itself: a counted step drains
    (non-final, the device never waits on the round trip) when a
    display is due or ``CREC_DRAIN_CHUNK`` steps are out, and a list
    folds when it holds that many. Such a part's TRAIN steps count into
    an accumulator of its own, so its AUC is the part's."""

    def __init__(self, app, local: Progress, kind: str,
                 pooled: Optional[list],
                 acc: Optional[MetricAccumulator] = None,
                 bounded: bool = False):
        self.app, self.local, self.kind, self.pooled = app, local, kind, pooled
        if bounded and kind == TRAIN:
            acc = MetricAccumulator()
        self.acc = acc
        self.bounded = bounded
        self.steps: list = []    # (metrics, labels_u8, layout) a step

    def _wait_scope(self):
        return self.app.timer.scope(
            ("" if self.kind == TRAIN else "eval_") + "wait")

    def count_step(self) -> None:
        """One more step added its row to the device accumulator."""
        self.acc.count += 1
        if self.bounded and (self.app.reporter.due() or self.acc.count
                             >= self.app.CREC_DRAIN_CHUNK):
            with self._wait_scope():
                self.drain(final=False)

    def add_step(self, metrics, labels_u8, layout: str) -> None:
        self.steps.append((metrics, labels_u8, layout))
        if self.bounded and len(self.steps) >= self.app.CREC_DRAIN_CHUNK:
            with self._wait_scope():
                self._fold_list(self.steps)

    def _fold_list(self, steps: list) -> None:
        """Fold a deferred list with one batched fetch: per-leaf fetches
        cost one blocking round trip each, and each one drains the
        dispatch pipeline."""
        if not steps:
            return
        # host-sync: one batched fetch drains the whole deferred list
        fetched = jax.device_get([s[0] for s in steps])
        for (_m, labels_u8, layout), row in zip(steps, fetched):
            margin = fold_row(self.local, row, layout, self.kind)
            if (margin is not None and self.pooled is not None
                    and labels_u8 is not None):
                # host-sync: fetched above — already on the host
                pool_margins(self.pooled, np.asarray(margin), labels_u8)
        steps.clear()

    def fold(self) -> None:
        """Fold the deferred list into ``local``; a TRAIN pass shows its
        row after its per-step vectors."""
        if self.steps:
            self._fold_list(self.steps)
            if self.kind == TRAIN:
                self.app._display(self.local)

    def drain(self, final: bool = True) -> None:
        """Fold everything outstanding into ``local``: the lists, then
        the accumulator, whose read stays an async ticket unless
        ``final``."""
        self.fold()
        if self.acc is not None:
            self._harvest_macc(final)

    def _harvest_macc(self, final: bool) -> None:
        """Harvest the on-device metric accumulator into ``local`` — one
        device read per window, and that read is ASYNC: the pending
        steps start a fetch immediately (the device never stalls), while
        the previous window's ticket — which has had a full window of
        wall-clock to fly home — is resolved. ``final`` resolves
        everything, blocking (flush/part boundaries). AUC comes from the
        RUNNING margin histograms, stored as auc*count so Progress
        merges reproduce the pass-level number. The packed row layout is
        ShardedStore's: [objv, num_ex, acc, wdelta2, pos, neg]."""
        acc, local = self.acc, self.local
        if acc.count:
            acc.tickets.append(
                (self.app.store.fetch_metrics_async(), acc.count))
            acc.count = 0
        resolved = False
        while acc.tickets and (final or len(acc.tickets) > 1):
            ticket, n = acc.tickets.pop(0)
            # the fetched accumulator is the psum'd metric buffer — this
            # resolve IS the collective boundary on the device step path
            with obs.trace.span("collective:metrics_window",
                                cat="collective",
                                args={"site": "async_sgd/metrics_window"}):
                # host-sync: a ticket started a window ago (or final)
                row = np.asarray(ticket)
            local.objv += float(row[0])
            local.num_ex += int(row[1])
            local.count += n
            local.acc += float(row[2])
            local.wdelta2 += float(row[3])
            bins = (len(row) - 4) // 2
            acc.hist[0] += row[4:4 + bins]
            acc.hist[1] += row[4 + bins:]
            resolved = True
        if resolved:
            local.auc = auc_from_hist(*acc.hist) * local.count
            self.app._display(local)
