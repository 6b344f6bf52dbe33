"""Kill-and-rejoin chaos drill: prove live rejoin under serving traffic.

One process simulates an N-rank bounded-staleness training world the way
the multichip phase simulates devices: each rank is a thread with its
own replicated :class:`~wormhole_tpu.learners.store.ShardedStore` and
:class:`~wormhole_tpu.ps.engine.ExchangeEngine` (real drain thread, real
gate/quiesce, real replay log), and the ``ps/delta`` allreduce is a
:class:`~wormhole_tpu.ft.rejoin.LocalGroup` — the in-process membership
collective, since jax.distributed cannot re-admit a process today.
Everything around the fake transport is the production subsystem it
exercises:

- the shared :class:`~wormhole_tpu.sched.workload_pool.WorkloadPool`
  (static split registered per owner; ``reset`` re-queues the dead
  rank's shards for survivors and the rejoiner to claim),
- real :class:`~wormhole_tpu.obs.heartbeat.HeartbeatWriter` files fed
  to the real :class:`~wormhole_tpu.ft.supervisor.DeadRankDetector`,
- real :class:`~wormhole_tpu.parallel.checkpoint.ShardCheckpointer`
  per-rank shard commits (rank override) for the rejoiner's restore,
- the real :class:`~wormhole_tpu.ft.rejoin.RejoinHandshake` — attach at
  a window boundary, bounded delta replay, admission,
- and the real serve tier (:class:`ForwardStep` + ``ServeFrontend`` +
  ``SnapshotPoller``) answering an open-loop client through the whole
  kill → detect → re-queue → restore → replay → admit cycle.

The drill kills one rank at a planted window, proves the survivors
finish the pass without restarting (thread identity), the rejoiner is
admitted after bounded replay, and serving latency holds. ``bench.py
--phases rejoin`` and tests/test_ft_rejoin_e2e.py both run this
function; the undisturbed baseline is the same call with ``kill=None``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from wormhole_tpu.ft.rejoin import (LocalGroup, RejoinHandshake, ReplayLog,
                                    VersionVector)
from wormhole_tpu.ft.supervisor import Supervisor

__all__ = ["run_rejoin_drill"]


def _make_store(nb: int):
    from wormhole_tpu.learners.handles import LearnRate, create_handle
    from wormhole_tpu.learners.store import ShardedStore, StoreConfig
    from wormhole_tpu.ops.penalty import L1L2
    handle = create_handle("dt2_adagrad", L1L2(0.0, 1e-4),
                           LearnRate(0.1, 1.0))
    return ShardedStore(StoreConfig(num_buckets=nb, loss="logit",
                                    fixed_bytes=0), handle)


def _make_batches(rng, nb: int, n: int, mb: int, nnz: int,
                  feat: int, kpad: int) -> list:
    """``n`` padded SparseBatches of planted logistic data over a fixed
    ``feat``-key vocabulary (one geometry -> one compile per store)."""
    from wormhole_tpu.data.feed import pad_to_batch
    from wormhole_tpu.data.localizer import Localizer
    from wormhole_tpu.data.rowblock import RowBlock
    vocab = rng.choice(nb, size=feat, replace=False).astype(np.uint64)
    w_true = (rng.standard_normal(feat) * 1.5).astype(np.float64)
    loc = Localizer(num_buckets=nb)
    out = []
    for _ in range(n):
        rows = [np.sort(rng.choice(feat, size=int(rng.integers(3, nnz)),
                                   replace=False)) for _ in range(mb)]
        offset = np.zeros(mb + 1, np.int64)
        np.cumsum([len(r) for r in rows], out=offset[1:])
        fidx = np.concatenate(rows)
        vals = rng.random(len(fidx)).astype(np.float32)
        margins = np.array([float(w_true[fidx[s:e]] @ vals[s:e])
                            for s, e in zip(offset[:-1], offset[1:])])
        label = (1.0 / (1.0 + np.exp(-margins))
                 > rng.random(mb)).astype(np.float32)
        blk = RowBlock(label=label, offset=offset,
                       index=vocab[fidx], value=vals)
        out.append(pad_to_batch(loc.localize(blk), mb, nnz, key_pad=kpad))
    return out


def run_rejoin_drill(
        workdir: str,
        world: int = 3,
        nb: int = 2048,
        parts: int = 6,
        batches_per_part: int = 4,
        minibatch: int = 64,
        nnz: int = 8,
        tau: int = 1,
        replay_windows: int = 256,
        ckpt_every: int = 3,
        kill: Optional[Tuple[int, int]] = (2, 6),
        rejoin: bool = True,
        dead_after_s: float = 0.5,
        idle_sleep_s: float = 0.01,
        serve_qps: float = 50.0,
        seed: int = 0,
        registry=None,
        group_timeout_s: float = 60.0,
) -> Dict[str, Any]:
    """One kill-and-rejoin cycle; returns the drill report dict.

    ``kill=(rank, window)`` plants a simulated SIGKILL (the rank thread
    stops dead at that submission index: no detach, no quiesce, no
    final heartbeat); ``kill=None`` is the undisturbed baseline the e2e
    test compares objv against. ``rejoin=False`` degrades to
    shrink-only (survivors finish, nobody comes back).
    """
    import jax.numpy as jnp

    from wormhole_tpu.obs.heartbeat import HeartbeatWriter
    from wormhole_tpu.parallel.checkpoint import ShardCheckpointer
    from wormhole_tpu.ps.engine import ExchangeEngine
    from wormhole_tpu.ps.telemetry import rejoin_metrics
    from wormhole_tpu.sched.workload_pool import TRAIN, Workload, WorkloadPool
    from wormhole_tpu.serve import ForwardStep, ServeFrontend, SnapshotPoller

    t_start = time.monotonic()
    hb_dir = os.path.join(workdir, "hb")
    ck_dir = os.path.join(workdir, "ckpt")
    os.makedirs(hb_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    depth = max(tau, 0) + replay_windows
    met = rejoin_metrics(registry) if registry is not None else None

    # -- data + shared scheduler state --------------------------------
    part_batches = {f"part{i}": _make_batches(rng, nb, batches_per_part,
                                              minibatch, nnz, feat=64,
                                              kpad=128)
                    for i in range(parts)}
    val_batches = _make_batches(rng, nb, 4, minibatch, nnz,
                                feat=64, kpad=128)
    pool = WorkloadPool()
    queued = [Workload(f"part{i}", 0, 1, TRAIN) for i in range(parts)]
    pool.add_parts(queued)
    # static round-robin split, registered per owning rank so that
    # reset(dead) re-queues exactly the dead rank's shards
    splits = {r: [wl for i, wl in enumerate(queued) if i % world == r]
              for r in range(world)}
    pool.take_static(world, 0)

    group = LocalGroup(world)
    stores = {r: _make_store(nb) for r in range(world)}
    engines = {r: ExchangeEngine(tau, replay=ReplayLog(depth))
               for r in range(world)}
    all_engines = list(engines.values())
    ckpts = {r: ShardCheckpointer(ck_dir, keep=4, rank=r, world=world)
             for r in range(world)}

    state = {r: {"idx": 0, "num_ex": 0, "applied_hi": -1}
             for r in range(world)}
    threads_per_rank = {r: 1 for r in range(world)}
    done = threading.Event()          # all training threads finished
    errors: List[BaseException] = []
    report: Dict[str, Any] = {
        "world": world, "kill": None, "rejoin": None,
        "replay_depth": depth,
    }

    # -- serving tier: owned snapshot + checkpoint hot-swap -----------
    fwd = ForwardStep.from_store(stores[0])
    fwd.swap({k: jnp.array(v) for k, v in fwd.params.items()})
    template = {"slots": np.asarray(stores[0].slots), "t": np.int64(0),
                "applied_hi": np.int64(-1)}
    poller = SnapshotPoller(
        ShardCheckpointer(ck_dir, keep=4, rank=0, world=world),
        template, fwd, poll_itv=0.2)
    fe = ServeFrontend(fwd, batch_rows=16, max_nnz=nnz, deadline_ms=5.0)

    def client() -> None:
        crng = np.random.default_rng(seed + 1000)
        futs = []
        t0 = time.monotonic()
        i = 0
        while not done.is_set():
            target = t0 + i / serve_qps
            now = time.monotonic()
            if now < target:
                time.sleep(min(target - now, 0.05))
                continue
            keys = crng.choice(nb, size=int(crng.integers(2, nnz)),
                               replace=False)
            vals = crng.random(len(keys)).astype(np.float32)
            futs.append(fe.submit(keys, vals))
            i += 1
        for f in futs:
            f.result(timeout=30)

    # -- one rank's window loop ---------------------------------------

    def run_rank(r: int, store, engine, vv: VersionVector,
                 static_parts: list, start_idx: int,
                 hb_stop: threading.Event) -> None:
        st = state[r]

        def feed():
            for wl in static_parts:
                for b in part_batches[wl.file]:
                    yield b
                pool.finish(wl.id)
            while True:
                wl = pool.get(r)
                if wl is None:
                    # nothing claimable RIGHT NOW — but a dead rank's
                    # shards may still be re-queued, so idle (the caller
                    # churns an empty window) instead of leaving
                    yield None
                    continue
                for b in part_batches[wl.file]:
                    yield b
                pool.finish(wl.id)

        it = feed()
        idx = start_idx

        def apply(tk) -> bool:
            res = tk.result
            delay = engine.note_applied(tk)
            store.ps_push(res["grad"], tau=float(delay))
            st["applied_hi"] = start_idx + tk.index
            vv.merge_row(res["vv"])
            st["num_ex"] += int(res["metrics"][1])
            return int(res["have"]) == 0

        def maybe_ckpt() -> None:
            hi = st["applied_hi"]
            if ckpt_every and hi >= 0 and (hi + 1) % ckpt_every == 0:
                ckpts[r].save(hi + 1, {
                    **store.state_pytree(),
                    "applied_hi": np.int64(hi)}, barrier=False)

        stop = False
        while not stop:
            if kill is not None and r == kill[0] and idx >= kill[1] \
                    and "t_kill" not in report:
                # simulated SIGKILL: no detach, no quiesce, no final
                # heartbeat — the detector must find out the hard way
                report["t_kill"] = time.monotonic()
                hb_stop.set()
                return
            dense = np.zeros(nb, np.float32)
            mets = np.zeros(4, np.float64)
            blk = next(it, None)
            if blk is not None:
                grad, _snap, m = store.dt2_pull(blk)
                np.add.at(dense, np.asarray(blk.uniq_keys),
                          np.asarray(grad) * np.asarray(blk.key_mask))
                nex = float(np.asarray(m[1]))
                mets += [float(np.asarray(m[0])), nex,
                         float(np.asarray(m[2])) * nex,
                         float(np.asarray(m[3])) * nex]
            else:
                # idle window: pace the loop so the detection gap costs
                # a bounded number of windows in the replay log
                time.sleep(idle_sleep_s)
            have = int(blk is not None or pool.pending() > 0)
            vv.bump(r)
            payload = {"grad": dense, "metrics": mets.astype(np.float32),
                       "have": np.int64(have), "vv": vv.one_hot(r)}
            engine.submit(
                lambda p=payload, i=idx: group.allreduce(
                    r, i, p, timeout=group_timeout_s))
            idx += 1
            st["idx"] = idx
            for tk in engine.gate():
                stop = apply(tk) or stop
            maybe_ckpt()
        for tk in engine.quiesce():
            apply(tk)
        maybe_ckpt()
        group.detach(r)
        hb_stop.set()

    def hb_loop(r: int, stop_ev: threading.Event) -> None:
        w = HeartbeatWriter(hb_dir, rank=r, interval=0.0)
        while not stop_ev.wait(0.1):
            w.beat(step=state[r]["idx"], num_ex=state[r]["num_ex"],
                   force=True)
        if kill is None or r != kill[0] or state[r].get("rejoined"):
            w.close(step=state[r]["idx"], num_ex=state[r]["num_ex"])

    def guarded(fn, *a) -> None:
        try:
            fn(*a)
        except BaseException as e:   # surfaced by the caller
            errors.append(e)
            done.set()

    # -- rejoiner ------------------------------------------------------

    def run_rejoiner(r: int, t_detect: float) -> None:
        store = _make_store(nb)
        ck = ShardCheckpointer(ck_dir, keep=4, rank=r, world=world)
        ver, st_loaded = ck.load({**store.state_pytree(),
                                  "applied_hi": np.int64(-1)})
        if ver <= 0:
            raise RuntimeError(
                f"rejoiner rank {r}: no committed checkpoint version")
        store.restore_pytree({"slots": st_loaded["slots"],
                              "t": st_loaded["t"]})
        have_idx = int(st_loaded["applied_hi"])
        vv = VersionVector(world)
        # any survivor's log will do: they all record the same windows
        donor = engines[min(rr for rr in group.live())]
        hs = RejoinHandshake(group, donor.replay, metrics=met)

        def apply_replay(i: int, payload) -> None:
            store.ps_push(payload["grad"], tau=0.0)
            vv.merge_row(payload["vv"])

        rep = hs.run(r, have_idx, apply_replay, timeout=group_timeout_s)
        debt = time.monotonic() - t_detect
        if met is not None:
            met.recovery_debt_s.set(debt)
            met.replay_evicted.inc(donor.replay.evicted)
        state[r]["rejoined"] = True
        state[r]["applied_hi"] = rep.join_idx - 1
        stores[r] = store
        engine = ExchangeEngine(tau, replay=ReplayLog(depth))
        engines[r] = engine
        all_engines.append(engine)
        sup.note_rejoined(r)
        report["rejoin"] = {
            "have_idx": rep.have_idx, "join_idx": rep.join_idx,
            "replayed": rep.replayed, "epoch": rep.epoch,
            "handshake_s": round(rep.handshake_s, 4),
            "recovery_debt_s": round(debt, 4),
            "admitted_within_bound": rep.replayed <= depth,
        }
        hb_stop = threading.Event()
        hb = threading.Thread(target=hb_loop, args=(r, hb_stop),
                              daemon=True)
        hb.start()
        aux.append(hb)
        # no static split: the rejoiner claims re-queued shards via get
        run_rank(r, store, engine, vv, [], rep.join_idx, hb_stop)

    # -- launch --------------------------------------------------------

    sup = Supervisor(world, elastic="rejoin" if rejoin else "shrink",
                     dead_after_s=dead_after_s)
    train_threads: List[threading.Thread] = []
    aux: List[threading.Thread] = []
    hb_stops = {}
    for r in range(world):
        hb_stops[r] = threading.Event()
        hb = threading.Thread(target=hb_loop, args=(r, hb_stops[r]),
                              daemon=True)
        hb.start()
        aux.append(hb)
        vv = VersionVector(world)
        t = threading.Thread(
            target=guarded, name=f"drill-rank{r}",
            args=(run_rank, r, stores[r], engines[r], vv, splits[r], 0,
                  hb_stops[r]),
            daemon=True)
        train_threads.append(t)
    # compile warmup off the hot loop: the first dt2_pull/ps_push/eval
    # trace costs ~seconds on CPU, long enough to stall heartbeat
    # threads past dead_after_s and blow the replay window budget
    wb = part_batches["part0"][0]
    for st_ in stores.values():
        st_.dt2_pull(wb)
        st_.ps_push(np.zeros(nb, np.float32), tau=0.0)
        st_.eval_step(val_batches[0])

    poller.start()
    cl = threading.Thread(target=guarded, args=(client,), daemon=True)
    cl.start()
    for t in train_threads:
        t.start()

    # -- supervision loop (the launcher-poll analogue) -----------------
    handled: set = set()
    try:
        while any(t.is_alive() for t in train_threads) \
                and not errors:
            time.sleep(0.05)
            sup.scan_heartbeats(hb_dir)
            for r in sorted(set(sup.dead) - handled):
                if kill is None or r != kill[0]:
                    continue   # only the planted kill is acted on: a
                    # spurious detection (GIL stall) must not corrupt
                    # the membership of a healthy rank
                handled.add(r)
                t_detect = time.monotonic()
                report["kill"] = {
                    "rank": r,
                    "detect_s": round(t_detect
                                      - report.get("t_kill", t_detect), 4),
                }
                pool.reset(r)
                epoch = group.mark_dead(r)
                if met is not None:
                    met.epoch.set(epoch)
                if rejoin:
                    rt = threading.Thread(
                        target=guarded, name=f"drill-rejoin{r}",
                        args=(run_rejoiner, r, t_detect), daemon=True)
                    threads_per_rank[r] += 1
                    train_threads.append(rt)
                    rt.start()
        for t in train_threads:
            t.join(timeout=group_timeout_s)
    finally:
        done.set()
        cl.join(timeout=60)
        poller.stop()
        fe.close()
        for eng in all_engines:
            try:
                eng.stop()
            except Exception:
                pass
        for ev in hb_stops.values():
            ev.set()
        for t in aux:
            t.join(timeout=5)
    if errors:
        raise errors[0]

    # -- verdicts ------------------------------------------------------

    def val_objv(store) -> float:
        tot = ex = 0.0
        for b in val_batches:
            m = store.eval_step(b)
            tot += float(np.asarray(m[0]))
            ex += float(np.asarray(m[1]))
        return tot / max(ex, 1.0)

    stats = fe.stats()
    survivors = [r for r in range(world)
                 if kill is None or r != kill[0]]
    s0 = survivors[0]
    report.update({
        "wall_s": round(time.monotonic() - t_start, 3),
        "windows": state[s0]["applied_hi"] + 1,
        "threads_per_rank": dict(threads_per_rank),
        "replay_evicted": engines[s0].replay.evicted,
        "objv": val_objv(stores[s0]),
        "serve": {
            "requests": int(stats.get("requests", 0)),
            "p50_ms": float(stats.get("p50_ms", 0.0)),
            "p99_ms": float(stats.get("p99_ms", 0.0)),
            "swaps": poller.swaps,
        },
    })
    report.pop("t_kill", None)
    if kill is not None and rejoin and report["rejoin"] is not None:
        rj = stores[kill[0]]
        w_s = np.asarray(stores[s0].handle.weights(
            stores[s0].slots.astype(jnp.float32)))
        w_r = np.asarray(rj.handle.weights(
            rj.slots.astype(jnp.float32)))
        denom = float(np.linalg.norm(w_s)) or 1.0
        report["rejoin"]["slots_rel_err"] = float(
            np.linalg.norm(w_r - w_s) / denom)
        report["objv_rejoined"] = val_objv(rj)
    return report
