"""Job launcher — the ``dmlc_local.py`` / ``dmlc_yarn.py`` analogue.

Reference trackers spawn N worker + S server processes and wire them up by
env (SURVEY.md §1 L6, ``learn/linear/guide/demo_local.sh:3``). On TPU the
roles collapse into one SPMD program, so the launcher's jobs are:

- ``--cluster sim``   : run the app in ONE process with N *virtual* CPU
  devices (``--xla_force_host_platform_device_count``) — the local testing
  story, matching ``dmlc_local.py`` ergonomics without any networking.
- ``--cluster mp``    : spawn N local processes joined through
  ``jax.distributed.initialize`` over localhost — exercises the real
  multi-controller runtime (the DCN path) on one machine.
- ``--cluster tpu``   : exec the app unchanged on every host of a pod slice
  (the pod runtime injects coordinator/topology; we only validate env).

``--restarts K`` is the elastic-recovery hook (reference: the tracker
relaunching failed nodes + rabit checkpoint restart, workload_pool.h:111 +
lbfgs.h:120-125): if the job exits nonzero, the WHOLE job is relaunched up
to K times — apps configured with ``checkpoint_dir`` resume from their
last committed version, which is the recovery model JAX multihost implies
(a lost process cannot rejoin a live mesh; SURVEY §5.3/§7 hard part (e)).

Usage:  python -m wormhole_tpu.parallel.launcher -n 8 [--cluster sim] -- \
            python your_app.py key=val ...
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from typing import List

# raw socket use lives in the wire module (checker WH-SOCKET); the
# launcher only needs its port probe
from wormhole_tpu.parallel.socket_wire import free_port as _free_port


def _base_env() -> dict:
    """Child env for the CPU simulation modes: ships the framework to
    the child like dmlc_local.py ships its binaries (repo root on
    PYTHONPATH). The `tpu` cluster mode leaves the env untouched."""
    env = dict(os.environ)
    pp = [p for p in env.get("PYTHONPATH", "").split(":") if p]
    cwd = os.getcwd()
    if cwd not in pp:
        pp.insert(0, cwd)
    env["PYTHONPATH"] = ":".join(pp)
    return env


def launch_sim(n: int, cmd: List[str]) -> int:
    # CPU simulation: the child is pinned to the CPU backend with n
    # forced host devices and never asks for a chip
    env = _base_env()
    xla = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = f"{xla} --xla_force_host_platform_device_count={n}".strip()
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.call(cmd, env=env)


def _pump_lines(stream, sink, lock, tag: bytes = b"") -> None:
    """Relay one child's output to ``sink`` a full line at a time.

    Children block-buffer when stdout is a pipe, so two ranks writing the
    shared pipe directly can flush MID-line (observed: ``num_ex=400OK`` —
    two ranks' lines spliced). Reading per-child pipes and writing whole
    lines under one lock makes the merged stream line-atomic, so tests
    (and any log consumer) can parse it with line-anchored patterns.
    ``tag`` (e.g. ``b"[w3] "``) prefixes every line so interleaved
    multi-process output stays attributable to its rank."""
    for line in iter(stream.readline, b""):
        with lock:
            if tag:
                sink.write(tag)
            sink.write(line)
            sink.flush()
    stream.close()


_TERM_GRACE_S = 5.0   # SIGTERM -> SIGKILL for bystanders of a failed job


def _attempt_dir(directory: str, attempt: int) -> str:
    """Telemetry dir for one launch attempt. Attempt 0 keeps the base
    dir (single-launch runs are unchanged); relaunches namespace
    ``attempt<k>/`` so a retry never clobbers — or gets mixed into —
    the previous attempt's heartbeat/trace files (obs/merge.py and
    scripts/bench_check.py read the latest attempt)."""
    if not directory or attempt <= 0:
        return directory
    return os.path.join(directory, f"attempt{attempt}")


def launch_mp(n: int, cmd: List[str], heartbeat_dir: str = "",
              straggler_factor: float = 3.0, trace_dir: str = "",
              attempt: int = 0, supervisor=None,
              comm_timeout_s: float = 0.0, drain: bool = False,
              rejoin_budget: int = 0) -> int:
    import threading
    port = _free_port()
    procs = []
    pumps = []
    out_lock = threading.Lock()
    monitor = None
    heartbeat_dir = _attempt_dir(heartbeat_dir, attempt)
    trace_dir = _attempt_dir(trace_dir, attempt)
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
    if heartbeat_dir:
        # children inherit the export dir (obs.setup falls back to this
        # env var), the launcher watches their heartbeat files and warns
        # on stragglers — the dist_monitor/scheduler view, file-based
        from wormhole_tpu.obs import (METRICS_EXPORT_ENV,
                                      HeartbeatMonitor)
        os.makedirs(heartbeat_dir, exist_ok=True)

        def _warn(msg: str) -> None:
            with out_lock:
                sys.stderr.write(msg + "\n")
                sys.stderr.flush()

        monitor = HeartbeatMonitor(heartbeat_dir,
                                   factor=straggler_factor,
                                   sink=_warn).start()
    def _spawn(i: int, attempt_idx: int, rejoin: bool = False):
        # CPU simulation: every rank is pinned to the CPU backend (n
        # processes cannot share one chip), so no rank asks for a chip
        env = _base_env()
        env["JAX_PLATFORMS"] = "cpu"
        # children write a pipe (block-buffered by default): unbuffer so
        # a killed/crashed rank doesn't lose its last lines and live runs
        # stream instead of bursting every 8KB
        env["PYTHONUNBUFFERED"] = "1"
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NUM_PROCESSES"] = str(n)
        env["PROCESS_ID"] = str(i)
        # relaunch attempt index: chaos injection (ft/chaos.py) fires
        # only on attempt 0, so a supervised retry — and a rejoined
        # rank, which gets attempt+1 while survivors keep their original
        # index — comes up clean
        env["WORMHOLE_ATTEMPT"] = str(attempt_idx)
        if rejoin:
            # respawned into a live world: the learner takes the
            # checkpoint-restore + handshake + replay path
            # (ft/supervisor.REJOIN_ENV)
            env["WORMHOLE_REJOIN_RANK"] = str(i)
        if comm_timeout_s > 0:
            env["WORMHOLE_COMM_TIMEOUT_S"] = str(comm_timeout_s)
        if drain:
            # opt-in SIGTERM→drain in the workers; unconditional install
            # would change plain `kill` semantics for unsupervised runs
            env["WORMHOLE_FT_DRAIN"] = "1"
        if heartbeat_dir:
            env["WORMHOLE_METRICS_EXPORT"] = heartbeat_dir
        if trace_dir:
            # workers trace into per-rank files under this directory
            # (obs.setup fallback); the launcher merges them at exit
            env["WORMHOLE_TRACE_EXPORT"] = trace_dir
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE)
        procs.append(p)
        tag = f"[w{i}] ".encode()
        for stream, sink in ((p.stdout, sys.stdout.buffer),
                             (p.stderr, sys.stderr.buffer)):
            t = threading.Thread(target=_pump_lines,
                                 args=(stream, sink, out_lock, tag),
                                 daemon=True)
            t.start()
            pumps.append(t)
        return p

    for i in range(n):
        _spawn(i, attempt)
    import time as _time
    rc = 0
    # live rejoin (supervisor.elastic == "rejoin"): a dead rank is
    # respawned into the still-running world instead of tearing the
    # whole job down for a relaunch
    rejoin_left = int(rejoin_budget) if (
        supervisor is not None
        and getattr(supervisor, "elastic", "") == "rejoin") else 0
    respawned: set = set()
    kill_at = None      # when SIGTERMed bystanders get SIGKILL
    try:
        # poll ALL ranks: as soon as any child dies nonzero, the rest are
        # wedged on collectives waiting for it — terminate them NOW so the
        # failed JOB exits promptly and a restart can rebuild the whole
        # mesh (SURVEY §5.3 recovery model; waiting on the jax
        # coordination-service heartbeat instead costs minutes)
        live = dict(enumerate(procs))  # rank -> proc
        last_scan = _time.monotonic()
        while live:
            for r, p in sorted(live.items()):
                code = p.poll()
                if code is None:
                    continue
                del live[r]
                if supervisor is not None:
                    supervisor.record_exit(r, code)
                if code != 0 and rejoin_left > 0 \
                        and supervisor is not None \
                        and supervisor.rejoinable(r):
                    # survivors keep running: respawn ONLY the dead rank
                    # (attempt+1 so chaos doesn't re-fire) and let it
                    # catch up via checkpoint + delta replay
                    rejoin_left -= 1
                    with out_lock:
                        sys.stderr.write(
                            f"[launcher] rank {r} lost (rc={code}); "
                            f"live rejoin — survivors keep running "
                            f"({rejoin_left} rejoin(s) left)\n")
                        sys.stderr.flush()
                    live[r] = _spawn(r, attempt + 1, rejoin=True)
                    respawned.add(r)
                    continue
                rc = rc or code   # first failure wins (terminated
                                  # bystanders exit -15 and must not
                                  # mask the originating code)
                if code != 0:
                    for q in live.values():
                        q.terminate()
                    if not drain and kill_at is None:
                        kill_at = _time.monotonic() + _TERM_GRACE_S
            if kill_at is not None and _time.monotonic() >= kill_at:
                # jax.distributed catches SIGTERM (its preemption
                # notifier), so a bystander that got one sits in its
                # shutdown barrier until the dead peer's heartbeat times
                # out, ~100 s later. Unsupervised ranks have no drain to
                # finish: past the grace, kill.
                for q in live.values():
                    if q.poll() is None:
                        q.kill()
                kill_at = None
            if respawned and supervisor is not None:
                # a respawned rank stays in the supervisor's dead set
                # (so the heartbeat scan doesn't SIGKILL it off its
                # STALE pre-death record) until fresh heartbeats show
                # up — or immediately when heartbeats aren't wired
                stale = set(supervisor.detector.check(heartbeat_dir)) \
                    if heartbeat_dir else set()
                for r in sorted(respawned):
                    if r in live and r not in stale:
                        supervisor.note_rejoined(r)
                        respawned.discard(r)
                        with out_lock:
                            sys.stderr.write(
                                f"[launcher] rank {r} rejoined "
                                f"(membership epoch "
                                f"{supervisor.epoch})\n")
                            sys.stderr.flush()
            now = _time.monotonic()
            if supervisor is not None and heartbeat_dir \
                    and now - last_scan >= 1.0:
                # a hung (not crashed) rank never exits on its own:
                # declare it dead on heartbeat silence and SIGKILL it,
                # which the loop above then handles like any crash
                last_scan = now
                for r in supervisor.scan_heartbeats(heartbeat_dir):
                    p = live.get(r)
                    if p is not None and p.poll() is None:
                        with out_lock:
                            sys.stderr.write(
                                f"[launcher] rank {r} heartbeat-silent > "
                                f"{supervisor.detector.dead_after_s:.0f}s; "
                                "declared dead, killing\n")
                            sys.stderr.flush()
                        p.kill()
            _time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for t in pumps:
            t.join(timeout=10)
        if monitor is not None:
            monitor.stop()
        if trace_dir:
            _merge_rank_traces(trace_dir, heartbeat_dir, out_lock)
        if heartbeat_dir:
            _merge_rank_timelines(heartbeat_dir, out_lock)
    return rc


def _merge_rank_traces(trace_dir: str, heartbeat_dir: str,
                       out_lock) -> None:
    """Exit-time aggregation: merge the ranks' trace files into one
    Perfetto doc + collective-skew report (obs/merge.py) and print the
    straggler attribution line. Best-effort — a merge failure must not
    change the job's exit code."""
    def emit(msg: str) -> None:
        with out_lock:
            sys.stderr.write(msg + "\n")
            sys.stderr.flush()

    try:
        from wormhole_tpu.obs import merge as _merge
        res = _merge.merge_run(trace_dir, heartbeat_dir)
        if res is None:
            emit(f"[launcher] no rank traces under {trace_dir}; "
                 "merge skipped")
            return
        merged_path, report = res
        emit(f"[launcher] merged trace: {merged_path} "
             f"({report['collectives_matched']} matched collectives, "
             f"report: {report['report_path']})")
        w = report.get("worst")
        if w:
            emit(f"[launcher] collective skew: w{w['rank']} last in "
                 f"{w['last_in']}/{w['of']} collectives, total "
                 f"lateness {w['lateness_ms']:.1f} ms")
    except Exception as e:
        emit(f"[launcher] trace merge failed: {e!r}")


def _merge_rank_timelines(heartbeat_dir: str, out_lock) -> None:
    """Exit-time aggregation of the ranks' timeline-sampler spills
    (host<rank>.timeline.jsonl, written when metrics_sample_itv_s > 0)
    onto one wall timeline via the heartbeat clock model
    (obs/merge.py). Best-effort and silent when no rank sampled."""
    def emit(msg: str) -> None:
        with out_lock:
            sys.stderr.write(msg + "\n")
            sys.stderr.flush()

    try:
        from wormhole_tpu.obs import merge as _merge
        res = _merge.merge_timelines(heartbeat_dir)
        if res is None:
            return
        path, report = res
        emit(f"[launcher] merged timeline: {path} "
             f"({report['samples']} samples from ranks "
             f"{report['ranks']}, clock: {report['clock_source']})")
    except Exception as e:
        emit(f"[launcher] timeline merge failed: {e!r}")


def launch_mp_supervised(n: int, cmd: List[str], restarts: int = 0,
                         heartbeat_dir: str = "",
                         straggler_factor: float = 3.0,
                         trace_dir: str = "", dead_after_s: float = 0.0,
                         elastic: str = "fixed",
                         comm_timeout_s: float = 0.0) -> int:
    """Supervised mp job: detection → drain → relaunch.

    Each attempt runs with the SIGTERM-drain protocol enabled and the
    supervisor watching heartbeats; on failure the world is relaunched
    (shrunk to the survivors under ``elastic="shrink"``) up to
    ``restarts`` times, resuming from the last committed checkpoint
    version. See docs/fault_tolerance.md for the state machine."""
    from wormhole_tpu.ft.supervisor import Supervisor
    sup = Supervisor(n, elastic=elastic, dead_after_s=dead_after_s)
    if elastic == "rejoin":
        # no stop-the-world: one launch, with the restarts budget spent
        # on per-rank respawns into the live world. A failure that
        # exhausts the budget (or isn't rejoinable) fails the job — the
        # caller opted out of whole-world relaunches.
        return launch_mp(sup.world, cmd, heartbeat_dir=heartbeat_dir,
                         straggler_factor=straggler_factor,
                         trace_dir=trace_dir, attempt=0,
                         supervisor=sup, comm_timeout_s=comm_timeout_s,
                         drain=True, rejoin_budget=restarts)
    attempt = 0
    while True:
        rc = launch_mp(sup.world, cmd, heartbeat_dir=heartbeat_dir,
                       straggler_factor=straggler_factor,
                       trace_dir=trace_dir, attempt=attempt,
                       supervisor=sup, comm_timeout_s=comm_timeout_s,
                       drain=True)
        if rc == 0 or attempt >= restarts:
            return rc
        dead = sorted(sup.dead)
        world = sup.plan_relaunch()
        attempt += 1
        print(f"[launcher] rank(s) {dead or 'unknown'} lost (rc={rc}); "
              f"supervised relaunch {attempt}/{restarts} with "
              f"world={world} ({elastic})", file=sys.stderr)


def launch_tpu(cmd: List[str]) -> int:
    # On a pod slice each host runs this identically; JAX's TPU runtime
    # discovers topology itself. Nothing to inject. The child is the ONE
    # process that takes this host's chips: the launcher imports jax but
    # never initializes a backend, so it holds none of them.
    return subprocess.call(cmd)


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        "wormhole-tpu launcher",
        description="dmlc tracker analogue for TPU/SPMD jobs")
    ap.add_argument("-n", "--num-devices", type=int, default=8,
                    help="virtual devices (sim) or processes (mp)")
    ap.add_argument("--cluster", choices=("sim", "mp", "tpu"), default="sim")
    ap.add_argument("--restarts", type=int, default=0,
                    help="relaunch a failed job up to K times (apps with "
                         "checkpoint_dir resume from the last version)")
    ap.add_argument("--heartbeat-dir", default="",
                    help="mp only: heartbeat/telemetry directory exported "
                         "to workers (WORMHOLE_METRICS_EXPORT); the "
                         "launcher watches it and warns on stragglers")
    ap.add_argument("--straggler-factor", type=float, default=3.0,
                    help="warn when a worker's ex/s falls below "
                         "median/FACTOR (with --heartbeat-dir)")
    ap.add_argument("--trace-dir", default="",
                    help="mp only: trace directory exported to workers "
                         "(WORMHOLE_TRACE_EXPORT); each rank traces "
                         "into it and the launcher merges the files at "
                         "exit into merged.trace.json + a collective "
                         "skew report")
    ap.add_argument("--ft-dead-after", type=float, default=0.0,
                    help="mp only: supervised fault tolerance — declare "
                         "a rank dead after S seconds of heartbeat "
                         "silence, SIGTERM-drain the survivors and "
                         "relaunch (uses the --restarts budget). 0 = "
                         "unsupervised (plain whole-job restarts)")
    ap.add_argument("--ft-elastic", choices=("fixed", "shrink", "rejoin"),
                    default="fixed",
                    help="supervised relaunch geometry: same world size "
                         "(fixed), shrink to the survivors, or rejoin — "
                         "survivors keep running and only the dead rank "
                         "is respawned into the live world (checkpoint "
                         "restore + delta replay; uses the --restarts "
                         "budget for per-rank respawns)")
    ap.add_argument("--comm-timeout", type=float, default=0.0,
                    help="mp only: exported collective watchdog timeout "
                         "(WORMHOLE_COMM_TIMEOUT_S) — a worker blocked "
                         "in a host collective longer than S seconds "
                         "exits with PEER_LOST instead of hanging")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- command to launch")
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given (append: -- python app.py ...)")
    if args.cluster == "mp" and (args.ft_dead_after > 0
                                 or args.ft_elastic == "rejoin"):
        return launch_mp_supervised(
            args.num_devices, cmd, restarts=args.restarts,
            heartbeat_dir=args.heartbeat_dir,
            straggler_factor=args.straggler_factor,
            trace_dir=args.trace_dir, dead_after_s=args.ft_dead_after,
            elastic=args.ft_elastic, comm_timeout_s=args.comm_timeout)
    run = {"sim": lambda a: launch_sim(args.num_devices, cmd),
           "mp": lambda a: launch_mp(args.num_devices, cmd,
                                     heartbeat_dir=args.heartbeat_dir,
                                     straggler_factor=args.straggler_factor,
                                     trace_dir=args.trace_dir,
                                     attempt=a,
                                     comm_timeout_s=args.comm_timeout),
           "tpu": lambda a: launch_tpu(cmd)}[args.cluster]
    rc = run(0)
    attempt = 0
    while rc != 0 and attempt < args.restarts:
        attempt += 1
        print(f"[launcher] job failed (rc={rc}); restart "
              f"{attempt}/{args.restarts} — checkpointed apps resume",
              file=sys.stderr)
        rc = run(attempt)
    return rc


if __name__ == "__main__":
    sys.exit(main())
