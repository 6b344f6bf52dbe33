#!/usr/bin/env python
"""Regression gate over the ``BENCH_r*.json`` trajectory.

Each roadmap run snapshots ``bench.py`` results into ``BENCH_rNN.json``
(wrapper: ``{cmd, n, parsed, rc, tail}`` where ``parsed`` is the
headline ``{metric, value, unit, vs_baseline, extra}``). This gate walks
the trajectory in run order and fails (exit 1) when the newest run
regresses against its predecessor:

- **Throughput**: every numeric ``*ex_per_sec`` / ``*examples_per_sec``
  / ``*rows_per_sec`` key reachable through ``parsed`` (recursively
  through nested dicts, by dotted path) must not drop below
  ``prev * (1 - tol)``. Default ``--tol 0.25``: the real trajectory's
  worst benign run-to-run ratio is 0.834 (criteo_text r02→r03 and
  e2e_cold_stream r03→r04 — CPU-host noise), so 25% passes history
  while catching a halving.
- **Headline**: ``parsed.value`` is compared only when the two runs'
  ``metric`` names match (r01 reports ``ftrl_async_sgd_examples_per_sec``,
  later runs ``end_to_end_examples_per_sec`` — not comparable).
- **Latency** (lower is better): every numeric ``*p50_ms`` / ``*p99_ms``
  key (the serve phase's tail-latency SLO numbers) must not GROW above
  ``prev * (1 + tol)`` at the same dotted path — a p99 regression gates
  just like a throughput drop, with the inequality flipped.
- **Recovery debt** (absolute): the NEWEST run's ``*recovery_debt_s``
  values (rejoin phase: detection → rejoiner admitted) must stay under
  ``--max-recovery-debt`` — a ceiling, not a trend, because past the
  drill's group timeout the handshake is dead by definition.
- **Hierarchy wire** (absolute + trend): the NEWEST run's
  ``hierarchy.*_bytes_wire`` values must be > 0 (the cross-host leg
  ships real encoded bytes — a zero means the sweep measured nothing)
  and its ``hierarchy.*_wire_ratio`` values must clear
  ``--min-wire-ratio``; the same ratio keys also ride the pairwise
  ``--tol`` machinery (higher is better) so a codec that quietly stops
  compressing gates like a throughput drop.
- **Bigmodel paging** (absolute + trend): the NEWEST run's
  ``bigmodel.bytes_h2d`` must be > 0 (the cold tier paged real rows
  through the ring — zero means the phase never left the hot set) and
  ``bigmodel.bigmodel_over_dense`` must clear ``--min-bigmodel-ratio``;
  the same ratio also rides the pairwise ``--tol`` machinery (higher is
  better), so a paging path that quietly starts stalling the consumer
  gates like a throughput drop.
- **Serve fleet** (absolute + trend): the NEWEST run's
  ``serve_fleet.scaling_1to4`` (1->4 replica qps_at_slo ratio) must
  clear ``--min-fleet-scaling``, its snapshot plane must have shipped
  real bytes (``snapshot.bytes_wire`` > 0) with ``cadence_ratio``
  (full-checkpoint disk-poll bytes over delta wire bytes, same
  freshness cadence) above ``--min-snapshot-ratio``, and the 2x
  overload stage must have HELD the SLO (``overload.x2.p99_ms`` <=
  the run's own ``slo_ms``) — shedding exists precisely so that number
  survives overload. Every ``*qps_at_slo`` key also rides the pairwise
  ``--tol`` machinery (higher is better). ``serve_fleet.*`` latency
  keys are deliberately EXCLUDED from the p50/p99 trend gate: the
  absolute SLO ceiling gates them, and single-core sub-second stage
  tails jitter far beyond any useful ``--tol``. Under ``--slo`` the
  newest run's ``overload.x2.burn`` (phase-local serve_p99 tracker)
  must also stay under ``--max-burn`` — the shed controller engages
  inside the SLO band, so a burning budget at 2x overload means it
  failed its one job.
- **SLO timeline** (``--slo``, absolute): the NEWEST run's per-phase
  ``timeline`` blocks (bench.py ``--sample-itv`` sampler;
  ``obs/timeline.summarize``) must keep their first-vs-last-quartile
  ex/s drift under ``--max-drift`` and every declared SLO objective's
  burn rate under ``--max-burn``. A run with no timeline blocks is
  skipped with a note — absent telemetry is a tooling gap, not a
  violation.
- **Ledger fractions**: when both runs carry a ledger block (bench.py
  ``--out`` telemetry, ``{"ledger": {"frac": {...}}}`` anywhere under
  ``parsed``), the ``unattributed`` and ``residual_stall`` fractions may
  not grow by more than ``--tol-frac`` (absolute, default 0.10) at the
  same path — growth there means wall time leaked out of the accounted
  buckets.

The ``MULTICHIP_r*.json`` trajectory (``bench.py --phases multichip``
snapshots: per-mesh-shape ring/sync/anchor ex/s plus scaling
efficiency) is gated with the same machinery, plus two multichip-only
rules:

- **Scaling trend**: every numeric ``*scaling_efficiency`` key shared
  between consecutive usable runs is higher-is-better under ``--tol``,
  exactly like a throughput key.
- **Scaling floor**: the NEWEST usable run's ``*scaling_efficiency``
  values must each clear ``--min-scaling`` (absolute). The default is
  calibrated to the measured CPU fake-mesh trajectory, where all
  "devices" share the host cores so efficiency sits near ``1/n`` — a
  real multi-chip host clears it by an order of magnitude.

Runs that did not produce a result (``parsed`` null or ``rc != 0`` —
e.g. r05's rc=124 timeout, or the early MULTICHIP dryrun snapshots that
carry no ``parsed`` block at all) are skipped with a note: a crashed
run is the roadmap's problem, not a throughput regression, and must not
poison the comparison chain.

Usage::

    python scripts/bench_check.py                 # gate ./BENCH_r*.json
    python scripts/bench_check.py --dir runs/ --tol 0.2
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

_RATE_PAT = re.compile(r"(ex_per_sec|examples_per_sec|rows_per_sec)$")
# lower-is-better keys: serve-phase tail latencies. Deliberately NOT
# `*_ms$` — step_ms etc. are derived from the throughput keys already
# gated above, and double-gating one measurement would double the noise
# exposure.
_LAT_PAT = re.compile(r"(p50_ms|p99_ms)$")
_SCALE_PAT = re.compile(r"scaling_efficiency$")
_FUSED_PAT = re.compile(r"fused_over_split$")
_CACHED_PAT = re.compile(r"cached_over_fused$")
_DEBT_PAT = re.compile(r"recovery_debt_s$")
# hierarchy-phase wire keys, gated only under the hierarchy block (the
# comm_filters / async_ps phases carry same-named leaves with different
# semantics — their payloads are synthetic fixtures, not the 2D sweep)
_BYTES_WIRE_PAT = re.compile(r"bytes_wire$")
_WIRE_RATIO_PAT = re.compile(r"wire_ratio$")
# socket_wire-phase throughput keys (socket_delta_mbps, sim_delta_mbps,
# *_snapshot_mbps), gated only under the socket_wire block: higher is
# better, trend-gated pairwise like the ex/s rates so a socket OR sim
# path that quietly slows down trips the --tol gate
_MBPS_PAT = re.compile(r"_mbps$")
# bigmodel-phase keys, gated only under the bigmodel block (bytes_h2d
# also appears in raw feed stats with different semantics)
_BM_BYTES_PAT = re.compile(r"bytes_h2d$")
_BM_RATIO_PAT = re.compile(r"bigmodel_over_dense$")
# serve_fleet-phase keys, gated only under the serve_fleet block.
# qps_at_slo is a MAXIMUM over the swept offered rates whose merged
# fleet p99 held the SLO — higher is better, like a throughput key.
_QPS_SLO_PAT = re.compile(r"qps_at_slo$")
_LEDGER_FRACS = ("unattributed", "residual_stall")
# default --min-scaling: the measured CPU fake-8-device trajectory sits
# at 0.09-0.13 across the swept shapes (all "devices" share the host
# cores, so ~1/n is the honest ceiling); 0.05 passes that with headroom
# while catching a mesh feed that serializes outright (efficiency ->
# 1/n^2 territory)
_MIN_SCALING = 0.05
# absolute floor on the newest BENCH run's *fused_over_split ratio
# (bench.py --phases tile_fused, same-window interleaved): the fused
# one-grid step exists to beat the two calls it replaces, so on the
# TPU backend < 1.0 is a regression by definition. Re-baselined round
# 7 against the CPU host, where the forced fused path runs the Pallas
# interpreter and still measures 1.028 (median of interleaved passes)
# — 0.95 keeps single-core timing noise from flapping a 2.8% margin
# while catching a real fused-path slowdown; gate TPU runs at 1.0.
_MIN_FUSED_RATIO = 0.95
# absolute floor on the newest BENCH run's *cached_over_fused ratio
# (tile_fused phase, narrow-block cache-on vs cache-off A/B in the
# same interleaved windows). On the TPU backend the phase-shared
# one-hot cache exists to beat the per-phase rebuild it replaces, so
# < 1.0 there is a regression — gate TPU runs at 1.0. The CPU default
# is calibrated to the Pallas interpreter, where the staged planes are
# pure extra numpy work (no VMEM refetch to save): the narrow bench
# geometry measures ~0.08, so 0.05 passes the honest CPU number with
# headroom while still catching a cache path that wedges outright.
_MIN_CACHED_RATIO = 0.05
# the tile_fused phase's resolution records, gated as string PREFIXES
# on the newest run: round 8 widened the fused admissibility, so a
# spill view of the bench file and a wide&deep store must both resolve
# fused, and the cached A/B must run at a geometry whose cache the
# resolver's auto budget genuinely admits (a forced-past-budget cache
# would not compile on the TPU backend, so timing one proves nothing).
# Prefixes, not exact strings: any fused-family resolution passes, any
# split fails (the linear store's in-place FTRL variant records "fused"
# too, and says "in place" in the record's second field).
_TILE_RESOLUTION_EXPECT = {
    "resolved_kernel": "fused",
    "spill_resolved_kernel": "fused",
    "wd_resolved_kernel": "fused",
    "cache_record": "onehot_cache=on",
}
# absolute ceiling on the newest BENCH run's *recovery_debt_s (bench.py
# --phases rejoin: heartbeat detection -> rejoiner admitted, dominated
# on CPU by the rejoiner's checkpoint restore + first-window jit
# compiles). 60s passes the CPU-host cost with headroom while catching
# a replay path that wedges into its GroupTimeout (the drill's
# survivors wait 60s before declaring the handshake dead)
_MAX_RECOVERY_DEBT = 60.0
# absolute floor on the newest BENCH run's hierarchy.*_wire_ratio: the
# cross-host delta leg ships quant8+zlib, which measures ~4.2x on the
# swept dense bucket deltas; 2.0 passes that with headroom while
# catching a chain that silently degrades to the raw codec (ratio -> 1)
_MIN_WIRE_RATIO = 2.0
# absolute floor on the newest BENCH run's socket_wire.socket_delta_mbps
# (bench.py --phases socket_wire: 2-process loopback delta allreduce
# through the full quant8+zlib chain over real TCP sockets). The
# single-core CPU host measures ~55 MB/s raw-payload rate; 2.0 passes
# that with a wide margin while catching a wire that degrades to
# per-frame syscall lockstep or loses its encode/send overlap outright.
# A multi-core host with a real NIC should be gated far higher.
_MIN_SOCKET_MBPS = 2.0
# absolute floor on the newest BENCH run's bigmodel.bigmodel_over_dense
# (paged 16x-oversubscribed table vs the dense hot-size anchor, same
# batch geometry). The single-core CPU host measures ~0.58 with zero
# pipeline overlap available — 0.4 passes that with headroom while
# catching a paging path that collapses to synchronous fills. A real
# TPU host overlaps the host-side plan/page work under the device step
# and should be gated at ~0.8 (the ISSUE's within-20% target).
_MIN_BIGMODEL_RATIO = 0.4
# absolute floor on the newest BENCH run's serve_fleet.scaling_1to4
# (aggregate qps_at_slo at 4 replicas over 1 replica, same p99 SLO).
# On the single-core CPU host every replica thread shares one core, so
# adding replicas buys routing/batching overhead without buying
# compute — two clean runs measured 0.57/0.65. 0.4 passes that with
# headroom while catching a router or snapshot plane that serializes
# the fleet outright. A real multi-host fleet gets a core set per
# replica and should be gated at the ISSUE's 1.6x target.
_MIN_FLEET_SCALING = 0.4
# absolute floor on the newest BENCH run's serve_fleet
# snapshot.cadence_ratio (full-checkpoint disk-poll bytes over delta
# wire bytes at the same freshness cadence). Quant8 deltas on the
# benched FTRL store measure ~15x; 3.0 is the ISSUE's floor and
# catches a publisher that degrades to shipping full frames every
# version (ratio -> ~1 after framing overhead).
_MIN_SNAPSHOT_RATIO = 3.0
# --slo defaults: absolute gates over the newest run's per-phase
# `timeline` blocks (bench.py --sample-itv; obs/timeline.summarize).
# Drift is the first-vs-last-quartile ex/s decay WITHIN a phase — a
# 6-second CPU phase jitters hard, so 0.5 catches a halving without
# flagging warm-up noise; burn > 1.0 means an SLO error budget spends
# faster than its window by definition (obs/slo.py).
_MAX_DRIFT = 0.5
_MAX_BURN = 1.0


def load_runs(bench_dir: str,
              prefix: str = "BENCH") -> List[Tuple[str, Optional[dict]]]:
    """[(run_name, parsed-or-None)] in run order; None = skipped run."""
    out: List[Tuple[str, Optional[dict]]] = []
    for path in sorted(glob.glob(
            os.path.join(bench_dir, f"{prefix}_r*.json"))):
        name = os.path.splitext(os.path.basename(path))[0]
        try:
            with open(path, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"bench_check: {name}: unreadable ({e}); skipped")
            out.append((name, None))
            continue
        parsed = doc.get("parsed")
        rc = doc.get("rc", 0)
        if not isinstance(parsed, dict) or rc != 0:
            print(f"bench_check: {name}: no result (rc={rc}); skipped")
            out.append((name, None))
            continue
        out.append((name, parsed))
    return out


def _keys_matching(parsed: dict, pat: "re.Pattern") -> Dict[str, float]:
    """dotted-path -> value for every numeric key under ``parsed`` whose
    leaf name matches ``pat``. Paths (not bare leaf names) keep r02's
    ``e2e.ex_per_sec`` distinct from r03's
    ``e2e_steady_cached.ex_per_sec`` — different benchmarks, never
    compared. An ``attempts`` list (chaos phase: one entry per
    supervised relaunch) contributes only its LAST entry, at the stable
    path ``<p>.latest`` — earlier attempts end at an injected fault and
    their count varies run to run, so comparing them would be noise."""
    found: Dict[str, float] = {}

    def walk(node, path: str) -> None:
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            p = f"{path}.{k}" if path else k
            if k == "attempts" and isinstance(v, list):
                if v and isinstance(v[-1], dict):
                    walk(v[-1], f"{p}.latest")
            elif isinstance(v, dict):
                walk(v, p)
            elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and pat.search(k):
                found[p] = float(v)
    walk(parsed, "")
    return found


def rate_keys(parsed: dict) -> Dict[str, float]:
    """Throughput keys (higher is better) under ``parsed``."""
    return _keys_matching(parsed, _RATE_PAT)


def latency_keys(parsed: dict) -> Dict[str, float]:
    """Tail-latency keys (LOWER is better) under ``parsed``."""
    return _keys_matching(parsed, _LAT_PAT)


def scaling_keys(parsed: dict) -> Dict[str, float]:
    """Multichip ``*scaling_efficiency`` keys (higher is better)."""
    return _keys_matching(parsed, _SCALE_PAT)


def ledger_fracs(parsed: dict) -> Dict[str, float]:
    """dotted-path -> fraction for the gated ledger fractions found in
    any ``{"ledger": {"frac": {...}}}`` block under ``parsed``."""
    fracs: Dict[str, float] = {}

    def walk(node, path: str) -> None:
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            p = f"{path}.{k}" if path else k
            if k == "ledger" and isinstance(v, dict) \
                    and isinstance(v.get("frac"), dict):
                for name in _LEDGER_FRACS:
                    fv = v["frac"].get(name)
                    if isinstance(fv, (int, float)):
                        fracs[f"{p}.frac.{name}"] = float(fv)
            elif k == "attempts" and isinstance(v, list):
                # latest attempt only — same rule as _keys_matching
                if v and isinstance(v[-1], dict):
                    walk(v[-1], f"{p}.latest")
            elif isinstance(v, dict):
                walk(v, p)
    walk(parsed, "")
    return fracs


def compare(prev_name: str, prev: dict, cur_name: str, cur: dict,
            tol: float, tol_frac: float) -> List[str]:
    """Regression messages for one consecutive pair (empty = clean)."""
    bad: List[str] = []
    if prev.get("metric") == cur.get("metric") \
            and isinstance(prev.get("value"), (int, float)) \
            and isinstance(cur.get("value"), (int, float)):
        pv, cv = float(prev["value"]), float(cur["value"])
        if pv > 0 and cv < pv * (1.0 - tol):
            bad.append(
                f"headline {cur['metric']}: {cv:.1f} < "
                f"{pv:.1f} * {1 - tol:.2f} ({cur_name} vs {prev_name})")
    prates, crates = rate_keys(prev), rate_keys(cur)
    for key in sorted(set(prates) & set(crates)):
        pv, cv = prates[key], crates[key]
        if key == "value" or pv <= 0:
            continue   # headline handled above (metric-name guarded)
        if cv < pv * (1.0 - tol):
            bad.append(
                f"{key}: {cv:.1f} < {pv:.1f} * {1 - tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name})")
    plats, clats = latency_keys(prev), latency_keys(cur)
    for key in sorted(set(plats) & set(clats)):
        # serve_fleet latencies are gated by fleet_gate's ABSOLUTE SLO
        # ceiling instead: its sub-second per-level stages put single-
        # digit-ms tails at the mercy of scheduler jitter (measured
        # run-to-run ratios past 2x at the same offered rate), so a
        # pairwise --tol trend would flap on every clean trajectory
        if ".serve_fleet." in f".{key}.":
            continue
        pv, cv = plats[key], clats[key]
        if pv <= 0:
            continue
        if cv > pv * (1.0 + tol):
            bad.append(
                f"{key}: {cv:.1f}ms > {pv:.1f}ms * {1 + tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name}) — "
                "serve tail latency regression")
    pscale, cscale = scaling_keys(prev), scaling_keys(cur)
    for key in sorted(set(pscale) & set(cscale)):
        pv, cv = pscale[key], cscale[key]
        if pv <= 0:
            continue
        if cv < pv * (1.0 - tol):
            bad.append(
                f"{key}: {cv:.4f} < {pv:.4f} * {1 - tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name}) — "
                "multichip scaling efficiency regression")
    phr, chr_ = (hier_keys(prev, _WIRE_RATIO_PAT),
                 hier_keys(cur, _WIRE_RATIO_PAT))
    for key in sorted(set(phr) & set(chr_)):
        pv, cv = phr[key], chr_[key]
        if pv <= 0:
            continue
        if cv < pv * (1.0 - tol):
            bad.append(
                f"{key}: {cv:.2f} < {pv:.2f} * {1 - tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name}) — "
                "hierarchy wire compression regression")
    psk, csk = (socket_keys(prev, _MBPS_PAT),
                socket_keys(cur, _MBPS_PAT))
    for key in sorted(set(psk) & set(csk)):
        pv, cv = psk[key], csk[key]
        if pv <= 0:
            continue
        if cv < pv * (1.0 - tol):
            bad.append(
                f"{key}: {cv:.1f} < {pv:.1f} * {1 - tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name}) — "
                "socket/sim wire throughput regression")
    pbm, cbm = (bigmodel_keys(prev, _BM_RATIO_PAT),
                bigmodel_keys(cur, _BM_RATIO_PAT))
    for key in sorted(set(pbm) & set(cbm)):
        pv, cv = pbm[key], cbm[key]
        if pv <= 0:
            continue
        if cv < pv * (1.0 - tol):
            bad.append(
                f"{key}: {cv:.3f} < {pv:.3f} * {1 - tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name}) — "
                "bigmodel paged/dense ratio regression")
    pqs, cqs = (fleet_keys(prev, _QPS_SLO_PAT),
                fleet_keys(cur, _QPS_SLO_PAT))
    for key in sorted(set(pqs) & set(cqs)):
        pv, cv = pqs[key], cqs[key]
        if pv <= 0:
            continue
        if cv < pv * (1.0 - tol):
            bad.append(
                f"{key}: {cv:.1f} < {pv:.1f} * {1 - tol:.2f} "
                f"({cv / pv:.2f}x, {cur_name} vs {prev_name}) — "
                "serve fleet qps-at-SLO regression")
    pfracs, cfracs = ledger_fracs(prev), ledger_fracs(cur)
    for key in sorted(set(pfracs) & set(cfracs)):
        if cfracs[key] > pfracs[key] + tol_frac:
            bad.append(
                f"{key}: {cfracs[key]:.3f} > {pfracs[key]:.3f} + "
                f"{tol_frac:.2f} ({cur_name} vs {prev_name}) — wall "
                "time leaking out of accounted buckets")
    return bad


def scaling_floor(name: str, parsed: dict,
                  min_scaling: float) -> List[str]:
    """Absolute floor on the newest multichip run's scaling efficiency:
    trend gating alone would wave through a trajectory that decays
    within tolerance every round."""
    return [
        f"{key}: {v:.4f} < --min-scaling {min_scaling:.4f} ({name}) — "
        "multichip scaling efficiency below the absolute floor"
        for key, v in sorted(scaling_keys(parsed).items())
        if v < min_scaling]


def fused_ratio_keys(parsed: dict) -> Dict[str, float]:
    """``*fused_over_split`` ratio keys (tile_fused phase)."""
    return _keys_matching(parsed, _FUSED_PAT)


def fused_floor(name: str, parsed: dict, min_ratio: float) -> List[str]:
    """Absolute floor on the newest run's fused/split step ratio: the
    fused kernel replacing the split pair must not be slower than it
    (the measurement is same-window interleaved)."""
    return [
        f"{key}: {v:.3f} < --min-fused-ratio {min_ratio:.3f} ({name}) "
        "— fused tile step slower than the split oracle it replaces"
        for key, v in sorted(fused_ratio_keys(parsed).items())
        if v < min_ratio]


def cached_ratio_keys(parsed: dict) -> Dict[str, float]:
    """``*cached_over_fused`` ratio keys (tile_fused phase)."""
    return _keys_matching(parsed, _CACHED_PAT)


def cached_floor(name: str, parsed: dict, min_ratio: float) -> List[str]:
    """Absolute floor on the newest run's cached/fused step ratio: the
    one-hot cache replay must not fall below its backend's calibrated
    floor vs the rebuild it skips (same-window interleaved)."""
    return [
        f"{key}: {v:.3f} < --min-cached-ratio {min_ratio:.3f} ({name}) "
        "— one-hot cache replay below the floor vs the per-phase "
        "rebuild"
        for key, v in sorted(cached_ratio_keys(parsed).items())
        if v < min_ratio]


def tile_resolution_gate(name: str, parsed: dict) -> List[str]:
    """Absolute gate on the newest run's tile_fused resolution records:
    every :data:`_TILE_RESOLUTION_EXPECT` key found under a
    ``tile_fused`` block must carry its expected string — a spill view
    or wide&deep store resolving split means the round-8 admissibility
    widening regressed, and a cache record other than ``on`` means the
    cached A/B timed an inadmissible (or disabled) cache. Keys absent
    from the run (pre-round-8 snapshots) are skipped — the records are
    gated, not required retroactively."""
    bad: List[str] = []

    def walk(node, path: str) -> None:
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            p = f"{path}.{k}" if path else k
            if isinstance(v, dict):
                walk(v, p)
            elif isinstance(v, str) and k in _TILE_RESOLUTION_EXPECT \
                    and ".tile_fused" in f".{p}":
                want = _TILE_RESOLUTION_EXPECT[k]
                if not v.startswith(want):
                    bad.append(
                        f"{p}: {v!r} != {want!r} ({name}) — tile_fused "
                        "resolution record regressed")
    walk(parsed, "")
    return bad


def debt_keys(parsed: dict) -> Dict[str, float]:
    """``*recovery_debt_s`` keys (rejoin phase)."""
    return _keys_matching(parsed, _DEBT_PAT)


def debt_ceiling(name: str, parsed: dict, max_debt: float) -> List[str]:
    """Absolute ceiling on the newest run's rejoin recovery debt: a
    run-to-run relative gate would ratchet along with a slowly
    regressing replay path, and the quantity has a hard meaning — past
    the drill's group timeout the survivors give the rejoiner up."""
    return [
        f"{key}: {v:.1f}s > --max-recovery-debt {max_debt:.1f}s "
        f"({name}) — rejoin recovery debt above the absolute ceiling"
        for key, v in sorted(debt_keys(parsed).items())
        if v > max_debt]


def hier_keys(parsed: dict, pat: "re.Pattern") -> Dict[str, float]:
    """``_keys_matching`` restricted to paths under a ``hierarchy``
    block — the wire gates apply to the 2D sweep only."""
    return {p: v for p, v in _keys_matching(parsed, pat).items()
            if ".hierarchy." in f".{p}."}


def hier_wire_gate(name: str, parsed: dict,
                   min_ratio: float) -> List[str]:
    """Absolute gates on the newest run's hierarchy wire leg: measured
    bytes on every cross-host config, and a compression-ratio floor —
    both hard meanings, not trends (zero bytes = the sweep measured
    nothing; ratio -> 1 = the filter chain stopped compressing)."""
    bad = [
        f"{key}: {v:.0f} <= 0 ({name}) — hierarchy cross-host leg "
        "moved no measured wire bytes"
        for key, v in sorted(hier_keys(parsed, _BYTES_WIRE_PAT).items())
        if v <= 0]
    bad += [
        f"{key}: {v:.2f} < --min-wire-ratio {min_ratio:.2f} ({name}) "
        "— hierarchy wire compression below the absolute floor"
        for key, v in sorted(hier_keys(parsed, _WIRE_RATIO_PAT).items())
        if v < min_ratio]
    return bad


def socket_keys(parsed: dict, pat: "re.Pattern") -> Dict[str, float]:
    """``_keys_matching`` restricted to paths under a ``socket_wire``
    block — the socket gates apply to the loopback measurement only
    (the hierarchy block carries same-named wire leaves with SimBus
    semantics)."""
    return {p: v for p, v in _keys_matching(parsed, pat).items()
            if ".socket_wire." in f".{p}."}


def socket_wire_gate(name: str, parsed: dict,
                     min_mbps: float) -> List[str]:
    """Absolute gates on the newest run's socket_wire phase, both hard
    meanings rather than trends: zero wire bytes means the loopback
    processes exchanged nothing measurable (the phase's entire reason
    to exist is real cross-process bytes), and a delta-allreduce rate
    under the floor means the TCP path collapsed — lost overlap,
    per-frame syscall lockstep, or a wedged outbox."""
    bad = [
        f"{key}: {v:.0f} <= 0 ({name}) — socket wire moved no "
        "measured wire bytes"
        for key, v in sorted(
            socket_keys(parsed, _BYTES_WIRE_PAT).items())
        if v <= 0]
    blk = (parsed.get("extra") or {}).get("socket_wire")
    if isinstance(blk, dict):
        v = blk.get("socket_delta_mbps")
        if isinstance(v, (int, float)) and v < min_mbps:
            bad.append(
                f"socket_wire.socket_delta_mbps: {v:.2f} < "
                f"--min-socket-mbps {min_mbps:.2f} ({name}) — socket "
                "delta-allreduce throughput below the absolute floor")
        parity = blk.get("parity_tau0")
        if parity is not None and parity is not True:
            bad.append(
                f"socket_wire.parity_tau0: {parity!r} ({name}) — "
                "socket-vs-sim digests diverged at tau=0")
    return bad


def bigmodel_keys(parsed: dict, pat: "re.Pattern") -> Dict[str, float]:
    """``_keys_matching`` restricted to paths under a ``bigmodel``
    block — the paging gates apply to the cold-tier sweep only."""
    return {p: v for p, v in _keys_matching(parsed, pat).items()
            if ".bigmodel." in f".{p}."}


def bigmodel_gate(name: str, parsed: dict,
                  min_ratio: float) -> List[str]:
    """Absolute gates on the newest run's bigmodel phase: real paged
    bytes on the H2D leg (zero = the sweep never overflowed the hot
    set, so it measured nothing) and a floor on the paged/dense rate
    ratio — the cold tier's whole point is growing the bucket space
    without giving the throughput back."""
    bad = [
        f"{key}: {v:.0f} <= 0 ({name}) — bigmodel phase paged no "
        "measured H2D bytes through the ring"
        for key, v in sorted(bigmodel_keys(parsed, _BM_BYTES_PAT).items())
        if v <= 0]
    bad += [
        f"{key}: {v:.3f} < --min-bigmodel-ratio {min_ratio:.3f} "
        f"({name}) — paged/dense throughput below the absolute floor"
        for key, v in sorted(bigmodel_keys(parsed, _BM_RATIO_PAT).items())
        if v < min_ratio]
    return bad


def fleet_keys(parsed: dict, pat: "re.Pattern") -> Dict[str, float]:
    """``_keys_matching`` restricted to paths under a ``serve_fleet``
    block — the fleet gates apply to the replica sweep only."""
    return {p: v for p, v in _keys_matching(parsed, pat).items()
            if ".serve_fleet." in f".{p}."}


def _fleet_block(parsed: dict) -> Optional[dict]:
    """The newest run's ``serve_fleet`` summary block, if any."""
    blk = (parsed.get("extra") or {}).get("serve_fleet")
    return blk if isinstance(blk, dict) else None


def fleet_gate(name: str, parsed: dict, min_fleet_scaling: float,
               min_snapshot_ratio: float) -> List[str]:
    """Absolute gates on the newest run's serve_fleet phase. All hard
    meanings, not trends: replica scaling below the floor means the
    router/snapshot plane eats the added replicas; zero wire bytes
    means the delta plane shipped nothing; a cadence ratio near 1
    means the publisher degraded to full frames; and an overload p99
    above the run's own SLO means the shed controller failed the one
    scenario it exists for. A run whose block is missing a stage
    (budget-truncated) skips that stage's gate — the truncation is
    already visible in the summary."""
    blk = _fleet_block(parsed)
    if blk is None:
        return []
    bad: List[str] = []
    sc = blk.get("scaling_1to4")
    if isinstance(sc, (int, float)) and sc < min_fleet_scaling:
        bad.append(
            f"serve_fleet.scaling_1to4: {sc:.3f} < --min-fleet-scaling "
            f"{min_fleet_scaling:.3f} ({name}) — 1->4 replica "
            "qps-at-SLO scaling below the absolute floor")
    snap = blk.get("snapshot")
    if isinstance(snap, dict):
        bw = snap.get("bytes_wire")
        if isinstance(bw, (int, float)) and bw <= 0:
            bad.append(
                f"serve_fleet.snapshot.bytes_wire: {bw:.0f} <= 0 "
                f"({name}) — snapshot plane shipped no measured bytes")
        cr = snap.get("cadence_ratio")
        if isinstance(cr, (int, float)) and cr < min_snapshot_ratio:
            bad.append(
                f"serve_fleet.snapshot.cadence_ratio: {cr:.2f} < "
                f"--min-snapshot-ratio {min_snapshot_ratio:.2f} "
                f"({name}) — delta shipping not beating full-checkpoint "
                "polling at the same freshness cadence")
    slo_ms = blk.get("slo_ms")
    x2 = (blk.get("overload") or {}).get("x2")
    if isinstance(x2, dict) and isinstance(slo_ms, (int, float)):
        p99 = x2.get("p99_ms")
        if isinstance(p99, (int, float)) and p99 > slo_ms:
            bad.append(
                f"serve_fleet.overload.x2.p99_ms: {p99:.1f}ms > "
                f"slo_ms {slo_ms:.1f}ms ({name}) — served-traffic p99 "
                "broke the SLO at 2x overload despite shedding")
    return bad


def fleet_burn_gate(name: str, parsed: dict,
                    max_burn: float = _MAX_BURN) -> List[str]:
    """(--slo) ceiling on the serve_fleet 2x-overload burn rate: the
    phase arms a serve/p99_ms ceiling objective and samples it through
    an SLOTracker while the shed controller works — a burn above the
    ceiling means the controller held p99 down too late or not at
    all, spending the error budget faster than its window."""
    blk = _fleet_block(parsed)
    x2 = ((blk or {}).get("overload") or {}).get("x2")
    burn = x2.get("burn") if isinstance(x2, dict) else None
    if isinstance(burn, (int, float)) and burn > max_burn:
        return [
            f"serve_fleet.overload.x2.burn: {burn:.2f} > --max-burn "
            f"{max_burn:.2f} ({name}) — shed controller let the p99 "
            "error budget burn at 2x overload"]
    return []


def timeline_blocks(parsed: dict) -> Dict[str, dict]:
    """Dotted path -> per-phase ``timeline`` block (bench.py --out
    telemetry, ``{"timeline": {...}}`` anywhere under ``parsed``)."""
    out: Dict[str, dict] = {}

    def walk(node, path):
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            p = f"{path}.{k}" if path else str(k)
            if k == "timeline" and isinstance(v, dict):
                out[p] = v
            elif isinstance(v, dict):
                walk(v, p)

    walk(parsed, "")
    return out


def slo_gate(name: str, parsed: dict, max_drift: float = _MAX_DRIFT,
             max_burn: float = _MAX_BURN) -> List[str]:
    """Absolute SLO gate on the newest run's timeline blocks: in-phase
    ex/s quartile drift and per-objective burn rates (obs/slo.py). A
    run with no timeline blocks (sampler off, or a pre-timeline
    snapshot) is skipped with a note — absent telemetry is a tooling
    gap, not an SLO violation."""
    blocks = timeline_blocks(parsed)
    if not blocks:
        print(f"bench_check: {name}: no timeline blocks; "
              "--slo gate skipped")
        return []
    bad: List[str] = []
    for path, tl in sorted(blocks.items()):
        exs = tl.get("ex_per_sec")
        drift = exs.get("drift_frac") if isinstance(exs, dict) else None
        if isinstance(drift, (int, float)) and drift > max_drift:
            bad.append(
                f"{path}.ex_per_sec.drift_frac: {drift:.3f} > "
                f"--max-drift {max_drift:.3f} ({name}) — throughput "
                "decaying within the phase")
        for obj, row in sorted((tl.get("slo") or {}).items()):
            burn = row.get("burn") if isinstance(row, dict) else None
            if isinstance(burn, (int, float)) and burn > max_burn:
                bad.append(
                    f"{path}.slo.{obj}.burn: {burn:.2f} > --max-burn "
                    f"{max_burn:.2f} ({name}) — SLO error budget "
                    "spending faster than its window")
    return bad


def _gate_trajectory(prefix: str, bench_dir: str, tol: float,
                     tol_frac: float, all_pairs: bool,
                     min_scaling: float, min_fused_ratio: float,
                     max_recovery_debt: float, slo: bool = False,
                     min_cached_ratio: float = _MIN_CACHED_RATIO,
                     max_drift: float = _MAX_DRIFT,
                     max_burn: float = _MAX_BURN,
                     min_wire_ratio: float = _MIN_WIRE_RATIO,
                     min_bigmodel_ratio: float = _MIN_BIGMODEL_RATIO,
                     min_fleet_scaling: float = _MIN_FLEET_SCALING,
                     min_snapshot_ratio: float = _MIN_SNAPSHOT_RATIO,
                     min_socket_mbps: float = _MIN_SOCKET_MBPS
                     ) -> Tuple[List[str], int, int]:
    """(failures, pairs_compared, keys_compared) for one run prefix."""
    runs = [(n, p) for n, p in load_runs(bench_dir, prefix)
            if p is not None]
    failures: List[str] = []
    if prefix == "MULTICHIP" and runs:
        failures.extend(scaling_floor(*runs[-1], min_scaling))
    if prefix == "BENCH" and runs:
        failures.extend(fused_floor(*runs[-1], min_fused_ratio))
        failures.extend(cached_floor(*runs[-1], min_cached_ratio))
        failures.extend(tile_resolution_gate(*runs[-1]))
        failures.extend(debt_ceiling(*runs[-1], max_recovery_debt))
        failures.extend(hier_wire_gate(*runs[-1], min_wire_ratio))
        failures.extend(bigmodel_gate(*runs[-1], min_bigmodel_ratio))
        failures.extend(fleet_gate(*runs[-1], min_fleet_scaling,
                                   min_snapshot_ratio))
        failures.extend(socket_wire_gate(*runs[-1], min_socket_mbps))
        if slo:
            failures.extend(fleet_burn_gate(*runs[-1],
                                            max_burn=max_burn))
    if slo and runs:
        failures.extend(slo_gate(*runs[-1], max_drift=max_drift,
                                 max_burn=max_burn))
    if len(runs) < 2:
        print(f"bench_check: {len(runs)} usable {prefix} run(s) under "
              f"{bench_dir!r}; nothing to gate pairwise")
        return failures, 0, 0
    pairs = list(zip(runs, runs[1:])) if all_pairs else [runs[-2:]]
    compared = 0
    for (pn, pp), (cn, cp) in pairs:
        compared += len(set(rate_keys(pp)) & set(rate_keys(cp)))
        compared += len(set(latency_keys(pp)) & set(latency_keys(cp)))
        compared += len(set(scaling_keys(pp)) & set(scaling_keys(cp)))
        compared += len(set(fleet_keys(pp, _QPS_SLO_PAT))
                        & set(fleet_keys(cp, _QPS_SLO_PAT)))
        compared += len(set(socket_keys(pp, _MBPS_PAT))
                        & set(socket_keys(cp, _MBPS_PAT)))
        failures.extend(compare(pn, pp, cn, cp, tol, tol_frac))
    return failures, len(pairs), compared


def run(bench_dir: str, tol: float, tol_frac: float,
        all_pairs: bool = False, min_scaling: float = _MIN_SCALING,
        min_fused_ratio: float = _MIN_FUSED_RATIO,
        max_recovery_debt: float = _MAX_RECOVERY_DEBT,
        slo: bool = False,
        min_cached_ratio: float = _MIN_CACHED_RATIO,
        max_drift: float = _MAX_DRIFT,
        max_burn: float = _MAX_BURN,
        min_wire_ratio: float = _MIN_WIRE_RATIO,
        min_bigmodel_ratio: float = _MIN_BIGMODEL_RATIO,
        min_fleet_scaling: float = _MIN_FLEET_SCALING,
        min_snapshot_ratio: float = _MIN_SNAPSHOT_RATIO,
        min_socket_mbps: float = _MIN_SOCKET_MBPS) -> int:
    failures: List[str] = []
    pairs = compared = 0
    for prefix in ("BENCH", "MULTICHIP"):
        f, p, c = _gate_trajectory(prefix, bench_dir, tol, tol_frac,
                                   all_pairs, min_scaling,
                                   min_fused_ratio, max_recovery_debt,
                                   slo=slo,
                                   min_cached_ratio=min_cached_ratio,
                                   max_drift=max_drift,
                                   max_burn=max_burn,
                                   min_wire_ratio=min_wire_ratio,
                                   min_bigmodel_ratio=min_bigmodel_ratio,
                                   min_fleet_scaling=min_fleet_scaling,
                                   min_snapshot_ratio=min_snapshot_ratio,
                                   min_socket_mbps=min_socket_mbps)
        failures.extend(f)
        pairs += p
        compared += c
    if failures:
        print(f"bench_check: {len(failures)} regression(s):",
              file=sys.stderr)
        for msg in failures:
            print(f"  {msg}", file=sys.stderr)
        return 1
    print(f"bench_check: OK ({pairs} pair(s), {compared} shared "
          f"throughput/latency/scaling keys, tol {tol:.0%}, ledger tol "
          f"+{tol_frac:.2f}, scaling floor {min_scaling})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_r*.json (default: cwd)")
    ap.add_argument("--tol", type=float, default=0.25,
                    help="relative throughput drop tolerated vs the "
                         "previous run (default 0.25; history's worst "
                         "benign ratio is 0.834)")
    ap.add_argument("--tol-frac", type=float, default=0.10,
                    help="absolute growth tolerated in the ledger "
                         "unattributed/residual_stall fractions "
                         "(default 0.10)")
    ap.add_argument("--min-scaling", type=float, default=_MIN_SCALING,
                    help="absolute floor on the newest MULTICHIP run's "
                         "*scaling_efficiency values (default "
                         f"{_MIN_SCALING}; the CPU fake-mesh trajectory "
                         "measures ~1/n_devices)")
    ap.add_argument("--min-fused-ratio", type=float,
                    default=_MIN_FUSED_RATIO,
                    help="absolute floor on the newest BENCH run's "
                         "*fused_over_split ratio (default "
                         f"{_MIN_FUSED_RATIO}, CPU-calibrated: the "
                         "interpret-mode fused step measures 1.028 vs "
                         "split; gate TPU runs at 1.0 — the fused step "
                         "must not be slower than the split oracle)")
    ap.add_argument("--min-cached-ratio", type=float,
                    default=_MIN_CACHED_RATIO,
                    help="absolute floor on the newest BENCH run's "
                         "*cached_over_fused ratio (default "
                         f"{_MIN_CACHED_RATIO}, CPU-calibrated: the "
                         "interpret-mode cache replay measures ~0.08 "
                         "because the staged planes are pure extra "
                         "work there; gate TPU runs at 1.0 — the "
                         "cache must beat the rebuild it skips)")
    ap.add_argument("--max-recovery-debt", type=float,
                    default=_MAX_RECOVERY_DEBT,
                    help="absolute ceiling (seconds) on the newest "
                         "BENCH run's *recovery_debt_s (default "
                         f"{_MAX_RECOVERY_DEBT}; rejoin phase, "
                         "detection -> admission)")
    ap.add_argument("--min-wire-ratio", type=float,
                    default=_MIN_WIRE_RATIO,
                    help="absolute floor on the newest BENCH run's "
                         "hierarchy.*_wire_ratio values (default "
                         f"{_MIN_WIRE_RATIO}; quant8+zlib measures "
                         "~4.2x on the swept dense bucket deltas)")
    ap.add_argument("--min-bigmodel-ratio", type=float,
                    default=_MIN_BIGMODEL_RATIO,
                    help="absolute floor on the newest BENCH run's "
                         "bigmodel.bigmodel_over_dense (default "
                         f"{_MIN_BIGMODEL_RATIO}, calibrated to the "
                         "single-core CPU host; gate a real TPU host "
                         "at ~0.8)")
    ap.add_argument("--min-fleet-scaling", type=float,
                    default=_MIN_FLEET_SCALING,
                    help="absolute floor on the newest BENCH run's "
                         "serve_fleet.scaling_1to4 (default "
                         f"{_MIN_FLEET_SCALING}, calibrated to the "
                         "single-core CPU host where replicas share "
                         "one core; gate a real multi-host fleet at "
                         "the 1.6x target)")
    ap.add_argument("--min-snapshot-ratio", type=float,
                    default=_MIN_SNAPSHOT_RATIO,
                    help="absolute floor on the newest BENCH run's "
                         "serve_fleet snapshot.cadence_ratio (default "
                         f"{_MIN_SNAPSHOT_RATIO}; quant8 deltas on the "
                         "benched FTRL store measure ~15x)")
    ap.add_argument("--min-socket-mbps", type=float,
                    default=_MIN_SOCKET_MBPS,
                    help="absolute floor on the newest BENCH run's "
                         "socket_wire.socket_delta_mbps (default "
                         f"{_MIN_SOCKET_MBPS}, CPU-calibrated: the "
                         "single-core loopback host measures ~55 MB/s "
                         "raw-payload rate; gate a real NIC far higher)")
    ap.add_argument("--all-pairs", action="store_true",
                    help="gate every consecutive pair in the "
                         "trajectory, not just the newest one")
    ap.add_argument("--slo", action="store_true",
                    help="also gate the newest run's per-phase "
                         "`timeline` blocks: ex/s drift and SLO burn "
                         "rates (skipped with a note when the run "
                         "carries no timeline)")
    ap.add_argument("--max-drift", type=float, default=_MAX_DRIFT,
                    help="(--slo) ceiling on a phase's first-vs-last-"
                         "quartile ex/s decay fraction (default "
                         f"{_MAX_DRIFT})")
    ap.add_argument("--max-burn", type=float, default=_MAX_BURN,
                    help="(--slo) ceiling on any SLO objective's burn "
                         f"rate (default {_MAX_BURN}; > 1.0 spends the "
                         "error budget faster than its window)")
    args = ap.parse_args(argv)
    return run(args.dir, args.tol, args.tol_frac,
               all_pairs=args.all_pairs, min_scaling=args.min_scaling,
               min_fused_ratio=args.min_fused_ratio,
               max_recovery_debt=args.max_recovery_debt,
               slo=args.slo, min_cached_ratio=args.min_cached_ratio,
               max_drift=args.max_drift,
               max_burn=args.max_burn,
               min_wire_ratio=args.min_wire_ratio,
               min_bigmodel_ratio=args.min_bigmodel_ratio,
               min_fleet_scaling=args.min_fleet_scaling,
               min_snapshot_ratio=args.min_snapshot_ratio,
               min_socket_mbps=args.min_socket_mbps)


if __name__ == "__main__":
    sys.exit(main())
