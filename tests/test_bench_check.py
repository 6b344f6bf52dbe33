"""The bench regression gate (scripts/bench_check.py): green on a
steady BENCH_r*.json trajectory, red on an injected throughput
drop or a ledger fraction creeping up, and unparseable runs (crashed /
timed-out benches) are skipped rather than poisoning the chain."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_check.py")


def _run(*args):
    return subprocess.run([sys.executable, SCRIPT, *args],
                          capture_output=True, text=True)


def _write_run(d, n, parsed, rc=0):
    doc = {"n": n, "cmd": "bench", "rc": rc, "tail": [], "parsed": parsed}
    with open(os.path.join(d, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump(doc, f)


def _parsed(value, extra=None, metric="end_to_end_examples_per_sec"):
    p = {"metric": metric, "value": value, "unit": "examples/sec",
         "vs_baseline": 1.0}
    if extra:
        p["extra"] = extra
    return p


def test_trajectory_with_timed_out_run_passes(tmp_path):
    """The shape the repo's own record had: a renamed headline metric
    after the first run, steady runs, one that hit the harness time
    limit (rc=124, nothing parsed), and a run after it. Green in both
    the consecutive and the --all-pairs mode, with the dead run named
    as skipped and never compared."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(600_000_000.0,
                             metric="ftrl_async_sgd_examples_per_sec"))
    _write_run(d, 2, _parsed(12_000_000.0,
                             {"e2e": {"ex_per_sec": 12_000_000.0}}))
    _write_run(d, 3, _parsed(12_400_000.0,
                             {"e2e": {"ex_per_sec": 12_400_000.0}}))
    _write_run(d, 4, None, rc=124)
    _write_run(d, 5, _parsed(12_100_000.0,
                             {"e2e": {"ex_per_sec": 12_100_000.0}}))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "OK" in r.stdout
    assert "BENCH_r04" in r.stdout and "skipped" in r.stdout
    r2 = _run("--dir", d, "--all-pairs")
    assert r2.returncode == 0, r2.stderr + r2.stdout


def test_injected_throughput_regression_fails(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"criteo_text_examples_per_sec": 50_000.0}))
    _write_run(d, 2, _parsed(48_000.0,      # 52% drop: way past tol
                             {"criteo_text_examples_per_sec": 49_000.0}))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "end_to_end_examples_per_sec" in r.stderr
    # the healthy satellite metric is not reported
    assert "criteo_text" not in r.stderr


def test_nested_extra_rate_regression_fails(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"e2e": {"ex_per_sec": 100_000.0}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"e2e": {"ex_per_sec": 40_000.0}}))
    r = _run("--dir", d)
    assert r.returncode == 1
    assert "e2e.ex_per_sec" in r.stderr


def test_within_tolerance_passes(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0))
    _write_run(d, 2, _parsed(80_000.0))     # -20% < default 25% tol
    r = _run("--dir", d)
    assert r.returncode == 0, r.stderr
    # tightening the tolerance flips the verdict
    assert _run("--dir", d, "--tol", "0.1").returncode == 1


def test_metric_rename_not_compared(tmp_path):
    # r01's headline metric differs from later runs' — never compared
    d = str(tmp_path)
    _write_run(d, 1, _parsed(600_000_000.0,
                             metric="ftrl_async_sgd_examples_per_sec"))
    _write_run(d, 2, _parsed(76_000.0))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stderr


def test_crashed_run_skipped_and_chain_bridges(tmp_path):
    # r2 timed out (rc=124, parsed null): the gate compares r3 vs r1
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0))
    _write_run(d, 2, None, rc=124)
    _write_run(d, 3, _parsed(95_000.0))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stderr
    assert "BENCH_r02" in r.stdout and "skipped" in r.stdout
    # and a real drop across the bridge still fails
    _write_run(d, 3, _parsed(40_000.0))
    assert _run("--dir", d).returncode == 1


def test_ledger_fraction_creep_fails(tmp_path):
    d = str(tmp_path)
    led = lambda unattr: {"telemetry": {"e2e": {"ledger": {
        "frac": {"unattributed": unattr, "residual_stall": 0.02}}}}}
    _write_run(d, 1, _parsed(100_000.0, led(0.05)))
    _write_run(d, 2, _parsed(100_000.0, led(0.30)))   # +0.25 > 0.10
    r = _run("--dir", d)
    assert r.returncode == 1
    assert "unattributed" in r.stderr
    # inside tolerance: fine
    _write_run(d, 2, _parsed(100_000.0, led(0.12)))
    assert _run("--dir", d).returncode == 0


def test_fewer_than_two_runs_is_vacuous(tmp_path):
    assert _run("--dir", str(tmp_path)).returncode == 0
    _write_run(str(tmp_path), 1, _parsed(1.0))
    r = _run("--dir", str(tmp_path))
    assert r.returncode == 0
    assert "nothing to gate" in r.stdout


def test_real_trajectory_with_injected_drop_fails(tmp_path):
    """ISSUE acceptance: copy the real trajectory, append a run whose
    throughput keys are half the newest usable run's -> nonzero exit.
    The injected run DERIVES from the real newest run so the test
    tracks the trajectory as it grows (an earlier shape hardcoded the
    newest run's name and went stale — and mutating an old run can't
    work anyway: consecutive runs on different hosts deliberately
    share no rate keys)."""
    d = str(tmp_path)
    names = sorted(n for n in os.listdir(REPO)
                   if n.startswith("BENCH_r") and n.endswith(".json"))
    for n in names:
        shutil.copy(os.path.join(REPO, n), os.path.join(d, n))
    newest = None
    for n in reversed(names):
        doc = json.load(open(os.path.join(d, n)))
        if isinstance(doc.get("parsed"), dict) and doc.get("rc", 0) == 0:
            newest = (n, doc)
            break
    assert newest is not None, "no usable run in the real trajectory"
    name, doc = newest

    def halve(node):
        for k, v in list(node.items()):
            if isinstance(v, dict):
                halve(v)
            elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and k.endswith(("ex_per_sec", "examples_per_sec",
                                    "rows_per_sec", "_mbps")):
                node[k] = v / 2
    halve(doc["parsed"])
    if isinstance(doc["parsed"].get("value"), (int, float)):
        doc["parsed"]["value"] /= 2
    nxt = int(name[len("BENCH_r"):-len(".json")]) + 1
    json.dump(doc, open(os.path.join(d, f"BENCH_r{nxt:02d}.json"), "w"))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "regression" in r.stderr


def test_latency_regression_fails(tmp_path):
    # serve tail latencies gate LOWER-is-better: growth past tol fails
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"serve": {"solo": {"p99_ms": 8.0,
                                                 "p50_ms": 3.0}}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"serve": {"solo": {"p99_ms": 20.0,
                                                 "p50_ms": 3.1}}}))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "serve.solo.p99_ms" in r.stderr
    assert "tail latency" in r.stderr
    # the healthy p50 is not reported
    assert "p50_ms" not in r.stderr


def test_latency_within_tolerance_passes(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"serve": {"solo": {"p99_ms": 10.0}}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"serve": {"solo": {"p99_ms": 12.0}}}))
    r = _run("--dir", d)   # +20% < default 25% tol
    assert r.returncode == 0, r.stderr
    assert _run("--dir", d, "--tol", "0.1").returncode == 1


def test_latency_improvement_never_fails(tmp_path):
    # lower-is-better means a big DROP in latency is pure win
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"serve": {"solo": {"p99_ms": 50.0}}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"serve": {"solo": {"p99_ms": 5.0}}}))
    assert _run("--dir", d).returncode == 0


def test_attempts_list_gates_latest_only(tmp_path):
    """Chaos-phase ``attempts`` lists: only the LAST entry (the attempt
    that completed) is compared, at a stable ``.latest`` path — earlier
    attempts end at an injected fault and their count varies run to
    run."""
    d = str(tmp_path)

    def chaos_extra(final_rate, n_attempts):
        rows = [{"attempt": k, "ex_per_sec": 1.0}     # killed attempts
                for k in range(n_attempts - 1)]
        rows.append({"attempt": n_attempts - 1, "ex_per_sec": final_rate})
        return {"chaos_recovery": {"shrink": {"attempts": rows}}}

    # attempt counts differ (2 vs 3) and the killed attempts' garbage
    # rates differ — neither may gate; equal final rates pass
    _write_run(d, 1, _parsed(100_000.0, chaos_extra(5_000.0, 2)))
    _write_run(d, 2, _parsed(100_000.0, chaos_extra(5_000.0, 3)))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr
    # a real drop in the completed attempt still fails, at .latest
    _write_run(d, 2, _parsed(100_000.0, chaos_extra(1_000.0, 3)))
    r = _run("--dir", d)
    assert r.returncode == 1
    assert "chaos_recovery.shrink.attempts.latest.ex_per_sec" \
        in r.stderr, r.stderr


def _write_mc(d, n, parsed, rc=0):
    doc = {"n": n, "cmd": "bench --phases multichip", "rc": rc,
           "tail": "", "parsed": parsed}
    with open(os.path.join(d, f"MULTICHIP_r{n:02d}.json"), "w") as f:
        json.dump(doc, f)


def _mc_parsed(ring, sync, anchor=100_000.0, eff=None, n_dev=8):
    eff = ring / (anchor * n_dev) if eff is None else eff
    return {"n_devices": n_dev, "anchor_ex_per_sec": anchor,
            "shapes": {f"data:{n_dev}": {
                "ring_ex_per_sec": ring, "sync_ex_per_sec": sync,
                "ring_vs_sync": ring / sync,
                "speedup_vs_anchor": ring / anchor,
                "scaling_efficiency": eff}}}


def test_multichip_scaling_floor_gates_newest_run(tmp_path):
    # a single usable MULTICHIP run is enough for the absolute floor
    d = str(tmp_path)
    _write_mc(d, 1, _mc_parsed(120_000.0, 100_000.0, eff=0.01))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "scaling_efficiency" in r.stderr and "floor" in r.stderr
    # clearing the floor (default 0.05) passes; a raised floor fails
    _write_mc(d, 1, _mc_parsed(120_000.0, 100_000.0, eff=0.12))
    assert _run("--dir", d).returncode == 0
    assert _run("--dir", d, "--min-scaling", "0.5").returncode == 1


def test_multichip_rate_regression_fails(tmp_path):
    d = str(tmp_path)
    _write_mc(d, 1, _mc_parsed(120_000.0, 100_000.0, eff=0.12))
    _write_mc(d, 2, _mc_parsed(55_000.0, 100_000.0, eff=0.12))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "ring_ex_per_sec" in r.stderr
    # within tolerance: fine (and the BENCH trajectory stays vacuous)
    _write_mc(d, 2, _mc_parsed(110_000.0, 95_000.0, eff=0.11))
    assert _run("--dir", d).returncode == 0


def test_multichip_scaling_trend_regression_fails(tmp_path):
    # rates hold but efficiency collapses (anchor got faster): gated
    d = str(tmp_path)
    _write_mc(d, 1, _mc_parsed(120_000.0, 100_000.0, eff=0.40))
    _write_mc(d, 2, _mc_parsed(120_000.0, 100_000.0, eff=0.10))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "scaling efficiency regression" in r.stderr


def test_multichip_dryrun_snapshots_skipped_and_bridge(tmp_path):
    """The early MULTICHIP_r01..05 snapshots carry no ``parsed`` block
    (dryrun-era wrappers): skipped with a note, and the comparison
    chain bridges across them."""
    d = str(tmp_path)
    with open(os.path.join(d, "MULTICHIP_r01.json"), "w") as f:
        json.dump({"n_devices": 8, "rc": 0, "ok": True, "tail": "x"}, f)
    _write_mc(d, 2, _mc_parsed(100_000.0, 90_000.0, eff=0.12))
    _write_mc(d, 3, _mc_parsed(98_000.0, 91_000.0, eff=0.12))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MULTICHIP_r01" in r.stdout and "skipped" in r.stdout
    # a drop across the bridge still fails
    _write_mc(d, 3, _mc_parsed(40_000.0, 91_000.0, eff=0.12))
    assert _run("--dir", d).returncode == 1


def test_recovery_debt_ceiling_gates_newest_run(tmp_path):
    """*recovery_debt_s is an absolute ceiling on the newest run only —
    a single run is enough to trip it (no pair needed), and the flag
    relaxes it."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"rejoin": {"recovery_debt_s": 99.5,
                                         "rejoin_p99_ms": 40.0}}))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "recovery_debt_s" in r.stderr
    assert "--max-recovery-debt" in r.stderr
    r2 = _run("--dir", d, "--max-recovery-debt", "200")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_recovery_debt_under_ceiling_passes(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"rejoin": {"recovery_debt_s": 1.2}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"rejoin": {"recovery_debt_s": 8.0}}))
    # growth within the ceiling is NOT a regression (absolute gate,
    # deliberately not trend-gated — see debt_ceiling's docstring)
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr


def test_rejoin_p99_trend_gated_like_serve_latency(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"rejoin": {"rejoin_p99_ms": 50.0}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"rejoin": {"rejoin_p99_ms": 80.0}}))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "rejoin.rejoin_p99_ms" in r.stderr


# -- --slo: absolute timeline gate on the newest run -------------------------

def _timeline(drift=0.1, burn=0.4):
    return {"tile": {"timeline": {
        "samples": 40, "span_s": 20.0, "dropped_samples": 0,
        "ex_per_sec": {"first_q": 100.0, "last_q": 90.0,
                       "drift_frac": drift},
        "slo": {"rss_slope": {"series": "proc/rss_bytes",
                              "kind": "slope", "bound": 8.0,
                              "burn": burn, "violations": 0,
                              "samples": 40}}}}}


def test_slo_drift_violation_fails(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _timeline(drift=0.9)))
    r = _run("--dir", d, "--slo")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "tile.timeline.ex_per_sec.drift_frac" in r.stderr
    assert "--max-drift" in r.stderr
    # the knob relaxes the absolute ceiling
    assert _run("--dir", d, "--slo", "--max-drift",
                "0.95").returncode == 0


def test_slo_burn_violation_fails(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _timeline(burn=3.2)))
    r = _run("--dir", d, "--slo")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "tile.timeline.slo.rss_slope.burn" in r.stderr
    assert "--max-burn" in r.stderr


def test_slo_healthy_timeline_passes(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _timeline()))
    _write_run(d, 2, _parsed(99_000.0, _timeline(drift=0.2, burn=0.8)))
    r = _run("--dir", d, "--slo")
    assert r.returncode == 0, r.stdout + r.stderr
    # only the NEWEST run is gated: an old bad run doesn't fail now
    _write_run(d, 0, _parsed(100_000.0, _timeline(drift=0.9)))
    assert _run("--dir", d, "--slo").returncode == 0


def test_slo_missing_timeline_skipped_with_note(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0))     # pre-timeline snapshot
    r = _run("--dir", d, "--slo")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "--slo gate skipped" in r.stdout


def test_slo_off_by_default(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _timeline(drift=0.9, burn=9.0)))
    assert _run("--dir", d).returncode == 0


def _hier(ex=2_000_000.0, wire=1_250_000, ratio=4.2):
    return {"hierarchy": {"h2_d2m2_tau0_ex_per_sec": ex,
                          "h2_d2m2_tau0_bytes_wire": wire,
                          "h2_d2m2_tau0_wire_ratio": ratio}}


def test_hierarchy_zero_wire_bytes_fails(tmp_path):
    """The tentpole acceptance gate: the cross-host leg must MOVE
    measured bytes — a zero means the sweep exchanged nothing (e.g. a
    degenerate all-zero delta reducing to cache hits)."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _hier(wire=0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "moved no measured wire bytes" in r.stderr


def test_hierarchy_wire_ratio_floor_gates_newest_run(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _hier(ratio=1.1)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-wire-ratio" in r.stderr
    # the flag relaxes the floor, same machinery as the other absolutes
    r2 = _run("--dir", d, "--min-wire-ratio", "1.0")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_hierarchy_wire_ratio_trend_rides_tol(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _hier(ratio=4.2)))
    _write_run(d, 2, _parsed(100_000.0, _hier(ratio=2.1)))  # halved
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "wire compression regression" in r.stderr
    # within --tol the same pair passes
    r2 = _run("--dir", d, "--tol", "0.6")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_hierarchy_rate_keys_auto_gated(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _hier(ex=2_000_000.0)))
    _write_run(d, 2, _parsed(100_000.0, _hier(ex=900_000.0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "h2_d2m2_tau0_ex_per_sec" in r.stderr


def test_other_phase_wire_keys_not_hier_gated(tmp_path):
    """comm_filters / async_ps carry same-named *_bytes_wire /
    *_wire_ratio leaves on synthetic fixtures — the hierarchy floors
    must not reach outside the hierarchy block."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"comm_filters": {"bytes_wire": 0,
                                               "wire_ratio": 1.1},
                              "async_ps": {"tau0_wire_ratio": 1.05}}))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr


def _bigmodel(paged=540_000.0, dense=930_000.0, ratio=0.58,
              bytes_h2d=2_159_028):
    return {"bigmodel": {"bigmodel_ex_per_sec": paged,
                         "dense_anchor_ex_per_sec": dense,
                         "bigmodel_over_dense": ratio,
                         "bytes_h2d": bytes_h2d,
                         "bytes_d2h": 1_354_824}}


def test_bigmodel_zero_h2d_bytes_fails(tmp_path):
    """The paging acceptance gate: the cold tier must page real rows
    through the ring — zero H2D bytes means the sweep never overflowed
    the hot set and measured a plain dense run."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _bigmodel(bytes_h2d=0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "paged no measured H2D bytes" in r.stderr


def test_bigmodel_ratio_floor_gates_newest_run(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _bigmodel(ratio=0.2)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-bigmodel-ratio" in r.stderr
    # the flag relaxes the floor, same machinery as the other absolutes
    r2 = _run("--dir", d, "--min-bigmodel-ratio", "0.1")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_bigmodel_ratio_trend_rides_tol(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _bigmodel(ratio=0.9)))
    _write_run(d, 2, _parsed(100_000.0, _bigmodel(ratio=0.45)))  # halved
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "paged/dense ratio regression" in r.stderr
    # within --tol the same pair passes
    r2 = _run("--dir", d, "--tol", "0.6")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_bigmodel_rate_keys_auto_gated(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _bigmodel(paged=540_000.0)))
    _write_run(d, 2, _parsed(100_000.0, _bigmodel(paged=200_000.0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "bigmodel_ex_per_sec" in r.stderr


def test_other_phase_h2d_keys_not_bigmodel_gated(tmp_path):
    """Feed stats carry same-named bytes_h2d leaves with different
    semantics — the bigmodel floors must not reach outside the
    bigmodel block."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"e2e_stream": {"bytes_h2d": 0}}))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr


def _fleet(scaling=0.6, p99_x2=12.0, burn=0.0, cadence=15.1,
           bytes_wire=520_000, q1=29_000.0, q4=17_000.0):
    return {"serve_fleet": {
        "slo_ms": 25.0, "capacity_qps": 35_000.0,
        "scaling_1to4": scaling,
        "sweep": {"r1": {"capacity_qps": 35_000.0, "qps_at_slo": q1,
                         "p99_at_slo_ms": 7.4},
                  "r4": {"capacity_qps": 20_000.0, "qps_at_slo": q4,
                         "p99_at_slo_ms": 12.8}},
        "overload": {"x2": {"offered_qps": 47_000.0,
                            "achieved_qps": 43_000.0,
                            "shed_frac": 0.08, "shed_storms": 1,
                            "p99_ms": p99_x2, "burn": burn}},
        "snapshot": {"versions": 10, "delta_frames": 8, "full_frames": 2,
                     "bytes_wire": bytes_wire, "cadence_ratio": cadence,
                     "full_ckpt_bytes": 786_485}}}


def test_fleet_scaling_floor_gates_newest_run(tmp_path):
    # a single usable run is enough for the absolute floor
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _fleet(scaling=0.2)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-fleet-scaling" in r.stderr
    # the flag relaxes the floor, same machinery as the other absolutes
    r2 = _run("--dir", d, "--min-fleet-scaling", "0.1")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_fleet_snapshot_plane_gates(tmp_path):
    """The ISSUE acceptance gates on the snapshot plane: real wire
    bytes, and delta shipping beating full-checkpoint polling by the
    --min-snapshot-ratio floor at the same freshness cadence."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _fleet(bytes_wire=0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "shipped no measured bytes" in r.stderr
    _write_run(d, 1, _parsed(100_000.0, _fleet(cadence=1.2)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-snapshot-ratio" in r.stderr
    r2 = _run("--dir", d, "--min-snapshot-ratio", "1.0")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_fleet_overload_p99_gated_against_runs_own_slo(tmp_path):
    # the 2x-overload p99 is gated against the run's OWN slo_ms — the
    # whole point of shedding is holding that number under overload
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _fleet(p99_x2=40.0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "broke the SLO at 2x overload" in r.stderr
    _write_run(d, 1, _parsed(100_000.0, _fleet(p99_x2=24.0)))
    assert _run("--dir", d).returncode == 0


def test_fleet_burn_gated_under_slo_flag_only(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _fleet(burn=5.0)))
    # without --slo the burn number is informational
    assert _run("--dir", d).returncode == 0
    r = _run("--dir", d, "--slo")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "serve_fleet.overload.x2.burn" in r.stderr
    # healthy burn passes under --slo
    _write_run(d, 1, _parsed(100_000.0, _fleet(burn=0.0)))
    assert _run("--dir", d, "--slo").returncode == 0


def test_fleet_qps_at_slo_trend_rides_tol(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _fleet(q1=29_000.0)))
    _write_run(d, 2, _parsed(100_000.0, _fleet(q1=14_000.0)))  # halved
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "qps-at-SLO regression" in r.stderr
    # within --tol the same pair passes
    r2 = _run("--dir", d, "--tol", "0.6")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_fleet_latency_keys_excluded_from_trend(tmp_path):
    """serve_fleet p99 keys jitter past any useful --tol on sub-second
    CPU stages (measured >2x run to run at the same offered rate); they
    are gated by the ABSOLUTE SLO ceiling instead, so a 4x wobble that
    stays under slo_ms must not trip the pairwise latency trend."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _fleet(p99_x2=5.0)))
    _write_run(d, 2, _parsed(100_000.0, _fleet(p99_x2=20.0)))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr


def _tile(fused=1.03, cached=0.08, cache_rec="onehot_cache=on",
          spill="fused", wd="fused"):
    return {"tile_fused_vs_split": {
        "tile_fused_ex_per_sec": 9_600.0,
        "tile_split_ex_per_sec": 9_100.0,
        "tile_cached_ex_per_sec": 700.0,
        "tile_narrow_fused_ex_per_sec": 8_700.0,
        "fused_over_split": fused,
        "cached_over_fused": cached,
        "resolved_kernel": "fused",
        "cache_record": cache_rec,
        "spill_resolved_kernel": spill,
        "wd_resolved_kernel": wd}}


def test_fused_ratio_floor_gates_newest_run(tmp_path):
    # a single usable run is enough for the absolute floor
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _tile(fused=0.7)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-fused-ratio" in r.stderr
    # the flag relaxes the floor, same machinery as the other absolutes
    r2 = _run("--dir", d, "--min-fused-ratio", "0.5")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_cached_ratio_floor_gates_newest_run(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _tile(cached=0.01)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-cached-ratio" in r.stderr
    assert "one-hot cache replay below the floor" in r.stderr
    # the flag relaxes the floor; the CPU-calibrated default (0.05)
    # passes the honest interpret-mode measurement (~0.08)
    r2 = _run("--dir", d, "--min-cached-ratio", "0.005")
    assert r2.returncode == 0, r2.stdout + r2.stderr
    _write_run(d, 1, _parsed(100_000.0, _tile()))
    assert _run("--dir", d).returncode == 0


def test_tile_resolution_records_gated(tmp_path):
    """Round-8 admissibility acceptance: the spill view and the
    wide&deep store must record a fused resolution, and the cached A/B
    must run at a geometry whose cache auto genuinely admits; a
    pre-round-8 snapshot without the records is skipped, not failed."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _tile(spill="split")))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "spill_resolved_kernel" in r.stderr
    assert "resolution record regressed" in r.stderr
    _write_run(d, 1, _parsed(100_000.0, _tile(wd="split")))
    assert "wd_resolved_kernel" in _run("--dir", d).stderr
    _write_run(d, 1, _parsed(
        100_000.0, _tile(cache_rec="onehot_cache=off:forced off")))
    assert "cache_record" in _run("--dir", d).stderr
    # records absent entirely (old snapshot): skipped, not required
    blk = _tile()
    for k in ("resolved_kernel", "cache_record",
              "spill_resolved_kernel", "wd_resolved_kernel"):
        del blk["tile_fused_vs_split"][k]
    _write_run(d, 1, _parsed(100_000.0, blk))
    assert _run("--dir", d).returncode == 0


# -- socket_wire gates (bench.py --phases socket_wire) -----------------------

def _socket(delta=54.7, sim=46.6, wire=3_212_602, parity=True):
    return {"socket_wire": {"socket_delta_mbps": delta,
                            "sim_delta_mbps": sim,
                            "socket_snapshot_mbps": 120.0,
                            "sim_snapshot_mbps": 110.0,
                            "bytes_wire": wire,
                            "parity_tau0": parity}}


def test_socket_zero_wire_bytes_fails(tmp_path):
    """The phase's reason to exist is real cross-process bytes: a zero
    means the loopback children exchanged nothing measurable."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _socket(wire=0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "socket wire moved no measured wire bytes" in r.stderr


def test_socket_mbps_floor_gates_newest_run(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _socket(delta=0.5)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "--min-socket-mbps" in r.stderr
    # the flag relaxes the floor, same machinery as the other absolutes
    r2 = _run("--dir", d, "--min-socket-mbps", "0.1")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_socket_parity_divergence_fails(tmp_path):
    """tau=0 bit parity is the correctness witness: a socket-vs-sim
    digest mismatch is a codec/framing bug, never a perf question."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _socket(parity=False)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "diverged at tau=0" in r.stderr


def test_socket_mbps_trend_rides_tol(tmp_path):
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0, _socket(delta=54.7)))
    _write_run(d, 2, _parsed(100_000.0, _socket(delta=20.0)))
    r = _run("--dir", d)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "socket/sim wire throughput regression" in r.stderr
    # within --tol the same pair passes
    r2 = _run("--dir", d, "--tol", "0.7")
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_mbps_keys_outside_socket_block_not_gated(tmp_path):
    """Same-named *_mbps leaves under another phase block must not pick
    up the socket floor or trend — the gates read the socket_wire
    block only."""
    d = str(tmp_path)
    _write_run(d, 1, _parsed(100_000.0,
                             {"warmup": {"socket_delta_mbps": 54.7,
                                         "bytes_wire": 0}}))
    _write_run(d, 2, _parsed(100_000.0,
                             {"warmup": {"socket_delta_mbps": 0.5,
                                         "bytes_wire": 0}}))
    r = _run("--dir", d)
    assert r.returncode == 0, r.stdout + r.stderr
