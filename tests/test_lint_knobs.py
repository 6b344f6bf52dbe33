"""The knob lint (scripts/lint_knobs.py) guards the PR-3 obs contract:
every Config field stays discoverable in docs/ (the reference table is
docs/config.md) and every literal metric name is declared at exactly one
site — two declarations of one name silently merge their streams."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "lint_knobs.py")


def _run(*args):
    return subprocess.run([sys.executable, SCRIPT, *args],
                          capture_output=True, text=True)


def _write_config(root, fields):
    pkg = root / "wormhole_tpu"
    (pkg / "utils").mkdir(parents=True, exist_ok=True)
    body = "".join(f"    {name}: int = 0\n" for name in fields)
    (pkg / "utils" / "config.py").write_text(
        "class Config:\n" + (body or "    pass\n"))


def test_repo_passes_lint():
    r = _run("--root", REPO)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_undocumented_knob_caught(tmp_path):
    _write_config(tmp_path, ["documented_knob", "secret_knob"])
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "config.md").write_text(
        "| `documented_knob` | 0 | a knob |\n")
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "secret_knob" in r.stderr
    assert "documented_knob" not in r.stderr


def test_word_boundary_not_substring(tmp_path):
    # `batch` mentioned only inside `minibatch` must not count as docs
    _write_config(tmp_path, ["batch"])
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "x.md").write_text("the minibatch knob\n")
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "batch" in r.stderr


def test_duplicate_metric_caught(tmp_path):
    _write_config(tmp_path, [])
    (tmp_path / "docs").mkdir()
    pkg = tmp_path / "wormhole_tpu"
    (pkg / "a.py").write_text('r.counter("steps_total")\n')
    (pkg / "b.py").write_text('reg.counter("steps_total")\n')
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "steps_total" in r.stderr
    assert "wormhole_tpu/a.py:1" in r.stderr
    assert "wormhole_tpu/b.py:1" in r.stderr


def test_computed_names_ignored(tmp_path):
    # adapter plumbing builds names at runtime; only literals are
    # declaration sites the uniqueness rule can reason about
    _write_config(tmp_path, [])
    (tmp_path / "docs").mkdir()
    pkg = tmp_path / "wormhole_tpu"
    (pkg / "a.py").write_text(
        'r.counter(prefix + "_seconds")\n'
        'r.counter(f"{prefix}_calls")\n'
        'r.gauge("ring_max", agg="max")\n')
    r = _run("--root", str(tmp_path))
    assert r.returncode == 0, r.stderr


def test_repo_metric_names_unique():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import lint_knobs
    finally:
        sys.path.pop(0)
    assert lint_knobs.duplicate_metrics(REPO) == {}
    # and the field extraction really sees the whole Config surface
    fields = lint_knobs.config_fields(REPO)
    assert "trace_path" in fields and "minibatch" in fields
    assert len(fields) >= 45


def test_encode_metrics_single_declaration_site():
    """The online tile-encode stage metrics (feed/encode_stall,
    feed/tile_fallback_blocks, feed/encode_native_blocks) are declared at
    exactly one site — obs/metrics.encode_counters and
    encode_native_counter; consumers must fetch them through those
    helpers, never re-declare the literals."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import lint_knobs
    finally:
        sys.path.pop(0)
    sites = lint_knobs.metric_sites(REPO)
    for name in ("feed/encode_stall", "feed/tile_fallback_blocks",
                 "feed/encode_native_blocks"):
        assert name in sites, name
        assert len(sites[name]) == 1, (name, sites[name])
        assert sites[name][0].startswith("wormhole_tpu/obs/metrics.py")
