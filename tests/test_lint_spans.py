"""The span lint (scripts/lint_spans.py) extends the lint_knobs contract
to trace spans: every instrumentation-site span name resolves through
the central SPAN_TABLE in wormhole_tpu/obs/ledger.py (declared exactly
once, no duplicate keys) — a renamed span that silently falls out of
the step ledger's buckets is a lint failure, not an attribution hole."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "lint_spans.py")


def _run(*args):
    return subprocess.run([sys.executable, SCRIPT, *args],
                          capture_output=True, text=True)


def _write_tree(root, ledger_body, extra=None):
    pkg = root / "wormhole_tpu"
    (pkg / "obs").mkdir(parents=True, exist_ok=True)
    (pkg / "obs" / "ledger.py").write_text(ledger_body)
    for name, body in (extra or {}).items():
        (pkg / name).write_text(body)


TABLE = ('SPAN_TABLE = {"dispatch": "device_compute",\n'
         '              "collective:allreduce_*": "collective_wait",\n'
         '              "put": "h2d_transfer"}\n')


def test_repo_passes_lint():
    r = _run("--root", REPO)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_undeclared_span_caught(tmp_path):
    _write_tree(tmp_path, TABLE, {
        "a.py": 'with tm.scope("dispatch"): pass\n'
                'with tm.scope("renamed_stage"): pass\n'})
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "renamed_stage" in r.stderr
    assert "wormhole_tpu/a.py:2" in r.stderr
    assert "dispatch" not in r.stderr


def test_prefix_patterns_and_rules_resolve(tmp_path):
    _write_tree(tmp_path, TABLE, {
        "a.py": 'trace.span(f"collective:allreduce_{op}")\n'
                'trace.span("collective:allreduce_sum")\n'
                'with tm.scope("eval_dispatch"): pass\n'
                'trace.span("ring_stall")\n'              # _stall rule
                'with tm.scope(pfx + "put"): pass\n'})     # prefixed literal
    r = _run("--root", str(tmp_path))
    assert r.returncode == 0, r.stderr


def test_unmatched_fstring_prefix_caught(tmp_path):
    _write_tree(tmp_path, TABLE, {
        "a.py": 'trace.span(f"mystery:{kind}")\n'})
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "mystery:" in r.stderr


def test_duplicate_table_key_caught(tmp_path):
    _write_tree(tmp_path,
                'SPAN_TABLE = {"dispatch": "device_compute",\n'
                '              "dispatch": "other"}\n')
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "duplicate" in r.stderr and "dispatch" in r.stderr


def test_second_declaration_site_caught(tmp_path):
    _write_tree(tmp_path, TABLE, {"rogue.py": 'SPAN_TABLE = {}\n'})
    r = _run("--root", str(tmp_path))
    assert r.returncode == 1
    assert "2 sites" in r.stderr
    assert "wormhole_tpu/rogue.py:1" in r.stderr


def test_lint_mirrors_runtime_resolution():
    """The lint's local resolver and the runtime span_bucket must agree
    on every span name the lint extracts from the real tree — otherwise
    a green lint could still mean a dead ledger bucket."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import lint_spans
    finally:
        sys.path.pop(0)
    from wormhole_tpu.obs.ledger import span_bucket
    keys, dups, sites = lint_spans.span_table(REPO)
    assert dups == [] and len(sites) == 1
    for (name, is_prefix), where in lint_spans.span_sites(REPO).items():
        if is_prefix:
            continue                      # prefix stems, not full names
        assert lint_spans._resolves(name, False, keys) \
            == (span_bucket(name) is not None), (name, where)
        assert span_bucket(name) is not None, (name, where)
