"""WH-SCATTER: no serialized scatter-adds outside the audited files.

Migrated from ``scripts/lint_scatters.py`` (now a shim over this
module). XLA:TPU lowers ``x.at[idx].add(v)`` to a serialized
per-element update loop, which is exactly the pathology ops/tilemm.py
and ops/histmm.py exist to avoid; this checker keeps the win from
regressing. Semantics, tables and legacy output are unchanged — see
the shim's original docstring (preserved in docs/static_analysis.md).
"""

from __future__ import annotations

import os
import re
import sys

from wormhole_tpu.analysis.engine import (Checker, Engine, FileContext,
                                          strip_comments)

# Audited files that legitimately keep `.at[...].add` sites. Every entry
# carries the reason the scatter is acceptable there. models/gbdt.py is
# deliberately ABSENT: its level-histogram scatters moved to ops/histmm
# (PR 2) and must not come back.
ALLOWLIST = {
    "wormhole_tpu/ops/spmv.py":
        "documented scatter fallback for the y = A^T x product; the "
        "matmul path is the default, this is the oracle",
    "wormhole_tpu/ops/tilemm.py":
        "COO overflow-bucket spill: O(overflow) elements, not O(nnz); "
        "the hot tile path is already a one-hot matmul. A long list of "
        "few buckets comes through the MXU and leaves ONE scatter of "
        "16,384 slots a hot tile (hot_grad_scatter). On channel "
        "planes: plane_spill_pull_rows (overflow pulls onto their rows) "
        "and spill_push_scatter_lanes (ONE scatter of O(overflow) lane "
        "rows into the tiled pushes, in place)",
    "wormhole_tpu/ops/histmm.py":
        "the scatter ORACLE kernels (_dense_scatter/_sparse_scatter) "
        "that the matmul kernels are parity-tested against",
    "wormhole_tpu/solver/lbfgs.py":
        "two-loop recursion history update: O(lbfgs_memory) ~ 10 "
        "elements, nothing to vectorize",
    "wormhole_tpu/models/kmeans.py":
        "per-cluster count/weight stats: O(clusters) cells, dominated "
        "by the distance matmul",
}

# Files whose scatters are live RUNTIME fallbacks — every `.at[...].add`
# site here must carry a `scatter-fallback:` comment (same line or the
# two lines above) saying why that particular scatter stays.
ANNOTATED = {
    "wormhole_tpu/learners/store.py":
        "uniq-key push, v1 dense-apply grad, overflow spills",
    "wormhole_tpu/models/fm.py":
        "uniq-key push + tile overflow spill",
    "wormhole_tpu/models/wide_deep.py":
        "uniq-key push + the mesh step's overflow spill (the one-device "
        "tile step's are ops/tilemm.py's helpers on planes)",
}

# the in-source audit marker required at each scatter site in ANNOTATED
# files (comment text, so it survives comment-stripping only in raw form)
MARKER = "scatter-fallback:"

# `.at[` ... `].add(` with the subscript allowed to span lines; targets
# only scatter-ADD — set/max/min/mul variants have different lowering
# and are not what tilemm/histmm replace.
_PAT = re.compile(r"\.at\s*\[[^\]]*\]\s*\.add\s*\(", re.S)

_strip_comments = strip_comments


def _scan_text(code: str) -> list:
    return [code.count("\n", 0, m.start()) + 1
            for m in _PAT.finditer(code)]


def _unannotated(raw_lines: list, lines: list) -> list:
    out = []
    for ln in lines:
        window = raw_lines[max(ln - 3, 0):ln]
        if not any(MARKER in w for w in window):
            out.append(ln)
    return out


def scan_file(path: str) -> list:
    """Return 1-based line numbers of scatter-add sites in ``path``."""
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        return _scan_text(strip_comments(f.read()))


def unannotated_sites(path: str, lines: list) -> list:
    """Scatter sites (1-based line numbers) lacking the ``MARKER``
    comment on the same line or within the two preceding lines."""
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        return _unannotated(f.read().splitlines(), lines)


class ScatterChecker(Checker):
    name = "scatters"
    code = "WH-SCATTER"

    def __init__(self, root: str) -> None:
        super().__init__(root)
        self.violations: list = []      # "rel:line"
        self.unannotated: list = []     # "rel:line"
        self.seen_allowed: set = set()

    def visit(self, ctx: FileContext) -> None:
        lines = _scan_text(ctx.code)
        if not lines:
            return
        if ctx.rel in ANNOTATED:
            self.seen_allowed.add(ctx.rel)
            for ln in _unannotated(ctx.raw_lines, lines):
                self.unannotated.append(f"{ctx.rel}:{ln}")
                self.report(ctx.rel, ln,
                            f"runtime-fallback scatter without a "
                            f"`{MARKER}` audit comment")
        elif ctx.rel in ALLOWLIST:
            self.seen_allowed.add(ctx.rel)
        else:
            for ln in lines:
                self.violations.append(f"{ctx.rel}:{ln}")
                self.report(ctx.rel, ln,
                            "serialized scatter-add (`.at[...].add`) "
                            "outside the allowlist")

    def finish(self) -> None:
        stale = (set(ALLOWLIST) | set(ANNOTATED)) - self.seen_allowed
        for rel in sorted(stale):
            self.warnings.append(
                f"lint_scatters: allowlist entry {rel} has no "
                f"scatter-adds (stale?)")

    def ok_line(self) -> str:
        return (f"{self.name}: OK ({len(self.seen_allowed)} audited "
                f"files, {len(ANNOTATED)} annotated)")

    # -- legacy shim surface -------------------------------------------

    def legacy_report(self, out=None, err=None) -> int:
        out = out or sys.stdout
        err = err or sys.stderr
        for w in self.warnings:
            print(w, file=err)
        if self.violations:
            print("lint_scatters: serialized scatter-add "
                  "(`.at[...].add`) outside the allowlist:", file=err)
            for v in self.violations:
                print(f"  {v}", file=err)
            print("either reformulate as a one-hot matmul (see "
                  "ops/histmm.py / ops/tilemm.py) or add the file to "
                  "ALLOWLIST in scripts/lint_scatters.py with a reason",
                  file=err)
        if self.unannotated:
            print("lint_scatters: runtime-fallback scatter without a "
                  f"`{MARKER}` audit comment (same line or the two "
                  "lines above):", file=err)
            for v in self.unannotated:
                print(f"  {v}", file=err)
            print("these files carry live scatter fallbacks (the "
                  "online tile-encode overflow route); each site must "
                  "say why it stays a scatter", file=err)
        if self.violations or self.unannotated:
            return 1
        print(f"lint_scatters: OK ({len(self.seen_allowed)} audited "
              f"files, {len(ANNOTATED)} annotated)", file=out)
        return 0


def run(root: str) -> int:
    """Scan ``root``/wormhole_tpu for violations; return a process rc."""
    pkg = os.path.join(root, "wormhole_tpu")
    if not os.path.isdir(pkg):
        print(f"lint_scatters: no wormhole_tpu package under {root!r}",
              file=sys.stderr)
        return 2
    chk = ScatterChecker(root)
    Engine(root, [chk]).run()
    return chk.legacy_report()
