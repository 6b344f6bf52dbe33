"""WH-HOSTSYNC: no hidden host syncs inside the ledger's hot loops.

JAX's async dispatch is the pipeline: the train loop stays ahead of
the device precisely because nothing on the hot path forces a
host round-trip. A stray ``np.asarray`` / ``.item()`` /
``float(np.asarray(...))`` / ``block_until_ready`` inside a loop the
step ledger attributes as ``device_compute`` or ``h2d_transfer``
serializes host and device and silently eats the overlap the ledger
then misattributes as compute.

Scope: the functions in :data:`HOT_PATHS` (rel path -> dotted
``Class.method`` / function names — the loops whose spans land in the
ledger's device_compute / h2d_transfer buckets). Every *deliberate*
sync there — windowed metric readbacks, completion gates — carries an
audited ``# host-sync: <why>`` marker on the line or the two lines
above; anything unmarked fails the build.

A scanned module may declare its own hot set with a module-level
``HOT_PATHS = ("func", "Class.method", ...)`` assignment (how fixture
trees opt in).

Flagged forms: ``jax.block_until_ready(x)`` / ``x.block_until_ready()``,
``jax.device_get``, ``.item()``, ``np.asarray``/``np.array`` of a
non-literal, ``float/int/bool(np.asarray(...))`` (counted once, at the
outer cast), and an ``if``/``while`` test calling ``jnp.*`` directly
(implicit device ``__bool__``).
"""

from __future__ import annotations

import ast
import re

from wormhole_tpu.analysis.engine import (Checker, FileContext,
                                          find_marker)

MARKER = "host-sync:"
_MARKER_PAT = re.compile(r"#\s*host-sync:")

# rel path -> dotted names of the hot loops. Each entry names the
# function whose trace spans the ledger folds into device_compute /
# h2d_transfer (SPAN_TABLE: dispatch/wait -> device_compute, put ->
# h2d_transfer): the sparse dispatch loops, the serve flush loop, and
# the forward hot path.
HOT_PATHS = {
    "wormhole_tpu/learners/async_sgd.py": (
        "AsyncSGD.process",
        "AsyncSGD._process_crec",
    ),
    # the crec passes' deferred metric fetches, batched or windowed,
    # which the pass loops above call from inside their wait scopes
    "wormhole_tpu/learners/window.py": (
        "MetricWindow.drain",
        "MetricWindow._fold_list",
        "MetricWindow._harvest_macc",
    ),
    "wormhole_tpu/serve/frontend.py": (
        "ServeFrontend._flush",
    ),
    "wormhole_tpu/serve/forward.py": (
        "ForwardStep.predict",
    ),
    # the bigmodel paging loop: tier moves run on the consumer thread
    # between device steps, so an unmarked sync here stalls the step
    # the paging was supposed to overlap
    "wormhole_tpu/bigmodel/paged.py": (
        "PagedStore.apply_plan",
        "PagedStore._resolve_pending",
        "PagedStore.flush",
        "PagedStore.stage_fresh",
    ),
    # the tile dispatch branches: one pallas dispatch per block under a
    # device_compute span (tilemm:fused_step / fused_cached /
    # fused_multi / mlp_phase) — an unmarked sync here serializes the
    # kernel stream the spans are supposed to measure
    "wormhole_tpu/learners/store.py": (
        "ShardedStore.tile_train_step",
    ),
    "wormhole_tpu/models/fm.py": (
        "FMStore.tile_train_step",
    ),
    "wormhole_tpu/models/wide_deep.py": (
        "WideDeepStore.tile_train_step",
    ),
}

_NP_NAMES = {"np", "numpy", "onp"}
_CASTS = {"float", "int", "bool"}


def _attr_tail(func) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def _is_np_materialize(node) -> bool:
    """np.asarray(x) / np.array(x) with a non-literal argument."""
    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("asarray", "array")
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in _NP_NAMES):
        return False
    if not node.args:
        return False
    return isinstance(node.args[0], (ast.Name, ast.Attribute,
                                     ast.Subscript, ast.Call))


def _inline_table(tree):
    for node in tree.body:
        if isinstance(node, ast.Assign) \
                and any(isinstance(t, ast.Name) and t.id == "HOT_PATHS"
                        for t in node.targets) \
                and isinstance(node.value, (ast.Tuple, ast.List,
                                            ast.Set)):
            return tuple(el.value for el in node.value.elts
                         if isinstance(el, ast.Constant)
                         and isinstance(el.value, str))
    return ()


def _hot_functions(tree, wanted):
    """Yield (dotted_name, FunctionDef) for the requested names."""
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in wanted:
            yield node.name, node
        elif isinstance(node, ast.ClassDef):
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) \
                        and f"{node.name}.{sub.name}" in wanted:
                    yield f"{node.name}.{sub.name}", sub


class HostSyncChecker(Checker):
    name = "hostsync"
    code = "WH-HOSTSYNC"

    def visit(self, ctx: FileContext) -> None:
        wanted = set(HOT_PATHS.get(ctx.rel, ()))
        if "HOT_PATHS" in ctx.raw:
            tree = ctx.tree
            if tree is None:
                return
            wanted.update(_inline_table(tree))
        if not wanted:
            return
        tree = ctx.tree
        if tree is None:
            return
        for dotted, func in _hot_functions(tree, wanted):
            self._scan(ctx, dotted, func)

    def _scan(self, ctx, dotted, func) -> None:
        skip = set()   # inner asarray nodes of a counted outer cast
        for node in ast.walk(func):
            if isinstance(node, ast.Call):
                if id(node) in skip:
                    continue
                tail = _attr_tail(node.func)
                if tail == "block_until_ready":
                    self._flag(ctx, node.lineno, dotted,
                               "block_until_ready")
                elif tail == "device_get":
                    self._flag(ctx, node.lineno, dotted, "device_get")
                elif tail == "item" and isinstance(node.func,
                                                   ast.Attribute) \
                        and not node.args:
                    self._flag(ctx, node.lineno, dotted, ".item()")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in _CASTS and node.args \
                        and _is_np_materialize(node.args[0]):
                    skip.add(id(node.args[0]))
                    self._flag(ctx, node.lineno, dotted,
                               f"{node.func.id}(np.asarray(...)) "
                               f"readback")
                elif _is_np_materialize(node):
                    self._flag(ctx, node.lineno, dotted,
                               "np.asarray/np.array materialization")
            elif isinstance(node, (ast.If, ast.While)):
                test = node.test
                if isinstance(test, ast.Call) \
                        and isinstance(test.func, ast.Attribute) \
                        and isinstance(test.func.value, ast.Name) \
                        and test.func.value.id == "jnp":
                    self._flag(ctx, test.lineno, dotted,
                               "implicit __bool__ on a device value")

    def _flag(self, ctx, line, dotted, what) -> None:
        if find_marker(ctx.raw_lines, line, _MARKER_PAT, above=2):
            return
        self.report(ctx.rel, line,
                    f"hidden host sync ({what}) inside hot path "
                    f"{dotted} — move it off the hot loop or audit it "
                    f"with `# {MARKER} <why>`")
