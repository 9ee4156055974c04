"""Host-sync lint: device↔host synchronization stays off the hot path.

A jax dispatch is asynchronous — the caller gets a future-like array and
keeps marshalling the next batch while the device executes.  Any operation
that MATERIALIZES a device value (``block_until_ready``, ``.item()``,
``jax.device_get``, ``np.asarray``/``float()`` on a device array, or the
verdict helpers ``fe_is_one``/``fq2_from_limbs``) stalls the calling thread
for the full device round trip.  The architecture confines those stalls to
three sanctioned places — the device supervisor's watchdog worker (which
exists precisely to absorb them), the async pipeline's executor leg (which
runs ON that worker via the supervisor), and the bench harness — so block
import, the scheduler workers and the pipeline *builder* never block inside
a device sync.  PR 8's pipeline win (caller wait p50 60 s → 6 s) is exactly
this discipline; one stray sync in the builder thread silently re-opens it.

Mechanics:

- **always-sync primitives** — ``block_until_ready``, ``.item()``,
  ``jax.device_get`` — are flagged wherever they appear in the scan dirs;
- **conditional wrappers** — ``np.asarray``/``np.array``, ``float``/
  ``int``/``bool``, ``fe_is_one``, ``fq2_from_limbs``/``fq12_from_limbs``/
  ``from_limbs16`` — are flagged only when fed a *device-tainted* value: a
  local assigned (directly or transitively) from a call to a known-jitted
  callable (the module's own jitted defs plus the ``ops/batch_axes.py``
  registry entries).  Host-side marshalling (``np.asarray`` over limb
  tables) stays quiet.  A sync call launders its result back to host: the
  assigned name is untainted afterwards.
- findings inside a **sanctioned context** (the committed
  ``SANCTIONED_CONTEXTS`` registry below) are classified, counted, and NOT
  violations; everything else is a ``hot-path-sync`` violation — fix it,
  pragma it (``# host-sync: ok(<reason>)``) or, for pre-existing debt,
  baseline it.

Taint is per-function (same single-level discipline as the other passes):
a device value returned through a helper boundary is not followed —
documented in ANALYSIS.md.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .common import (
    PragmaIndex,
    Violation,
    dotted_path,
    is_jit_decorator,
    iter_py_files,
    load_batch_axes,
    local_jit_names,
    parse_file,
    terminal_name,
)

PASS = "host-sync"

SCAN_DIRS = (
    "lighthouse_tpu/ops",
    "lighthouse_tpu/device_mesh.py",
    "lighthouse_tpu/device_pipeline.py",
    "lighthouse_tpu/device_supervisor.py",
    "lighthouse_tpu/device_telemetry.py",
    # Self-tuning controller (ISSUE 15): reads telemetry rings and JSON
    # files HOST-side only — zero device syncs, enforced here (the
    # device-touching legs live in ops/compile_cache.py and ops/fq.py
    # under their own sanctioned contexts).
    "lighthouse_tpu/autotune.py",
    # Incident black box (ISSUE 17): capture/snapshot runs on FAILURE
    # paths, often while a device op is wedged — it must never
    # materialize a device value (SCAN_DIRS rot fix, ISSUE 18 satellite).
    "lighthouse_tpu/blackbox.py",
    # Node-scoped telemetry (ISSUE 19): journal/flight/log mirrors ride
    # failure and gossip hot paths — host-side plumbing only, like
    # blackbox.
    "lighthouse_tpu/telemetry_scope.py",
    "bench.py",
)

#: Attribute/name calls that ALWAYS synchronize with the device.
ALWAYS_SYNC = frozenset({"block_until_ready", "item", "device_get"})

#: Calls that synchronize when fed a device value.
SYNC_WRAPPERS = frozenset({
    "asarray", "array", "float", "int", "bool",
    "fe_is_one", "fq2_from_limbs", "fq12_from_limbs", "from_limbs16",
})

#: The sanctioned sync points: context prefixes per file.  These run on the
#: supervisor's watchdog worker (the device supervisor re-runs the device_fn
#: there — a hung sync strands the worker, never the caller) or inside the
#: bench harness.  ``"*"`` sanctions a whole file.  The async pipeline's
#: builder/executor threads are deliberately NOT here: the executor syncs
#: only THROUGH ops/verify.execute_built_batch (supervised), and the
#: builder must never sync at all.
SANCTIONED_CONTEXTS: Dict[str, Tuple[str, ...]] = {
    # the device leg of every bucketed op (dispatch, wait, verdict): it
    # runs on the supervisor's watchdog worker, or, for the epoch family,
    # inside the device_fn its caller supervises (per_epoch.py)
    "lighthouse_tpu/ops/dispatch.py": ("execute",),
    # the autotune fq A/B probe: runs on the supervisor's autotune_probe
    # watchdog worker (autotune.measure_fq_backend wraps it) — timing the
    # dispatch IS its job
    "lighthouse_tpu/ops/fq.py": ("measure_backend_seconds",),
    # the bench harness measures the device; blocking is its job
    "bench.py": ("*",),
}


def _sync_wrapper_name(call: ast.Call) -> Optional[str]:
    """The wrapper primitive this call is, or None.  ``asarray``/``array``
    count only for numpy (``jnp.asarray`` of a device value is a no-op, not
    a sync)."""
    name = terminal_name(call.func)
    if name not in SYNC_WRAPPERS:
        return None
    if name in ("asarray", "array") and isinstance(call.func, ast.Attribute):
        root = (dotted_path(call.func) or "").split(".")[0]
        if root not in ("np", "numpy"):
            return None
    return name


def _sanctioned(rel_path: str, context: str) -> bool:
    for prefix in SANCTIONED_CONTEXTS.get(rel_path, ()):
        if prefix == "*" or context == prefix or context.startswith(prefix + "."):
            return True
    return False


class _SyncAuditor(ast.NodeVisitor):
    """Single-pass walk of one outermost function: tracks device-tainted
    locals and collects every sync site with its classification."""

    def __init__(self, rel_path: str, pragmas: PragmaIndex,
                 jit_names: Set[str]):
        self.rel_path = rel_path
        self.pragmas = pragmas
        self.jit_names = jit_names
        self.tainted: Set[str] = set()
        self.scope: List[str] = []
        #: (Violation, sanctioned) pairs — classify() splits them.
        self.sites: List[Tuple[Violation, bool]] = []

    @property
    def context(self) -> str:
        return ".".join(self.scope) if self.scope else "<module>"

    def _record(self, node: ast.AST, primitive: str) -> None:
        if self.pragmas.suppresses(PASS, node):
            return
        ctx = self.context
        sanctioned = _sanctioned(self.rel_path, ctx)
        code = "sanctioned-sync" if sanctioned else "hot-path-sync"
        self.sites.append((
            Violation(
                PASS, self.rel_path, node.lineno, code, ctx,
                f"`{primitive}` materializes a device value on this thread"
                + (
                    " (sanctioned sync point)" if sanctioned else
                    " — move it onto the supervisor worker, return a future,"
                    " or pragma `# host-sync: ok(<reason>)`"
                ),
            ),
            sanctioned,
        ))

    # ------------------------------------------------------------- helpers

    def _expr_device_tainted(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                if sub.id in self.tainted:
                    return True
            elif isinstance(sub, ast.Call):
                if terminal_name(sub.func) in self.jit_names:
                    return True
        return False

    def _expr_has_sync(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                if terminal_name(sub.func) in ALWAYS_SYNC:
                    return True
                if _sync_wrapper_name(sub) is not None and any(
                    self._expr_device_tainted(a)
                    for a in list(sub.args) + [k.value for k in sub.keywords]
                ):
                    return True
        return False

    # --------------------------------------------------------------- scope

    def _visit_scoped(self, node) -> None:
        self.scope.append(node.name)
        outer_tainted = set(self.tainted)
        self.generic_visit(node)
        self.tainted = outer_tainted
        self.scope.pop()

    visit_FunctionDef = _visit_scoped
    visit_AsyncFunctionDef = _visit_scoped

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._visit_scoped(node)

    # --------------------------------------------------------------- taint

    def _assign(self, targets: List[ast.AST], value: ast.AST) -> None:
        synced = self._expr_has_sync(value)
        is_dev = not synced and self._expr_device_tainted(value)
        for t in targets:
            if isinstance(t, ast.Name):
                if is_dev:
                    self.tainted.add(t.id)
                else:
                    self.tainted.discard(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                self._assign(list(t.elts), value)

    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        self._assign(list(node.targets), node.value)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self.visit(node.value)
            self._assign([node.target], node.value)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)
        if isinstance(node.target, ast.Name) and self._expr_device_tainted(
            node.value
        ):
            self.tainted.add(node.target.id)

    # --------------------------------------------------------------- sites

    def visit_Call(self, node: ast.Call) -> None:
        name = terminal_name(node.func)
        wrapper = _sync_wrapper_name(node)
        if name in ALWAYS_SYNC:
            dotted = name if isinstance(node.func, ast.Name) else f".{name}"
            self._record(node, f"{dotted}()")
        elif wrapper is not None and any(
            self._expr_device_tainted(a)
            for a in list(node.args) + [k.value for k in node.keywords]
        ):
            self._record(node, f"{wrapper}(<device value>)")
        self.generic_visit(node)


def classify(root: str, scan_dirs: Tuple[str, ...] = SCAN_DIRS
             ) -> Tuple[List[Violation], List[Violation]]:
    """(violations, sanctioned_sites) over the scanned tree."""
    registry = load_batch_axes(root) or {}
    registry_fn_names = {key.rsplit(":", 1)[-1] for key in registry}
    violations: List[Violation] = []
    sanctioned: List[Violation] = []
    for abs_path, rel_path in iter_py_files(root, scan_dirs):
        tree, _, pragmas = parse_file(abs_path)
        jit_names = local_jit_names(tree) | registry_fn_names
        for node in tree.body:
            funcs: List[ast.AST] = []
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.append(node)
            elif isinstance(node, ast.ClassDef):
                funcs.extend(
                    n for n in node.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                )
            for fn in funcs:
                if any(is_jit_decorator(d) for d in fn.decorator_list):
                    continue  # traced code can't sync (device-purity's beat)
                auditor = _SyncAuditor(rel_path, pragmas, jit_names)
                auditor.visit(fn)
                for v, ok in auditor.sites:
                    (sanctioned if ok else violations).append(v)
    return violations, sanctioned


def run(root: str, scan_dirs: Tuple[str, ...] = SCAN_DIRS) -> List[Violation]:
    return classify(root, scan_dirs)[0]
