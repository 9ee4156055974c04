"""Device-purity lint for ``lighthouse_tpu/ops/``.

A traced function (``@jax.jit`` or a Pallas kernel) executes its Python
body ONCE at trace time; host side effects inside it either silently
vanish on subsequent calls (metrics/log/print fire once, not per step),
capture trace-time values forever (``time.time()``, host randomness), or
mutate host state from inside a compiled region (cache writes).  64-bit
dtypes additionally downcast silently to 32-bit unless dispatch is wrapped
in ``jax.enable_x64(True)`` — the classic "my balances truncated"
bug.

Flags, inside jit/Pallas functions:

- ``host-effect``      — print / logging / metrics ``.inc()``/``.observe()``
  / ``time.*`` calls
- ``host-randomness``  — ``random.*`` / ``np.random.*`` (jax.random is fine:
  explicit keys trace correctly)
- ``global-mutation``  — ``global`` statements, or writes through a
  module-level name (cache dicts etc.)
- ``unguarded-x64``    — 64-bit dtype references when the module never
  touches ``enable_x64`` nor declares an ``x64=True`` dispatch program

Suppress intentional sites with ``# device-purity: ok(<reason>)``.
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
    parse_file,
    terminal_name,
)

PASS = "device-purity"

SCAN_DIRS = ("lighthouse_tpu/ops",)

LOGGING_ATTRS = frozenset(
    {"debug", "info", "warning", "warn", "error", "exception", "critical"}
)
LOGGER_NAMES = frozenset({"log", "logger", "logging"})
METRIC_ATTRS = frozenset({"inc", "observe"})
TIME_ATTRS = frozenset({"time", "perf_counter", "monotonic", "sleep", "process_time"})
X64_DTYPES = frozenset({"int64", "uint64", "float64"})
HOST_RNG_ROOTS = frozenset({"random", "np", "numpy"})


def _pallas_kernel_names(tree: ast.Module) -> Set[str]:
    """Function names passed as the kernel argument to ``pl.pallas_call``."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and terminal_name(node.func) == "pallas_call":
            for arg in node.args[:1]:
                if isinstance(arg, ast.Name):
                    names.add(arg.id)
    return names


def _module_guards_x64(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and any(
            a.name == "enable_x64" for a in node.names
        ):
            return True
        if isinstance(node, (ast.Name, ast.Attribute)) and (
            terminal_name(node) == "enable_x64"
        ):
            return True
        # ``ops/dispatch.Program(..., x64=True)``: the seam dispatches the
        # program under ``jax.enable_x64(True)``.
        if (isinstance(node, ast.keyword) and node.arg == "x64"
                and isinstance(node.value, ast.Constant)
                and node.value.value is True):
            return True
    return False


def _module_level_names(tree: ast.Module) -> Set[str]:
    names: Set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            if isinstance(node.target, ast.Name):
                names.add(node.target.id)
    return names


class _PurityChecker(ast.NodeVisitor):
    def __init__(self, rel_path: str, func_ctx: str, pragmas: PragmaIndex,
                 module_names: Set[str], x64_guarded: bool):
        self.rel_path = rel_path
        self.ctx = func_ctx
        self.pragmas = pragmas
        self.module_names = module_names
        self.x64_guarded = x64_guarded
        self.violations: List[Violation] = []

    def _flag(self, node: ast.AST, code: str, message: str) -> None:
        if self.pragmas.suppresses(PASS, node):
            return
        self.violations.append(
            Violation(PASS, self.rel_path, node.lineno, code, self.ctx, message)
        )

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Name) and func.id == "print":
            self._flag(node, "host-effect",
                       "print() inside a traced function fires at trace time only")
        elif isinstance(func, ast.Attribute):
            recv = terminal_name(func.value)
            path = dotted_path(func) or ""
            if func.attr in LOGGING_ATTRS and recv in LOGGER_NAMES:
                self._flag(node, "host-effect",
                           f"logging call `{path}` inside a traced function")
            elif func.attr in METRIC_ATTRS:
                self._flag(node, "host-effect",
                           f"metrics call `{path}` inside a traced function "
                           "records at trace time only")
            elif recv == "time" and func.attr in TIME_ATTRS:
                self._flag(node, "host-effect",
                           f"`{path}()` captures the trace-time clock")
            elif path.split(".")[0] in HOST_RNG_ROOTS and "random" in path:
                self._flag(node, "host-randomness",
                           f"host randomness `{path}` is frozen at trace time; "
                           "use jax.random with an explicit key")
        self.generic_visit(node)

    def visit_Global(self, node: ast.Global) -> None:
        self._flag(node, "global-mutation",
                   f"`global {', '.join(node.names)}` inside a traced function")

    def _check_store(self, target: ast.AST, node: ast.AST) -> None:
        base = target
        while isinstance(base, (ast.Subscript, ast.Attribute)):
            base = base.value
        if isinstance(base, ast.Name) and base.id in self.module_names:
            self._flag(node, "global-mutation",
                       f"write through module-level `{base.id}` from a traced "
                       "function (executes at trace time only)")

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            if isinstance(t, (ast.Subscript, ast.Attribute)):
                self._check_store(t, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        if isinstance(node.target, (ast.Subscript, ast.Attribute)):
            self._check_store(node.target, node)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if node.attr in X64_DTYPES and not self.x64_guarded:
            self._flag(node, "unguarded-x64",
                       f"64-bit dtype `{dotted_path(node) or node.attr}` in a "
                       "traced function, but the module never enables x64 — "
                       "values silently truncate to 32-bit")
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        if isinstance(node.value, str) and node.value in X64_DTYPES and not self.x64_guarded:
            self._flag(node, "unguarded-x64",
                       f"64-bit dtype string {node.value!r} in a traced "
                       "function without an x64 guard")


def run(root: str, scan_dirs: Tuple[str, ...] = SCAN_DIRS) -> List[Violation]:
    violations: List[Violation] = []
    for abs_path, rel_path in iter_py_files(root, scan_dirs):
        tree, _, pragmas = parse_file(abs_path)
        kernel_names = _pallas_kernel_names(tree)
        x64_guarded = _module_guards_x64(tree)
        module_names = _module_level_names(tree)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            jitted = any(is_jit_decorator(d) for d in node.decorator_list)
            if not (jitted or node.name in kernel_names):
                continue
            kind = "jit" if jitted else "pallas"
            checker = _PurityChecker(
                rel_path, f"{node.name}[{kind}]", pragmas, module_names, x64_guarded
            )
            for stmt in node.body:
                checker.visit(stmt)
            violations.extend(checker.violations)
    return violations
