#!/usr/bin/env python
"""Import-layering guard: keep the execution stack's import graph downward.

The refactor that split ``core/spmm.py`` into plan IR -> executor pipeline
-> dynamic -> serving only stays split if nothing quietly re-introduces an
upward import.  This script AST-scans every module under ``src/repro`` —
top-level *and* function-local imports, plus ``importlib.import_module``
calls with literal arguments — and fails CI when a package imports a layer
above itself:

    errors, obs             (shared taxonomy + telemetry: no repro deps)
    robust                  (fault harness: errors + obs only)
    kernels, distributed    (leaf utilities: Pallas kernels; the 1-D
                             mesh and spec helpers of the sharded executor)
        -> core             (plan IR + plan builders)
        -> exec             (executor pipeline + health table)
        -> dynamic          (incremental plan maintenance)
        -> serve            (request batching / async compaction)
        -> sparse           (the user-facing operator facade; imports
                             anything, imported by nothing below)

``repro.errors`` (a top-level module), ``repro.obs`` (the telemetry
registry, trace ring and phase spans) and ``repro.robust`` sit at the very
bottom: any layer may import them, they import nothing above (``obs``
imports only itself; ``robust`` may import ``errors``, ``obs`` and
itself).  Keeping ``obs`` dependency-free is what lets every counter
island in the stack publish into one registry without bending the graph.

One documented allowance: ``core/spmm.py`` is the public facade and
forwards execution names to ``repro.exec.api`` through a lazy PEP 562
``__getattr__`` (an ``importlib.import_module`` call).  That keeps the
historical ``repro.core.spmm.execute`` call sites working while core's
*logic* stays independent of the upper layers; the allowlist below pins it
to exactly that one module/target pair so anything broader still fails.

Exception note: ``kernels`` may import ``core.cost_model`` (the fringe
dispatch-tier selection used by ``tier="auto"``) — the cost model is leaf
math with no plan/executor dependencies.

Dependency-inverted seam: the autotuner (``core/tuner.py``) persists its
table through ``PlanRegistry``, which lives two layers *up* in ``dynamic``.
Rather than import upward, core defines a store protocol and a module hook,
``install_store``, and ``dynamic/tuning.py`` hands the registry-backed
store down.  The seam only stays downward if nothing in the lower layers
ever *calls* the hook itself — so beyond the import rules, this script
AST-scans for ``install_store(...)`` call sites and fails CI when one
appears outside the ``dynamic``/``serve`` layers (defining it in core is
fine; calling it there would collapse the inversion).

Usage: python tools/check_layers.py  (exit 1 on violation)
"""
from __future__ import annotations

import ast
import os
import sys
from typing import Iterator, List, Tuple

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")
PKG = "repro"

# package -> layers it must never import (prefix match on absolute module)
FORBIDDEN = {
    # bottom of the graph: the error taxonomy imports nothing from the
    # package, the telemetry registry only itself, the fault harness only
    # repro.errors + repro.obs (see ALLOWED_PREFIXES)
    "errors": ("repro",),
    "obs": ("repro",),
    "robust": ("repro",),
    "kernels": ("repro.core", "repro.exec", "repro.dynamic", "repro.serve",
                "repro.distributed", "repro.sparse"),
    "distributed": ("repro.core", "repro.exec", "repro.dynamic",
                    "repro.serve", "repro.sparse"),
    "core": ("repro.exec", "repro.dynamic", "repro.serve", "repro.sparse"),
    "exec": ("repro.dynamic", "repro.serve", "repro.sparse"),
    "dynamic": ("repro.serve", "repro.sparse"),
    "serve": ("repro.sparse",),
}

# (module path relative to src, imported target) pairs that are allowed
# despite the rules above — each must be justified here.
ALLOWED = {
    # the public-API facade: lazy PEP 562 forwarding of execution names
    ("repro/core/spmm.py", "repro.exec.api"),
}

# kernels -> core.cost_model is the one sanctioned core import (see module
# docstring); expressed as an allowed *prefix* rather than per-file pairs.
ALLOWED_PREFIXES = {
    "kernels": ("repro.core.cost_model",),
    # the telemetry package may import itself (relative imports resolve to
    # repro.obs.*) and nothing else from the package
    "obs": ("repro.obs",),
    # the fault harness may import the taxonomy, the telemetry registry it
    # publishes seam counters to, and its own package
    "robust": ("repro.errors", "repro.obs", "repro.robust"),
}

# the tuner persistence hook may only be *called* from these layers — the
# store flows downward into core, never the other way (see docstring)
STORE_SEAM_HOOK = "install_store"
STORE_SEAM_CALLERS = ("dynamic", "serve")


def _resolve_relative(module_path: str, level: int, name: str) -> str:
    """Absolute module of a ``from ..x import y`` seen in ``module_path``."""
    parts = module_path.replace(os.sep, "/").split("/")
    # containing package: drop the filename ("__init__.py" resolves
    # against its own package, plain modules against their parent — both
    # are the directory part)
    pkg_parts = parts[:-1]
    base = pkg_parts[: len(pkg_parts) - (level - 1)] if level > 1 else pkg_parts
    return ".".join(base + ([name] if name else [])).rstrip(".")


def iter_imports(module_rel: str, tree: ast.AST) -> Iterator[Tuple[int, str]]:
    """Yield (lineno, absolute module target) for every import in the AST."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0:
                yield node.lineno, node.module or ""
            else:
                yield node.lineno, _resolve_relative(
                    module_rel, node.level, node.module or ""
                )
        elif isinstance(node, ast.Call):
            # importlib.import_module("literal") — the lazy-facade pattern;
            # scanned so the guard cannot be bypassed by stringly imports
            func = node.func
            is_import_module = (
                isinstance(func, ast.Attribute)
                and func.attr == "import_module"
            ) or (isinstance(func, ast.Name) and func.id == "import_module")
            if is_import_module and node.args and isinstance(
                    node.args[0], ast.Constant) and isinstance(
                    node.args[0].value, str):
                yield node.lineno, node.args[0].value


def iter_store_seam_calls(tree: ast.AST) -> Iterator[int]:
    """Line numbers of ``install_store(...)`` call sites in the AST."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = (
            func.attr if isinstance(func, ast.Attribute)
            else func.id if isinstance(func, ast.Name) else None
        )
        if name == STORE_SEAM_HOOK:
            yield node.lineno


def check_tree(src_root: str = SRC) -> List[str]:
    violations: List[str] = []
    pkg_root = os.path.join(src_root, PKG)
    for dirpath, _dirnames, filenames in os.walk(pkg_root):
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, src_root).replace(os.sep, "/")
            part = rel.split("/")[1] if "/" in rel else ""
            # top-level modules (repro/errors.py) rule-match by stem
            subpkg = part[:-3] if part.endswith(".py") else part
            with open(path, encoding="utf-8") as f:
                try:
                    tree = ast.parse(f.read(), filename=path)
                except SyntaxError as e:  # pragma: no cover
                    violations.append(f"{rel}: unparseable ({e})")
                    continue
            if subpkg not in STORE_SEAM_CALLERS:
                for lineno in iter_store_seam_calls(tree):
                    violations.append(
                        f"{rel}:{lineno}: {STORE_SEAM_HOOK}() may only be "
                        f"called from {'/'.join(STORE_SEAM_CALLERS)} — the "
                        f"tuner store seam points downward only"
                    )
            rules = FORBIDDEN.get(subpkg)
            if not rules:
                continue
            for lineno, target in iter_imports(rel, tree):
                if not target.startswith("repro."):
                    continue
                if any(target.startswith(p)
                       for p in ALLOWED_PREFIXES.get(subpkg, ())):
                    continue
                for forbidden in rules:
                    if target == forbidden or target.startswith(
                            forbidden + "."):
                        if (rel, target) in ALLOWED:
                            break
                        violations.append(
                            f"{rel}:{lineno}: {subpkg}/ must not import "
                            f"{target} (layering: {forbidden} sits above "
                            f"{subpkg})"
                        )
                        break
    return violations


def main() -> int:
    violations = check_tree()
    if violations:
        print("import-layering violations:", file=sys.stderr)
        for v in violations:
            print(f"  {v}", file=sys.stderr)
        return 1
    print("import layering ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
