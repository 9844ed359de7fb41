"""Satellite rule: the declared dependency surface must match reality.

The seed repo shipped an EMPTY requirements.txt while the worker metrics
path quietly imported ``psutil`` — the classic undeclared-dependency
drift. ``undeclared-import`` walks every Import/ImportFrom in the
analyzed set (function-local lazy imports included), classifies the
top-level module (stdlib / local / third-party), and requires every
third-party module to appear in requirements.txt. The reverse direction
is checked too: a requirement nothing imports is flagged as stale.

requirements.txt itself is generated from a ``--format=json`` pass of
this rule (see docs/static_analysis.md for the refresh workflow).
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import Finding, Project, Rule, register

REQUIREMENTS = "requirements.txt"

# import name -> PyPI distribution name, where they differ
DIST_NAMES = {
    "yaml": "pyyaml",
    "orbax": "orbax-checkpoint",
}
# distributions whose import name differs (normalized, reverse direction)
_IMPORT_OF_DIST = {v: k for k, v in DIST_NAMES.items()}

_STDLIB: Set[str] = set(sys.stdlib_module_names) | {"__future__"}
_REQ_LINE = re.compile(r"^([A-Za-z0-9_.\-]+)")


def _norm(name: str) -> str:
    return name.lower().replace("-", "_").replace(".", "_")


def _top_level_imports(tree: ast.Module) -> Dict[str, int]:
    """top-level module name -> first line it's imported on."""
    out: Dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                top = alias.name.split(".")[0]
                out.setdefault(top, node.lineno)
        elif isinstance(node, ast.ImportFrom):
            if node.level:            # relative import — local by definition
                continue
            if node.module:
                out.setdefault(node.module.split(".")[0], node.lineno)
    return out


def _local_packages(root: str) -> Set[str]:
    """Importable names the repo itself provides (dirs with __init__.py or
    top-level .py files)."""
    out: Set[str] = set()
    try:
        entries = os.listdir(root)
    except OSError:
        return out
    for e in entries:
        p = os.path.join(root, e)
        if os.path.isdir(p) and os.path.exists(
                os.path.join(p, "__init__.py")):
            out.add(e)
        elif os.path.isdir(p):
            out.add(e)                 # namespace package (scripts/)
        elif e.endswith(".py"):
            out.add(e[:-3])
    return out


def declared_requirements(root: str) -> Optional[Set[str]]:
    """Normalized import-level names declared in requirements.txt, or None
    when the file doesn't exist."""
    path = os.path.join(root, REQUIREMENTS)
    if not os.path.exists(path):
        return None
    out: Set[str] = set()
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = _REQ_LINE.match(line)
            if not m:
                continue
            dist = m.group(1)
            out.add(_norm(dist))
            imp = _IMPORT_OF_DIST.get(dist.lower())
            if imp:
                out.add(_norm(imp))
    return out


def third_party_imports(project: Project) -> Dict[str, Tuple[str, int]]:
    """third-party top-level module -> (first relpath, line)."""
    local = _local_packages(project.root)
    out: Dict[str, Tuple[str, int]] = {}
    for mod in project.modules:
        if mod.tree is None:
            continue
        for top, line in sorted(_top_level_imports(mod.tree).items()):
            if top in _STDLIB or top in local:
                continue
            if top not in out:
                out[top] = (mod.relpath, line)
    return out


@register
class UndeclaredImport(Rule):
    id = "undeclared-import"
    family = "drift"
    severity = "error"
    doc = ("every third-party import (lazy ones included) must be declared "
           "in requirements.txt; every requirement must be imported "
           "somewhere — the seed repo's undeclared-psutil failure mode")

    def check_project(self, project: Project) -> Iterable[Finding]:
        third = third_party_imports(project)
        if not third:
            return ()
        declared = declared_requirements(project.root)
        out: List[Finding] = []
        if declared is None:
            out.append(Finding(
                rule=self.id, path=REQUIREMENTS, line=1,
                message=f"{REQUIREMENTS} missing but the tree imports "
                        f"{len(third)} third-party module(s): "
                        f"{', '.join(sorted(third))}",
                key="missing-requirements"))
            return out
        for top, (rel, line) in sorted(third.items()):
            dist = DIST_NAMES.get(top, top)
            if _norm(top) not in declared and _norm(dist) not in declared:
                out.append(Finding(
                    rule=self.id, path=rel, line=line,
                    message=f"import {top} ({dist} on PyPI) is not "
                            f"declared in {REQUIREMENTS}",
                    key=f"undeclared:{top}"))
        # reverse: stale requirement nothing imports. jaxlib is the one
        # legitimate import-less dist (jax's binary backend).
        imported = {_norm(t) for t in third} | \
            {_norm(DIST_NAMES.get(t, t)) for t in third}
        for dist in sorted(declared - imported - {"jaxlib"}):
            if dist in {_norm(i) for i in _IMPORT_OF_DIST.values()}:
                continue              # counted under its import name
            out.append(Finding(
                rule=self.id, path=REQUIREMENTS, line=1,
                message=f"requirement {dist} is declared but never "
                        f"imported by the analyzed tree — stale "
                        f"dependency", key=f"stale:{dist}"))
        return out
