"""Layering: ``repro.core`` imports nothing from ``repro.serve``, the
layer above it, except the ``server=`` client of
``simulate_dataflow_many`` (a named debt: it falls back to local
resolution when no daemon answers)."""

import ast
import os

import repro.core

ALLOWED = {("simulator.py", "repro.serve.client")}


def _imports(path: str, package: str):
    """Every module a file imports, with relative imports resolved."""
    tree = ast.parse(open(path).read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            mod = node.module
            if node.level:
                parts = package.split(".")
                base = parts[:len(parts) - node.level + 1]
                mod = ".".join(base + ([mod] if mod else []))
            yield mod
            for a in node.names:  # ``from .. import serve``
                yield f"{mod}.{a.name}"


def test_core_imports_nothing_from_serve():
    core = os.path.dirname(repro.core.__file__)
    found = set()
    for f in sorted(os.listdir(core)):
        if f.endswith(".py"):
            for mod in _imports(os.path.join(core, f), "repro.core"):
                if mod == "repro.serve" or mod.startswith("repro.serve."):
                    found.add((f, mod))
    # ``from ..serve.client import X`` names both the module and X
    found = {(f, m) for f, m in found
             if not any(m.startswith(a + ".") and f == af
                        for af, a in ALLOWED)}
    assert found <= ALLOWED, sorted(found - ALLOWED)
