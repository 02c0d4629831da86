"""The package's imports resolve and point one way down its layers.

Static: each file's AST is read, nothing is imported.  Two checks:

* every ``repro`` import — absolute or relative, at module level or
  inside a function — names a module that exists under ``src/repro/``,
  and every name taken by ``from X import name`` is a submodule of X or
  a name X binds at its top level.  A lazy import inside a function is
  how a deleted package would otherwise survive unnoticed.  The same
  check covers the examples, the benchmarks and ``chip_smoke.py``.
* each ``src/repro`` module imports only from its own layer or the ones
  below it (``LAYERS``): the served path never loads the static
  analyzer, and nothing below ``serving`` or ``distributed`` reaches
  up into them.
"""
from __future__ import annotations

import ast
import functools
from pathlib import Path
from typing import Iterator, List, Optional, Set, Tuple

import pytest

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"

_BASE = {"trace", "compat", "compile_cache"}
# layer (the first component below ``repro``) -> the layers it may import
LAYERS = {
    "trace": set(),
    "compat": set(),
    "compile_cache": set(),
    "kernels": _BASE | {"kernels"},
    "core": _BASE | {"kernels", "core"},
    "serving": _BASE | {"kernels", "core", "serving"},
    "distributed": _BASE | {"kernels", "core", "distributed"},
    "analysis": {"analysis"},
}


def _module_name(path: Path) -> str:
    parts = list(path.relative_to(SRC).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


def _module_path(name: str) -> Optional[Path]:
    """The file that defines module ``name``, or None if there is none.
    ``repro`` itself is a namespace package and has no file."""
    base = SRC.joinpath(*name.split("."))
    for cand in (base.with_suffix(".py"), base / "__init__.py"):
        if cand.is_file():
            return cand
    return None


def _exists(name: str) -> bool:
    return name == "repro" or _module_path(name) is not None


@functools.lru_cache(maxsize=None)
def _tree(path: Path) -> ast.Module:
    return ast.parse(path.read_text(), filename=str(path))


def _bound_names(body: List[ast.stmt]) -> Iterator[str]:
    """Names a module binds at its top level, through if/try/with/for
    blocks but not into functions or classes."""
    for node in body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            yield node.name
            continue
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for sub in (n for t in targets for n in ast.walk(t)):
                if isinstance(sub, ast.Name):
                    yield sub.id
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                yield alias.asname or alias.name.split(".")[0]
        for field in ("body", "orelse", "finalbody"):
            yield from _bound_names(getattr(node, field, []))
        for handler in getattr(node, "handlers", []):
            yield from _bound_names(handler.body)


@functools.lru_cache(maxsize=None)
def _binds(name: str) -> Set[str]:
    path = _module_path(name)
    return set(_bound_names(_tree(path).body)) if path else set()


def _repro_imports(path: Path) -> Iterator[Tuple[int, str, List[str]]]:
    """``(line, module, names)`` for every repro import in ``path``:
    ``names`` is empty for ``import X``.  Relative imports resolve
    against the file's own package when it lies under ``src/``."""
    in_src = SRC in path.parents
    package = ""
    if in_src:
        package = _module_name(path)
        if path.name != "__init__.py":
            package = package.rpartition(".")[0]
    for node in ast.walk(_tree(path)):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "repro":
                    yield node.lineno, alias.name, []
        elif isinstance(node, ast.ImportFrom):
            names = [a.name for a in node.names]
            if node.level:
                if not in_src:
                    continue          # a relative import of another package
                parts = package.split(".")
                parts = parts[:len(parts) - (node.level - 1)]
                base = ".".join(parts + ([node.module] if node.module else []))
                yield node.lineno, base, names
            elif node.module and node.module.split(".")[0] == "repro":
                yield node.lineno, node.module, names


def _targets(module: str, names: List[str]) -> Iterator[str]:
    """The modules an import reaches: ``X.name`` for each name that is a
    submodule of X, and X itself for ``import X`` or any other name."""
    subs = [f"{module}.{n}" for n in names if _exists(f"{module}.{n}")]
    if module != "repro" and (not names or len(subs) < len(names)):
        yield module
    yield from subs


SRC_FILES = sorted(p for p in (SRC / "repro").rglob("*.py"))


def _imports_repro(path: Path) -> bool:
    return next(_repro_imports(path), None) is not None


CALLER_FILES = sorted(
    p for p in [*(REPO / "examples").rglob("*.py"),
                *(REPO / "benchmarks").rglob("*.py"),
                REPO / "chip_smoke.py"]
    if _imports_repro(p))


def _rel(path: Path) -> str:
    return str(path.relative_to(REPO))


def test_file_lists_are_whole():
    """The parametrisations below see the whole package and its callers."""
    assert len(SRC_FILES) >= 40
    rels = {_rel(p) for p in CALLER_FILES}
    assert "chip_smoke.py" in rels
    assert "examples/quickstart.py" in rels
    assert "benchmarks/hcpe/run.py" in rels


@pytest.mark.parametrize("path", SRC_FILES + CALLER_FILES, ids=_rel)
def test_repro_imports_resolve(path):
    bad = []
    for line, module, names in _repro_imports(path):
        if not _exists(module):
            bad.append(f"{line}: no module {module}")
            continue
        for name in names:
            if name == "*" or _exists(f"{module}.{name}"):
                continue
            if name not in _binds(module):
                bad.append(f"{line}: {module} has no {name}")
    assert not bad, f"{_rel(path)}:\n" + "\n".join(bad)


@pytest.mark.parametrize("path", SRC_FILES,
                         ids=lambda p: _module_name(p))
def test_imports_point_down_the_layers(path):
    layer = _module_name(path).split(".")[1]
    assert layer in LAYERS, f"{layer} has no place in LAYERS"
    bad = []
    for line, module, names in _repro_imports(path):
        for target in _targets(module, names):
            parts = target.split(".")
            if len(parts) < 2:
                continue
            if parts[1] not in LAYERS[layer]:
                bad.append(f"{line}: {layer} imports {target}")
    assert not bad, f"{_rel(path)}:\n" + "\n".join(bad)
