"""The package layering of ``src/repro``, pinned.

Every module is parsed with ``ast`` (nothing is imported), and every
``import repro.X…`` / ``from repro.X… import …`` it contains, inside
functions too, is checked against the rank table: a module may import
its own package or a package of strictly lower rank.

Adding a package, or an import that points up the table, fails here;
the fix is to move code down, or — for a deliberate exception — to add
it to ``EXCEPTIONS`` below with its reason, where a reviewer sees it.
"""
from __future__ import annotations

import ast
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
ROOT = SRC / "repro"

RANK = {
    "obs": 0, "graphs": 0,
    "kernels": 1,
    "core": 2,
    "paths": 3,
    "shard": 4, "serve": 4,
    "launch": 5,
}

# (importing module or subpackage, imported module) -> reason.
# docs/ARCHITECTURE.md "Layering" and ROADMAP.md name both as debt.
EXCEPTIONS = {
    ("repro.kernels.label_intersect", "repro.core.labels"):
        "the kernels decode the packed label codec, which lives in core",
    ("repro.core.index", "repro.paths"):
        "ISLabelIndex.path_engine() lazily builds the paths engine",
}

MODULES = sorted(ROOT.rglob("*.py"))


def module_name(path: Path) -> str:
    parts = list(path.relative_to(SRC).with_suffix("").parts)
    if parts[-1] == "__init__":
        parts.pop()
    return ".".join(parts)


def repro_imports(path: Path) -> set[str]:
    """Every ``repro.*`` module named by an import statement in ``path``."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"relative import in {path}"
            if node.module == "repro":
                names = [f"repro.{a.name}" for a in node.names]
            else:
                names = [node.module]
        else:
            continue
        out.update(n for n in names if n.startswith("repro."))
    return out


def exceptions_taken(module: str, imported: str) -> set[tuple[str, str]]:
    return {(src, dst) for src, dst in EXCEPTIONS
            if imported == dst
            and (module == src or module.startswith(src + "."))}


def violations(path: Path) -> list[str]:
    module = module_name(path)
    pkg = module.split(".")[1]
    bad = []
    for imported in sorted(repro_imports(path)):
        dep = imported.split(".")[1]
        if dep == pkg or exceptions_taken(module, imported):
            continue
        if dep not in RANK or RANK[dep] >= RANK[pkg]:
            bad.append(f"{module} (rank {RANK[pkg]}) imports {imported} "
                       f"(rank {RANK.get(dep, 'unranked')})")
    return bad


def test_top_level_packages_are_exactly_the_ranked_ones():
    packages = {p.name for p in ROOT.iterdir()
                if p.is_dir() and p.name != "__pycache__"}
    loose = [p.name for p in ROOT.glob("*.py")]
    assert packages == set(RANK) and not loose, (sorted(packages), loose)


@pytest.mark.parametrize(
    "path", MODULES, ids=[str(p.relative_to(SRC)) for p in MODULES])
def test_module_imports_only_its_own_or_lower_packages(path):
    assert violations(path) == []


def test_every_exception_is_still_taken():
    """A repaired upward edge must leave EXCEPTIONS, so it cannot return
    unseen."""
    taken = set()
    for path in MODULES:
        for imported in repro_imports(path):
            taken |= exceptions_taken(module_name(path), imported)
    assert taken == set(EXCEPTIONS)
