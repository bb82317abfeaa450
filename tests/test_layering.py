"""Import layering: the arrows point from launchers down to the library.

The model layer reads no sharding state and no mesh (nothing under
``repro.models`` imports ``repro.distribution`` or ``repro.launch``), and
the split executor and the serving engine import no launcher. Every
import is checked, those inside functions included.
"""
import ast
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parent.parent / "src"


def imported_modules(path: Path):
    """Every module ``path`` imports, as dotted names."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def violations(package: str, forbidden):
    out = []
    for path in sorted((SRC / package.replace(".", "/")).rglob("*.py")):
        for mod in imported_modules(path):
            if any(mod == f or mod.startswith(f + ".") for f in forbidden):
                out.append(f"{path.relative_to(SRC)}: {mod}")
    return out


@pytest.mark.parametrize("package,forbidden", [
    ("repro.models", ("repro.distribution", "repro.launch")),
    ("repro.core", ("repro.launch",)),
    ("repro.serving", ("repro.launch",)),
], ids=["models", "core", "serving"])
def test_package_imports_no_higher_layer(package, forbidden):
    assert (SRC / package.replace(".", "/")).is_dir(), package
    assert violations(package, forbidden) == []
