"""The library's lower layers import nothing above them.

``sparse`` (containers), ``kernels`` (Pallas) and ``core`` (``prepare``,
solvers, sharding) may import each other, ``obs`` and ``util``, and nothing
else in ``repro``.  Each module is parsed with ``ast``, not imported, so the
check costs no JAX start-up and sees imports inside functions too.
"""
import ast
import os

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src", "repro")
LOWER = ("sparse", "kernels", "core")
ALLOWED = set(LOWER) | {"obs", "util"}


def _modules():
    for pkg in LOWER:
        for dirpath, _, files in os.walk(os.path.join(SRC, pkg)):
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.relpath(os.path.join(dirpath, f), SRC)


def _repro_imports(path):
    """``(line, module)`` for every absolute ``repro.*`` import in ``path``."""
    with open(os.path.join(SRC, path)) as fh:
        tree = ast.parse(fh.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            names = [node.module]
        else:
            continue
        for name in names:
            if name == "repro" or name.startswith("repro."):
                yield node.lineno, name


@pytest.mark.parametrize("path", list(_modules()))
def test_lower_layer_imports_only_lower_layers(path):
    bad = [f"{path}:{line} imports {name}"
           for line, name in _repro_imports(path)
           if name.partition(".")[2].split(".")[0] not in ALLOWED]
    assert not bad, "\n".join(bad)
