"""The layers import downward only.

``parallel/`` is the lowest layer of the package: the operators
(``ops/``), the solvers (``solvers/``), the service (``serving/``) and
the tuner (``tuning/``) are built on it, so none of its modules may
import them. The service may not ask the tuner either. The check reads
each module's source with ``ast``, so imports inside functions count.
"""

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "pylops_mpi_tpu")

ABOVE_PARALLEL = ("tuning", "ops", "solvers", "serving")

CASES = [(os.path.join("parallel", f), ABOVE_PARALLEL)
         for f in sorted(os.listdir(os.path.join(PKG, "parallel")))
         if f.endswith(".py")]
CASES.append((os.path.join("serving", "engine.py"), ("tuning",)))


def _imported(rel):
    """Every module ``rel`` imports, as a dotted name under
    ``pylops_mpi_tpu``."""
    here = ["pylops_mpi_tpu"] + os.path.dirname(rel).split(os.sep)
    with open(os.path.join(PKG, rel)) as f:
        tree = ast.parse(f.read(), filename=rel)
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            names.append(mod)
            if node.module is None:
                names += [f"{mod}.{a.name}" for a in node.names]
    return names


@pytest.mark.parametrize("rel,forbidden", CASES,
                         ids=[rel for rel, _ in CASES])
def test_parallel_imports_nothing_above_it(rel, forbidden):
    bad = [m for m in _imported(rel)
           for pkg in forbidden
           if m == f"pylops_mpi_tpu.{pkg}"
           or m.startswith(f"pylops_mpi_tpu.{pkg}.")]
    assert bad == [], f"{rel} imports {bad}"
