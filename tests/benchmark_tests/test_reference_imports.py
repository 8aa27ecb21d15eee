"""A reference imports nothing of the program: the imports of
``benchmark/reference.py``, of every module under
``benchmark/references/`` (none yet) and of the test-only ones under
``seam/references/`` are walked, and through whatever of ``benchmark``
they import in turn. ``check.py`` imports no reference at all: it takes
the module from the cell."""

import ast
import glob
import os

import pytest

from benchmark import spec

SEAM = os.path.join(os.path.dirname(os.path.abspath(__file__)), "seam")
REFERENCES = sorted(
    [os.path.join(spec.HERE, "reference.py")]
    + [p for d in (os.path.join(spec.HERE, "references"),
                   os.path.join(SEAM, "references"))
       for p in glob.glob(os.path.join(d, "*.py"))
       if os.path.basename(p) != "__init__.py"])
ALLOWED = {"__future__", "concurrent", "typing", "numpy", "benchmark"}


def imported(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path}: relative import"
            yield node.module
            for a in node.names:           # from benchmark import x
                yield f"{node.module}.{a.name}"


def source_of(module):
    """The file of a ``benchmark...`` module, wherever a test keeps it."""
    rel = module.split(".")[1:]
    for root in (spec.HERE, SEAM):
        for cand in (os.path.join(root, *rel) + ".py",
                     os.path.join(root, *rel, "__init__.py")):
            if rel and os.path.isfile(cand):
                return cand
    return None


def walk(path, seen):
    for module in imported(path):
        top = module.split(".")[0]
        assert top in ALLOWED, f"{path} imports {module}"
        src = source_of(module) if top == "benchmark" else None
        if src and src not in seen:
            seen.add(src)
            walk(src, seen)
    return seen


@pytest.mark.parametrize("path", REFERENCES, ids=[
    os.path.relpath(p, spec.ROOT) for p in REFERENCES])
def test_a_reference_imports_nothing_of_the_program(path):
    seen = walk(path, {path})
    for src in seen:
        with open(src) as f:
            assert "dmlp_tpu" not in f.read(), src
    # nor the harness's own pieces that do: run.py drives the program
    assert os.path.join(spec.HERE, "run.py") not in seen


def test_the_walk_would_catch_the_program(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import numpy\nfrom dmlp_tpu.golden import reference\n")
    with pytest.raises(AssertionError, match="dmlp_tpu"):
        walk(str(bad), set())


def test_check_imports_no_reference():
    got = set(imported(os.path.join(spec.HERE, "check.py")))
    assert not {m for m in got if "reference" in m}
    assert "benchmark.data" in got
