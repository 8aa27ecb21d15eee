"""The tree holds together: what tier-1 cannot see by running the package.

The driver runs pytest only, never ``make test``'s smoke targets, so a
tool whose import a deletion broke, a README recipe that names a file
that is gone, or a record dropped at the repo root is seen by nobody.
These cases read the tree (AST, Makefile, README) without running any
tool.
"""

import ast
import fnmatch
import importlib.util
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = sorted(f for f in os.listdir(os.path.join(ROOT, "tools"))
               if not f.startswith((".", "__")))
TOOL_SCRIPTS = [f for f in TOOLS if f.endswith(".py")]
#: top-level directories and root modules whose imports must resolve
REPO_TOP = {"dmlp_tpu", "tools", "benchmark", "tests", "chip_smoke",
            "__graft_entry__"}
RECORD_RE = re.compile(r"^[A-Z_]+_r[0-9]+[a-z0-9_]*\.jsonl?$")


def _read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


# -- (a) every import a tool makes of a repo module resolves -------------------

def _module_file(dotted, extra_dirs=()):
    """The file defining module ``dotted``, or None."""
    rel = dotted.replace(".", os.sep)
    for base in (ROOT, *extra_dirs):
        for cand in (rel + ".py", os.path.join(rel, "__init__.py")):
            path = os.path.join(base, cand)
            if os.path.isfile(path):
                return path
    return None


def _top_level_names(path):
    """Names a module binds at top level (defs, classes, assignments,
    imports), looking into top-level ``if``/``try``/``with`` blocks."""
    names = set()

    def walk(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                names.add(node.name)
            elif isinstance(node, (ast.Assign, ast.AnnAssign,
                                   ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            names.add(n.id)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for a in node.names:
                    names.add((a.asname or a.name).split(".")[0])
            elif isinstance(node, (ast.If, ast.Try, ast.With)):
                for field in ("body", "orelse", "finalbody"):
                    walk(getattr(node, field, []))
                for h in getattr(node, "handlers", []):
                    walk(h.body)

    with open(path) as f:
        walk(ast.parse(f.read()).body)
    return names


def _unresolved_imports(path, sibling_dir=None):
    """Imports in ``path`` of repo modules that name no existing module
    or no name that module binds, and top-level names nothing provides
    (a bare import of a deleted sibling). ``sibling_dir``: a directory
    the script puts on ``sys.path`` itself (tools import one another
    bare)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    siblings = set()
    if sibling_dir:
        siblings = {f[:-3] for f in os.listdir(sibling_dir)
                    if f.endswith(".py")}
    extra = (sibling_dir,) if sibling_dir else ()

    def ours(top):
        return top in REPO_TOP or top in siblings

    def provided(top):        # stdlib or installed; searched, not imported
        return importlib.util.find_spec(top) is not None

    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                top = a.name.split(".")[0]
                if (_module_file(a.name, extra) is None if ours(top)
                        else not provided(top)):
                    bad.append(f"import {a.name}")
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            top = mod.split(".")[0]
            if not ours(top):
                if not provided(top):
                    bad.append(f"from {mod} import ...")
                continue
            mfile = _module_file(mod, extra)
            if mfile is None:
                bad.append(f"from {mod} import ...")
                continue
            bound = _top_level_names(mfile)
            for a in node.names:
                if a.name == "*" or a.name in bound:
                    continue
                if _module_file(f"{mod}.{a.name}", extra) is None:
                    bad.append(f"from {mod} import {a.name}")
    return bad


@pytest.mark.parametrize("script", TOOL_SCRIPTS)
def test_tool_imports_of_repo_modules_resolve(script):
    tools_dir = os.path.join(ROOT, "tools")
    bad = _unresolved_imports(os.path.join(tools_dir, script),
                              sibling_dir=tools_dir)
    assert not bad, f"tools/{script}: {bad}"


# -- (b) no orphan under tools/ ------------------------------------------------

def _py_files(rel_dir):
    for base, _dirs, files in os.walk(os.path.join(ROOT, rel_dir)):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(base, f)


def test_every_tool_is_named_by_makefile_test_package_or_readme():
    texts = [_read("Makefile"), _read("README.md")]
    for rel_dir in ("tests", "dmlp_tpu"):
        for path in _py_files(rel_dir):
            if os.path.abspath(path) == os.path.abspath(__file__):
                continue
            with open(path) as f:
                texts.append(f.read())
    blob = "\n".join(texts)
    orphans = [t for t in TOOLS if f"tools/{t}" not in blob
               and not re.search(rf"\btools\.{re.escape(t[:-3])}\b", blob)]
    assert not orphans, (
        f"nothing names {orphans}: run it from the Makefile or a test, "
        "or delete it")


# -- (c) root hygiene ----------------------------------------------------------

ROOT_ALLOWED = {
    ".gitignore", ".chiprunignore", "BASELINE.json", "BENCHMARK.json",
    "Makefile", "PERF_LEDGER.jsonl", "__graft_entry__.py",
    "check_baseline.json", "chip_smoke.py", "pyproject.toml",
}


def _root_files():
    """Root-level files git would commit (tracked, or untracked and not
    ignored); without git, the directory listing less what .gitignore
    names."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard"], cwd=ROOT, capture_output=True,
            text=True, timeout=60)
        if out.returncode == 0:
            return sorted({ln for ln in out.stdout.splitlines()
                           if ln and "/" not in ln
                           and os.path.exists(os.path.join(ROOT, ln))})
    except (OSError, subprocess.TimeoutExpired):
        pass
    ignored = [ln.strip().rstrip("/") for ln in
               _read(".gitignore").splitlines()
               if ln.strip() and not ln.startswith("#")]
    return sorted(f for f in os.listdir(ROOT)
                  if os.path.isfile(os.path.join(ROOT, f))
                  and not any(fnmatch.fnmatch(f, pat) for pat in ignored))


def test_repo_root_holds_no_run_records_and_only_listed_files():
    files = _root_files()
    records = [f for f in files if RECORD_RE.match(f)]
    assert not records, (
        f"{records}: run records belong under outputs/ (git-ignored); "
        "the performance record is the driver's PERF_LEDGER.jsonl")
    # documents (*.md) come and go with the round; anything else new at
    # the root is a decision: put it on the list here
    extra = [f for f in files
             if f not in ROOT_ALLOWED and not f.endswith(".md")]
    assert not extra, f"unlisted files at the repo root: {extra}"


# -- (d) the package imports nothing that lives beside it ----------------------

@pytest.mark.parametrize("outside", ["tools", "benchmark", "bench"])
def test_package_does_not_import(outside):
    """``dmlp_tpu`` is what ships; ``tools/`` and ``benchmark/`` stand
    beside it and import it, never the other way (``bench``: a
    root-level module, not ``dmlp_tpu.bench``)."""
    bad = []
    for path in _py_files("dmlp_tpu"):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mods = [node.module or ""]
            if any(m.split(".")[0] == outside for m in mods):
                bad.append(f"{os.path.relpath(path, ROOT)}:{node.lineno}")
    assert not bad, f"dmlp_tpu imports {outside}: {bad}"


# -- (d1) the kernel's tiles come from the checkout alone ----------------------

def test_no_tune_cache_and_the_resolver_reads_nothing_outside_its_arguments():
    """The tiles a dispatch runs with are ``ops.pallas_extract
    .resolve_variant`` of its shape: no file outside the checkout, no
    variable, can pick another kernel for a cell (PR 43 deleted the tune
    cache that could). The environment switches that remain
    (DMLP_TPU_FUSED, DMLP_TPU_PRUNE) live in pallas_fused / summaries."""
    gone = ("DMLP_TPU_TUNE_CACHE", "lookup_variant", "dmlp_tpu.tune",
            "dmlp_tpu/tune")
    assert not os.path.exists(os.path.join(ROOT, "dmlp_tpu", "tune"))
    files = [os.path.join(ROOT, f) for f in ("chip_smoke.py", "Makefile",
                                             "tests/conftest.py")]
    for rel_dir in ("dmlp_tpu", "tools"):
        for base, _dirs, names in os.walk(os.path.join(ROOT, rel_dir)):
            if "__pycache__" not in base:
                files += [os.path.join(base, f) for f in names]
    bad = []
    for path in files:
        with open(path, errors="ignore") as f:
            text = f.read()
        bad += [f"{os.path.relpath(path, ROOT)}: {name}"
                for name in gone if name in text]
    assert not bad, bad
    tree = ast.parse(_read("dmlp_tpu/ops/pallas_extract.py"))
    modules = {a.name.split(".")[0] for node in ast.walk(tree)
               if isinstance(node, ast.Import) for a in node.names} | {
        (node.module or "").split(".")[0] for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)}
    assert not modules & {"os", "io", "pathlib", "json"}
    named = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)} | {
        n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert not named & {"open", "environ", "getenv"}


# -- (d2) one resident fold body -----------------------------------------------

def _imported_names(tree):
    return {a.name for node in ast.walk(tree)
            if isinstance(node, ast.ImportFrom) for a in node.names}


def _called_names(tree):
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            out.add(f.attr if isinstance(f, ast.Attribute)
                    else getattr(f, "id", ""))
    return out


@pytest.mark.parametrize("name", ["fori_loop", "while_loop", "scan",
                                  "extract_topk", "fused_topk"])
def test_the_mesh_engine_holds_no_chunk_loop_of_its_own(name):
    """The resident fold is ``serve.engine.fold_chunks``, traced by both
    resident engines; the mesh engine wraps it in ``shard_map`` and
    neither loops over chunks on the device nor calls a kernel itself."""
    tree = ast.parse(_read("dmlp_tpu/fleet/mesh_engine.py"))
    assert name not in _called_names(tree)
    assert name not in _imported_names(tree)


def test_the_mesh_engine_imports_the_shared_fold_and_no_throttle():
    tree = ast.parse(_read("dmlp_tpu/fleet/mesh_engine.py"))
    assert "fold_chunks" in _imported_names(tree)
    assert "fold_chunks" in _called_names(tree)
    assert "ChunkThrottle" not in _imported_names(tree) | {
        n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    # and the one-chip program is a jit of the same body
    serve = ast.parse(_read("dmlp_tpu/serve/engine.py"))
    fold_stack = next(n for n in ast.walk(serve)
                      if isinstance(n, ast.FunctionDef)
                      and n.name == "_fold_stack")
    assert "fold_chunks" in _called_names(fold_stack)
    assert "fori_loop" not in _called_names(fold_stack)


# -- (e) README: what it tells a reader to run or open exists ------------------

def _make_targets():
    return set(re.findall(r"^([A-Za-z0-9_.\-/]+):(?!=)", _read("Makefile"),
                          flags=re.M))


def test_readme_python_m_modules_resolve():
    mods = set(re.findall(r"python3? -m ([A-Za-z_][\w.]*)",
                          _read("README.md")))
    assert mods, "README shows no `python -m` recipe at all"
    missing = sorted(m for m in mods
                     if m.split(".")[0] in REPO_TOP
                     and _module_file(m) is None)
    assert not missing, f"README runs modules that do not exist: {missing}"


def test_readme_paths_exist():
    text = _read("README.md")
    paths = set(re.findall(
        r"(?<![\w/.\-])((?:tools|dmlp_tpu|benchmark)/[\w./\-]*\w)", text))
    missing = sorted(p for p in paths
                     if not os.path.exists(os.path.join(ROOT, p)))
    assert not missing, f"README names paths that do not exist: {missing}"


def test_readme_make_targets_exist():
    named = set(re.findall(r"`make ([a-z0-9\-]+)", _read("README.md")))
    missing = sorted(named - _make_targets())
    assert not missing, f"README names make targets that do not exist: " \
        f"{missing}"


# -- (f) Makefile: `test:` and every recipe point at things that exist ---------

def test_makefile_test_prerequisites_are_targets():
    text = _read("Makefile").replace("\\\n", " ")
    m = re.search(r"^test:(.*)$", text, flags=re.M)
    assert m, "Makefile has no test: target"
    prereqs = m.group(1).split()
    assert prereqs, "test: lists no prerequisite"
    missing = sorted(set(prereqs) - _make_targets())
    assert not missing, f"test: depends on undefined targets: {missing}"


def test_makefile_recipes_run_scripts_that_exist():
    text = _read("Makefile")
    scripts = set(re.findall(r"python3? ((?:tools/)?[\w\-]+\.py)", text))
    mods = set(re.findall(r"python3? -m ([A-Za-z_][\w.]*)", text))
    assert scripts and mods
    missing = sorted(s for s in scripts
                     if not os.path.isfile(os.path.join(ROOT, s)))
    missing += sorted(m for m in mods if m.split(".")[0] in REPO_TOP
                      and _module_file(m) is None)
    assert not missing, f"Makefile recipes run what does not exist: " \
        f"{missing}"
