"""R3 — host-sync hazards on the hot paths (``engine/``, ``ops/``,
``parallel/``).

A single stray ``.item()`` or implicit ``np.asarray`` readback in the
enqueue loop serializes the whole chunk pipeline against the device (a
full host round trip per chunk). The rule flags the
sync primitives themselves plus implicit conversions of
device-producing expressions, with a light forward taint pass per
function:

- seeds: ``jnp.*`` / ``jax.lax.*`` calls, calls of this module's jitted
  functions, and calls of known device-producing ops
  (``extract_topk``, ``streaming_topk``, ...);
- propagation: assignment targets whose right side contains a tainted
  name or a seed call become tainted (tuple unpacking included).

Intentional, fenced readbacks are part of the design (the result fetch
IS a readback) — they carry ``# check: allow-host-sync`` and, for
runtime enforcement, go through the *explicit* ``jax.device_get``,
which the ``--sanitize`` transfer guard permits while implicit
conversions raise. Static rule and runtime guard agree by
construction: what R3 wants annotated is exactly what
``jax.transfer_guard("disallow")`` would reject un-annotated.

Known limit (documented, deliberate): taint is per-function and
syntactic, so a device value returned through ``self._solve(...)`` is
not tracked across the method boundary. The runtime sanitizer covers
that remainder — between them the static pass catches the cheap 95%
at zero runtime cost and the guard catches the rest under ``make
check``'s sanitized smoke.
"""

from __future__ import annotations

import ast
from typing import Set

from dmlp_tpu.check.common import ModuleInfo, call_name
from dmlp_tpu.check.findings import Finding

#: path fragments that make a module a hot path for this family —
#: serve/ joined when the resident engine's gate-stats readback turned
#: out to carry a dead allowlist (the serving solve loop is exactly as
#: sync-sensitive as the batch engines); fleet/ joined with the
#: mesh-resident serving engine (its fold loop is the same hot path)
HOT_DIRS = ("dmlp_tpu/engine/", "dmlp_tpu/ops/", "dmlp_tpu/parallel/",
            "dmlp_tpu/serve/", "dmlp_tpu/fleet/")

#: call prefixes whose results live on device (taint seeds)
DEVICE_PREFIXES = ("jnp.", "jax.numpy.", "jax.lax.", "lax.")
#: known device-producing functions by leaf name (imported from ops/)
DEVICE_PRODUCERS = {
    "extract_topk", "streaming_topk", "init_topk", "select_topk",
    "merge_topk", "device_put", "allgather_merge_topk",
    "ring_allreduce_topk", "masked_pairwise_sq_l2", "pallas_distance",
}
#: conversions that force an implicit device->host transfer
_CONVERTERS = {"float": "R303", "int": "R303", "bool": "R303",
               "np.asarray": "R304", "np.array": "R304",
               "numpy.asarray": "R304", "numpy.array": "R304"}

ALLOW = "allow-host-sync"


def in_scope(relpath: str) -> bool:
    rel = relpath.replace("\\", "/")
    return any(rel.startswith(d) or f"/{d}" in rel for d in HOT_DIRS)


def _is_device_call(node: ast.Call, jit_names: Set[str]) -> bool:
    name = call_name(node)
    if name is None:
        return False
    if any(name.startswith(p) for p in DEVICE_PREFIXES):
        return True
    leaf = name.rsplit(".", 1)[-1]
    return leaf in DEVICE_PRODUCERS or name in jit_names


def _contains_device_expr(node: ast.AST, tainted: Set[str],
                          jit_names: Set[str]) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and _is_device_call(sub, jit_names):
            return True
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load) \
                and sub.id in tainted:
            return True
    return False


def _taint_targets(target: ast.AST, tainted: Set[str]) -> None:
    for sub in ast.walk(target):
        if isinstance(sub, ast.Name):
            tainted.add(sub.id)


#: wrappers _launders looks through to find the converting call
_TRANSPARENT = {"list", "tuple", "sorted", "reversed"}
#: resilient_get (engine.single) is the retry-wrapped jax.device_get —
#: its one annotated device_get site is the fence, so its RESULT is a
#: host value exactly like a direct device_get's.
_LAUNDERING = set(_CONVERTERS) | {"jax.device_get", "device_get",
                                  "resilient_get",
                                  "np.ascontiguousarray",
                                  "numpy.ascontiguousarray", "str"}


def _launders(expr: ast.AST) -> bool:
    """Does this RHS produce a HOST value even from device inputs?
    ``np.asarray(x)[:n]``, ``list(jax.device_get(...))``, ``x is None``
    — conversions and identity tests launder taint; flagging their
    *results* downstream would double-count the one real sync."""
    while isinstance(expr, (ast.Subscript, ast.Starred)):
        expr = expr.value
    if isinstance(expr, ast.Compare) \
            and all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in expr.ops):
        return True
    if isinstance(expr, ast.Call):
        name = call_name(expr) or ""
        if name in _LAUNDERING:
            return True
        if name in _TRANSPARENT and expr.args:
            return _launders(expr.args[0])
    return False


def _is_none_test(expr: ast.AST) -> bool:
    return isinstance(expr, ast.Compare) \
        and all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops)


class HostSyncRule:
    def run(self, mod: ModuleInfo, add) -> None:
        if not in_scope(mod.relpath):
            return
        jit_names = {n for n, info in mod.traced.items()
                     if info.kind == "jit"}
        traced_defs = {id(fn) for fn, _ in mod.traced_def_nodes()}
        funcs = [n for n in ast.walk(mod.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for fn in funcs:
            self._run_function(mod, fn, jit_names,
                               id(fn) in traced_defs, add)

    def _run_function(self, mod: ModuleInfo, fn, jit_names: Set[str],
                      is_traced: bool, add) -> None:
        """One forward pass in STATEMENT order: each statement is checked
        against the taint state as of its execution point, then updates
        it — so a laundering rebind (``x = jax.device_get(x)``) clears
        ``x`` for everything after it but not before. Loop-carried taint
        (a use textually before its loop-body def) is the documented
        miss of the single pass."""
        scope = (mod.scope_of(fn) + "." + fn.name).lstrip(".")
        tainted: Set[str] = set()

        def untaint(target: ast.AST) -> None:
            for sub in ast.walk(target):
                if isinstance(sub, ast.Name):
                    tainted.discard(sub.id)

        def check_exprs(node: ast.AST) -> None:
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    self._check_call(mod, sub, scope, tainted, jit_names,
                                     add)

        def visit(stmts) -> None:
            for st in stmts:
                if isinstance(st, ast.Assign):
                    check_exprs(st.value)
                    self._update(st.targets, st.value, tainted,
                                 jit_names, untaint)
                elif isinstance(st, ast.AnnAssign) \
                        and st.value is not None:
                    check_exprs(st.value)
                    self._update([st.target], st.value, tainted,
                                 jit_names, untaint)
                elif isinstance(st, ast.AugAssign):
                    check_exprs(st.value)
                    if _contains_device_expr(st.value, tainted,
                                             jit_names) \
                            and not _launders(st.value):
                        _taint_targets(st.target, tainted)
                elif isinstance(st, ast.For):
                    check_exprs(st.iter)
                    if _contains_device_expr(st.iter, tainted,
                                             jit_names):
                        _taint_targets(st.target, tainted)
                    visit(st.body)
                    visit(st.orelse)
                elif isinstance(st, (ast.If, ast.While)):
                    check_exprs(st.test)
                    if is_traced and not _is_none_test(st.test) \
                            and _contains_device_expr(st.test, tainted,
                                                      jit_names) \
                            and not mod.allowed(st, ALLOW):
                        add(Finding(
                            "R305", mod.relpath, st.lineno,
                            st.col_offset, scope, "traced-branch",
                            "Python branch on a traced value inside a "
                            "jit body — concretization error or silent "
                            "trace-time constant"))
                    visit(st.body)
                    visit(st.orelse)
                elif isinstance(st, (ast.With, ast.AsyncWith)):
                    for item in st.items:
                        check_exprs(item.context_expr)
                    visit(st.body)
                elif isinstance(st, ast.Try):
                    visit(st.body)
                    for h in st.handlers:
                        visit(h.body)
                    visit(st.orelse)
                    visit(st.finalbody)
                elif isinstance(st, (ast.FunctionDef,
                                     ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    pass  # nested defs run as their own functions
                else:
                    check_exprs(st)

        visit(fn.body)

    @staticmethod
    def _update(targets, value, tainted: Set[str], jit_names: Set[str],
                untaint) -> None:
        if _launders(value):
            for t in targets:
                untaint(t)
        elif _contains_device_expr(value, tainted, jit_names):
            for t in targets:
                _taint_targets(t, tainted)
        else:
            for t in targets:
                untaint(t)

    def _check_call(self, mod: ModuleInfo, node: ast.Call, scope: str,
                    tainted: Set[str], jit_names: Set[str], add) -> None:
        name = call_name(node)
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "item" and not node.args \
                and not mod.allowed(node, ALLOW):
            add(Finding(
                "R301", mod.relpath, node.lineno, node.col_offset,
                scope, "item", ".item() forces a blocking device sync"))
            return
        if name in ("jax.device_get", "device_get"):
            if not mod.allowed(node, ALLOW):
                add(Finding(
                    "R302", mod.relpath, node.lineno, node.col_offset,
                    scope, "device_get",
                    "jax.device_get readback — if this fence is "
                    "intentional, annotate `# check: allow-host-sync`"))
            return
        rule = _CONVERTERS.get(name or "")
        if rule and node.args \
                and _contains_device_expr(node.args[0], tainted,
                                          jit_names) \
                and not mod.allowed(node, ALLOW):
            add(Finding(
                rule, mod.relpath, node.lineno, node.col_offset, scope,
                f"convert:{name}",
                f"{name}() on a device-producing expression forces an "
                f"implicit transfer; fence it explicitly with "
                f"jax.device_get (and annotate) if intentional"))
