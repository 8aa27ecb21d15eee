"""R2 — recompilation and stale-trace hazards around ``jax.jit``.

The family exists because of a real review bug (PR 3): the extract
kernel's variant resolution originally ran *inside* the jitted body, so
what it read at trace time was baked into a trace the jit kept
replaying. The fix — resolve outside, make the concrete variant part of
the jit cache key — is now a lint (R203), together with its relatives:

- **R201** non-hashable (mutable) default arguments on jitted
  functions: jax hashes static arguments; a ``[]``/``{}`` default
  either crashes or, worse, silently aliases across traces.
- **R202** f-string construction inside traced bodies: trace-time
  string building is a smell that host state (names, config reprs) is
  leaking into the traced program — except in ``raise``/``assert``
  error paths, which run once at trace time and abort.
- **R203** variant/config resolution (``resolve_*``) inside traced
  bodies — the PR 3 bug class.
- **R204** keyword-only parameters with obviously-static names
  (``select``, ``use_pallas``, ``kc`` ...) missing from
  ``static_argnames``: tracing them as arrays either fails or bakes a
  silent recompile per value.
- **R205** traced bodies closing over module-level mutable literals:
  jit reads them at trace time only; later mutation is silently
  ignored — the closed-over-mutable variant of the stale-cache bug.
"""

from __future__ import annotations

import ast
from typing import Set

from dmlp_tpu.check.common import ModuleInfo, call_name
from dmlp_tpu.check.findings import Finding

#: resolution calls that must happen OUTSIDE traced bodies (R203)
RESOLUTION_FNS = {
    "resolve_variant", "_resolve_variant",
    "resolve_select", "resolve_streaming_select", "resolve_dtype",
    "resolve_granule", "resolve_data_block", "resolve_kcap",
    # the fused-megakernel selection surface (ops.pallas_fused): which
    # kernel runs — and the env kill switch that flips it — must be
    # baked into the jit cache key, never read inside a traced body
    "resolve_topk_kernel", "fused_enabled",
}

#: keyword-only parameter names that are plainly Python-level config —
#: if one of these is traced (not in static_argnames) the jit either
#: fails or recompiles per value (R204). Names that are legitimately
#: traced arrays (n_real, id_base, floor, carries, ...) are NOT listed.
OBVIOUSLY_STATIC = {
    "select", "use_pallas", "interpret", "schedule", "staging",
    "k", "kc", "data_block", "chunk_rows", "query_block", "granule",
    "num_labels", "n_micro", "n_stages", "n_classes", "n_experts",
    "n_virtual", "ne", "unroll", "tile_q", "tile_n", "block_skip",
    "fresh", "capacity", "merge", "mode", "dtype", "na",
}

_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)


def _is_mutable_default(node: ast.AST) -> bool:
    if isinstance(node, _MUTABLE_LITERALS):
        return True
    return isinstance(node, ast.Call) \
        and call_name(node) in ("list", "dict", "set", "bytearray")


def _local_bindings(fn: ast.AST) -> Set[str]:
    """Parameter names + assigned names inside ``fn`` (shadow check)."""
    out = {a.arg for a in fn.args.posonlyargs + fn.args.args
           + fn.args.kwonlyargs}
    if fn.args.vararg:
        out.add(fn.args.vararg.arg)
    if fn.args.kwarg:
        out.add(fn.args.kwarg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(
                node.ctx, (ast.Store, ast.Del)):
            out.add(node.id)
    return out


def _in_error_path(mod: ModuleInfo, node: ast.AST) -> bool:
    cur = node
    while cur is not None:
        if isinstance(cur, (ast.Raise, ast.Assert)):
            return True
        cur = mod.parents.get(cur)
    return False


class RecompileRule:
    def run(self, mod: ModuleInfo, add) -> None:
        traced = mod.traced_def_nodes()
        for fn, info in traced:
            if mod.allowed(fn, "allow-recompile"):
                continue
            scope = (mod.scope_of(fn) + "." + fn.name).lstrip(".")
            for d in list(fn.args.defaults) + [
                    d for d in fn.args.kw_defaults if d is not None]:
                if _is_mutable_default(d):
                    add(Finding(
                        "R201", mod.relpath, d.lineno, d.col_offset,
                        scope, "mutable-default",
                        f"jitted function {fn.name} has a mutable "
                        f"(non-hashable) default argument"))
            if info.kind == "jit" and info.static_argnames:
                for a in fn.args.kwonlyargs:
                    if a.arg in OBVIOUSLY_STATIC \
                            and a.arg not in info.static_argnames:
                        add(Finding(
                            "R204", mod.relpath, a.lineno, a.col_offset,
                            scope, f"static:{a.arg}",
                            f"keyword-only param {a.arg!r} of jitted "
                            f"{fn.name} looks static but is missing "
                            f"from static_argnames"))
            self._body_checks(mod, fn, scope, add)
            self._closure_check(mod, fn, scope, add)

    def _body_checks(self, mod: ModuleInfo, fn, scope: str, add) -> None:
        for node in ast.walk(fn):
            if isinstance(node, ast.JoinedStr) \
                    and not isinstance(mod.parents.get(node),
                                       ast.FormattedValue) \
                    and not _in_error_path(mod, node) \
                    and not mod.allowed(node, "allow-recompile"):
                add(Finding(
                    "R202", mod.relpath, node.lineno, node.col_offset,
                    scope, "fstring",
                    f"f-string built inside traced body {fn.name} — "
                    f"host state leaking into the trace"))
            if isinstance(node, ast.Call):
                name = call_name(node)
                leaf = name.rsplit(".", 1)[-1] if name else None
                if leaf in RESOLUTION_FNS \
                        and not mod.allowed(node, "allow-recompile"):
                    add(Finding(
                        "R203", mod.relpath, node.lineno,
                        node.col_offset, scope, f"resolve:{leaf}",
                        f"{leaf}() runs inside traced body {fn.name}; "
                        f"hoist it out so the resolved value is part "
                        f"of the jit cache key (PR 3 stale-trace bug)"))

    def _closure_check(self, mod: ModuleInfo, fn, scope: str, add) -> None:
        if not mod.mutable_globals:
            return
        local = _local_bindings(fn)
        seen: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load) \
                    and node.id in mod.mutable_globals \
                    and node.id not in local and node.id not in seen \
                    and not mod.allowed(node, "allow-recompile"):
                seen.add(node.id)
                add(Finding(
                    "R205", mod.relpath, node.lineno, node.col_offset,
                    scope, f"closure:{node.id}",
                    f"traced body {fn.name} closes over module-level "
                    f"mutable {node.id!r}: jit reads it at trace time "
                    f"only, later mutation is silently ignored"))
