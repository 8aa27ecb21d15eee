"""dmlp_tpu.check — the static analysis suite.

Three layers: (1) fixture snippets per rule family, positive AND
negative, proving each seeded violation class is caught and each
legitimate idiom is not; (2) the REAL package, which must be clean of
non-baselined findings (the committed baseline is empty — keep it so);
(3) the baseline round-trip (new finding fails -> baselined passes ->
fixed reports stale) and the ``--json`` CLI contract.
"""

import json
import os
import subprocess
import sys
import textwrap

from dmlp_tpu.check.analyzer import (analyze_package, analyze_paths,
                                     package_root)
from dmlp_tpu.check.baseline import (diff_baseline, load_baseline,
                                     save_baseline)


def write(tmp_path, rel, source):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return str(p)


def rules_of(findings):
    return sorted(f.rule for f in findings)


def run_check(tmp_path, families):
    return analyze_paths([str(tmp_path)], families, root=str(tmp_path))


# ---------------------------------------------------------------------------
# R1 — collective-axis contract
# ---------------------------------------------------------------------------

MESH_SRC = """
DATA_AXIS = "data"
QUERY_AXIS = "query"
"""


class TestR1Collectives:
    def test_r101_undeclared_axis_caught(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            def f(x):
                return jax.lax.psum(x, "bogus")
        """)
        fs = run_check(tmp_path, ["R1"])
        assert "R101" in rules_of(fs)
        assert any("bogus" in f.message for f in fs)

    def test_r101_declared_axis_clean_incl_constant(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            from dmlp_tpu.parallel.mesh import DATA_AXIS
            def f(x):
                return jax.lax.psum(x, DATA_AXIS) + \\
                    jax.lax.axis_index("query")
        """)
        assert run_check(tmp_path, ["R1"]) == []

    def test_r102_axis_not_in_shard_map_specs(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from dmlp_tpu.utils.compat import shard_map
            from jax.sharding import PartitionSpec as P

            def build(mesh):
                def local(a):
                    return jax.lax.psum(a, "query")  # check: no-traffic
                return shard_map(local, mesh=mesh,
                                 in_specs=(P("data"),),
                                 out_specs=P("data"))
        """)
        fs = run_check(tmp_path, ["R1"])
        assert "R102" in rules_of(fs)

    def test_r102_spec_axis_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from dmlp_tpu.utils.compat import shard_map
            from jax.sharding import PartitionSpec as P

            def build(mesh):
                def local(a):
                    return jax.lax.psum(a, "data")  # check: no-traffic
                return shard_map(local, mesh=mesh,
                                 in_specs=(P("data"),),
                                 out_specs=P("data"))
        """)
        assert run_check(tmp_path, ["R1"]) == []

    def test_r103_unannotated_traffic_collective(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/train/x.py", """
            import jax
            def f(x):
                return jax.lax.psum(x, "data")
        """)
        assert "R103" in rules_of(run_check(tmp_path, ["R1"]))

    def test_r103_annotated_with_real_model_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/obs/comms.py", """
            def psum_traffic(nbytes, axis_size):
                return nbytes
        """)
        write(tmp_path, "dmlp_tpu/train/x.py", """
            import jax
            def f(x):
                # check: comms-model=psum_traffic
                return jax.lax.psum(x, "data")
        """)
        assert run_check(tmp_path, ["R1"]) == []

    def test_r104_annotation_names_missing_model(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/obs/comms.py", "def real_model():\n    pass\n")
        write(tmp_path, "dmlp_tpu/train/x.py", """
            import jax
            def f(x):
                # check: comms-model=renamed_away_traffic
                return jax.lax.psum(x, "data")
        """)
        assert "R104" in rules_of(run_check(tmp_path, ["R1"]))

    def test_axis_helper_call_site_checked(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/parallel/helpers.py", """
            import jax
            def merge(local, k, axis_name):
                # check: comms-model=m
                return jax.lax.all_gather(local, axis_name)
        """)
        write(tmp_path, "dmlp_tpu/obs/comms.py", "def m():\n    pass\n")
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.parallel.helpers import merge
            def f(local, k):
                return merge(local, k, "not_an_axis")
        """)
        fs = run_check(tmp_path, ["R1"])
        assert "R101" in rules_of(fs)
        assert any(f.path.endswith("engine/x.py") for f in fs)


# ---------------------------------------------------------------------------
# R105/R106 — kernel-dispatch cost coverage (R1 family)
# ---------------------------------------------------------------------------


class TestDispatchCost:
    def test_r105_dispatch_without_probe(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops.pallas_fused import fused_topk

            def drive(q, d):
                obs_counters.record_dispatch(fused_topk, (q, d), site="s")
                return fused_topk(q, d, n_real=4, kc=8)
        """)
        fs = run_check(tmp_path, ["R1"])
        assert "R105" in rules_of(fs)
        assert any("MeasuredIters" in f.message for f in fs)

    def test_r105_resolver_bound_kernel_var_covered(self, tmp_path):
        """``kern, impl = resolve_topk_kernel(...)`` binds a kernel
        variable — dispatching it without a probe is the same hole."""
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops import pallas_fused

            def drive(q, d):
                kern, impl = pallas_fused.resolve_topk_kernel(8, 8, 8, 8)
                obs_counters.record_dispatch(kern, (q, d), site="s")
                return kern(q, d, n_real=4, kc=8)
        """)
        assert "R105" in rules_of(run_check(tmp_path, ["R1"]))

    def test_r105_probe_in_function_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.engine.single import MeasuredIters
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops.pallas_fused import fused_topk

            def drive(eng, q, d):
                mi = MeasuredIters(eng, "s", (1, 2, 3, 4))
                obs_counters.record_dispatch(fused_topk, (q, d), site="s")
                od, oi, it = fused_topk(q, d, n_real=4, kc=8)
                mi.add(it)
                mi.done()
                return od
        """)
        assert run_check(tmp_path, ["R1"]) == []

    def test_r105_queue_iters_protocol_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops import pallas_fused

            def drive(self, q, d):
                kern, impl = pallas_fused.resolve_topk_kernel(8, 8, 8, 8)
                obs_counters.record_dispatch(kern, (q, d), site="s")
                od, oi, it = kern(q, d, n_real=4, kc=8)
                self._queue_iters("s", "extract", it, 8, 8, 8, 8,
                                  impl=impl)
                return od
        """)
        assert run_check(tmp_path, ["R1"]) == []

    def test_r106_unmodeled_ops_kernel(self, tmp_path):
        """A kernel imported from dmlp_tpu.ops with no analytic_cost
        registry entry (parsed from the REAL kernel_cost.py) fails —
        the fused-megakernel drift class."""
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.engine.single import MeasuredIters
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops.pallas_next import hyper_kernel

            def drive(eng, q, d):
                mi = MeasuredIters(eng, "s", (1, 2, 3, 4))
                obs_counters.record_dispatch(hyper_kernel, (q, d),
                                             site="s")
                od, oi, it = hyper_kernel(q, d, n_real=4, kc=8)
                mi.add(it)
                mi.done()
                return od
        """)
        fs = run_check(tmp_path, ["R1"])
        assert rules_of(fs) == ["R106"]
        assert any("hyper_kernel" in f.message for f in fs)

    def test_r106_registered_kernels_clean(self, tmp_path):
        """extract_topk and fused_topk ARE in the parsed model table —
        this pins the registry parse itself (an empty parse would make
        R106 fire on every legitimate dispatch or none)."""
        from dmlp_tpu.check.analyzer import load_modules
        from dmlp_tpu.check.dispatchcost import _modeled_kernels
        mods, _ = load_modules([package_root()])
        modeled = _modeled_kernels(mods)
        assert {"extract_topk", "fused_topk",
                "fused_dist_segmin"} <= modeled

    def test_r105_allow_directive(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops.pallas_fused import fused_topk

            def drive(q, d):
                # check: allow-collective
                obs_counters.record_dispatch(fused_topk, (q, d), site="s")
                return fused_topk(q, d, n_real=4, kc=8)
        """)
        assert run_check(tmp_path, ["R1"]) == []

    def test_r105_outside_engine_ignored(self, tmp_path):
        """tools/bench measure what they please — engine/ only."""
        write(tmp_path, "dmlp_tpu/bench/x.py", """
            from dmlp_tpu.obs import counters as obs_counters
            from dmlp_tpu.ops.pallas_fused import fused_topk

            def drive(q, d):
                obs_counters.record_dispatch(fused_topk, (q, d), site="s")
                return fused_topk(q, d, n_real=4, kc=8)
        """)
        assert run_check(tmp_path, ["R1"]) == []


# ---------------------------------------------------------------------------
# R2 — recompilation hazards
# ---------------------------------------------------------------------------


class TestR2Recompile:
    def test_r203_fused_selection_inside_jit(self, tmp_path):
        """ISSUE 8 small fix: the fused/two-pass selection
        (resolve_topk_kernel, and the kill-switch read behind it) is
        the PR 3 in-jit-resolution bug class — R203 must provably
        cover it so the choice is always part of the jit cache key."""
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from dmlp_tpu.ops.pallas_fused import resolve_topk_kernel

            @jax.jit
            def solve(q, d):
                kern, impl = resolve_topk_kernel(8, 8, 8, 8)
                return kern(q, d, n_real=4, kc=8)
        """)
        fs = run_check(tmp_path, ["R2"])
        assert "R203" in rules_of(fs)
        assert any("resolve_topk_kernel" in f.message for f in fs)

    def test_r203_fused_kill_switch_read_inside_jit(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from dmlp_tpu.ops.pallas_fused import fused_enabled

            @jax.jit
            def solve(q, d):
                if fused_enabled():
                    return q
                return d
        """)
        assert "R203" in rules_of(run_check(tmp_path, ["R2"]))

    def test_r203_fused_selection_outside_jit_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import functools
            import jax
            from dmlp_tpu.ops.pallas_fused import resolve_topk_kernel

            def solve(q, d):
                kern, impl = resolve_topk_kernel(8, 8, 8, 8)
                run = jax.jit(functools.partial(kern, n_real=4, kc=8))
                return run(q, d)
        """)
        assert "R203" not in rules_of(run_check(tmp_path, ["R2"]))
    def test_r201_mutable_default_on_jit(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            @jax.jit
            def f(x, opts=[]):
                return x
        """)
        assert "R201" in rules_of(run_check(tmp_path, ["R2"]))

    def test_r202_fstring_in_jit_body(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            @jax.jit
            def f(x):
                name = f"variant_{x.shape}"
                return x, name
        """)
        assert "R202" in rules_of(run_check(tmp_path, ["R2"]))

    def test_r202_fstring_in_raise_is_fine(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            @jax.jit
            def f(x):
                if x.shape[0] % 8:
                    raise ValueError(f"bad shape {x.shape}")
                return x
        """)
        assert run_check(tmp_path, ["R2"]) == []

    def test_r203_variant_resolution_inside_jit(self, tmp_path):
        # The PR 3 review bug, reduced: the variant resolved inside the
        # traced body -> baked into a trace the jit keeps replaying.
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            from dmlp_tpu.ops.pallas_extract import resolve_variant
            @jax.jit
            def f(x):
                v = resolve_variant(8, x.shape[0])
                return x * v["ne"]
        """)
        assert "R203" in rules_of(run_check(tmp_path, ["R2"]))

    def test_r203_resolution_outside_jit_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            from dmlp_tpu.ops.pallas_extract import resolve_variant
            @jax.jit
            def _impl(x, ne):
                return x * ne
            def f(x):
                v = resolve_variant(8, x.shape[0])
                return _impl(x, v["ne"])
        """)
        assert run_check(tmp_path, ["R2"]) == []

    def test_r204_obviously_static_kwonly_missing(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("k",))
            def f(x, *, k, select):
                return x[:k] if select == "sort" else x
        """)
        fs = run_check(tmp_path, ["R2"])
        assert "R204" in rules_of(fs)
        assert any("select" in f.message for f in fs)

    def test_r204_traced_kwonly_names_not_flagged(self, tmp_path):
        # n_real/id_base/floor style params are legitimately traced.
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import functools
            import jax
            @functools.partial(jax.jit, static_argnames=("kc",))
            def f(x, *, n_real, id_base, kc, floor):
                return x[:kc] + n_real + id_base
        """)
        assert run_check(tmp_path, ["R2"]) == []

    def test_r205_closure_over_module_mutable(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            _CACHE = {}
            @jax.jit
            def f(x):
                return x * len(_CACHE)
        """)
        assert "R205" in rules_of(run_check(tmp_path, ["R2"]))

    def test_shard_mapped_body_is_traced_too(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.utils.compat import shard_map
            def build(mesh, specs):
                def local(a):
                    tag = f"cell_{a.shape}"
                    return a, tag
                return shard_map(local, mesh=mesh, in_specs=specs,
                                 out_specs=specs)
        """)
        assert "R202" in rules_of(run_check(tmp_path, ["R2"]))


# ---------------------------------------------------------------------------
# R3 — host-sync hazards
# ---------------------------------------------------------------------------


class TestR3HostSync:
    def test_r301_item(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            def f(arr):
                return arr.item()
        """)
        assert "R301" in rules_of(run_check(tmp_path, ["R3"]))

    def test_r302_device_get_needs_annotation(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            def f(arr):
                return jax.device_get(arr)
        """)
        assert "R302" in rules_of(run_check(tmp_path, ["R3"]))

    def test_allowlist_comment_silences(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            def f(arr):
                return jax.device_get(arr)  # check: allow-host-sync
        """)
        assert run_check(tmp_path, ["R3"]) == []

    def test_trailing_allowlist_does_not_leak_to_next_line(self, tmp_path):
        # A trailing directive covers ITS statement only; the
        # un-annotated implicit transfer on the next line must still
        # flag (review finding: `lineno - 1` lookups silently widened
        # every allowlist by one line).
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            import numpy as np
            import jax.numpy as jnp
            def f(x):
                fetched = jax.device_get(x)  # check: allow-host-sync
                return np.asarray(jnp.sum(x))
        """)
        assert "R304" in rules_of(run_check(tmp_path, ["R3"]))

    def test_r303_float_on_device_expr(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax.numpy as jnp
            def f(a, b):
                s = jnp.dot(a, b)
                return float(s)
        """)
        assert "R303" in rules_of(run_check(tmp_path, ["R3"]))

    def test_r304_np_asarray_on_device_expr(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import numpy as np
            import jax.numpy as jnp
            def f(a):
                out = jnp.sort(a)
                return np.asarray(out)
        """)
        assert "R304" in rules_of(run_check(tmp_path, ["R3"]))

    def test_device_get_launders_taint(self, tmp_path):
        # The sanctioned pattern: explicit fence, then host math freely.
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            import numpy as np
            import jax.numpy as jnp
            def f(a):
                out = jnp.sort(a)
                # check: allow-host-sync
                out = jax.device_get(out)
                return float(np.asarray(out)[0])
        """)
        assert run_check(tmp_path, ["R3"]) == []

    def test_host_numpy_untouched(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import numpy as np
            def f(attrs):
                a = np.zeros((8, 4), np.float32)
                a[:4] = attrs
                return float(np.einsum("na,na->n", a, a).max())
        """)
        assert run_check(tmp_path, ["R3"]) == []

    def test_r305_branch_on_traced_value(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            import jax.numpy as jnp
            @jax.jit
            def f(x):
                if jnp.sum(x) > 0:
                    return x
                return -x
        """)
        assert "R305" in rules_of(run_check(tmp_path, ["R3"]))

    def test_is_none_branch_in_jit_is_fine(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            import jax.numpy as jnp
            @jax.jit
            def f(x, carry):
                if carry is None:
                    carry = jnp.zeros_like(x)
                return x + carry
        """)
        assert run_check(tmp_path, ["R3"]) == []

    def test_out_of_scope_dirs_ignored(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            def f(arr):
                return arr.item()
        """)
        assert run_check(tmp_path, ["R3"]) == []


# ---------------------------------------------------------------------------
# R4 — compat-bypass
# ---------------------------------------------------------------------------


class TestR4Compat:
    def test_r401_shard_map_import(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from jax.experimental.shard_map import shard_map
        """)
        assert "R401" in rules_of(run_check(tmp_path, ["R4"]))

    def test_r402_axis_size_attr(self, tmp_path):
        write(tmp_path, "dmlp_tpu/train/x.py", """
            import jax
            def f(ax):
                return jax.lax.axis_size(ax)
        """)
        assert "R402" in rules_of(run_check(tmp_path, ["R4"]))

    def test_r403_compiler_params_attr(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            from jax.experimental.pallas import tpu as pltpu
            def f():
                return pltpu.CompilerParams()
        """)
        assert "R403" in rules_of(run_check(tmp_path, ["R4"]))

    def test_r404_memory_kind_literal(self, tmp_path):
        write(tmp_path, "dmlp_tpu/train/x.py", """
            def f(sharding):
                return sharding.with_memory_kind("pinned_host")
        """)
        assert "R404" in rules_of(run_check(tmp_path, ["R4"]))

    def test_compat_module_exempt(self, tmp_path):
        write(tmp_path, "dmlp_tpu/utils/compat.py", """
            import jax
            def axis_size(ax):
                if hasattr(jax.lax, "axis_size"):
                    return jax.lax.axis_size(ax)
                return jax.lax.psum(1, ax)
            def host_memory_kind():
                return "pinned_host"
        """)
        assert run_check(tmp_path, ["R4"]) == []

    def test_docstring_mention_not_flagged(self, tmp_path):
        write(tmp_path, "dmlp_tpu/train/x.py", '''
            def f():
                """Docs may say "pinned_host" freely."""
                return None
        ''')
        assert run_check(tmp_path, ["R4"]) == []


# ---------------------------------------------------------------------------
# R5 — resilience-path silent swallowing
# ---------------------------------------------------------------------------


class TestR5Resilient:
    def test_r501_broad_swallow_in_resilience_module(self, tmp_path):
        write(tmp_path, "dmlp_tpu/resilience/x.py", """
            def f(op):
                try:
                    return op()
                except Exception:
                    return None
        """)
        assert "R501" in rules_of(run_check(tmp_path, ["R5"]))

    def test_r501_importer_of_resilience_in_scope(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from dmlp_tpu.resilience import retry as rs_retry
            def f(op):
                try:
                    return rs_retry.call_with_retry(op, "s")
                except Exception:
                    return None
        """)
        assert "R501" in rules_of(run_check(tmp_path, ["R5"]))

    def test_r501_reraise_is_compliant(self, tmp_path):
        write(tmp_path, "dmlp_tpu/resilience/x.py", """
            def f(op):
                try:
                    return op()
                except Exception as e:
                    raise RuntimeError("wrapped") from e
        """)
        assert run_check(tmp_path, ["R5"]) == []

    def test_r501_annotation_silences(self, tmp_path):
        write(tmp_path, "dmlp_tpu/resilience/x.py", """
            def f(op):
                try:
                    return op()
                except Exception:  # check: no-retry
                    return None
        """)
        assert run_check(tmp_path, ["R5"]) == []

    def test_r501_narrow_catch_is_fine(self, tmp_path):
        write(tmp_path, "dmlp_tpu/resilience/x.py", """
            def f(op):
                try:
                    return op()
                except ValueError:
                    return None
        """)
        assert run_check(tmp_path, ["R5"]) == []

    def test_r501_nested_def_raise_does_not_count(self, tmp_path):
        # Defining a raiser inside the handler is not raising: the
        # swallow still needs a re-raise or the annotation.
        write(tmp_path, "dmlp_tpu/resilience/x.py", """
            def f(op):
                try:
                    return op()
                except Exception:
                    def _report():
                        raise RuntimeError("later")
                    return None
        """)
        assert "R501" in rules_of(run_check(tmp_path, ["R5"]))

    def test_module_without_resilience_import_out_of_scope(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            def f(op):
                try:
                    return op()
                except Exception:
                    return None
        """)
        assert run_check(tmp_path, ["R5"]) == []

# ---------------------------------------------------------------------------
# R6 — telemetry metric-name contract (obs.telemetry registry)
# ---------------------------------------------------------------------------


class TestR6MetricNames:
    def test_r601_fstring_name_caught(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            def f(site):
                REGISTRY.counter(f"retries.{site}").inc()
        """)
        assert "R601" in rules_of(run_check(tmp_path, ["R6"]))

    def test_r601_variable_name_caught(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            from dmlp_tpu.obs import telemetry
            def f(name):
                telemetry.registry().gauge(name).set(1)
        """)
        assert "R601" in rules_of(run_check(tmp_path, ["R6"]))

    def test_r601_camelcase_literal_caught(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            REGISTRY.histogram("SolveLatencyMs")
        """)
        assert "R601" in rules_of(run_check(tmp_path, ["R6"]))

    def test_r601_literal_dotted_snake_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            def f(site):
                REGISTRY.counter("engine.retries").inc(label=site)
                REGISTRY.gauge("mem.device.bytes_in_use").set(1)
                REGISTRY.histogram("span.latency_ms").observe(2.5)
        """)
        assert run_check(tmp_path, ["R6"]) == []

    def test_r601_annotation_silences_deliberate_seam(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            def f(safe):
                h = REGISTRY.histogram(safe + ".ms")  # check: allow-metric-name
                h.observe(1.0)
        """)
        assert run_check(tmp_path, ["R6"]) == []

    def test_r602_conflicting_kinds_cross_module(self, tmp_path):
        write(tmp_path, "dmlp_tpu/obs/a.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            REGISTRY.counter("engine.solves")
        """)
        write(tmp_path, "dmlp_tpu/obs/b.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            REGISTRY.gauge("engine.solves")
        """)
        fs = run_check(tmp_path, ["R6"])
        assert "R602" in rules_of(fs)

    def test_r602_same_kind_many_sites_clean(self, tmp_path):
        # get-or-create is the contract: one name, one kind, any
        # number of use sites.
        write(tmp_path, "dmlp_tpu/obs/a.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            REGISTRY.counter("engine.solves")
        """)
        write(tmp_path, "dmlp_tpu/obs/b.py", """
            from dmlp_tpu.obs.telemetry import REGISTRY
            REGISTRY.counter("engine.solves").inc()
        """)
        assert run_check(tmp_path, ["R6"]) == []

    def test_non_registry_receiver_out_of_scope(self, tmp_path):
        # A collections.Counter-style .counter attr on a non-registry
        # object must not trip the rule.
        write(tmp_path, "dmlp_tpu/obs/x.py", """
            def f(store, name):
                store.counter(name)
        """)
        assert run_check(tmp_path, ["R6"]) == []


# ---------------------------------------------------------------------------
# R7 — concurrency discipline
# ---------------------------------------------------------------------------


class TestR7Concurrency:
    def test_r701_inversion_across_functions(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            LOCK_A = threading.Lock()
            LOCK_B = threading.Lock()
            def f():
                with LOCK_A:
                    with LOCK_B:
                        pass
            def g():
                with LOCK_B:
                    with LOCK_A:
                        pass
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R701" in rules_of(fs)
        assert any("inverts" in f.message for f in fs)

    def test_r701_consistent_order_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            LOCK_A = threading.Lock()
            LOCK_B = threading.Lock()
            def f():
                with LOCK_A:
                    with LOCK_B:
                        pass
            def g():
                with LOCK_A:
                    with LOCK_B:
                        pass
        """)
        assert run_check(tmp_path, ["R7"]) == []

    def test_r701_cross_module_inversion_via_call(self, tmp_path):
        # a holds A and calls b's taker (A->B); b holds B and calls
        # a's taker (B->A): the cycle spans modules and call chains.
        write(tmp_path, "dmlp_tpu/serve/a.py", """
            import threading
            from dmlp_tpu.serve.b import take_b
            LOCK_A = threading.Lock()
            def take_a():
                with LOCK_A:
                    pass
            def f():
                with LOCK_A:
                    take_b()
        """)
        write(tmp_path, "dmlp_tpu/serve/b.py", """
            import threading
            from dmlp_tpu.serve.a import take_a
            LOCK_B = threading.Lock()
            def take_b():
                with LOCK_B:
                    pass
            def g():
                with LOCK_B:
                    take_a()
        """)
        fs = run_check(tmp_path, ["R7"])
        assert rules_of(fs).count("R701") >= 2  # both edges flagged

    def test_r701_nested_nonreentrant_self_deadlock(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                def f(self):
                    with self._lock:
                        with self._lock:
                            pass
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R701" in rules_of(fs)
        assert any("self-deadlock" in f.message for f in fs)

    def test_r702_unguarded_read_of_guarded_field(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def add(self):
                    with self._lock:
                        self.n += 1
                def peek(self):
                    return self.n
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R702" in rules_of(fs)
        assert any("self.n" in f.message for f in fs)

    def test_r702_guarded_access_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def add(self):
                    with self._lock:
                        self.n += 1
                def peek(self):
                    with self._lock:
                        return self.n
        """)
        assert run_check(tmp_path, ["R7"]) == []

    def test_r702_mutable_escape_by_reference(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []
                def add(self, x):
                    with self._lock:
                        self._items = self._items + [x]
                def items(self):
                    with self._lock:
                        return self._items
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R702" in rules_of(fs)
        assert any("escape" in f.key for f in fs)

    def test_r702_copy_return_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._items = []
                def add(self, x):
                    with self._lock:
                        self._items = self._items + [x]
                def items(self):
                    with self._lock:
                        return list(self._items)
        """)
        assert run_check(tmp_path, ["R7"]) == []

    def test_r702_allow_directive_with_invariant(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def add(self):
                    with self._lock:
                        self.n += 1
                def peek(self):
                    # check: allow-concurrency=R702 — racy int read is
                    # benign: single GIL load, monitoring only
                    return self.n
        """)
        assert run_check(tmp_path, ["R7"]) == []

    def test_r703_sleep_under_lock(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            import time
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                def run(self):
                    with self._lock:
                        time.sleep(0.1)
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R703" in rules_of(fs)

    def test_r703_call_mediated_sleep_under_lock(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            import time
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                def _nap(self):
                    time.sleep(0.01)
                def run(self):
                    with self._lock:
                        self._nap()
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R703" in rules_of(fs)
        assert any("_nap" in f.message for f in fs)

    def test_r703_sleep_outside_lock_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            import time
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                def run(self):
                    with self._lock:
                        n = 1
                    time.sleep(0.1)
                    return n
        """)
        assert run_check(tmp_path, ["R7"]) == []

    def test_r703_condition_wait_on_held_lock_clean(self, tmp_path):
        # cond.wait RELEASES the held lock — the legal blocking wait.
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            class Q:
                def __init__(self):
                    self._cond = threading.Condition()
                    self.items = []
                def get(self):
                    with self._cond:
                        while not self.items:
                            self._cond.wait(timeout=0.1)
                        return self.items.pop()
        """)
        fs = run_check(tmp_path, ["R7"])
        assert "R703" not in rules_of(fs)

    def test_r704_thread_without_daemon_or_join(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            def go(f):
                t = threading.Thread(target=f)
                t.start()
        """)
        assert "R704" in rules_of(run_check(tmp_path, ["R7"]))

    def test_r704_daemon_thread_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            def go(f):
                threading.Thread(target=f, daemon=True).start()
        """)
        assert run_check(tmp_path, ["R7"]) == []

    def test_r704_joined_thread_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/serve/x.py", """
            import threading
            def go(f):
                t = threading.Thread(target=f)
                t.start()
                t.join()
        """)
        assert run_check(tmp_path, ["R7"]) == []


# ---------------------------------------------------------------------------
# R9 — compiler-sharded (GSPMD) surface contract
# ---------------------------------------------------------------------------


class TestR9AutoShard:
    def test_r901_undeclared_pspec_axis_caught(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from jax.sharding import NamedSharding, PartitionSpec as P
            def shardings(mesh):
                return NamedSharding(mesh, P("dataa", None))
        """)
        fs = run_check(tmp_path, ["R9"])
        assert "R901" in rules_of(fs)
        assert any("dataa" in f.message for f in fs)

    def test_r901_declared_axes_and_none_entries_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from jax.sharding import NamedSharding, PartitionSpec as P
            from dmlp_tpu.parallel.mesh import DATA_AXIS, QUERY_AXIS
            def shardings(mesh):
                return (NamedSharding(mesh, P(DATA_AXIS, None, None)),
                        NamedSharding(mesh, P(QUERY_AXIS, None)),
                        NamedSharding(mesh, P()))
        """)
        assert run_check(tmp_path, ["R9"]) == []

    def test_r901_allow_directive_respected(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            from jax.sharding import PartitionSpec as P
            def spec():
                # check: allow-auto-shard=R901 — doc example axis
                return P("stage")
        """)
        assert run_check(tmp_path, ["R9"]) == []

    def test_r902_unpinned_jit_in_auto_engine_caught(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/auto.py", """
            import jax
            def build(fn):
                return jax.jit(fn)
        """)
        fs = run_check(tmp_path, ["R9"])
        assert "R902" in rules_of(fs)
        assert any("in_shardings" in f.message for f in fs)

    def test_r902_pinned_jit_clean_and_other_files_exempt(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/engine/auto.py", """
            import jax
            def build(fn, ins, outs):
                return jax.jit(fn, in_shardings=ins, out_shardings=outs)
        """)
        write(tmp_path, "dmlp_tpu/engine/other.py", """
            import jax
            def build(fn):
                return jax.jit(fn)
        """)
        assert run_check(tmp_path, ["R9"]) == []


# ---------------------------------------------------------------------------
# --stale-allows + the fingerprint cache
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# R8 — low-precision MXU contract (the Pallas kernel modules)
# ---------------------------------------------------------------------------

SPLIT_SRC = """
    import jax.numpy as jnp
    def split_bf16(x):
        hi = x.astype(jnp.bfloat16){note}
        rest = x - hi.astype(jnp.float32)
        return hi, rest.astype(jnp.bfloat16){note}
"""


class TestR8LowPrec:
    """The "bf16x3" form's split (ops/pallas_extract.py:split_bf16) is
    two casts below float32: each must name the bound that covers it."""

    def test_r802_flags_the_split_written_without_its_bound(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/pallas_x.py",
              SPLIT_SRC.format(note=""))
        fs = run_check(tmp_path, ["R8"])
        assert rules_of(fs) == ["R802", "R802"]
        assert all("bfloat16" in f.message for f in fs)

    def test_r802_annotated_split_is_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/ops/pallas_x.py",
              SPLIT_SRC.format(note="  # check: lowp-eps=lowp_eps"))
        assert run_check(tmp_path, ["R8"]) == []

    def test_r803_the_named_bound_must_exist(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/finalize.py", """
            def lowp_eps(precision, qn, dn_max):
                return 0.0
        """)
        write(tmp_path, "dmlp_tpu/ops/pallas_x.py",
              SPLIT_SRC.format(note="  # check: lowp-eps=split_eps"))
        assert rules_of(run_check(tmp_path, ["R8"])) == ["R803", "R803"]

    def test_r8_scope_is_the_kernel_modules(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", SPLIT_SRC.format(note=""))
        assert run_check(tmp_path, ["R8"]) == []


class TestStaleAllows:
    def test_dead_directive_reported_live_one_kept(self, tmp_path):
        from dmlp_tpu.check.analyzer import (analyze_paths_tracking,
                                             stale_allow_directives)
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            def live(arr):
                return jax.device_get(arr)  # check: allow-host-sync
            def dead(arr):
                return arr  # check: allow-host-sync
        """)
        _fs, mods = analyze_paths_tracking(
            [str(tmp_path)], ["R0", "R1", "R2", "R3", "R4", "R5", "R6",
                              "R7"], root=str(tmp_path))
        stale = stale_allow_directives(mods)
        assert [(ln, d) for _p, ln, d in stale] == \
            [(6, "allow-host-sync")]

    def test_prose_mentions_not_reported(self, tmp_path):
        from dmlp_tpu.check.analyzer import (analyze_paths_tracking,
                                             stale_allow_directives)
        write(tmp_path, "dmlp_tpu/obs/x.py", '''
            def f():
                """Docs may say annotate `# check: no-retry` freely."""
                return 1
        ''')
        _fs, mods = analyze_paths_tracking(
            [str(tmp_path)], ["R5"], root=str(tmp_path))
        assert stale_allow_directives(mods) == []

    def test_cli_stale_allows_json(self, tmp_path):
        write(tmp_path, "dmlp_tpu/engine/x.py", """
            def dead(arr):
                return arr  # check: allow-host-sync
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "dmlp_tpu.check", "--stale-allows",
             "--json", str(tmp_path / "dmlp_tpu")],
            capture_output=True, text=True, env=env)
        assert r.returncode == 1
        verdict = json.loads(r.stdout)
        assert verdict["ok"] is False
        assert verdict["stale_allows"][0]["directive"] == \
            "allow-host-sync"


VIOLATION_R1 = """
import jax
def f(x):
    return jax.lax.psum(x, "bogus")
"""


class TestFingerprintCache:
    def _cache(self, tmp_path):
        from dmlp_tpu.check.cache import CheckCache
        return CheckCache(directory=str(tmp_path / "cache"),
                          enabled=True)

    def test_second_run_hits_and_findings_identical(self, tmp_path):
        from dmlp_tpu.check.analyzer import analyze_paths
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION_R1)
        c1 = self._cache(tmp_path)
        cold = analyze_paths([str(tmp_path)], ["R1"],
                             root=str(tmp_path), cache=c1)
        assert c1.misses == 2 and c1.hits == 0
        c2 = self._cache(tmp_path)
        warm = analyze_paths([str(tmp_path)], ["R1"],
                             root=str(tmp_path), cache=c2)
        assert c2.hits == 2 and c2.misses == 0
        assert [f.fingerprint() for f in warm] == \
            [f.fingerprint() for f in cold]
        assert "R101" in rules_of(warm)

    def test_edit_invalidates_only_the_changed_file(self, tmp_path):
        from dmlp_tpu.check.analyzer import analyze_paths
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        src = write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION_R1)
        analyze_paths([str(tmp_path)], ["R1"], root=str(tmp_path),
                      cache=self._cache(tmp_path))
        # facts-neutral edit (a comment): only x.py re-analyzes
        with open(src) as f:
            body = f.read()
        open(src, "w").write("# shifted\n" + body)
        c = self._cache(tmp_path)
        fs = analyze_paths([str(tmp_path)], ["R1"], root=str(tmp_path),
                           cache=c)
        assert c.hits == 1 and c.misses == 1
        assert "R101" in rules_of(fs)
        # the fix lands -> cached verdict must NOT resurrect the finding
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            def f(x):
                return jax.lax.psum(x, "data")  # check: no-traffic
        """)
        fs2 = analyze_paths([str(tmp_path)], ["R1"], root=str(tmp_path),
                            cache=self._cache(tmp_path))
        assert fs2 == []

    def test_facts_change_invalidates_everyone(self, tmp_path):
        from dmlp_tpu.check.analyzer import analyze_paths
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION_R1)
        analyze_paths([str(tmp_path)], ["R1"], root=str(tmp_path),
                      cache=self._cache(tmp_path))
        # declaring the axis changes mesh.py's FACTS: the other file's
        # cached (now wrong) verdict must be invalidated too
        write(tmp_path, "dmlp_tpu/parallel/mesh.py",
              MESH_SRC + 'BOGUS_AXIS = "bogus"\n')
        c = self._cache(tmp_path)
        fs = analyze_paths([str(tmp_path)], ["R1"], root=str(tmp_path),
                           cache=c)
        assert fs == []              # the axis is declared now
        assert c.hits == 0           # every findings entry missed

    def test_disabled_cache_is_noop(self, tmp_path):
        from dmlp_tpu.check.analyzer import analyze_paths
        from dmlp_tpu.check.cache import CheckCache
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION_R1)
        c = CheckCache(directory=str(tmp_path / "cache"), enabled=False)
        fs = analyze_paths([str(tmp_path)], ["R1"], root=str(tmp_path),
                           cache=c)
        assert "R101" in rules_of(fs)
        assert not (tmp_path / "cache").exists()


# ---------------------------------------------------------------------------
# R0 — hygiene (the ruff-subset fallback behind make lint)
# ---------------------------------------------------------------------------


class TestR0Hygiene:
    def test_unused_import(self, tmp_path):
        write(tmp_path, "dmlp_tpu/x.py", """
            import os
            import sys
            print(sys.argv)
        """)
        fs = run_check(tmp_path, ["R0"])
        assert rules_of(fs) == ["R001"]
        assert "os" in fs[0].message

    def test_noqa_and_init_reexports_respected(self, tmp_path):
        write(tmp_path, "dmlp_tpu/x.py", """
            import os  # noqa: F401
        """)
        write(tmp_path, "dmlp_tpu/__init__.py", """
            from dmlp_tpu.x import thing
        """)
        assert run_check(tmp_path, ["R0"]) == []

    def test_bare_except(self, tmp_path):
        write(tmp_path, "dmlp_tpu/x.py", """
            def f():
                try:
                    return 1
                except:
                    return 0
        """)
        assert "R002" in rules_of(run_check(tmp_path, ["R0"]))

    def test_mutable_default(self, tmp_path):
        write(tmp_path, "dmlp_tpu/x.py", """
            def f(xs=[]):
                return xs
        """)
        assert "R003" in rules_of(run_check(tmp_path, ["R0"]))

    def test_fstring_without_placeholder(self, tmp_path):
        write(tmp_path, "dmlp_tpu/x.py", """
            def f():
                return f"static text"
        """)
        assert "R004" in rules_of(run_check(tmp_path, ["R0"]))

    def test_format_spec_fstrings_not_flagged(self, tmp_path):
        # py3.10 nests the ":.6f" spec as its own JoinedStr — must not
        # false-positive (the bug the first run over the tree surfaced).
        write(tmp_path, "dmlp_tpu/x.py", """
            def f(v):
                return f"{v:.6f}"
        """)
        assert run_check(tmp_path, ["R0"]) == []


# ---------------------------------------------------------------------------
# the real package + baseline + CLI
# ---------------------------------------------------------------------------


def test_real_package_clean_of_default_family_findings():
    """R1-R4 over the installed package: zero findings. Anything new
    must be fixed or explicitly baselined in check_baseline.json."""
    assert analyze_package() == []


def test_real_package_clean_of_hygiene_findings():
    assert analyze_package(["R0"]) == []


def test_committed_baseline_is_empty_and_loadable():
    path = os.path.join(os.path.dirname(package_root()),
                        "check_baseline.json")
    assert os.path.exists(path), "check_baseline.json must be committed"
    assert sum(load_baseline(path).values()) == 0


VIOLATION = """
import jax
def f(x):
    return jax.lax.psum(x, "bogus")
"""


class TestBaselineRoundTrip:
    def test_new_finding_then_baseline_then_stale(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        src = write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION)
        findings = run_check(tmp_path, ["R1"])
        assert findings  # the seeded violation is caught

        # un-baselined -> new (fails make check)
        new, matched, stale = diff_baseline(findings, {})
        assert new and not matched and not stale

        # baselined -> passes
        bl_path = str(tmp_path / "check_baseline.json")
        save_baseline(bl_path, findings)
        new, matched, stale = diff_baseline(findings,
                                            load_baseline(bl_path))
        assert not new and len(matched) == len(findings) and not stale

        # baseline survives unrelated line shifts (fingerprint has no
        # line numbers)
        with open(src) as f:
            shifted = "# a new comment line\n" + f.read()
        open(src, "w").write(shifted)
        findings2 = run_check(tmp_path, ["R1"])
        new, matched, _ = diff_baseline(findings2, load_baseline(bl_path))
        assert not new and matched

        # fixed -> stale baseline entry reported, exit stays clean
        write(tmp_path, "dmlp_tpu/ops/x.py", """
            import jax
            DATA = "data"
            def f(x):
                return jax.lax.psum(x, "data")  # check: no-traffic
        """)
        findings3 = run_check(tmp_path, ["R1"])
        new, _, stale = diff_baseline(findings3, load_baseline(bl_path))
        assert not new and stale


class TestCLI:
    def _run(self, args, cwd=None):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run(
            [sys.executable, "-m", "dmlp_tpu.check", *args],
            capture_output=True, text=True, env=env, cwd=cwd)

    def test_json_verdict_pure_stdout_and_exit_codes(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION)
        r = self._run(["--json", "--families", "R1", "--no-baseline",
                       str(tmp_path / "dmlp_tpu")])
        assert r.returncode == 1
        verdict = json.loads(r.stdout)  # stdout is pure JSON
        assert verdict["ok"] is False
        assert any(f["rule"] == "R101" for f in verdict["new"])
        assert "finding" in r.stderr  # narration on stderr

    def test_write_baseline_then_clean(self, tmp_path):
        write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        write(tmp_path, "dmlp_tpu/ops/x.py", VIOLATION)
        bl = str(tmp_path / "bl.json")
        target = str(tmp_path / "dmlp_tpu")
        assert self._run(["--families", "R1", "--write-baseline",
                          "--baseline", bl, target]).returncode == 0
        r = self._run(["--families", "R1", "--baseline", bl, target])
        assert r.returncode == 0

    def test_list_rules(self, tmp_path):
        r = self._run(["--list-rules"])
        assert r.returncode == 0
        for rule in ("R101", "R203", "R302", "R404", "R001"):
            assert rule in r.stdout
