"""obs.hlo — compiled-program introspection and the three-way reconcile.

Four layers: (1) pure-text parsing fixtures, one per collective kind,
covering both ``replica_groups`` spellings, async start/done pairs and
while-loop trip counts, with HAND-COMPUTED byte counts; (2) the live
engines on the 8-virtual-device mesh — the hand-rolled schedules must
reconcile against their own analytic models at ratio 1.0 and the auto
engine must yield a non-empty partitioner schedule; (3) the markers
(memory/cost/trace unavailable), the fingerprint cache, and the CLI
``--hlo-report`` round-trip through the ledger; (4) the R10/R1001 and
R903 check-family fixtures, positive and negative.
"""

import io
import json
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dmlp_tpu.check.analyzer import analyze_paths
from dmlp_tpu.cli import main as cli_main
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.auto import AutoShardedEngine
from dmlp_tpu.engine.sharded import RingEngine, ShardedEngine
from dmlp_tpu.io.datagen import generate_input_text
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import counters as obs_counters
from dmlp_tpu.obs import hlo as obs_hlo
from dmlp_tpu.obs.comms import CollectiveTraffic
from dmlp_tpu.parallel.mesh import make_mesh


def _inp(seed: int = 7, n: int = 256, nq: int = 8, na: int = 4,
         kmax: int = 6) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(
        Params(n, nq, na),
        rng.integers(0, 5, n).astype(np.int32),
        rng.uniform(-10, 10, (n, na)),
        rng.integers(1, kmax + 1, nq).astype(np.int32),
        rng.uniform(-10, 10, (nq, na)))


# ---------------------------------------------------------------------------
# parsing fixtures — hand-computed byte counts per collective kind
# ---------------------------------------------------------------------------

AG_EXPLICIT = """\
HloModule jit_ag, num_partitions=8

ENTRY %main.1 (p.1: f32[4,8]) -> f32[16,8] {
  %p.1 = f32[4,8] parameter(0)
  ROOT %ag.2 = f32[16,8] all-gather(f32[4,8] %p.1), channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, use_global_device_ids=true
}
"""

AR_IOTA = """\
HloModule jit_ar, num_partitions=8

%add.1 (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %a.1 = f32[] add(f32[] %x.1, f32[] %y.1)
}

ENTRY %main.2 (p.2: f32[16]) -> f32[16] {
  %p.2 = f32[16] parameter(0)
  ROOT %ar.2 = f32[16] all-reduce(f32[16] %p.2), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add.1
}
"""

RS_DEFAULT_GROUPS = """\
HloModule jit_rs, num_partitions=8

ENTRY %main (p: f32[8,4]) -> f32[1,4] {
  %p = f32[8,4] parameter(0)
  ROOT %rs = f32[1,4] reduce-scatter(f32[8,4] %p), channel_id=1, replica_groups={}, dimensions={0}, to_apply=%add
}
"""

A2A = """\
HloModule jit_a2a, num_partitions=8

ENTRY %main (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4] parameter(0)
  ROOT %a2a = f32[8,4] all-to-all(f32[8,4] %p), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
}
"""

CP = """\
HloModule jit_cp, num_partitions=4

ENTRY %main (p: f32[4,8]) -> f32[4,8] {
  %p = f32[4,8] parameter(0)
  ROOT %cp = f32[4,8] collective-permute(f32[4,8] %p), channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
}
"""

WHILE_TRIP = """\
HloModule jit_scan, num_partitions=4

%body.5 (param.1: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %param.1 = (s32[], f32[8,8]) parameter(0)
  %gte.1 = f32[8,8] get-tuple-element((s32[], f32[8,8]) %param.1), index=1
  %cp.2 = f32[8,8] collective-permute(f32[8,8] %gte.1), channel_id=2, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
}

%cond.7 (param.2: (s32[], f32[8,8])) -> pred[] {
  %param.2 = (s32[], f32[8,8]) parameter(0)
  ROOT %lt.1 = pred[] compare(s32[] %gte.2, s32[] %c.1), direction=LT
}

ENTRY %main.9 (p.3: f32[8,8]) -> f32[8,8] {
  %p.3 = f32[8,8] parameter(0)
  %w.4 = (s32[], f32[8,8]) while((s32[], f32[8,8]) %init.1), condition=%cond.7, body=%body.5, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %gte.9 = f32[8,8] get-tuple-element((s32[], f32[8,8]) %w.4), index=1
}
"""

ASYNC_PAIR = """\
HloModule jit_async, num_partitions=8

ENTRY %main (p: f32[4,8]) -> f32[32,8] {
  %p = f32[4,8] parameter(0)
  %ags = (f32[4,8], f32[32,8]) all-gather-start(f32[4,8] %p), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  ROOT %agd = f32[32,8] all-gather-done((f32[4,8], f32[32,8]) %ags)
}
"""


class TestParsing:
    def test_all_gather_explicit_groups(self):
        ops = obs_hlo.parse_collectives(AG_EXPLICIT)
        assert len(ops) == 1
        op = ops[0]
        assert op["kind"] == "all-gather"
        # operand f32[4,8] = 128 B; two groups of 4
        assert op["operand_bytes"] == 128
        assert (op["group_size"], op["n_groups"]) == (4, 2)
        # ring bound: (g-1) * shard per device, all devices, both groups
        assert op["bytes_moved"] == (4 - 1) * 128 * 4 * 2 == 3072

    def test_all_reduce_iota_groups(self):
        ops = obs_hlo.parse_collectives(AR_IOTA)
        assert len(ops) == 1
        op = ops[0]
        assert op["kind"] == "all-reduce"
        assert op["operand_bytes"] == 64            # f32[16]
        assert (op["group_size"], op["n_groups"]) == (4, 2)   # [2,4]<=[8]
        # 2(g-1)/g x buffer per device
        assert op["bytes_moved"] == round(2 * 3 * 64 / 4) * 4 * 2 == 768

    def test_reduce_scatter_default_groups(self):
        ops = obs_hlo.parse_collectives(RS_DEFAULT_GROUPS)
        op = ops[0]
        assert op["kind"] == "reduce-scatter"
        # empty replica_groups: one group of num_partitions=8
        assert (op["group_size"], op["n_groups"]) == (8, 1)
        assert op["bytes_moved"] == round(7 * 128 / 8) * 8 == 896

    def test_all_to_all(self):
        op = obs_hlo.parse_collectives(A2A)[0]
        assert op["kind"] == "all-to-all"
        assert op["bytes_moved"] == round(7 * 128 / 8) * 8 == 896

    def test_collective_permute_pairs(self):
        op = obs_hlo.parse_collectives(CP)[0]
        assert op["kind"] == "collective-permute"
        assert op["n_pairs"] == 4
        assert op["group_size"] == 4      # one 4-cycle ring
        # full operand per source->target pair
        assert op["bytes_moved"] == 128 * 4 == 512

    def test_while_trip_count_multiplies(self):
        op = obs_hlo.parse_collectives(WHILE_TRIP)[0]
        assert op["kind"] == "collective-permute"
        assert op["count"] == 3
        assert "trip_count_unknown" not in op
        # f32[8,8] = 256 B x 4 pairs x 3 iterations
        assert op["bytes_moved"] == 256 * 4 * 3 == 3072

    def test_while_unknown_trip_marked_not_guessed(self):
        text = WHILE_TRIP.replace(
            ', backend_config={"known_trip_count":{"n":"3"}}', "")
        op = obs_hlo.parse_collectives(text)[0]
        assert op["count"] == 1            # honest lower bound
        assert op["trip_count_unknown"] is True

    def test_operands_named_without_shapes_resolve_from_their_definition(
            self):
        # jax 0.9's compiled text: `collective-permute(%gte.1)` — the
        # shape sits on %gte.1's own line
        text = WHILE_TRIP.replace(
            "collective-permute(f32[8,8] %gte.1)",
            "collective-permute(%gte.1)")
        op = obs_hlo.parse_collectives(text)[0]
        assert op["operand_bytes"] == 256
        assert op["bytes_moved"] == 256 * 4 * 3 == 3072
        assert "operand_shape_unresolved" not in op

    def test_operand_the_text_never_defines_is_marked_not_priced(self):
        text = CP.replace("collective-permute(f32[4,8] %p)",
                          "collective-permute(%elsewhere.7)")
        op = obs_hlo.parse_collectives(text)[0]
        assert op["operand_bytes"] == 0
        assert op["operand_shape_unresolved"] is True
        totals = obs_hlo.collective_totals([op])
        assert totals["collective-permute"]["ops_shape_unresolved"] == 1

    def test_async_start_counted_done_skipped(self):
        ops = obs_hlo.parse_collectives(ASYNC_PAIR)
        assert len(ops) == 1               # -done is bookkeeping
        assert ops[0]["kind"] == "all-gather"
        assert ops[0]["operand_bytes"] == 128

    def test_totals_and_dispatch_multiplicity(self):
        ops = obs_hlo.parse_collectives(AG_EXPLICIT)
        totals = obs_hlo.collective_totals(ops, dispatch_count=5)
        assert totals["all-gather"]["bytes_moved"] == 3072 * 5
        assert totals["all-gather"]["count"] == 5

    def test_guess_axis_unique_or_unknown(self):
        axes = {"data": 4, "query": 2}
        assert obs_hlo.guess_axis(4, axes) == "data"
        assert obs_hlo.guess_axis(2, axes) == "query"
        assert obs_hlo.guess_axis(8, axes) == "unknown"
        assert obs_hlo.guess_axis(4, {"a": 4, "b": 4}) == "unknown"
        assert obs_hlo.guess_axis(4, None) == "unknown"


# ---------------------------------------------------------------------------
# reconcile legs on fixture reports
# ---------------------------------------------------------------------------

def _fixture_report(text, label="fix"):
    ops = obs_hlo.parse_collectives(text)
    return obs_hlo.HloReport(
        label=label, fingerprint=obs_hlo.fingerprint_text(text),
        collectives=ops, totals=obs_hlo.collective_totals(ops),
        memory={}, cost={})


class TestReconcile:
    def test_comms_unpriced_hlo_side_says_so_and_gives_no_verdict(self):
        text = CP.replace("collective-permute(f32[4,8] %p)",
                          "collective-permute(%elsewhere.7)")
        model = CollectiveTraffic("ring_allreduce_topk", "data", 4,
                                  128, 128)
        rec = obs_hlo.reconcile_comms(
            [(_fixture_report(text), 1, "solve")], [model])
        ent = rec["kinds"]["collective-permute"]
        assert ent["hlo_shape_unavailable"] == 1
        assert ent["within_tolerance"] is False
        assert "ratio" not in ent and "model_only" not in ent

    def test_comms_exact_match_within_tolerance(self):
        rep = _fixture_report(AG_EXPLICIT)
        # model twin: per-device (g-1) x 128 = 384 B over 2 groups of 4
        model = CollectiveTraffic("all_gather_merge_topk", "data", 4,
                                  384, 384, n_groups=2)
        rec = obs_hlo.reconcile_comms([(rep, 1, "solve")], [model])
        ent = rec["kinds"]["all-gather"]
        assert ent["ratio"] == 1.0
        assert ent["within_tolerance"] is True
        assert ent["models"] == ["all_gather_merge_topk"]

    def test_comms_mismatch_flagged(self):
        rep = _fixture_report(AG_EXPLICIT)
        model = CollectiveTraffic("all_gather_merge_topk", "data", 4,
                                  90, 90, n_groups=2)   # 720 B total
        rec = obs_hlo.reconcile_comms([(rep, 1, "solve")], [model])
        assert rec["kinds"]["all-gather"]["within_tolerance"] is False

    def test_comms_one_sided_markers(self):
        rep = _fixture_report(AG_EXPLICIT)
        model = CollectiveTraffic("psum_grads", "data", 4, 64, 64)
        rec = obs_hlo.reconcile_comms([(rep, 1, "s")], [model])
        assert rec["kinds"]["all-gather"]["hlo_only"] is True
        assert rec["kinds"]["all-reduce"]["model_only"] is True
        empty = obs_hlo.reconcile_comms([], [])
        assert empty["no_collectives"] is True

    def test_trace_leg_markers(self):
        rep = _fixture_report(AG_EXPLICIT)
        rec = obs_hlo.reconcile_trace([(rep, 1, "s")], [])
        assert "trace_unavailable" in rec
        ev = [{"name": "dist.allgather_candidates",
               "args": {"nbytes": 3072}}]
        rec = obs_hlo.reconcile_trace([(rep, 1, "s")], ev)
        assert rec["kinds"]["all-gather"]["ratio"] == 1.0
        assert rec["kinds"]["all-gather"]["within_tolerance"] is True

    def test_memory_leg_marker_and_ratio(self):
        rep = _fixture_report(AG_EXPLICIT)
        rep.memory = {"argument_bytes": 1000, "output_bytes": 200,
                      "temp_bytes": 300}
        rec = obs_hlo.reconcile_memory(
            [(rep, 1, "s")], {"model_bytes": 1500})
        assert rec["hlo_peak_bytes"] == 1500
        assert rec["ratio"] == 1.0 and rec["within_tolerance"] is True
        rep2 = _fixture_report(AR_IOTA, label="m")
        rep2.memory = {"hlo_memory_unavailable": "backend says no"}
        rec = obs_hlo.reconcile_memory([(rep2, 1, "s")], None)
        assert rec["hlo_memory_unavailable"] == "backend says no"


# ---------------------------------------------------------------------------
# markers on hostile compiled objects
# ---------------------------------------------------------------------------

class TestMarkers:
    def test_memory_report_marker_paths(self):
        class _Raises:
            def memory_analysis(self):
                raise RuntimeError("no backend stats")

        class _NoneBack:
            def memory_analysis(self):
                return None

        m = obs_hlo.memory_report(_Raises())
        assert "no backend stats" in m["hlo_memory_unavailable"]
        m = obs_hlo.memory_report(_NoneBack())
        assert "hlo_memory_unavailable" in m

    def test_cost_report_marker(self):
        class _Raises:
            def cost_analysis(self):
                raise NotImplementedError("nope")

        assert "cost_unavailable" in obs_hlo.cost_report(_Raises())

    def test_report_for_fn_unlowerable_returns_none(self):
        assert obs_hlo.report_for_fn(lambda x: x, (1,)) is None

    def test_counters_unrecognized_cost_shape_recorded(self):
        # the obs.counters bugfix: an unknown cost_analysis() shape must
        # leave a diagnosable trail, not a silent None
        obs_counters._unrecognized_shapes.clear()
        assert obs_counters.normalize_cost({"weird_key": 1.0}) is None
        assert obs_counters.normalize_cost([]) is None
        shapes = list(obs_counters._unrecognized_shapes)
        assert any("weird_key" in d.get("keys", []) for d in shapes)
        assert any(d["type"] == "list" for d in shapes)
        obs_counters._unrecognized_shapes.clear()


# ---------------------------------------------------------------------------
# fingerprint cache
# ---------------------------------------------------------------------------

def test_fingerprint_cache_hit_on_same_program():
    fn = jax.jit(lambda x: x * 2 + 1)
    spec = jax.ShapeDtypeStruct((8,), jnp.float32)
    c1 = fn.lower(spec).compile()
    c2 = fn.lower(spec).compile()
    obs_hlo.clear_cache()
    r1 = obs_hlo.report_for(c1, label="first")
    r2 = obs_hlo.report_for(c2, label="second")
    assert r1.fingerprint == r2.fingerprint
    assert r2.label == "first"        # first introspection's label sticks
    assert obs_hlo.cache_stats == {"hits": 1, "misses": 1}
    obs_hlo.clear_cache()


# ---------------------------------------------------------------------------
# live engines on the 8-virtual-device mesh
# ---------------------------------------------------------------------------

def _probe_run(engine, inp):
    probe = obs_counters.install()
    try:
        engine.run(inp)
        reports, skipped = obs_hlo.probe_reports(probe)
    finally:
        obs_counters.uninstall()
    return reports, skipped


class TestLiveEngines:
    def test_sharded_allgather_reconciles_exactly(self):
        eng = ShardedEngine(EngineConfig(mode="sharded"),
                            mesh=make_mesh((4, 2)))
        reports, _sk = _probe_run(eng, _inp())
        assert reports
        rec = obs_hlo.reconcile_comms(reports, eng.last_comms)
        ag = rec["kinds"]["all-gather"]
        assert ag["within_tolerance"] is True
        assert ag["ratio"] == 1.0      # same convention, no fudge factor

    def test_ring_permute_reconciles_with_trip_counts(self):
        eng = RingEngine(EngineConfig(mode="ring"),
                         mesh=make_mesh((4, 2)))
        reports, _sk = _probe_run(eng, _inp(seed=11))
        rec = obs_hlo.reconcile_comms(reports, eng.last_comms)
        cp = rec["kinds"]["collective-permute"]
        # the scanned ring's R-1 hops only reconcile if while-loop trip
        # counts are folded in (1/3 of the model otherwise)
        assert cp["within_tolerance"] is True
        assert cp["ratio"] == 1.0

    def test_auto_engine_schedule_nonempty_with_real_comms(self):
        eng = AutoShardedEngine(EngineConfig(mode="auto"),
                                mesh=make_mesh((4, 2)))
        eng.run(_inp(seed=13))
        rep = eng.comms_from_hlo()
        assert rep is not None and rep.totals
        # the partitioner's schedule becomes a REAL comms record
        assert eng.last_comms
        recs = [t.to_dict() for t in eng.last_comms]
        assert all(r["collective"].startswith("gspmd_") for r in recs)
        # the gspmd_* records reproduce the schedule's bytes (per-device
        # rounding only), so the reconcile against them is exact
        rec = obs_hlo.reconcile_comms([(rep, 1, "auto.solve")],
                                      eng.last_comms)
        for ent in rec["kinds"].values():
            assert ent["within_tolerance"] is True
            assert 0.99 <= ent["ratio"] <= 1.01
        # per-axis attribution lands on declared mesh axes or 'unknown'
        assert {r["axis"] for r in recs} <= {"data", "query", "unknown"}

    def test_build_report_doc_and_flat_metrics(self):
        eng = ShardedEngine(EngineConfig(mode="sharded"),
                            mesh=make_mesh((4, 2)))
        reports, skipped = _probe_run(eng, _inp(seed=5))
        doc = obs_hlo.build_report_doc(
            reports, skipped=skipped, traffics=eng.last_comms,
            mesh_axes={"data": 4, "query": 2})
        assert doc["schema"] == obs_hlo.SCHEMA_VERSION
        assert doc["collective_bytes_total"] > 0
        assert doc["executables"]
        assert "comms_model" in doc["reconcile"]
        assert "trace_unavailable" in doc["reconcile"]["trace"]
        flat = obs_hlo.flat_metrics(doc)
        assert flat["collective_bytes_total"] \
            == doc["collective_bytes_total"]
        assert flat["executables_introspected"] == len(doc["executables"])
        assert flat["all_gather_bytes"] > 0
        json.dumps(doc)                # the record must be JSON-safe


# ---------------------------------------------------------------------------
# CLI --hlo-report writes a RunRecord that loads back
# ---------------------------------------------------------------------------

def _run_cli(args, text):
    out, err = io.StringIO(), io.StringIO()
    rc = cli_main(args, stdin=io.StringIO(text), stdout=out, stderr=err)
    assert rc == 0
    return out.getvalue(), err.getvalue()


@pytest.mark.parametrize("mode", ["sharded", "auto"])
def test_cli_hlo_report_loads_as_runrecord(tmp_path, mode):
    text = generate_input_text(90, 11, 4, -3, 3, 1, 7, 3, seed=44)
    base, _ = _run_cli(["--mode", mode], text)
    path = tmp_path / "HLO.jsonl"
    out, _ = _run_cli(["--mode", mode, "--hlo-report", str(path)], text)
    assert out == base          # introspection never changes the contract
    doc = json.loads(path.read_text().splitlines()[-1])
    assert doc["kind"] == "hlo"
    assert doc["config"]["mode"] == mode
    assert doc["metrics"]["collective_bytes_total"] > 0
    rec = doc["comms"]["reconcile"]
    assert "comms_model" in rec and "memory" in rec
    if mode == "sharded":
        ag = rec["comms_model"]["kinds"]["all-gather"]
        assert ag["within_tolerance"] is True

    from dmlp_tpu.obs.run import RunRecord
    back = RunRecord.load(str(path))
    assert (back.kind, back.tool) == ("hlo", "dmlp_tpu.cli")
    assert back.config["mode"] == mode
    assert back.metrics["collective_bytes_total"] \
        == doc["metrics"]["collective_bytes_total"]
    assert back.round is None      # "HLO.jsonl" names no round
    assert back.device             # the solving process's own stamp


# ---------------------------------------------------------------------------
# check families R10 (R1001) and R903 — fixtures
# ---------------------------------------------------------------------------

def _write(tmp_path, rel, source):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return str(p)


def _rules(tmp_path, families):
    fs = analyze_paths([str(tmp_path)], families, root=str(tmp_path))
    return sorted(f.rule for f in fs), fs


MESH_SRC = """
DATA_AXIS = "data"
QUERY_AXIS = "query"
"""


class TestR10HloIntro:
    def test_dangling_annotation_caught(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from dmlp_tpu.parallel.mesh import DATA_AXIS
            def f(x):
                return jax.lax.psum(x, DATA_AXIS)  # check: comms-model=renamed_away_traffic
        """)
        rules, fs = _rules(tmp_path, ["R10"])
        assert rules == ["R1001"]
        assert "renamed_away_traffic" in fs[0].message

    def test_mapped_annotation_clean(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from dmlp_tpu.parallel.mesh import DATA_AXIS
            def f(x):
                return jax.lax.psum(x, DATA_AXIS)  # check: comms-model=psum_traffic
        """)
        assert _rules(tmp_path, ["R10"])[0] == []

    def test_allow_directive_waives(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            def f(x):
                return jax.lax.psum(x, "data")  # check: comms-model=unmapped_traffic allow-hlo-model
        """)
        assert _rules(tmp_path, ["R10"])[0] == []

    def test_out_of_scope_dirs_skipped(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/obs/x.py", """
            import jax
            def f(x):
                return jax.lax.psum(x, "data")  # check: comms-model=unmapped_traffic
        """)
        assert _rules(tmp_path, ["R10"])[0] == []

    def test_fixture_table_overrides_installed(self, tmp_path):
        # a fixture tree carrying its own obs/hlo.py table: annotations
        # naming REAL package models must flag against the fixture table
        _write(tmp_path, "dmlp_tpu/obs/hlo.py", """
            MODEL_COLLECTIVE_KINDS = {"custom_traffic": "all-gather"}
        """)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            def f(x):
                return jax.lax.psum(x, "data")  # check: comms-model=psum_traffic
            def g(x):
                return jax.lax.psum(x, "data")  # check: comms-model=custom_traffic
        """)
        rules, fs = _rules(tmp_path, ["R10"])
        assert rules == ["R1001"]
        assert "psum_traffic" in fs[0].message

    def test_real_package_table_covers_every_annotation(self):
        # every comms-model annotation in the real package maps — and
        # every table key names a real obs/comms model (no drift)
        from dmlp_tpu.obs import comms
        for model in obs_hlo.MODEL_COLLECTIVE_KINDS:
            assert callable(getattr(comms, model))
        for kind in obs_hlo.MODEL_COLLECTIVE_KINDS.values():
            assert kind in obs_hlo.COLLECTIVE_KINDS


class TestR903Constraints:
    def test_variable_held_undeclared_axis_caught(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def f(x, mesh):
                sh = NamedSharding(mesh, P("typo_axis"))
                return jax.lax.with_sharding_constraint(x, sh)
        """)
        rules, fs = _rules(tmp_path, ["R9"])
        assert "R903" in rules
        assert any("typo_axis" in f.message for f in fs
                   if f.rule == "R903")

    def test_variable_held_declared_axis_clean(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from dmlp_tpu.parallel.mesh import DATA_AXIS
            def f(x, mesh):
                sh = NamedSharding(mesh, P(DATA_AXIS, None))
                return jax.lax.with_sharding_constraint(x, sh)
        """)
        assert _rules(tmp_path, ["R9"])[0] == []

    def test_opaque_binding_skipped_not_guessed(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            def f(x, sharding_factory):
                sh = sharding_factory()
                return jax.lax.with_sharding_constraint(x, sh)
        """)
        assert _rules(tmp_path, ["R9"])[0] == []

    def test_scoped_allow_waives(self, tmp_path):
        _write(tmp_path, "dmlp_tpu/parallel/mesh.py", MESH_SRC)
        _write(tmp_path, "dmlp_tpu/engine/x.py", """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def f(x, mesh):
                sh = NamedSharding(mesh, P("replica_local"))
                # check: allow-auto-shard=R903 allow-auto-shard=R901
                return jax.lax.with_sharding_constraint(x, sh)
        """)
        rules, _fs = _rules(tmp_path, ["R9"])
        assert "R903" not in rules
