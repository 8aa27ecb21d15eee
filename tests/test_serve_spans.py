"""PR 25's spans and always-on phase timings, by structure and not by
duration: one micro-batch through a daemon with a Tracer installed
yields every span of the batch cycle once, tiling it and sharing one
``batch``; with no sink installed the daemon still answers "queue or
solve?" from ``stats``; the kernels carry their names."""

from __future__ import annotations

import itertools
import json
import re
import socket
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.engine.single import SingleChipEngine
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import telemetry
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve.daemon import PHASE_HISTOGRAMS, ServeDaemon

NA = 8

#: the batcher thread's spans of one batch cycle, in order
CYCLE = ["serve.batch_assemble", "serve.micro_batch", "serve.batch_deliver"]
#: the children that tile serve.micro_batch, in order
IN_BATCH = ["serve.solve_stage", "serve.solve_extract",
            "serve.solve_epilogue", "single.fetch", "single.hazard",
            "single.finalize", "serve.after_batch"]
#: a flagged batch's device retry (PR 38): its enqueue before the
#: batch's own float64 finalize, its fence and rescore after, both
#: inside the span; what the retry does not clear would add
#: single.repair there (tests/test_serve_retry.py)
#: and the batch's own float64 gather-and-score (PR 46): one a
#: micro-batch, inside the finalize (the retry's rescore of its wider
#: lists is the retry span's ``host_ms``, not a second one)
NESTED = {"single.retry_begin": "single.finalize",
          "single.rescore": "single.finalize",
          "single.retry": "single.finalize"}
#: the whole-corpus norm pass: set-up's since PR 26, no batch's child
DN_MAX = "single.dn_max"
REQUEST = ["parse", "queue", "coalesce", "solve", "finalize", "respond",
           "write"]


def tied_corpus(copies=40, points=60, seed=5) -> KNNInput:
    """Every point ``copies`` times: a query AT a point finds more rows
    at distance 0 than the candidate window holds, so the boundary test
    flags it and the device retry runs (its 512 slots hold the 40)."""
    rng = np.random.default_rng(seed)
    pts = rng.integers(-8, 9, (points, NA)).astype(np.float64)
    rows = np.repeat(pts, copies, axis=0)
    n = len(rows)
    return KNNInput(Params(n, 0, NA),
                    rng.integers(0, 4, n).astype(np.int32), rows,
                    np.zeros(0, np.int32), np.zeros((0, NA)))


def ask(port, obj):
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        f = s.makefile("rwb")
        f.write((json.dumps(obj) + "\n").encode())
        f.flush()
        return json.loads(f.readline())


def query(corpus, rid="", nq=3):
    return {"op": "query", "id": "q", "k": 4,
            "queries": corpus.data_attrs[:nq * 40:40].tolist(),
            **({"rid": rid} if rid else {})}


@pytest.fixture(scope="module")
def traced():
    """One request = one micro-batch through a daemon on the extract
    path, traced; the events after warm-up."""
    corpus = tied_corpus()
    tracer = obs_trace.install(obs_trace.Tracer())
    daemon = None
    try:
        daemon = ServeDaemon(
            corpus, EngineConfig(use_pallas=True, select="extract"),
            warm_buckets=[(3, 4)])
        daemon.start()
        mark = len(tracer.events())
        resp = ask(daemon.port, query(corpus, rid="r-1"))
        assert resp["ok"], resp
        stats = ask(daemon.port, {"op": "stats"})["stats"]
    finally:
        if daemon is not None:
            daemon.close()
        obs_trace.uninstall()
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    return {"all": events,
            "served": [e for e in tracer.events()[mark:]
                       if e.get("ph") == "X"],
            "stats": stats}


def named(events, name, batch=None):
    """The spans called ``name``; with ``batch``, those of that
    micro-batch (two batches are alive at once under load, so a span
    belongs to the batch its ``batch`` arg names, not to the
    ``serve.micro_batch`` it happens to lie in)."""
    return [e for e in events if e["name"] == name
            and (batch is None or e["args"].get("batch") == batch)]


def work_args(e) -> dict:
    """A span's args without its CPU account (PR 51: every span's)."""
    return {k: v for k, v in e["args"].items()
            if k not in ("cpu_ms", "offcpu_ms")}


def inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


@pytest.mark.parametrize(
    "name", CYCLE + IN_BATCH + sorted(NESTED)
    + [f"serve.phase.{p}" for p in REQUEST])
def test_one_micro_batch_yields_the_span_once(traced, name):
    spans = named(traced["served"], name)
    if name == "serve.phase.write":      # the stats reply is written too
        spans = [e for e in spans if e.get("args", {}).get("rid")]
    assert len(spans) == 1, [e["name"] for e in traced["served"]]
    assert spans[0]["args"]["batch"] == 1


#: clock pairs whose two ends lie on one thread (PR 51): the handler's
ONE_THREAD = ["read", "parse", "respond", "write"]
#: ... and those that cross threads, or cross another batch's work
CROSSING = ["serve.phase.queue", "serve.phase.coalesce",
            "serve.phase.solve", "serve.phase.finalize",
            "serve.micro_batch"]


@pytest.mark.parametrize(
    "name", [n for n in CYCLE + IN_BATCH + sorted(NESTED)
             # (the two the batcher stitches from clock reads of its own)
             if n not in ("serve.micro_batch", "serve.solve_epilogue")]
    + [f"serve.phase.{p}" for p in ONE_THREAD] + ["serve.wait.device"])
def test_a_one_thread_span_says_how_long_it_was_on_a_core(traced, name):
    """Every ``with`` span, the host-sync bracket's span and the four
    handler phases timed on one thread carry ``cpu_ms`` (the thread's
    CPU time between the span's ends) and ``offcpu_ms``, which tile the
    duration."""
    spans = [e for e in named(traced["served"], name)
             if name != "serve.phase.write" or e["args"].get("rid")]
    assert spans
    for e in spans:
        a = e["args"]
        assert abs(e["dur"] / 1e3 - a["cpu_ms"] - a["offcpu_ms"]) < 1e-6
        assert 0 <= a["cpu_ms"] <= e["dur"] / 1e3 + 0.05, (name, a)


@pytest.mark.parametrize("name", CROSSING)
def test_a_clock_pair_that_crosses_threads_has_no_cpu_account(traced, name):
    spans = named(traced["served"], name)
    assert spans
    for e in spans:
        assert not {"cpu_ms", "offcpu_ms"} & set(e["args"]), name


def test_batcher_spans_tile_the_cycle_in_order(traced):
    cycle = [named(traced["served"], n)[0] for n in CYCLE]
    assert len({e["tid"] for e in cycle}) == 1          # one thread
    for a, b in zip(cycle, cycle[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]
    batch = cycle[1]
    assert batch["args"]["overlapped"] == 0     # nothing was in flight
    kids = [named(traced["served"], n, batch["args"]["batch"])[0]
            for n in IN_BATCH]
    assert {e["tid"] for e in kids} == {batch["tid"]}
    for k in kids:
        assert inside(k, batch), k["name"]
    for a, b in zip(kids, kids[1:]):                    # disjoint, in order
        assert a["ts"] + a["dur"] <= b["ts"], (a["name"], b["name"])
    for child, parent in NESTED.items():
        assert inside(
            named(traced["served"], child, batch["args"]["batch"])[0],
            named(traced["served"], parent, batch["args"]["batch"])[0])


def test_span_args_say_what_the_work_was(traced):
    ev = {n: named(traced["served"], n)[0]["args"]
          for n in CYCLE + IN_BATCH + sorted(NESTED)
          + ["serve.phase.parse", "serve.phase.respond"]}
    for n in ("serve.batch_assemble", "serve.batch_deliver"):
        assert (ev[n]["requests"], ev[n]["queries"]) == (1, 3)
    assert ev["serve.micro_batch"]["queries"] == 3      # as before PR 25
    assert ev["serve.solve_stage"]["qpad"] == \
        ev["serve.micro_batch"]["qpad"]
    # one program folds every scheduled chunk (one, at 2400 rows)
    loop = ev["serve.solve_extract"]
    assert loop["dispatches"] == 1
    assert loop["chunks"] == loop["scheduled"] == 1
    assert loop["kernel_dispatch_ms"] >= 0 and loop["throttle_wait_ms"] == 0
    assert ev["single.hazard"]["rows"] == 2400
    # the hazard test read the engine's resident max row norm
    assert ev["single.hazard"]["dn_max_cached"] is True
    # every query sits on 40 copies of its point: all three are flagged
    assert ev["single.hazard"]["flagged"] == 3
    assert ev["single.finalize"]["repairs"] == 3
    assert ev["single.retry_begin"]["queries"] == 3
    # the float64 gather-and-score: the batch's three queries at the
    # bucket's window, the bytes the finalize span reports, the score
    rescore = ev["single.rescore"]
    assert rescore["queries"] == 3 and rescore["slots"] >= 8
    # ``rows``: what the rescore gathered. A flagged query's band is its
    # whole window (PR 48; the 40 copies sit inside the bound), so here
    # it is every slot; ``gather_bytes`` is rows x A x 8 B either way
    assert rescore["rows"] == 3 * rescore["slots"]
    assert rescore["band_pct"] == 100.0
    assert rescore["bytes"] == ev["single.finalize"]["gather_bytes"] \
        == rescore["rows"] * NA * 8
    for n in ("serve.solve_extract", "single.hazard", "single.finalize",
              "single.retry_begin", "single.rescore"):
        assert ev[n]["score"] == "l2", n
    retry = ev["single.retry"]
    assert (retry["queries"], retry["kcap"], retry["passes"]) == (3, 512, 1)
    assert (retry["cleared"], retry["fell_through"]) == (3, 0)
    assert not named(traced["served"], "single.repair")
    # always on, a process's: warm-up's batch of corpus rows flags too
    counts = traced["stats"]["engine"]["repairs"]
    assert counts["flagged_queries"] == counts["device"] + counts["host"]
    assert counts["device"] >= 3
    after = ev["serve.after_batch"]
    assert after["tiles"] >= 1
    # what select_wide_pct.bulk reads: visits that extracted at full
    # width, of the visits the fold made
    assert 0 <= after["wide"] <= after["tiles"] - after["gated"]
    assert after["wide_pct"] == pytest.approx(
        100.0 * after["wide"] / after["tiles"], abs=1e-3)
    assert ev["serve.phase.parse"]["queries"] == 3
    assert ev["serve.phase.parse"]["bytes"] > 0
    assert ev["serve.phase.parse"]["rid"] == "r-1"
    assert ev["serve.phase.respond"]["rid"] == "r-1"


def test_the_rescore_counter_adds_up_over_batches(traced):
    """Always on: ``stats.engine.rescore`` is the candidate slots
    finalized in float64 and the rows gathered for them since start:
    warm-up's batch and the served one, as their spans say it."""
    spans = [e["args"] for e in named(traced["all"], "single.rescore")]
    assert len(spans) >= 2
    assert traced["stats"]["engine"]["rescore"] == {
        "slots": sum(a["queries"] * a["slots"] for a in spans),
        "rows": sum(a["rows"] for a in spans)}
    finals = [e["args"] for e in named(traced["all"], "single.finalize")]
    assert sum(a["gather_bytes"] for a in finals) \
        == sum(a["rows"] for a in spans) * NA * 8


@pytest.mark.parametrize("name", ["serve.solve_extract",
                                  "serve.warmup_bucket"])
def test_a_resident_solve_says_its_norms_were_staged(traced, name):
    """PR 41: the fold reads the rows' norms from beside the stack;
    the spans that name the kernel's variant say so, set-up's staging
    span says what the norm array weighs, and ``stats.engine`` counts
    the chunks whose norms were written (each once: nothing was
    ingested)."""
    args = named(traced["all"], name)[-1]["args"]
    assert args["norms"] == "staged"
    assert args["tile_q"] >= 8 and args["mxu_passes"] in (1, 3, 6)
    stage = named(traced["all"], "serve.stage_chunks")[0]["args"]
    assert stage["norm_bytes"] == stage["chunks"] * stage["chunk_rows"] * 4
    assert traced["stats"]["engine"]["norm_restages"] == stage["chunks"]


@pytest.mark.parametrize("name", ["serve.init.host_copy",
                                  "serve.init.row_hashes",
                                  DN_MAX,
                                  "serve.stage_resident",
                                  "serve.stage_chunks",
                                  "serve.warmup_bucket"])
def test_set_up_is_spanned_once_per_daemon(traced, name):
    spans = named(traced["all"], name)
    assert len(spans) == 1
    assert not named(traced["served"], name)    # and before any request


def test_warm_up_solves_carry_no_batch(traced):
    warm = [e for e in named(traced["all"], "single.hazard")
            if e not in traced["served"]]
    assert warm and all("batch" not in e["args"] for e in warm)


def test_the_norm_pass_is_set_up_and_no_hazard_span_holds_it(traced):
    """The resident engine's one pass over the whole corpus runs while
    the engine is built: before warm-up's solves, outside every
    ``single.hazard`` (warm-up's too), tagged with no batch."""
    (pas,) = named(traced["all"], DN_MAX)
    assert work_args(pas) == {"rows": 2400}
    hazards = named(traced["all"], "single.hazard")
    assert len(hazards) >= 2                    # warm-up's and the served
    assert not any(inside(pas, h) for h in hazards)
    assert all(pas["ts"] + pas["dur"] <= h["ts"] for h in hazards)
    assert all(h["args"]["dn_max_cached"] is True for h in hazards)


# -- the programs one micro-batch runs ------------------------------------------

def _programs(events, lo, hi):
    """Names of the compiled programs the host dispatched in [lo, hi):
    the profiler's ``PjitFunction(name)`` host events (nested repeats
    of one call folded) — and how many executables ran there."""
    inside_ = [(s, n) for s, n in events if lo <= s < hi]
    names = [n[len("PjitFunction("):-1] for _, n in inside_
             if n.startswith("PjitFunction(")]
    runs = sum(n == "PjRtCpuExecutable::Execute" for _, n in inside_)
    return [n for n, _ in itertools.groupby(names)], runs


@pytest.fixture(scope="module")
def profiled_batch(tmp_path_factory):
    """One warm micro-batch over three resident chunks, under the
    Tracer's own profiler capture (``annotate`` mirrors the spans into
    it): the host thread's events and the span args."""
    import glob
    from dmlp_tpu.serve.engine import ResidentEngine
    rng = np.random.default_rng(8)
    n = 30000                               # 3 chunks of 12800 rows
    corpus = KNNInput(Params(n, 0, 4),
                      rng.integers(0, 4, n).astype(np.int32),
                      rng.uniform(-10, 10, (n, 4)),
                      np.zeros(0, np.int32), np.zeros((0, 4)))
    eng = ResidentEngine(corpus, EngineConfig(
        use_pallas=True, select="extract", data_block=12800))
    q, ks = rng.uniform(-10, 10, (5, 4)), np.full(5, 4, np.int32)
    eng.warmup([(5, 4)])
    eng.solve_batch(q, ks)
    out = str(tmp_path_factory.mktemp("profile"))
    tracer = obs_trace.install(obs_trace.Tracer(annotate=True,
                                                profile_dir=out))
    try:
        assert tracer._profiling, "jax.profiler would not start"
        eng.solve_batch(q, ks)
    finally:
        obs_trace.uninstall()
        tracer.write(out + "/spans.json")
    (pb,) = glob.glob(out + "/plugins/profile/*/*.xplane.pb")
    spans = {e["name"]: e.get("args", {}) for e in tracer.events()
             if e.get("ph") == "X"}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            events = [(e.start_ns, e.name) for e in line.events]
            if any(n == "serve.solve_extract" for _, n in events):
                at = {n: s for s, n in events}
                ends = {e.name: e.start_ns + e.duration_ns
                        for e in line.events}
                return {"events": events, "at": at, "ends": ends,
                        "spans": spans}
    raise AssertionError("the capture holds no serve.solve_extract")


def test_the_fold_is_one_program_a_batch(profiled_batch):
    at, ends = profiled_batch["at"], profiled_batch["ends"]
    names, runs = _programs(profiled_batch["events"],
                            at["serve.solve_extract"],
                            ends["serve.solve_extract"])
    assert (names, runs) == (["_fold_stack"], 1)
    loop = profiled_batch["spans"]["serve.solve_extract"]
    assert (loop["dispatches"], loop["chunks"], loop["scheduled"]) \
        == (1, 3, 3)


def test_no_eager_gate_counter_between_stage_and_fetch(profiled_batch):
    """From the start of serve.solve_stage to the readback (the first
    half, which serve.solve_epilogue closes) the host dispatches the
    prune scorer,
    the fold, and the epilogue's two programs: no per-chunk
    ``jit_equal`` / ``jit__reduce_sum`` / ``jit_add``."""
    at = profiled_batch["at"]
    names, runs = _programs(profiled_batch["events"],
                            at["serve.solve_stage"], at["single.fetch"])
    assert not {"equal", "_reduce_sum", "add"} & set(names)
    assert names == ["_score", "_fold_stack", "_extract_finalize",
                     "_boundary_cols"]
    assert runs == len(names)


# -- without a tracer -----------------------------------------------------------

@pytest.fixture(scope="module")
def untraced():
    """Three requests, one after the other (three micro-batches),
    through a daemon with neither a Tracer nor a telemetry session."""
    assert obs_trace.active() is None and not obs_trace.sinks_active()
    corpus = tied_corpus()
    daemon = ServeDaemon(
        corpus, EngineConfig(use_pallas=True, select="extract"),
        warm_buckets=[(3, 4)])
    try:
        daemon.start()
        null_while_serving = obs_trace.span("serve.micro_batch")
        for _ in range(3):
            assert ask(daemon.port, query(corpus))["ok"]
        # a response reaches its client a moment before its handler
        # notes the write and the batcher closes the cycle
        for _ in range(200):
            stats = ask(daemon.port, {"op": "stats"})["stats"]
            if stats["phases_ms"]["request"]["write"]["count"] == 3 \
                    and stats["batcher"]["cycles"] == 3:
                break
            time.sleep(0.01)
    finally:
        daemon.close()
    return {"stats": stats, "span": null_while_serving}


def test_no_sink_means_no_span(untraced):
    assert untraced["span"] is obs_trace.NULL_SPAN
    assert not telemetry.enabled()


@pytest.mark.parametrize("group,key", [(g, k) for g, names in
                                       PHASE_HISTOGRAMS.items()
                                       for k, _ in names])
def test_stats_reports_every_phase_without_a_tracer(untraced, group, key):
    stats = untraced["stats"]
    if (group, key) == ("batch", "merge"):
        # a mesh daemon's part (tests/test_mesh_serve.py): one chip
        # merges nothing and reports none
        assert key not in stats["phases_ms"][group]
        return
    assert stats["requests_completed"] == 3 and stats["batches"] == 3
    got = stats["phases_ms"][group][key]
    assert got["count"] == 3        # the requests, or the batches, served
    assert 0 <= got["p50"] <= got["p95"]
    assert stats["request_latency_ms"]["count"] == 3


def test_phase_timings_exclude_warm_up(traced):
    """Warm-up solves run through solve_batch too; only the batcher
    feeds the histograms, so the counts are those of served batches."""
    parts = traced["stats"]["phases_ms"]["batch"]
    assert {k: v["count"] for k, v in parts.items()} == {
        "dispatch": 1, "fetch": 1, "hazard": 1, "finalize": 1}


def test_batch_engine_reports_the_hazard_pass():
    """engine.last_phase_ms: fetch no longer hides the hazard pass."""
    corpus = tied_corpus()
    q = corpus.data_attrs[:120:40]
    inp = KNNInput(Params(corpus.params.num_data, len(q), NA),
                   corpus.labels, corpus.data_attrs,
                   np.full(len(q), 4, np.int32), q)
    eng = SingleChipEngine(EngineConfig())
    eng.run(inp)
    assert {"fetch", "hazard", "finalize"} <= set(eng.last_phase_ms)
    assert all(v >= 0 for v in eng.last_phase_ms.values())


def test_batch_engine_makes_the_norm_pass_once_a_run_inside_hazard():
    """A batch solve owns no corpus: each run computes the value on
    first need, inside that segment's ``single.hazard``
    (``dn_max_cached`` false); the run's later segment reuses it. A k
    beyond the kernel's window routes the run into two segments."""
    corpus = tied_corpus()
    q = corpus.data_attrs[:6 * 40:40]
    ks = np.array([4, 700, 2, 8, 640, 1], np.int32)
    inp = KNNInput(Params(corpus.params.num_data, len(q), NA),
                   corpus.labels, corpus.data_attrs, ks, q)
    eng = SingleChipEngine(EngineConfig(use_pallas=True, select="extract"))
    tracer = obs_trace.install(obs_trace.Tracer())
    try:
        eng.run(inp)
        first = len(tracer.events())
        eng.run(inp)
    finally:
        obs_trace.uninstall()
    for events in (tracer.events()[:first], tracer.events()[first:]):
        spans = [e for e in events if e.get("ph") == "X"]
        (pas,) = named(spans, DN_MAX)
        assert work_args(pas) == {"rows": 2400}
        first_seg, second_seg = named(spans, "single.hazard")
        assert eng.last_hetk == (4, 2)
        assert first_seg["args"]["dn_max_cached"] is False
        assert second_seg["args"]["dn_max_cached"] is True
        assert inside(pas, first_seg)


# -- kernel names ---------------------------------------------------------------

def _pallas_names(jaxpr) -> list:
    """The ``name=dmlp_...`` params the printed jaxpr carries (the
    pallas_call's; the jits around it have other names)."""
    return re.findall(r"\bname=(dmlp_\w+)", str(jaxpr))


@pytest.mark.parametrize("mxu_gate,carry,want", [
    (True, False, "dmlp_topk_fused_fresh"),
    (True, True, "dmlp_topk_fused"),
    (False, False, "dmlp_topk_extract_fresh"),
    (False, True, "dmlp_topk_extract"),
])
def test_extract_kernel_is_named_by_its_form(mxu_gate, carry, want):
    from dmlp_tpu.ops.pallas_extract import extract_topk
    q, d = jnp.zeros((8, 16)), jnp.zeros((1024, 16))
    lists = (jnp.zeros((8, 16)), jnp.zeros((8, 16), jnp.int32)) \
        if carry else (None, None)
    jaxpr = jax.make_jaxpr(
        lambda q, d, cd, ci: extract_topk(
            q, d, cd, ci, n_real=1000, kc=16, interpret=True,
            mxu_gate=mxu_gate))(q, d, *lists)
    assert _pallas_names(jaxpr) == [want]


def test_distance_kernel_is_named():
    from dmlp_tpu.ops.pallas_distance import fused_dist_segmin
    q, d = jnp.zeros((8, 16)), jnp.zeros((1024, 16))
    jaxpr = jax.make_jaxpr(
        lambda q, d, i: fused_dist_segmin(q, d, i, interpret=True))(
            q, d, jnp.arange(1024, dtype=jnp.int32))
    assert _pallas_names(jaxpr) == ["dmlp_dist_segmin"]


# -- a cosine daemon's own spans (PR 49) --------------------------------------

NORMALIZE = ["serve.normalize_rows", "serve.normalize_queries"]


def _scored_events(score: str):
    """The events of one daemon under ``score``: set-up, one request of
    three queries (the second a zero query), an ingest of four rows (the
    last a zero row), one more request."""
    corpus = tied_corpus()
    tracer = obs_trace.install(obs_trace.Tracer())
    daemon = None
    try:
        daemon = ServeDaemon(
            corpus, EngineConfig(use_pallas=True, select="extract",
                                 score=score), warm_buckets=[(3, 4)])
        daemon.start()
        mark = len(tracer.events())
        req = query(corpus)
        req["queries"][1] = [0.0] * NA
        assert ask(daemon.port, req)["ok"]
        rows = corpus.data_attrs[:4] * 3.0
        rows[3] = 0.0
        got = ask(daemon.port, {"op": "ingest", "labels": [0, 1, 2, 3],
                                "rows": rows.tolist()})
        assert got["ok"], got
        assert ask(daemon.port, req)["ok"]
        zero_rows = telemetry.registry().counter("serve.zero_rows").total()
    finally:
        if daemon is not None:
            daemon.close()
        obs_trace.uninstall()
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    return events, [e for e in tracer.events()[mark:]
                    if e.get("ph") == "X"], zero_rows


@pytest.mark.parametrize("score", ["l2", "ip"])
def test_a_daemon_under_another_score_emits_no_normalize_span(score):
    events, _served, _zero = _scored_events(score)
    for name in NORMALIZE:
        assert named(events, name) == [], (score, name)
    assert {e["args"]["score"] for e in named(events, "single.hazard")} \
        == {score}


def test_a_cosine_daemon_normalises_rows_at_set_up_and_at_ingest():
    """``serve.normalize_rows`` (rows, zero_rows, bytes): the float64
    norm pass over the corpus inside the construction, one span a staged
    block inside ``serve.stage_resident`` and ``serve.stage_chunks``,
    and again inside ``serve.ingest`` for the rows it brings (the zero
    row among them counted, in the span and in the registry);
    ``serve.normalize_queries`` (queries, zero_queries) once a
    micro-batch, inside the cycle's ``serve.solve_stage``."""
    zero0 = telemetry.registry().counter("serve.zero_rows").total()
    events, served, zero1 = _scored_events("cosine")
    rows = named(events, "serve.normalize_rows")
    n = tied_corpus().params.num_data
    for e in rows:
        assert set(e["args"]) >= {"rows", "zero_rows", "bytes", "site"}
        assert e["args"]["bytes"] == e["args"]["rows"] * NA * 8
    setup = [e for e in rows if e not in served]
    assert [e["args"]["site"] for e in setup[:2]] == ["norms", "stage"]
    assert setup[0]["args"]["rows"] == setup[1]["args"]["rows"] == n
    (resident,) = named(events, "serve.stage_resident")
    assert inside(setup[1], resident)
    (chunks,) = named(events, "serve.stage_chunks")
    staged = [e for e in setup[2:] if inside(e, chunks)]
    assert staged and sum(e["args"]["rows"] for e in staged) == n
    assert chunks["args"]["score"] == "cosine"
    (ingest,) = named(served, "serve.ingest")
    during = [e for e in named(served, "serve.normalize_rows")
              if inside(e, ingest)]
    assert [e["args"]["site"] for e in during][:2] == ["norms", "stage"]
    assert during[0]["args"]["rows"] == 4
    assert during[0]["args"]["zero_rows"] == 1
    assert zero1 - zero0 == 1
    # (the corpus ties forty deep, so every query is flagged: the device
    # retry stages its group's queries too, inside single.retry_begin)
    queries = named(served, "serve.normalize_queries")
    retries = named(served, "single.retry_begin")
    own = [e for e in queries if not any(inside(e, r) for r in retries)]
    assert len(queries) == 4 and len(own) == 2
    assert [e["args"]["queries"] for e in own] == [3, 3]
    assert [e["args"]["zero_queries"] for e in own] == [1, 1]
    stages = named(served, "serve.solve_stage")
    for e in own:
        assert e["args"]["batch"] is not None
        assert any(inside(e, s) and s["args"]["batch"] == e["args"]["batch"]
                   for s in stages)
    for name in ("serve.solve_extract", "single.hazard", "single.rescore",
                 "single.finalize"):
        assert {e["args"]["score"] for e in named(served, name)} \
            == {"cosine"}, name
