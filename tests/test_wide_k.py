"""Neighbour lists past the kernel's 512-slot window, served (PR 33).

A k whose bucket plans more than 512 candidate slots takes the resident
multipass driver (``ResidentEngine._solve_resident_multipass``): pass 1
folds the resident chunks at ``kc`` 512, every further pass sweeps the
whole stack above the floor the pass before reached, the merge dedups.
Here a ``ServeDaemon`` answers such requests over its socket, and every
answer is held to ``benchmark/reference.py:knn_plain``, the plain
float64 brute force that imports nothing of the program. On the CPU the
kernel runs in interpret mode: these tests hold the path, the counters
and the answers, not a time.
"""

from __future__ import annotations

import json
import socket
import time

import numpy as np
import pytest

from benchmark import reference
from dmlp_tpu.config import EngineConfig
from dmlp_tpu.io.grammar import KNNInput, Params
from dmlp_tpu.obs import trace as obs_trace
from dmlp_tpu.serve.daemon import ServeDaemon

N, NA = 1408, 4
#: what each request asks of its three queries, and the bucket it makes:
#: key, candidate slots (k + k / 8, no more than the 2048 rows of
#: capacity), passes at 512 slots a pass
REQUESTS = {"k1024": ([600, 1000, 1000], "q128k1024", 1152, 3),
            "k2048": ([1100, 1100, 1100], "q128k2048", 2048, 4)}
#: (rows, request, query, k): real-valued rows answer k in {600, 1000,
#: 1100}; integer-valued rows, whose tie plateaus pass 512, two of them
CASES = [("real", "k1024", 0, 600), ("real", "k1024", 1, 1000),
         ("real", "k2048", 0, 1100), ("integer", "k1024", 0, 600),
         ("integer", "k1024", 2, 1000)]


def config():
    # ``python -m dmlp_tpu.serve --pallas --dtype float32`` on the
    # extract path whatever the corpus's size (the 8192-row switch is
    # the benchmark rehearsal's to pass); one resident chunk of one
    # extraction block: the interpreter spends seconds a block a pass
    return EngineConfig(select="extract", use_pallas=True,
                        dtype="float32")


def draw(kind: str, rng, n: int) -> np.ndarray:
    if kind == "real":
        return rng.random((n, NA), dtype=np.float32).astype(np.float64) \
            * 255.0
    # integer-valued, and only two coordinates vary, over {0, 1}: a
    # squared distance is 0, 1 or 2 and half of the rows tie at 1, a
    # plateau of ~700 rows that no pass's 512 slots hold
    rows = np.zeros((n, NA))
    rows[:, :2] = rng.integers(0, 2, (n, 2))
    return rows


def ask(port: int, doc: dict) -> dict:
    with socket.create_connection(("127.0.0.1", port), timeout=900) as s:
        s.sendall((json.dumps(doc) + "\n").encode())
        return json.loads(s.makefile("rb").readline())


@pytest.fixture(scope="module")
def served():
    """A traced daemon a kind of rows, each asked its requests once
    over the socket (``parse_request`` -> batcher -> the multipass solve
    -> ``query_response``): what every test below reads."""
    out = {}
    for kind, names in (("real", ["k1024", "k2048"]),
                        ("integer", ["k1024"])):
        rng = np.random.default_rng(3301 if kind == "real" else 3302)
        rows = draw(kind, rng, N)
        labels = rng.integers(0, 10, N).astype(np.int32)
        corpus = KNNInput(Params(N, 0, NA), labels, rows,
                          np.zeros(0, np.int32), np.zeros((0, NA)))
        tracer = obs_trace.install(obs_trace.Tracer())
        daemon = ServeDaemon(corpus, config(), port=0,
                             warm_buckets=[(1, 8)])
        daemon.start()
        try:
            got = {}
            for name in names:
                ks = REQUESTS[name][0]
                q = draw(kind, rng, len(ks))
                before = daemon.stats()["engine"]
                mark = len(tracer.events())
                resp = ask(daemon.port, {
                    "op": "query", "id": name, "rid": f"r-{name}",
                    "ks": ks, "debug": True, "queries": q.tolist()})
                # the handler records the respond and write spans
                # after the line has gone out: wait for them
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline and not any(
                        e.get("name") == "serve.phase.write"
                        for e in tracer.events()[mark:]):
                    time.sleep(0.01)
                got[name] = {
                    "q": q, "resp": resp, "before": before,
                    "after": daemon.stats()["engine"],
                    "stamp": daemon.stats()["device"],
                    "events": [e for e in tracer.events()[mark:]
                               if e.get("ph") == "X"]}
            out[kind] = {
                "rows": rows, "labels": labels, "requests": got,
                "plans": {name: daemon.engine.bucket_plan(
                    3, max(REQUESTS[name][0])) for name in names},
                "models": {k: daemon.engine.mem_model(3, k)["terms"]
                           for k in (10, 1000, 1100)},
                "price": daemon.engine.batch_model_bytes(3, 1000),
                "chunk_bytes": daemon.engine._chunks.nbytes,
                "rejected": daemon.admission.snapshot()["rejected"]}
        finally:
            daemon.close()
            obs_trace.uninstall()
    return out


@pytest.mark.parametrize("kind,name,j,k", CASES)
def test_deep_lists_are_served_exact(served, kind, name, j, k):
    """Labels, ids, checksums and float64 distances are the plain brute
    force's, id for id and bit for bit."""
    side = served[kind]
    req = side["requests"][name]
    resp = req["resp"]
    assert resp["ok"] is True, resp
    assert REQUESTS[name][0][j] == k
    (ans,) = reference.knn_plain(side["rows"], side["labels"],
                                 req["q"][j:j + 1], [k])
    assert len(resp["neighbors"][j]) == k
    assert resp["labels"][j] == ans.label
    assert resp["neighbors"][j] == [int(i) for i in ans.ids]
    assert resp["checksums"][j] == ans.checksum \
        == reference.fnv1a(resp["labels"][j], resp["neighbors"][j])
    assert np.array_equal(np.asarray(resp["dists"][j]), ans.dists)


@pytest.mark.parametrize("kind,name", [("real", "k1024"), ("real", "k2048"),
                                       ("integer", "k1024")])
def test_the_multipass_path_says_what_it_did(served, kind, name):
    req = served[kind]["requests"][name]
    ks, key, kcap, passes = REQUESTS[name]
    eng, before = req["after"], req["before"]

    # the path: a multipass bucket, on the kernel, counted always-on
    assert eng["paths"][key] == "multipass"
    assert served[kind]["plans"][name] == (128, int(key.split("k")[1]),
                                           kcap)
    assert req["stamp"]["select"] == "extract"
    assert eng["extract_chunks"] == 1
    assert eng["last_mp_passes"] == passes
    assert eng["last_kernel_calls"] == 1 + passes - 1
    # every pass, the fold and the sweeps, at the exact engines' form:
    # three bf16 MXU passes over split operands (PR 36)
    assert eng["precision_plan"] == "bf16x3"
    assert eng["last_precision"]["active"] == "bf16x3"
    mp, mp0 = eng["multipass"], before["multipass"]
    assert mp["batches"] == mp0["batches"] + 1
    assert mp["passes"] == mp0["passes"] + passes

    # the spans: one solve, a pass span a pass, one merge, one fence
    new = req["events"]
    (solve,) = [e for e in new if e["name"] == "serve.solve_multipass"]
    args = solve["args"]
    assert (args["qpad"], args["kcap"], args["passes"], args["queries"],
            args["chunks"]) == (128, kcap, passes, len(ks), 1)
    assert max(args["stalled"], args["shortfall"]) <= args["flagged"] \
        <= len(ks)
    kids = [e for e in new if e["name"] in (
        "serve.mp_pass", "serve.mp_merge", "serve.mp_fetch")]
    assert [e["name"] for e in kids] == ["serve.mp_pass"] * passes \
        + ["serve.mp_merge", "serve.mp_fetch"]
    assert [e["args"]["pass"] for e in kids[:passes]] \
        == list(range(1, passes + 1))
    assert all(e["args"]["kc"] == 512 and e["args"]["rows"] == N
               for e in kids[:passes])
    for e in kids:
        assert solve["ts"] <= e["ts"] \
            and e["ts"] + e["dur"] <= solve["ts"] + solve["dur"]
    (batch,) = [e for e in new if e["name"] == "serve.micro_batch"]
    assert batch["ts"] <= solve["ts"] and solve["ts"] + solve["dur"] \
        <= batch["ts"] + batch["dur"]
    (respond,) = [e for e in new if e["name"] == "serve.phase.respond"]
    (write,) = [e for e in new if e["name"] == "serve.phase.write"]
    assert respond["args"]["k"] == max(ks)
    assert respond["args"]["queries"] == len(ks)
    assert respond["args"]["bytes"] == write["args"]["bytes"] \
        > sum(ks) * 4
    assert write["args"]["pieces"] == 1     # under a megabyte: one write
    assert respond["args"]["rid"] == f"r-{name}"

    # what the driver flagged: real-valued rows clear every floor; on
    # the integer rows a tie plateau wider than 512 stalls the floor
    # chain, the query is flagged, and the host repair keeps it exact
    # (test_deep_lists_are_served_exact)
    assert mp["flagged_stalled"] - mp0["flagged_stalled"] \
        == args["stalled"]
    assert mp["flagged_shortfall"] - mp0["flagged_shortfall"] \
        == args["shortfall"]
    (final,) = [e for e in new if e["name"] == "single.finalize"]
    if kind == "real":
        assert (args["flagged"], args["stalled"], args["shortfall"]) \
            == (0, 0, 0)
    else:
        assert args["stalled"] >= 1
        assert final["args"]["repairs"] >= args["flagged"] >= 1


def test_a_wide_bucket_is_priced_at_what_the_driver_allocates(served):
    """Admission's price of a multipass batch: every pass's list pair,
    their concatenation and the merge's copies, beside the merged
    result; no term of the corpus's size (the sweeps read the resident
    stack itself)."""
    side = served["real"]
    terms = side["models"][1000]
    assert terms["multipass_lists"] == 4 * 128 * (3 * 512) * 8
    assert terms["topk_carries"] == 2 * 128 * 1152 * 12
    assert side["price"] == terms["query_blocks"] \
        + terms["topk_carries"] + terms["multipass_lists"]
    assert side["models"][1100]["multipass_lists"] \
        == 4 * 128 * (4 * 512) * 8
    assert "multipass_lists" not in side["models"][10]
    assert "multipass_resident" not in terms
    assert terms["extract_chunks"] == side["chunk_bytes"]
    assert side["rejected"] == {}


# -- the whole-array sweep must tile, or the solve stops -----------------------

def _untileable_sweep(monkeypatch, full_rows: int):
    """The variant of the whole-array row count alone becomes one whose
    sub-blocks (7 a block, 896 rows) cannot tile it; a chunk's stays as
    it is."""
    from dmlp_tpu.ops import pallas_extract
    real = pallas_extract.resolve_variant

    def resolve_variant(kc, b, qb=None, a=None):
        v = real(kc, b, qb, a)
        if b == full_rows:
            v["ne"] = 7
        return v
    monkeypatch.setattr(pallas_extract, "resolve_variant", resolve_variant)


def test_resident_sweep_assertion_fires_before_any_dispatch(monkeypatch):
    from dmlp_tpu.serve import engine as serve_engine
    from dmlp_tpu.serve.engine import ResidentEngine
    rng = np.random.default_rng(5)
    # (two chunks: the sweep's row count is not a chunk's)
    corpus = KNNInput(Params(14080, 0, 4),
                      rng.integers(0, 4, 14080).astype(np.int32),
                      rng.uniform(0, 60, (14080, 4)),
                      np.zeros(0, np.int32), np.zeros((0, 4)))
    eng = ResidentEngine(corpus, EngineConfig(
        select="extract", use_pallas=True, data_block=512))
    full_rows = eng._ex_nchunks * eng._ex_chunk_rows
    assert eng._ex_nchunks == 2 and full_rows % (128 * 7) != 0
    _untileable_sweep(monkeypatch, full_rows)
    dispatched = []
    monkeypatch.setattr(serve_engine, "_fold_stack",
                        lambda *a, **k: dispatched.append("fold"))
    monkeypatch.setattr(serve_engine, "_sweep_stack",
                        lambda *a, **k: dispatched.append("sweep"))
    with pytest.raises(AssertionError, match="full-array sweep"):
        eng.solve_batch(rng.uniform(0, 60, (2, 4)),
                        np.asarray([520, 600], np.int32))
    assert dispatched == []


def test_batch_sweep_assertion_fires_on_a_variant_that_cannot_tile(
        monkeypatch):
    from dmlp_tpu.engine.single import SingleChipEngine
    from dmlp_tpu.io.datagen import generate_input_text
    from dmlp_tpu.io.grammar import parse_input_text
    inp = parse_input_text(
        generate_input_text(60_000, 128, 8, 0.0, 100.0, 600, 600, 4,
                            seed=3))
    eng = SingleChipEngine(EngineConfig(use_pallas=True, select="extract"))
    _untileable_sweep(monkeypatch, 2 * 38400)
    with pytest.raises(AssertionError, match="full-array sweep"):
        eng._solve_extract_multipass(inp)
