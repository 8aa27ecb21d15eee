"""Every engine without the inner-product form refuses ``score="ip"``
by name (PR 46), and ``score="cosine"`` (PR 49: the ip kernel form over
operands normalised at staging, so whatever lacks the one lacks the
other): at construction (the batch solve, the batch mesh engines, a
resident engine whose corpus takes the streaming select, a mesh daemon
whose corpus takes the monolithic stream layout), at admission (a k
whose window passes the kernel's one pass: the multipass driver), at its
entry (the multi-host feed). None answers an inner-product or a cosine
corpus in squared L2, the ladder's ``streaming`` rung included: it is
skipped, and the host oracle answers under the engine's score. Since
PR 53 the mesh daemon's extract path HAS both forms
(``tests/test_mesh_score.py``); what is left of its refusal is the
layout that ranks by squared L2 alone."""

from __future__ import annotations

import types

import numpy as np
import pytest

from dmlp_tpu.config import EngineConfig
from dmlp_tpu.io.grammar import KNNInput, Params


def corpus(n=300, na=8, seed=46) -> KNNInput:
    rng = np.random.default_rng(seed)
    return KNNInput(Params(n, 0, na), rng.integers(0, 4, n).astype(np.int32),
                    rng.uniform(-1, 1, (n, na)), np.zeros(0, np.int32),
                    np.zeros((0, na)))


def _single(score):
    from dmlp_tpu.engine.single import SingleChipEngine
    SingleChipEngine(EngineConfig(score=score))


def _cli(mode):
    def build(score):
        from dmlp_tpu.cli import make_engine
        make_engine(EngineConfig(mode=mode, score=score, mesh_shape=(2, 1)
                                 if mode != "single" else None))
    return build


def _mesh_daemon_off_the_extract_path(score):
    from dmlp_tpu.serve.daemon import ServeDaemon
    ServeDaemon(corpus(), EngineConfig(mode="sharded", score=score),
                mesh_shape=(2, 1))                          # no use_pallas


def _streaming_select(score):
    from dmlp_tpu.serve.engine import ResidentEngine
    ResidentEngine(corpus(), EngineConfig(score=score))     # no use_pallas


def _small_auto_corpus(score):
    from dmlp_tpu.serve.engine import ResidentEngine
    ResidentEngine(corpus(), EngineConfig(score=score, use_pallas=True))


def _distributed(score):
    from dmlp_tpu.parallel.distributed import distributed_contract_run
    distributed_contract_run("/nonexistent", types.SimpleNamespace(
        config=EngineConfig(mode="sharded", score=score)))


def _multipass(score):
    from dmlp_tpu.serve.engine import ResidentEngine
    eng = ResidentEngine(corpus(2000), EngineConfig(
        score=score, use_pallas=True, select="extract"))
    assert eng.max_k == 256         # the last bucket of one kernel pass
    eng.solve_batch(np.ones((2, 8)), np.full(2, 300, np.int32))


REFUSALS = {
    "batch_solve": (_single, r"engine\.single\.SingleChipEngine \(the batch "
                             r"solve\) has no score='SCORE' form"),
    "cli_single": (_cli("single"), r"engine\.single\.SingleChipEngine"),
    "sharded": (_cli("sharded"), r"engine\.sharded\.ShardedEngine has no "
                                 r"score='SCORE' form"),
    "ring": (_cli("ring"), r"engine\.sharded\.RingEngine has no"),
    "auto": (_cli("auto"), r"engine\.auto\.AutoShardedEngine has no"),
    "mesh_daemon_off_the_extract_path": (
        _mesh_daemon_off_the_extract_path,
        r"fleet\.mesh_engine\.MeshResidentEngine's monolithic stream path "
        r"\(a corpus that does not take the extract path.*\) has no "
        r"score='SCORE' form"),
    "streaming_select": (_streaming_select,
                         r"ResidentEngine's streaming select .* has no "
                         r"score='SCORE' form"),
    "small_auto_corpus": (_small_auto_corpus,
                          r"ResidentEngine's streaming select .*8192 rows"),
    "multi_host_feed": (_distributed,
                        r"parallel\.distributed\.distributed_contract_run"),
    "multipass": (_multipass, r"k=300 beyond the serving cap 256 under "
                              r"score='SCORE': serve\.engine\.ResidentEngine"
                              r"'s multipass driver"),
}


@pytest.mark.parametrize("case", REFUSALS)
def test_an_engine_without_the_ip_form_refuses_it_by_name(case):
    build, message = REFUSALS[case]
    with pytest.raises(ValueError, match=message.replace("SCORE", "ip")):
        build("ip")


@pytest.mark.parametrize("case", REFUSALS)
def test_an_engine_without_the_cosine_form_refuses_it_by_name(case):
    build, message = REFUSALS[case]
    with pytest.raises(ValueError,
                       match=message.replace("SCORE", "cosine")) as e:
        build("cosine")
    # what it is told to do instead names its own score
    assert "--score ip" not in str(e.value)


def test_an_unknown_score_is_refused_and_l2_builds_everywhere():
    with pytest.raises(ValueError, match="unknown score 'manhattan'"):
        EngineConfig(score="manhattan")
    from dmlp_tpu.cli import make_engine
    for mode in ("single", "sharded", "ring", "auto"):
        make_engine(EngineConfig(mode=mode, mesh_shape=(2, 1)
                                 if mode != "single" else None))


def test_admission_refuses_a_k_past_one_pass_on_the_wire():
    """What a client is told: ``k_too_large`` (admission reads the
    engine's cap, 256 under ip where squared L2 serves the capacity)."""
    from dmlp_tpu.serve.admission import AdmissionController
    from dmlp_tpu.serve.engine import ResidentEngine
    caps = {}
    for score in ("l2", "ip", "cosine"):
        eng = ResidentEngine(corpus(2000), EngineConfig(
            score=score, use_pallas=True, select="extract"))
        caps[score] = AdmissionController(eng).max_k
    assert caps["ip"] == caps["cosine"] == 256 < caps["l2"]


def test_the_ladder_skips_the_streaming_rung_under_ip():
    """An engine under ip or cosine that runs out of memory on every
    kernel rung steps from ``heuristic`` to the host oracle, which
    answers under its score; under squared L2 the ``streaming`` rung is
    still tried."""
    from dmlp_tpu.golden.reference import knn_golden
    from dmlp_tpu.resilience import degrade
    from dmlp_tpu.resilience.retry import SimulatedResourceExhausted
    c = corpus(60)
    inp = KNNInput(Params(60, 3, 8), c.labels, c.data_attrs,
                   np.full(3, 4, np.int32), c.data_attrs[:3] * 2.0)
    for score, want in (("ip", list(degrade.RUNGS[:4])),
                        ("cosine", list(degrade.RUNGS[:4])),
                        ("l2", list(degrade.RUNGS[:5]))):
        eng = types.SimpleNamespace(config=EngineConfig(score=score))
        tried = []

        def solve(_inp):
            tried.append(eng._degrade_rung)
            raise SimulatedResourceExhausted("RESOURCE_EXHAUSTED")
        got = degrade.run_ladder(eng, inp, solve)
        assert tried == want and eng.last_degrade_rung == "host"
        for a, b in zip(got, knn_golden(inp, score=score)):
            assert np.array_equal(a.neighbor_ids, b.neighbor_ids)
            assert np.array_equal(a.neighbor_dists, b.neighbor_dists)
