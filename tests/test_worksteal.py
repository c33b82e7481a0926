"""Work-stealing harness tests: the paper scenarios end-to-end on small
graphs — protocol integrity (every chunk processed exactly once THROUGH the
simulated memory), solution correctness, and the paper's qualitative
ordering (sRSP >= RSP, both beat global-sync baselines).

Tier-1 runs the sRSP scenario (the paper's contribution and the default
engine's hottest path); the full five-scenario sweep, the cross-scenario
ordering claims and the sssp/mis apps are `slow` (run with `make test-slow`).
Scenario sims are compiled once per fixture and caches cleared afterwards —
the compiled round loops are large."""
import jax
import numpy as np
import pytest

from repro.data.graphs import collab_like, road_like
from repro.workloads.worksteal import WSConfig, run_app, reference_solution

WS = WSConfig(n_wgs=4, chunk_cap=32, n_chunks_max=16)
G = collab_like(n=384, m=3, seed=1)
SCENARIOS = ["baseline", "scope_only", "steal_only", "rsp", "srsp"]


@pytest.fixture(scope="module")
def srsp_result():
    out = run_app("pagerank", G, "srsp", WS, max_iters=2)
    yield out
    jax.clear_caches()


@pytest.fixture(scope="module")
def results():
    out = {s: run_app("pagerank", G, s, WS, max_iters=2) for s in SCENARIOS}
    yield out
    jax.clear_caches()


def test_srsp_every_chunk_processed_exactly_once(srsp_result):
    assert srsp_result.proc_errors == 0


def test_srsp_pagerank_solution_matches_reference(srsp_result):
    ref = reference_solution("pagerank", G, max_iters=2)
    np.testing.assert_allclose(srsp_result.solution, ref, rtol=1e-5)


def test_srsp_stealing_actually_happens(srsp_result):
    assert srsp_result.counters["steals"] > 0
    assert srsp_result.counters["promotions"] > 0  # PA-TBL promotion fired


@pytest.mark.slow
def test_every_chunk_processed_exactly_once(results):
    for s, r in results.items():
        assert r.proc_errors == 0, (s, r.proc_errors)


@pytest.mark.slow
def test_pagerank_solution_matches_reference(results):
    ref = reference_solution("pagerank", G, max_iters=2)
    for s in ("baseline", "srsp", "rsp"):
        np.testing.assert_allclose(results[s].solution, ref, rtol=1e-5)


@pytest.mark.slow
def test_paper_ordering_holds(results):
    base = results["baseline"].makespan
    assert results["steal_only"].makespan < base          # balance helps
    assert results["srsp"].makespan <= results["rsp"].makespan  # the claim
    assert results["srsp"].counters["inv_full"] < \
        results["rsp"].counters["inv_full"]
    assert results["srsp"].counters["l2_accesses"] <= \
        results["rsp"].counters["l2_accesses"]            # Fig. 5
    assert results["srsp"].makespan < results["baseline"].makespan
    assert results["srsp"].makespan < results["steal_only"].makespan


@pytest.mark.slow
def test_sssp_and_mis_on_srsp():
    g = road_like(n=400, seed=3)
    ws = WSConfig(n_wgs=4, chunk_cap=32, n_chunks_max=16)
    ref = reference_solution("sssp", g, max_iters=6)
    r = run_app("sssp", g, "srsp", ws, max_iters=6)
    assert r.proc_errors == 0
    np.testing.assert_array_equal(r.solution, ref)
    ref_m = reference_solution("mis", G, max_iters=4)
    rm = run_app("mis", G, "srsp", WS, max_iters=4)
    assert rm.proc_errors == 0
    np.testing.assert_array_equal(rm.solution, ref_m)
    jax.clear_caches()
