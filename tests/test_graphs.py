"""`data/graphs.collab_like` and `collab_degrees` against the per-node
`rng.choice` loop they replace (DESIGN.md §15).

The oracle below is that loop as it stood.  The fast path decodes numpy's
PCG64 stream itself, so a numpy release that changes the bit generator,
the 32-bit half-word buffer or the bounded-integer rule fails here first.
"""
import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.data import graphs
from repro.workloads import worksteal

REFERENCE = (pathlib.Path(__file__).resolve().parents[1] / "bench"
             / "reference" / "graphs.py")
CELL_N, CELL_M = 32768, 3      # the work-steal cells' graph
# seeds whose cell-size draw runs Lemire's rejection path (two each)
REJECTING_SEEDS = (1, 2)


def oracle(n, m, seed):
    """(graph, rng after it): one `rng.choice` per node."""
    rng = np.random.default_rng(seed)
    src, dst = [], []
    repeated = list(range(m))
    for v in range(m, n):
        picks = rng.choice(len(repeated), size=m, replace=True)
        chosen = {repeated[p] for p in picks}
        for t in chosen:
            src.append(v)
            dst.append(t)
            repeated.append(t)
            repeated.append(v)
    g = graphs._to_csr(n, np.array(src, np.int64), np.array(dst, np.int64),
                       rng, f"collab_like_n{n}")
    return g, rng


def _assert_same_graph(g, want):
    assert g.name == want.name
    for name in ("indptr", "indices", "weights"):
        np.testing.assert_array_equal(getattr(g, name), getattr(want, name))


def _rng_after(n, m, seed):
    """The rng `collab_like` leaves, with the graph it drew."""
    repeated, rng, _ = graphs._attach(n, m, seed)
    edges = np.array(repeated, np.int64)
    return graphs._to_csr(n, edges[m + 1::2], edges[m::2], rng, ""), rng


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("m", [1, 2, 3, 6])
def test_collab_like_equals_per_node_choice_loop(m, seed):
    for n in (1, m, m + 1, m + 2, 97, 400):
        want, rng_want = oracle(n, m, seed)
        _assert_same_graph(graphs.collab_like(n, m, seed), want)
        degrees, _ = graphs.collab_degrees(n, m, seed)
        np.testing.assert_array_equal(degrees, want.degrees)
        assert degrees.dtype == np.int32
        # the generator is left where the loop left it: its next draws,
        # 32-bit and 64-bit, agree
        _, rng = _rng_after(n, m, seed)
        assert rng.bit_generator.state == rng_want.bit_generator.state
        np.testing.assert_array_equal(rng.integers(0, 7, size=5),
                                      rng_want.integers(0, 7, size=5))
        assert rng.random() == rng_want.random()


@pytest.mark.parametrize("seed", REJECTING_SEEDS)
def test_cell_size_graph_through_the_rejection_path(seed):
    degrees, draws = graphs.collab_degrees(CELL_N, CELL_M, seed)
    assert draws.rejections > 0
    halves = CELL_M * (CELL_N - CELL_M) + draws.rejections
    assert draws.words == -(-halves // 2)
    want, _ = oracle(CELL_N, CELL_M, seed)
    _assert_same_graph(graphs.collab_like(CELL_N, CELL_M, seed), want)
    np.testing.assert_array_equal(degrees, want.degrees)


@pytest.mark.parametrize("seed", [2, 2147480030])
def test_collab_degrees_equals_the_benchmark_reference(seed):
    spec = importlib.util.spec_from_file_location("reference_graphs",
                                                  REFERENCE)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    degrees, _ = graphs.collab_degrees(CELL_N, CELL_M, seed)
    np.testing.assert_array_equal(
        degrees, ref.collab_degrees(CELL_N, CELL_M, seed))


def test_refuses_a_generator_it_cannot_decode(monkeypatch):
    mt = lambda seed: np.random.Generator(np.random.MT19937(seed))  # noqa: E731
    monkeypatch.setattr(np.random, "default_rng", mt)
    with pytest.raises(TypeError, match="MT19937"):
        graphs.collab_like(16, 2, 0)


@pytest.mark.parametrize("seed", [0, 5])
def test_worksteal_build_equals_a_build_from_the_oracle_degrees(
        monkeypatch, seed):
    got = worksteal.build("srsp", 4, seed=seed)
    monkeypatch.setattr(
        worksteal, "collab_degrees",
        lambda n, m, seed: (oracle(n, m, seed)[0].degrees,
                            graphs.Draws(0, 0)))
    want = worksteal.build("srsp", 4, seed=seed)
    for a, b in zip(jax.tree.leaves((got.state, got.ops)),
                    jax.tree.leaves((want.state, want.ops)), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
