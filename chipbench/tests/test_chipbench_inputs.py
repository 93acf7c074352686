"""The benchmark's own inputs: the Kronecker generator and the per-seed
draws keep every shape fixed."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import inputs  # noqa: E402
from chipbench.drivers import submit_open  # noqa: E402
from chipbench.run import load_cell  # noqa: E402
from chipbench.tests.tiny import CELLS, tiny_bench  # noqa: E402



def test_kronecker_edges_are_simple_and_fixed_by_the_seed():
    e = inputs.kronecker_edges(10, 16, seed=3)
    assert e.dtype == np.int32 and e.shape[1] == 2
    assert (e[:, 0] < e[:, 1]).all()                       # no self-loops
    assert len(np.unique(e[:, 0] * 1024 + e[:, 1])) == len(e)  # no repeats
    assert e.max() < 1024
    assert np.array_equal(e, inputs.kronecker_edges(10, 16, seed=3))
    # Graph500's skew: the heaviest vertex holds far more than the mean
    deg = np.bincount(e.ravel(), minlength=1024)
    assert deg.max() > 10 * deg.mean()


def test_kronecker_matches_the_rmat_generator_in_distribution():
    """The same Graph500 initiator as ``gen.rmat``: after deduplication
    the two keep about the same number of edges and the same skew."""
    from repro.graph import generators as gen
    ours = inputs.kronecker_edges(12, 16, seed=0)
    theirs = gen.rmat(12, 32.0, seed=0).edges
    assert abs(len(ours) - len(theirs)) < 0.01 * len(theirs)
    top = [np.sort(np.bincount(e.ravel(), minlength=4096))[-40:].sum()
           for e in (ours, theirs)]
    assert abs(top[0] - top[1]) < 0.1 * top[1]


def test_relabel_keeps_shapes_and_degrees():
    g = inputs.UGraph(256, inputs.kronecker_edges(8, 16, seed=0))
    h = inputs.relabel(g, np.random.default_rng(7))
    assert (h.n, h.m) == (g.n, g.m)
    assert sorted(g.degrees()) == sorted(h.degrees())


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", CELLS)
def test_two_seeds_give_identical_shapes(bench, cell):
    parts = load_cell(bench, cell)      # the cell's shapes, at a tiny scale
    graphs = inputs.base_graphs(parts["config"])
    plans = [parts["driver"].plan(graphs, parts["traffic"],
                                  np.random.default_rng(s), 2.0)
             for s in (1, 2**31 + 11)]
    shapes = [sorted(r.shape() for r in p) for p in plans]
    assert shapes[0] == shapes[1]
    if cell.startswith("fleet.submit"):
        # one schedule: the same arrivals and kinds in the same order
        assert [(r.due, r.problem, r.graphs[0].n) for r in plans[0]] == \
            [(r.due, r.problem, r.graphs[0].n) for r in plans[1]]
    # and the seed does change the inputs
    a, b = plans[0][0].graphs[0], plans[1][0].graphs[0]
    assert not np.array_equal(a.edges, b.edges)


@pytest.mark.parametrize("weights", [False, True])
def test_a_work_seed_gives_every_seed_the_same_work(weights):
    """With ``work_seed`` two run seeds solve the same weighted edges with
    the same rank seeds, in their own edge orders."""
    from chipbench.drivers import solve_loop
    g = inputs.UGraph(128, inputs.kronecker_edges(7, 16, seed=0))
    traffic = {"problem": "mis", "graph": 0, "pool": 3, "work_seed": 5,
               "weights": weights}
    plans = [solve_loop.plan([g], traffic, np.random.default_rng(s), 1.0)
             for s in (1, 2**31 + 11)]
    assert [r.seed for r in plans[0]] == [r.seed for r in plans[1]]
    a, b = plans[0][0].graphs[0], plans[1][0].graphs[0]
    assert not np.array_equal(a.edges, b.edges)

    def edge_set(h):
        w = h.weights if weights else np.zeros(h.m)
        return sorted(zip(h.edges[:, 0], h.edges[:, 1], w))
    for ra, rb in zip(*plans):
        assert edge_set(ra.graphs[0]) == edge_set(rb.graphs[0])


def test_distinct_weights_are_a_permutation():
    w = inputs.distinct_weights(1000, np.random.default_rng(0))
    assert w.dtype == np.float32
    assert sorted(w.tolist()) == list(range(1, 1001))


def test_configs_state_their_sizes():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_open_loop_spreads_the_mix_over_the_window(bench):
    """Each block of requests holds every (problem, graph) pair in its
    share, so the rare heavy requests cannot bunch up by the seed."""
    parts = load_cell(bench, "fleet.submit-poisson")
    graphs = inputs.base_graphs(parts["config"])
    reqs = parts["driver"].plan(graphs, parts["traffic"],
                                np.random.default_rng(9), 51.0)
    blocks = len(reqs) // submit_open.MIX_BLOCK
    size = -(-len(reqs) // blocks)
    kinds = [(r.problem, r.graphs[0].n) for r in reqs]
    for kind in set(kinds):
        per_block = [kinds[i:i + size].count(kind)
                     for i in range(0, len(reqs), size)]
        assert max(per_block) - min(per_block) <= 1, (kind, per_block)
