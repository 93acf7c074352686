"""Maximal matching: all four implementations compute the exact LFMM."""
import numpy as np
import pytest

from repro.graph import generators as gen
from repro.core import matching as mm, oracle
from repro.core.rounds import RoundLedger

FAMILIES = [
    ("er", lambda: gen.erdos_renyi(250, 5.0, seed=6)),
    ("rmat", lambda: gen.rmat(9, 10.0, seed=7)),
    ("geo", lambda: gen.random_geometric(120, 1.0, seed=3)[0]),
    ("star", lambda: gen.star(40)),
]


@pytest.mark.parametrize("name,make", FAMILIES)
def test_mm_ampc_is_lfmm(name, make):
    g = make()
    got, st = mm.mm_ampc(g, seed=8)
    want = oracle.greedy_mm(g, st["erank"])
    assert np.array_equal(got, want)
    assert oracle.is_maximal_matching(g, got)


@pytest.mark.parametrize("name,make", FAMILIES)
def test_mm_levels_algorithm4(name, make):
    g = make()
    got, st = mm.mm_ampc_levels(g, seed=8)
    want = oracle.greedy_mm(g, st["erank"])
    assert np.array_equal(got, want)
    # Lemma 4.4: the level count k = ceil(log2 log2 Delta) + 1
    delta = max(int(g.degrees().max()), 2)
    assert st["k"] == int(np.ceil(np.log2(max(np.log2(delta), 1.000001)))) + 1


@pytest.mark.parametrize("name,make", FAMILIES[:2])
def test_mm_vertex_process_theorem2_part2(name, make):
    g = make()
    got, st = mm.mm_ampc_vertex_process(g, epsilon=0.5, seed=8)
    want = oracle.greedy_mm(g, st["erank"])
    assert np.array_equal(got, want)
    # O(1/eps) launches (Lemma 4.7): generous constant
    assert st["launches"] <= 10
    # total space O(m + n^{1+eps})
    assert st["queries"] <= 4 * (g.m + g.n * st["budget"]) + 1000


@pytest.mark.parametrize("name,make", FAMILIES[:2])
def test_mm_mpc_rootset(name, make):
    g = make()
    got, st = mm.mm_mpc_rootset(g, seed=8)
    want = oracle.greedy_mm(g, st["erank"])
    assert np.array_equal(got, want)


def test_shuffle_counts_table3():
    """AMPC MM uses O(1) shuffles; MPC uses 2 per phase (Table 3)."""
    g = gen.rmat(9, 8.0, seed=1)
    la = RoundLedger("ampc_mm")
    mm.mm_ampc(g, seed=0, ledger=la)
    assert la.shuffles == 2
    lm = RoundLedger("mpc_mm")
    _, st = mm.mm_mpc_rootset(g, seed=0, ledger=lm)
    assert lm.shuffles == 2 * st["phases"]
    assert lm.shuffles > la.shuffles


def test_caching_reduces_queries():
    """Fig 4: dedup (caching) reduces KV-store traffic."""
    g = gen.rmat(9, 8.0, seed=2)
    _, st = mm.mm_ampc(g, seed=0)
    assert st["queries_dedup"] < st["queries_nodedup"]


# ------------------------------------------------------------ lane counters
UNKNOWN, IN, OUT = mm.UNKNOWN, mm.IN, mm.OUT

# seeded Kronecker (R-MAT) and random graphs; the second and third fall in
# one solve_many bucket (n 512, m 1,024), both with padded edge lanes
LANE_GRAPHS = {
    "rmat": lambda: gen.rmat(8, 8.0, seed=11),
    "er": lambda: gen.erdos_renyi(300, 4.0, seed=12),
    "er2": lambda: gen.erdos_renyi(260, 4.0, seed=13),
}

# the parent's outputs and counts, per (graph, seed): matched edges, sum of
# their indices, waves, queries_nodedup, queries_dedup; and the levels
# path's matched edges, index sum and waves per level
PINNED = {
    ("rmat", 1): ((52, 19565, 4, 1914, 334), (52, 19565, [2, 2])),
    ("rmat", 2): ((52, 20818, 4, 1908, 311), (52, 20818, [3, 2])),
    ("er", 1): ((121, 34361, 4, 1476, 443), (121, 34361, [4])),
    ("er", 2): ((127, 36801, 3, 1450, 441), (127, 36801, [3])),
    ("er2", 1): ((102, 26635, 3, 1232, 368), (102, 26635, [3])),
    ("er2", 2): ((98, 27308, 4, 1244, 373), (98, 27308, [4])),
}


def _numpy_waves(g, rank, status=None):
    """The matching fixpoint replayed in numpy, one wave at a time: an
    unresolved edge joins when no endpoint is matched and its rank is the
    lowest of the unresolved edges at both endpoints; then every
    unresolved edge at a matched vertex dies.  Returns the final status and
    the unresolved edges at the start of each wave."""
    u, v = g.edges[:, 0], g.edges[:, 1]
    status = np.zeros(g.m, np.int32) if status is None else status.copy()
    live = []
    while (status == UNKNOWN).any():
        unk = status == UNKNOWN
        live.append(int(unk.sum()))
        matched = np.zeros(g.n, bool)
        matched[u[status == IN]] = matched[v[status == IN]] = True
        low = np.full(g.n, np.inf, np.float32)
        np.minimum.at(low, u[unk], rank[unk])
        np.minimum.at(low, v[unk], rank[unk])
        join = (unk & ~matched[u] & ~matched[v]
                & (rank <= low[u]) & (rank <= low[v]))
        status[join] = IN
        matched[u[join]] = matched[v[join]] = True
        status[(status == UNKNOWN) & (matched[u] | matched[v])] = OUT
    return status, live


def _pinned(mask):
    idx = np.flatnonzero(mask)
    return len(idx), int(idx.sum())


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_mm_lane_counters_match_a_numpy_recount(name, seed):
    g = LANE_GRAPHS[name]()
    got, st = mm.mm_ampc(g, seed=seed)
    status, live = _numpy_waves(g, st["erank"])
    assert np.array_equal(got, status == IN)
    assert st["fixpoint_iters"] == len(live)
    assert st["mm_lane_steps"] == sum(live)
    assert st["mm_lane_steps_run"] == g.m * len(live)
    assert 0 < st["mm_lane_steps"] < st["mm_lane_steps_run"]
    # outputs and the paper's query counts are the parent's, bit for bit
    want, _ = PINNED[name, seed]
    assert (*_pinned(got), st["fixpoint_iters"], st["queries_nodedup"],
            st["queries_dedup"]) == want


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_mm_levels_lane_counters_sum_the_levels(name, seed):
    """Algorithm 4 runs the fixpoint once per level on the sampled edges
    (the rest start OUT and never count as live); its counters sum the
    levels.  Where one level holds every edge they equal the sequential
    solve's."""
    g = LANE_GRAPHS[name]()
    got, st = mm.mm_ampc_levels(g, seed=seed)
    u, v = g.edges[:, 0], g.edges[:, 1]
    erank01 = np.random.default_rng(seed).permutation(g.m) / g.m
    rank = erank01.astype(np.float32)
    status = np.zeros(g.m, np.int32)
    live = []
    for level in st["levels"]:
        in_h = (erank01 <= level["threshold"]) & (status == UNKNOWN)
        lvl, lvl_live = _numpy_waves(
            g, np.where(in_h, rank, np.inf).astype(np.float32),
            np.where(in_h, UNKNOWN, OUT).astype(np.int32))
        assert level["iters"] == len(lvl_live)
        live += lvl_live
        status[(lvl == IN) & in_h] = IN
        matched = np.zeros(g.n, bool)
        matched[u[status == IN]] = matched[v[status == IN]] = True
        status[(status == UNKNOWN) & (matched[u] | matched[v])] = OUT
    assert np.array_equal(got, status == IN)
    assert st["mm_lane_steps"] == sum(live)
    assert st["mm_lane_steps_run"] == g.m * len(live)
    _, want = PINNED[name, seed]
    assert (*_pinned(got), [lv["iters"] for lv in st["levels"]]) == want
    if len(st["levels"]) == 1:
        _, seq = mm.mm_ampc(g, seed=seed)
        assert st["mm_lane_steps"] == seq["mm_lane_steps"]
        assert st["mm_lane_steps_run"] == seq["mm_lane_steps_run"]


@pytest.mark.parametrize("seed", [1, 2])
def test_every_matching_path_reports_the_same_lane_steps(seed):
    """Sequential, batched (one bucket, padded edge lanes), weighted
    matching and vertex cover: the same live lanes; a batched lane runs its
    padded width every wave."""
    from repro.ampc import AmpcEngine
    names = ["er", "er2"]
    graphs = [LANE_GRAPHS[name]() for name in names]
    eng = AmpcEngine(seed=seed)
    seq = [eng.solve(g, "matching") for g in graphs]
    batched = eng.solve_many(graphs, "matching")
    widths = {res.stats["solver_cache"]["key"][3] for res in batched}
    assert widths == {1024} and all(g.m < 1024 for g in graphs)
    for name, g, s, b in zip(names, graphs, seq, batched):
        assert np.array_equal(b.output, s.output)
        _, live = _numpy_waves(g, s.stats["erank"])
        assert s.stats["mm_lane_steps"] == b.stats["mm_lane_steps"] == \
            sum(live)
        assert s.stats["mm_lane_steps_run"] == g.m * len(live)
        assert b.stats["mm_lane_steps_run"] == 1024 * len(live)
        assert (s.stats["fixpoint_iters"], s.stats["queries_nodedup"],
                s.stats["queries_dedup"]) == PINNED[name, seed][0][2:]
        vc = eng.solve(g, "vertex-cover")
        assert vc.stats["mm_lane_steps"] == s.stats["mm_lane_steps"]
        assert vc.stats["mm_lane_steps_run"] == s.stats["mm_lane_steps_run"]
        gw = g.with_random_weights(seed)
        for res in [eng.solve(gw, "weighted-matching"),
                    eng.solve_many([gw], "weighted-matching")[0]]:
            _, wlive = _numpy_waves(gw, res.stats["erank"])
            assert res.stats["mm_lane_steps"] == sum(wlive)
