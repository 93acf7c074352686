"""MIS: AMPC + MPC implementations compute the exact LFMIS (Section 5.3)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ampc.solvers import _build_mis_solver, _per_graph_ranks
from repro.graph import generators as gen
from repro.graph.batching import pad_graphs
from repro.graph.coo import UGraph
from repro.core import mis, oracle
from repro.core.rounds import RoundLedger

FAMILIES = [
    ("er", lambda: gen.erdos_renyi(300, 6.0, seed=2)),
    ("rmat", lambda: gen.rmat(9, 8.0, seed=3)),
    ("grid", lambda: gen.grid2d(14, 13)),
    ("star", lambda: gen.star(50)),
]


@pytest.mark.parametrize("name,make", FAMILIES)
def test_mis_ampc_is_lfmis(name, make):
    g = make()
    got, st = mis.mis_ampc(g, seed=4)
    rng = np.random.default_rng(4)
    want = oracle.greedy_mis(g, rng.permutation(g.n).astype(np.float32))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("name,make", FAMILIES[:2])
def test_mis_mpc_rootset(name, make):
    g = make()
    got, st = mis.mis_mpc_rootset(g, seed=4)
    rng = np.random.default_rng(4)
    want = oracle.greedy_mis(g, rng.permutation(g.n).astype(np.float32))
    assert np.array_equal(got, want)


def test_same_randomness_same_mis():
    """Paper: 'By specifying the same source of randomness, both the MPC and
    AMPC algorithms compute the same MIS.'"""
    g = gen.rmat(9, 6.0, seed=5)
    a, _ = mis.mis_ampc(g, seed=11)
    b, _ = mis.mis_mpc_rootset(g, seed=11)
    assert np.array_equal(a, b)


def test_shuffle_counts_table3():
    """AMPC MIS: 2 shuffles (1 heavy); MPC: 2 per phase, 8+ total."""
    g = gen.rmat(9, 8.0, seed=1)
    la = RoundLedger("ampc_mis")
    mis.mis_ampc(g, seed=0, ledger=la)
    assert la.shuffles == 2
    lm = RoundLedger("mpc_mis")
    _, st = mis.mis_mpc_rootset(g, seed=0, ledger=lm)
    assert lm.shuffles == 2 * st["phases"] and lm.shuffles >= 8


def test_caching_savings_factor():
    """Fig 4: caching reduces KV bytes by ~2-12x on skewed graphs."""
    g = gen.rmat(10, 12.0, seed=6)
    _, st = mis.mis_ampc(g, seed=0)
    assert st["cache_savings_factor"] > 1.2


def test_fixpoint_iters_log_n():
    """Fischer–Noever: O(log n) dependency depth w.h.p."""
    g = gen.erdos_renyi(2000, 8.0, seed=7)
    _, st = mis.mis_ampc(g, seed=0)
    assert st["fixpoint_iters"] <= 6 * np.log2(g.n)


# --------------------------------------------------------------------------
# The sorted-segment fixpoint against the scatter wave it replaced
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n",))
def _scatter_fixpoint(senders, receivers, rank, n, edge_ok):
    """The fixpoint as it was before the sorted-segment wave: two
    ``segment_max`` scatters and a probe scatter per wave."""
    UNKNOWN, IN, OUT = mis.UNKNOWN, mis.IN, mis.OUT

    def cond(s):
        return jnp.any(s[0] == UNKNOWN)

    def body(s):
        status, it, q0, q1 = s
        s_unk = (status[senders] == UNKNOWN) & edge_ok
        lower = rank[receivers] < rank[senders]
        blocked = s_unk & lower & (status[receivers] != OUT)
        has_block = jax.ops.segment_max(blocked.astype(jnp.int32), senders,
                                        num_segments=n)
        nbr_in = s_unk & (status[receivers] == IN)
        has_in = jax.ops.segment_max(nbr_in.astype(jnp.int32), senders,
                                     num_segments=n)
        unk = status == UNKNOWN
        status = jnp.where(unk & (has_in > 0), OUT, status)
        status = jnp.where(unk & (has_in <= 0) & (has_block <= 0), IN, status)
        probe = jnp.zeros((n,), jnp.int32).at[
            jnp.where(s_unk, receivers, n)].set(1, mode="drop")
        live = unk.any().astype(jnp.int32)
        return status, it + live, q0 + s_unk.sum(), q1 + probe.sum()

    zero = jnp.int32(0)
    return jax.lax.while_loop(
        cond, body, (jnp.zeros((n,), jnp.int32), zero, zero, zero))


def _lanes(n, edges, n_pad, seed):
    """Both directions of ``edges``, then ``n_pad`` masked lanes at vertex 0,
    shuffled together, with a rank permutation drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    edges = np.asarray(edges, np.int32).reshape(-1, 2)
    s = np.concatenate([edges[:, 0], edges[:, 1], np.zeros(n_pad, np.int32)])
    r = np.concatenate([edges[:, 1], edges[:, 0], np.zeros(n_pad, np.int32)])
    ok = np.arange(s.size) < 2 * len(edges)
    order = rng.permutation(s.size)
    rank = rng.permutation(n).astype(np.float32)
    return s[order], r[order], rank, ok[order]


def _random_multigraph(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 120))
    edges = rng.integers(0, n, (int(rng.integers(1, 4 * n)), 2))
    # repeat some edges, so duplicates (and self-loops) are present
    return n, np.concatenate([edges, edges[: len(edges) // 3]])


FIXPOINT_CASES = {
    **{f"random-{k}": (lambda k=k: (*_random_multigraph(k), 3 * k % 17))
       for k in range(6)},
    "isolated": lambda: (60, np.array([[0, 1], [1, 2], [2, 0], [3, 4]]), 5),
    "empty": lambda: (9, np.zeros((0, 2)), 0),
    "empty-masked": lambda: (9, np.zeros((0, 2)), 4),
    "n1": lambda: (1, np.zeros((0, 2)), 0),
    "n1-masked": lambda: (1, np.zeros((0, 2)), 3),
}


@pytest.mark.parametrize("case", sorted(FIXPOINT_CASES))
def test_sorted_fixpoint_matches_scatter_wave(case):
    n, edges, n_pad = FIXPOINT_CASES[case]()
    s, r, rank, ok = _lanes(n, edges, n_pad, seed=len(case))
    got = mis._mis_fixpoint_masked(s, r, rank, n, ok)
    want = _scatter_fixpoint(s, r, rank, n, ok)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), case
    assert not (np.asarray(got[0]) == mis.UNKNOWN).any()


def test_sorted_fixpoint_matches_scatter_wave_vmapped_bucket():
    """A ``solve_many`` bucket: graphs of several sizes padded to one shape,
    run through the vmapped solver the engine builds."""
    graphs = [gen.erdos_renyi(n, 3.0, seed=n) for n in (40, 55, 64, 23)]
    graphs.append(UGraph(17, np.zeros((0, 2), np.int32)))
    m_bucket = max(g.m for g in graphs) + 9
    batch = pad_graphs(graphs, range(len(graphs)), 64, m_bucket)
    s, r, ok = batch.padded_symmetric()
    ranks = _per_graph_ranks(batch, seed=3)
    got = _build_mis_solver(64)(s, r, ranks, ok)
    want = jax.vmap(lambda *a: _scatter_fixpoint(*a[:3], 64, a[3]))(
        s, r, ranks, ok)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _while_bodies(text):
    """The text of every ``stablehlo.while`` body in a lowered module, with
    the private functions it calls (transitively) appended."""
    funcs = dict(re.findall(r"func\.func \w+ @([\w.]+)\((.*?)\n  }", text,
                            re.S))

    def region(at):
        depth, i = 0, text.index("{", at)
        for j in range(i, len(text)):
            depth += {"{": 1, "}": -1}.get(text[j], 0)
            if depth == 0:
                return text[i:j + 1]

    bodies = []
    for m in re.finditer(r"\} do \{", text):
        body, seen = region(m.start() + 2), set()
        todo = re.findall(r"call @([\w.]+)", body)
        while todo:
            name = todo.pop()
            if name not in seen:
                seen.add(name)
                body += funcs[name]
                todo += re.findall(r"call @([\w.]+)", funcs[name])
        bodies.append(body)
    return bodies


def test_fixpoint_waves_have_no_scatter():
    """Indexed updates serialize per element on the TPU: the fixpoint's
    waves reduce over sorted segments, and no scatter may come back."""
    n, e = 16, 8
    text = mis._mis_fixpoint_masked.lower(
        jnp.zeros(e, jnp.int32), jnp.zeros(e, jnp.int32),
        jnp.arange(n, dtype=jnp.float32), n, jnp.ones(e, bool)).as_text()
    bodies = _while_bodies(text)
    assert bodies and any("stablehlo.gather" in b for b in bodies)
    assert not any("stablehlo.scatter" in b for b in bodies)
    # the guard sees a scatter where one is
    old = _scatter_fixpoint.lower(
        jnp.zeros(e, jnp.int32), jnp.zeros(e, jnp.int32),
        jnp.arange(n, dtype=jnp.float32), n, jnp.ones(e, bool)).as_text()
    assert any("stablehlo.scatter" in b for b in _while_bodies(old))
