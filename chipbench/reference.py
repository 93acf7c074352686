"""Plain references for every problem, and the control that must fail them.

Each check compares one answer with what the problem's definition
guarantees and returns counts of violations, each held to the limit 0:

  mis           the set is independent and maximal;
  matching      the edge set is a matching and maximal;
  connectivity  the component partition equals scipy's;
  msf           the edge set equals scipy's minimum spanning forest, which
                is unique because the weights are distinct.

These import nothing of the engine but the graph container.  The control
(:func:`control_answer`) is a reference computed with one guarantee broken,
in the way a faster program would be tempted to break it: the greedy
fixpoints of mis, matching and connectivity stopped after
``CONTROL_WAVES`` waves, and the msf computed on weights rounded to
bfloat16, the precision below the configuration's float32.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph

CONTROL_WAVES = 2

# the numbers each problem's check reports, all with the limit 0
CHECKS = {
    "mis": ("mis.dependent_edges", "mis.undominated_vertices"),
    "matching": ("matching.shared_vertices", "matching.free_edges"),
    "connectivity": ("connectivity.mislabeled_vertices",),
    "msf": ("msf.edges_differing",),
}


def _adjacency(n: int, edges: np.ndarray, weights) -> sp.csr_matrix:
    return sp.csr_matrix((weights, (edges[:, 0], edges[:, 1])), shape=(n, n))


def _min_id_labels(labels: np.ndarray) -> np.ndarray:
    """Component labels renamed to the smallest vertex id in each."""
    labels = np.asarray(labels)
    _, inv = np.unique(labels, return_inverse=True)
    rep = np.full(inv.max() + 1, labels.shape[0], np.int64)
    np.minimum.at(rep, inv, np.arange(labels.shape[0]))
    return rep[inv]


def components(g) -> np.ndarray:
    _, lab = csgraph.connected_components(
        _adjacency(g.n, g.edges, np.ones(g.m)), directed=False)
    return _min_id_labels(lab)


def msf_mask(g, weights=None) -> np.ndarray:
    """Minimum spanning forest over ``g.edges`` by scipy, as an edge mask.
    ``weights`` defaults to the graph's own; edges are recovered by their
    (u, v) pair."""
    w = np.asarray(g.weights if weights is None else weights, np.float64)
    lo = np.minimum(g.edges[:, 0], g.edges[:, 1]).astype(np.int64)
    hi = np.maximum(g.edges[:, 0], g.edges[:, 1]).astype(np.int64)
    forest = csgraph.minimum_spanning_tree(
        _adjacency(g.n, np.stack([lo, hi], 1), w)).tocoo()
    fl = np.minimum(forest.row, forest.col).astype(np.int64)
    fh = np.maximum(forest.row, forest.col).astype(np.int64)
    key = lo * g.n + hi
    order = np.argsort(key)
    pos = np.searchsorted(key[order], fl * g.n + fh)
    mask = np.zeros(g.m, bool)
    mask[order[pos]] = True
    return mask


def _shape_ok(out, size: int) -> bool:
    return isinstance(out, np.ndarray) and out.shape == (size,)


def check(problem: str, g, out) -> dict:
    """Violation counts of one answer (see :data:`CHECKS`).  An answer of
    the wrong shape counts every vertex or edge as a violation."""
    names = CHECKS[problem]
    size = g.m if problem in ("matching", "msf") else g.n
    if out is None or not _shape_ok(np.asarray(out), size):
        return {k: size for k in names}
    out = np.asarray(out)
    u, v = g.edges[:, 0], g.edges[:, 1]
    if problem == "mis":
        s = out.astype(bool)
        dominated = s.copy()
        dominated[u[s[v]]] = True
        dominated[v[s[u]]] = True
        return {names[0]: int((s[u] & s[v]).sum()),
                names[1]: int((~dominated).sum())}
    if problem == "matching":
        e = out.astype(bool)
        deg = np.bincount(u[e], minlength=g.n) + np.bincount(v[e],
                                                             minlength=g.n)
        matched = deg > 0
        return {names[0]: int((deg > 1).sum()),
                names[1]: int((~matched[u] & ~matched[v]).sum())}
    if problem == "connectivity":
        return {names[0]: int((_min_id_labels(out) != components(g)).sum())}
    return {names[0]: int((out.astype(bool) != msf_mask(g)).sum())}


# ---------------------------------------------------------------- control
def _rank(size: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).permutation(size)


def _capped_greedy_mis(g, seed: int, waves: int) -> np.ndarray:
    """Rank-greedy MIS, one wave at a time: an undecided vertex joins when
    its rank is below every undecided neighbour's.  Stopped after
    ``waves`` waves; the still undecided vertices are left out."""
    rank = _rank(g.n, seed)
    u = np.concatenate([g.edges[:, 0], g.edges[:, 1]])
    v = np.concatenate([g.edges[:, 1], g.edges[:, 0]])
    undecided = np.ones(g.n, bool)
    inset = np.zeros(g.n, bool)
    for _ in range(waves):
        if not undecided.any():
            break
        live = undecided[u] & undecided[v]
        lowest = np.full(g.n, g.n, np.int64)
        np.minimum.at(lowest, u[live], rank[v[live]])
        join = undecided & (rank < lowest)
        inset |= join
        undecided &= ~join
        undecided[v[join[u]]] = False
    return inset


def _capped_greedy_matching(g, seed: int, waves: int) -> np.ndarray:
    """Rank-greedy matching, one wave at a time: an undecided edge joins
    when its rank is below every undecided edge at either endpoint."""
    rank = _rank(g.m, seed)
    u, v = g.edges[:, 0], g.edges[:, 1]
    undecided = np.ones(g.m, bool)
    inset = np.zeros(g.m, bool)
    for _ in range(waves):
        if not undecided.any():
            break
        lowest = np.full(g.n, g.m, np.int64)
        np.minimum.at(lowest, u[undecided], rank[undecided])
        np.minimum.at(lowest, v[undecided], rank[undecided])
        join = undecided & (rank == lowest[u]) & (rank == lowest[v])
        inset |= join
        matched = np.zeros(g.n, bool)
        matched[u[join]] = matched[v[join]] = True
        undecided &= ~(matched[u] | matched[v])
    return inset


def _capped_label_propagation(g, waves: int) -> np.ndarray:
    """Min-label propagation stopped after ``waves`` rounds."""
    lab = np.arange(g.n)
    u, v = g.edges[:, 0], g.edges[:, 1]
    for _ in range(waves):
        nxt = lab.copy()
        np.minimum.at(nxt, u, lab[v])
        np.minimum.at(nxt, v, lab[u])
        lab = nxt
    return lab


def control_answer(problem: str, g, seed: int):
    """The control's answer to one request, in the program's place."""
    if problem == "mis":
        return _capped_greedy_mis(g, seed, CONTROL_WAVES)
    if problem == "matching":
        return _capped_greedy_matching(g, seed, CONTROL_WAVES)
    if problem == "connectivity":
        return _capped_label_propagation(g, CONTROL_WAVES)
    low = np.asarray(g.weights).astype(ml_dtypes.bfloat16).astype(np.float64)
    return msf_mask(g, low)
