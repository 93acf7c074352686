"""Benchmark-owned inputs: the Graph500 Kronecker generator and the
per-seed relabeling, weights and request seeds.

The topology of every graph is fixed by its configuration file (scale, edge
factor, initiator, topology seed).  ``--seed`` only relabels the vertices,
shuffles the edge order and draws weights and per-request seeds, so every
seed gives graphs of the same shapes and hits the same compiled programs.
"""
from __future__ import annotations

import numpy as np

from repro.graph.coo import UGraph


def kronecker_edges(scale: int, edge_factor: int, seed: int,
                    initiator=(0.57, 0.19, 0.19)) -> np.ndarray:
    """Graph500 Kronecker generator, vectorised over edges and levels.

    Draws ``edge_factor * 2**scale`` directed edges; at every level the
    source bit is 1 with probability ``1 - (A + B)`` and the destination
    bit follows the initiator's conditional probabilities, as in the
    Graph500 reference (``kronecker_generator.m``).  The vertices are then
    permuted, self-loops dropped and duplicate undirected edges merged.
    Returns ``(m, 2)`` int32 edges with ``u < v``.
    """
    a, b, c = initiator
    n, draws = 1 << scale, edge_factor << scale
    rng = np.random.default_rng(seed)
    # one level at a time, in the order of one (2, scale, draws) draw, so
    # the host holds a level's floats, not all of them
    src_bits = np.empty((scale, draws), bool)
    src = np.zeros(draws, np.int64)
    for level in range(scale):
        src_bits[level] = rng.random(draws, dtype=np.float32) > a + b
        src |= src_bits[level].astype(np.int64) << level
    dst = np.zeros(draws, np.int64)
    for level in range(scale):
        p = np.where(src_bits[level], c / (1.0 - a - b), a / (a + b))
        dst |= (rng.random(draws, dtype=np.float32) > p).astype(
            np.int64) << level
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    keep = lo != hi
    key = np.unique(lo[keep] * n + hi[keep])
    return np.stack([key // n, key % n], axis=1).astype(np.int32)


GENERATORS = {"graph500_kronecker": kronecker_edges}


def base_graphs(config: dict) -> list:
    """The configuration's base graphs, in file order, without weights."""
    gen = GENERATORS[config["generator"]]
    out = []
    for spec in config["graphs"]:
        edges = gen(spec["scale"], spec["edge_factor"], spec["topology_seed"],
                    tuple(config["initiator"]))
        out.append(UGraph(1 << spec["scale"], edges))
    return out


def relabel(g: UGraph, rng: np.random.Generator) -> UGraph:
    """An isomorphic copy: vertices permuted, edge order shuffled."""
    perm = rng.permutation(g.n).astype(np.int32)
    order = rng.permutation(g.m)
    return UGraph(g.n, perm[g.edges[order]])


def distinct_weights(m: int, rng: np.random.Generator) -> np.ndarray:
    """Uniform random edge order as weights 1..m: float32 holds them
    exactly below 2**24, and distinct weights make the MSF unique."""
    if m >= 1 << 24:
        raise ValueError(f"{m} edges: float32 weights would not be distinct")
    return rng.permutation(m).astype(np.float32) + 1.0


def request_seed(rng: np.random.Generator) -> int:
    """A seed for one request's rank permutation."""
    return int(rng.integers(0, 2**31 - 1))
