"""Closed loop of ``AmpcEngine.solve``: one client sends its next request
when the last one has returned.

Traffic parameters:
  problem       the problem every request solves;
  graph         index of the configuration's base graph;
  pool          requests drawn in set-up; the window cycles through them;
  weights       true: every request gets fresh distinct random weights;
  solve_opts    keyword options of ``solve``, stated by the traffic;
  work_seed     optional: draw the work from this seed instead of the
                run's (below).

The run's seed relabels the base graph once; each request draws its own
rank seed (and weights).  Where the rank seeds set how much work a request
is, as the waves of the MIS fixpoint are, ``work_seed`` draws the
relabeling, rank seeds and weights instead, the same in every run, and the
run's seed draws only the order of the graph's edges: every seed then does
the same work on its own input arrays.  The window runs whole requests
until ``seconds`` have passed; it ends with the last request that began
inside it.
"""
from __future__ import annotations

import time

import numpy as np

from repro.graph.coo import UGraph

from chipbench import inputs
from chipbench.workload import Record, Request, Window, closed_loop, fail, \
    fill, first_of_each_shape


def plan(graphs, traffic, rng, seconds):
    work, order = rng, None
    if "work_seed" in traffic:
        work = np.random.default_rng(traffic["work_seed"])
    base = inputs.relabel(graphs[traffic["graph"]], work)
    if work is not rng:
        order = rng.permutation(base.m)
        base = UGraph(base.n, base.edges[order])
    out = []
    for _ in range(traffic["pool"]):
        g = base
        if traffic.get("weights"):
            w = inputs.distinct_weights(base.m, work)
            g = UGraph(base.n, base.edges, w if order is None else w[order])
        out.append(Request(traffic["problem"], [g],
                           inputs.request_seed(work),
                           dict(traffic.get("solve_opts", {}))))
    return out


def _solve(engine, req: Request) -> Record:
    rec = Record(req, start=time.perf_counter())
    try:
        return fill(rec, [engine.solve(req.graphs[0], req.problem,
                                       seed=req.seed, **req.opts)])
    except Exception as e:  # noqa: BLE001 - a failed request is counted
        return fail(rec, e)


def warmup(engine, requests):
    for req in first_of_each_shape(requests):
        _solve(engine, req)


def window(engine, requests, seconds) -> Window:
    return closed_loop(lambda req: _solve(engine, req), requests, seconds)


def edges_per_s(w: Window) -> float:
    """Input edges of every completed solve over the whole window."""
    done = [r for r in w.records if r.good]
    return sum(r.request.graphs[0].m for r in done) / w.elapsed


END_TO_END = {"edges_per_s": edges_per_s}
