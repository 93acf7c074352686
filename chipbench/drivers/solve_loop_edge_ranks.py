"""Closed loop of ``AmpcEngine.solve`` for problems whose ranks belong to
edge positions, as maximal matching's do: the engine draws one rank per
edge index from the request's seed.  Warm-up, window and metrics are
``solve_loop``'s; only the plan differs.

Traffic parameters:
  problem       the problem every request solves;
  graph         index of the configuration's base graph;
  pool          requests drawn in set-up; the window cycles through them;
  solve_opts    keyword options of ``solve``, stated by the traffic;
  work_seed     draws the relabeling, the edge order and the rank seeds.

``solve_loop``'s run seed draws the edge order, which would hand every edge
another rank in every run and so another matching, with another number of
waves.  Here the run's seed only renames the vertices: every edge keeps its
position and so its rank, and every seed does the same work (the same
waves, the same matched edge positions) on its own input arrays.
"""
from __future__ import annotations

import numpy as np

from repro.graph.coo import UGraph

from chipbench import inputs
from chipbench.drivers.solve_loop import END_TO_END, warmup, window  # noqa: F401
from chipbench.workload import Request


def plan(graphs, traffic, rng, seconds):
    work = np.random.default_rng(traffic["work_seed"])
    base = inputs.relabel(graphs[traffic["graph"]], work)
    names = rng.permutation(base.n).astype(np.int32)
    g = UGraph(base.n, names[base.edges])
    return [Request(traffic["problem"], [g], inputs.request_seed(work),
                    dict(traffic.get("solve_opts", {})))
            for _ in range(traffic["pool"])]
