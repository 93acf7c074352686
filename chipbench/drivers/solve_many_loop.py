"""Closed loop of ``AmpcEngine.solve_many``: one client sends its next
batch when the last one has returned.

Traffic parameters:
  composition   graphs per batch of each base graph, in file order;
  problems      the problem of each call, in rotation;
  pool          distinct batches drawn in set-up; calls cycle through them.

Each batch holds fresh relabelings of the base graphs in a shuffled
order; each call draws its own rank seed.
"""
from __future__ import annotations

import time

from chipbench import inputs
from chipbench.workload import Record, Request, Window, closed_loop, fail, \
    fill, first_of_each_shape


def plan(graphs, traffic, rng, seconds):
    batches = []
    for _ in range(traffic["pool"]):
        members = [inputs.relabel(graphs[i], rng)
                   for i, count in enumerate(traffic["composition"])
                   for _ in range(count)]
        batches.append([members[j] for j in rng.permutation(len(members))])
    problems = traffic["problems"]
    # every (batch, problem) pair appears before any repeats
    calls = len(batches) * len(problems)
    return [Request(problems[i % len(problems)], batches[i % len(batches)],
                    inputs.request_seed(rng)) for i in range(calls)]


def _call(engine, req: Request) -> Record:
    rec = Record(req, start=time.perf_counter())
    try:
        return fill(rec, engine.solve_many(req.graphs, req.problem,
                                           seed=req.seed))
    except Exception as e:  # noqa: BLE001 - a failed call is counted
        return fail(rec, e)


def warmup(engine, requests):
    # one call per problem: the batch shapes are the same in every call
    for req in first_of_each_shape(requests):
        _call(engine, req)


def window(engine, requests, seconds) -> Window:
    return closed_loop(lambda req: _call(engine, req), requests, seconds)


def graphs_per_s(w: Window) -> float:
    """Graphs of every whole completed call over the whole window."""
    done = [r for r in w.records if r.good]
    return sum(len(r.request.graphs) for r in done) / w.elapsed


END_TO_END = {"graphs_per_s": graphs_per_s}
