"""Open loop of ``AmpcEngine.submit``: requests are sent when they are
due, whatever the engine's backlog, and timed from their due time.

Traffic parameters:
  rate_per_s    offered load;
  problems      {problem: share of requests};
  graphs        share of requests on each base graph, in file order.

A run sends ``round(rate_per_s * seconds)`` requests on one fixed
schedule drawn from ``ARRIVAL_SEED``: exponential inter-arrival gaps, and
the (problem, graph) pairs in their shares, in random order within
consecutive blocks of about ``MIX_BLOCK`` requests, each of which holds
every pair in its share.  The run's seed draws only the relabeling and the
rank seed of each request.  So every seed does the same work in the same
order: where a tail is set by rare heavy requests and the requests queued
behind them, an order of its own per seed would make it a reading of that
order.  After the window closes the harness waits for every answer, at
most ``DRAIN_S`` seconds more; a request still open then has failed.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import inputs
from chipbench.workload import Record, Request, Window, fail, fill, \
    first_of_each_shape

DRAIN_S = 60.0
WAITERS = 16      # client threads that wait on futures, in arrival order
ARRIVAL_SEED = 0  # one arrival schedule for every run's seed
MIX_BLOCK = 50    # requests per block that holds the whole mix


def _counts(shares: list, total: int) -> list:
    """Whole counts in the given shares that add up to ``total``."""
    raw = np.asarray(shares, float) / sum(shares) * total
    counts = np.floor(raw).astype(int)
    for i in np.argsort(counts - raw)[:total - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def plan(graphs, traffic, rng, seconds):
    total = int(round(traffic["rate_per_s"] * seconds))
    problems = list(traffic["problems"])
    pairs = [(p, gi)
             for p, np_ in zip(problems, _counts(
                 [traffic["problems"][p] for p in problems], total))
             for gi, k in enumerate(_counts(traffic["graphs"], np_))
             for _ in range(k)]
    schedule = np.random.default_rng(ARRIVAL_SEED)
    gaps = schedule.exponential(1.0, total)
    # exactly `total` arrivals inside the window, the last one gap early
    gaps *= seconds * total / (total + 1) / max(gaps.sum(), 1e-300)
    due = np.cumsum(gaps)
    # deal the pairs, grouped by kind, round robin into the blocks
    blocks = max(total // MIX_BLOCK, 1)
    order = np.concatenate([schedule.permutation(np.arange(b, total, blocks))
                            for b in range(blocks)]).astype(int)
    out = []
    for t, j in zip(due, order):
        p, gi = pairs[j]
        out.append(Request(p, [inputs.relabel(graphs[gi], rng)],
                           inputs.request_seed(rng), due=float(t)))
    return out


def warmup(engine, requests):
    for req in first_of_each_shape(requests):
        engine.submit(req.graphs[0], req.problem, seed=req.seed).result()


def window(engine, requests, seconds) -> Window:
    records = []
    lateness = []
    t0 = time.perf_counter()
    deadline = t0 + seconds + DRAIN_S

    def wait(rec, fut):
        try:
            res = fut.result(timeout=max(deadline - time.perf_counter(), 0))
        except TimeoutError:
            return                       # never answered: rec.end stays None
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            fail(rec, e)
            return
        fill(rec, [res])

    with ThreadPoolExecutor(WAITERS) as waiters:
        for req in requests:
            due = t0 + req.due
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            rec = Record(req, start=due)
            records.append(rec)
            try:
                fut = engine.submit(req.graphs[0], req.problem,
                                    seed=req.seed)
            except Exception as e:  # noqa: BLE001 - refused: failed
                fail(rec, e)
                continue
            lateness.append(time.perf_counter() - due)
            waiters.submit(wait, rec, fut)
        t_close = time.perf_counter()
    # leaving the block waited for every waiter, at most until the deadline
    ends = [r.end for r in records if r.end is not None]
    t_end = max(ends + [t_close])
    late_ms = 1e3 * np.asarray(lateness or [0.0])
    return Window(records, t0=t0, t_close=t_close, t_end=t_end,
                  notes={"send_lateness_ms_max": float(late_ms.max()),
                         "send_lateness_ms_p95": float(
                             np.percentile(late_ms, 95))})


def _latencies_ms(w: Window) -> np.ndarray:
    """Due time to answer, per request.  A request that failed, was never
    answered or answered wrong counts as answered at the end of the drain,
    so it misses every latency limit the others meet."""
    return np.asarray([1e3 * ((r.end if r.good else w.t_close + DRAIN_S)
                              - r.start) for r in w.records])


def latency_p50_ms(w: Window) -> float:
    return float(np.percentile(_latencies_ms(w), 50))


def latency_p95_ms(w: Window) -> float:
    return float(np.percentile(_latencies_ms(w), 95))


END_TO_END = {"latency_p50_ms": latency_p50_ms,
              "latency_p95_ms": latency_p95_ms}
