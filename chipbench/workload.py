"""What the drivers share: requests, their records, and the window.

A driver module (``chipbench/drivers/<kind>.py``) provides

  plan(graphs, traffic, rng, seconds) -> [Request]
      every request the run may send, drawn from the run's seed in set-up;
  warmup(engine, requests)
      one request of each shape the window uses;
  window(engine, requests, seconds) -> Window
      the measured window;
  END_TO_END: {metric name: function(Window) -> value}
      the end-to-end metrics the driver can report.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from repro.graph.coo import UGraph


@dataclasses.dataclass
class Request:
    problem: str
    graphs: List[UGraph]             # one per answer; several for solve_many
    seed: int
    opts: Dict[str, Any] = dataclasses.field(default_factory=dict)
    due: float = 0.0                 # seconds after the window opens

    def shape(self) -> tuple:
        return (self.problem, tuple(sorted((g.n, g.m) for g in self.graphs)),
                tuple(sorted(self.opts.items())))


@dataclasses.dataclass
class Record:
    request: Request
    start: float                     # perf_counter: sent, or due (open loop)
    end: Optional[float] = None      # perf_counter: result on the client
    outputs: Optional[list] = None   # one per graph
    stats: Optional[list] = None
    ledgers: Optional[list] = None
    error: Optional[str] = None
    wrong: Optional[int] = None      # answers missing or failing a check
    solve_s: Optional[float] = None  # the engine's own wall time

    @property
    def answered(self) -> bool:
        return self.end is not None and self.error is None

    @property
    def good(self) -> bool:
        """Answered, and no answer failed its check."""
        return self.answered and not self.wrong


@dataclasses.dataclass
class Window:
    records: List[Record]
    t0: float                        # perf_counter when the window opened
    t_close: float                   # when it stopped sending
    t_end: float                     # when the last answer came back
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def elapsed(self) -> float:
        return self.t_end - self.t0


def closed_loop(call, requests: List[Request], seconds: float) -> Window:
    """One client: ``call(request) -> Record`` back to back, cycling
    through ``requests``, until ``seconds`` have passed; the window ends
    with the last request that began inside it."""
    records = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        records.append(call(requests[len(records) % len(requests)]))
    t_end = records[-1].end
    return Window(records, t0=t0, t_close=t_end, t_end=t_end)


def first_of_each_shape(requests: List[Request]) -> List[Request]:
    seen, out = set(), []
    for r in requests:
        if r.shape() not in seen:
            seen.add(r.shape())
            out.append(r)
    return out


def fill(rec: Record, results) -> Record:
    """Store the engine's results (one ``AmpcResult`` per graph)."""
    rec.end = time.perf_counter()
    rec.outputs = [r.output for r in results]
    rec.stats = [r.stats for r in results]
    rec.solve_s = sum(r.wall_time_s for r in results)
    rec.ledgers = [r.ledger for r in results]
    return rec


def fail(rec: Record, exc: BaseException) -> Record:
    rec.end = time.perf_counter()
    rec.error = f"{type(exc).__name__}: {exc}"
    return rec
