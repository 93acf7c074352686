"""From a profiler trace to device metrics.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Trace`: the device operations and programs of every TPU, and the
host spans whose names the caller knows (the engine's tracer spans, which
``Tracer(annotate_device=True)`` writes into the trace, and the harness's
own window annotation).  The reductions below work on that form alone, so
they can be checked on a trace recorded on the chip and stored as JSON.

All times are nanoseconds on the trace's clock, which the profiler shares
between the host and the devices.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "chipbench.window"        # host annotation around the window
NO_SPAN = "no span"                 # label of idle time outside any span

Interval = Tuple[str, float, float]  # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    ops: List[List[Interval]]        # per device: XLA operations
    programs: List[List[Interval]]   # per device: XLA programs (modules)
    host: List[Interval]             # known host spans, all threads
    window: Tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def to_json(self) -> dict:
        return {"ops": self.ops, "programs": self.programs,
                "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        conv = lambda xs: [(n, float(s), float(e)) for n, s, e in xs]
        return cls(ops=[conv(d_) for d_ in d["ops"]],
                   programs=[conv(d_) for d_ in d["programs"]],
                   host=conv(d["host"]), window=tuple(d["window"]))


def _program_name(name: str) -> str:
    """``jit_truncated_prim(42)`` -> ``jit_truncated_prim``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(log_dir: str, host_names: Sequence[str]) -> Optional[Trace]:
    """Read the newest ``.xplane.pb`` under ``log_dir``.  Returns None when
    it holds no TPU plane or no window annotation."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    wanted = set(host_names) | {WINDOW}
    ops, programs, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev_ops, dev_programs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":  # "%fusion.3 = ..." -> "%fusion.3"
                    dev_ops += [(e.name.split(" ", 1)[0], e.start_ns,
                                 e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    dev_programs += [(_program_name(e.name), e.start_ns,
                                      e.end_ns) for e in line.events]
            ops.append(dev_ops)
            programs.append(dev_programs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns)
                         for e in line.events if e.name in wanted]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not ops or not windows:
        return None
    return Trace(ops=ops, programs=programs,
                 host=[h for h in host if h[0] != WINDOW],
                 window=max(windows, key=lambda w: w[1] - w[0]))


# ------------------------------------------------------------- reductions
def merged(intervals: Sequence[Interval], window) -> List[Tuple[float, float]]:
    """The union of the intervals, clipped to the window, as sorted
    disjoint ``(start, end)`` pairs."""
    lo, hi = window
    spans = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                   if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(trace: Trace) -> float:
    """Nanoseconds in which some operation ran, averaged over devices."""
    per_dev = [sum(e - s for s, e in merged(ops, trace.window))
               for ops in trace.ops]
    return sum(per_dev) / len(per_dev)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_ns(trace) / trace.window_ns


def gaps(trace: Trace, device: int = 0) -> List[Tuple[float, float]]:
    """Idle stretches of one device inside the window."""
    lo, hi = trace.window
    out, t = [], lo
    for s, e in merged(trace.ops[device], trace.window):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_label(trace: Trace, at_ns: float) -> str:
    """The innermost known host span open at ``at_ns``."""
    return _labeller(trace)(at_ns)


def _labeller(trace: Trace):
    names = [n for n, _, _ in trace.host]
    start = np.array([s for _, s, _ in trace.host], float)
    end = np.array([e for _, _, e in trace.host], float)

    def label(at_ns: float) -> str:
        open_ = (start <= at_ns) & (at_ns < end)
        if not open_.any():
            return NO_SPAN
        dur = np.where(open_, end - start, np.inf)
        return names[int(np.argmin(dur))]
    return label


def idle_by_host_span(trace: Trace, device: int = 0) -> Dict[str, float]:
    """Idle nanoseconds of one device, by the host span open at the middle
    of each idle stretch."""
    label = _labeller(trace)
    out: Dict[str, float] = {}
    for s, e in gaps(trace, device):
        name = label((s + e) / 2)
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def program_ns(trace: Trace, patterns: Sequence[str]) -> Optional[float]:
    """Device nanoseconds inside the window of the programs whose name
    matches one of the regular expressions, summed over devices; None when
    no program matches."""
    lo, hi = trace.window
    hits = [(s, e) for dev in trace.programs for n, s, e in dev
            if any(re.search(p, n) for p in patterns) and e > lo and s < hi]
    if not hits:
        return None
    return sum(min(e, hi) - max(s, lo) for s, e in hits)


def program_ms_per(trace: Optional[Trace], patterns: Sequence[str],
                   requests: int) -> Optional[float]:
    """:func:`program_ns` in milliseconds per request; None when there is
    no trace, no request or no matching program."""
    ns = program_ns(trace, patterns) if trace is not None else None
    if ns is None or not requests:
        return None
    return ns / 1e6 / requests


def top_programs(trace: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """Device seconds inside the window per program, the ``k`` largest."""
    lo, hi = trace.window
    tot: Dict[str, float] = {}
    for dev in trace.programs:
        for n, s, e in dev:
            if e > lo and s < hi:
                tot[n] = tot.get(n, 0.0) + min(e, hi) - max(s, lo)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(n, ns / 1e9) for n, ns in ranked]


def breakdown(trace: Trace, k: int = 10) -> dict:
    idle = sorted(idle_by_host_span(trace).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in top_programs(trace, k)],
            "idle_gaps": [[n, ns / 1e9] for n, ns in idle[:k]]}
