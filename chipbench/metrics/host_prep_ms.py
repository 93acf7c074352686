"""Host milliseconds per solve in the engine's host input pipeline, from
the profiler trace: the engine's ``host:*`` spans (rank draws,
``symmetric()``, ternarize, adjacency, host-to-device copies, assembly
after the harvest; they never nest), clipped to the traced window, over
the solves answered in it.  None without a trace, without an answered
solve, or where the program opens no ``host:*`` span."""

PREFIX = "host:"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window
    spans = [(s, e) for n, s, e in ctx.trace.host if n.startswith(PREFIX)]
    solves = sum(r.answered for r in ctx.window.records)
    if not spans or not solves:
        return None
    ns = sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in spans)
    return ns / 1e6 / solves
