"""Device milliseconds of the matching fixpoint (``core/matching.py``) per
solve, from the profiler trace: its programs' time inside the traced
window over the solves answered in it."""
from chipbench import trace

PROGRAMS = (r"^jit__mm_fixpoint",)


def read(ctx):
    return trace.program_ms_per(ctx.trace, PROGRAMS,
                                sum(r.answered for r in ctx.window.records))
