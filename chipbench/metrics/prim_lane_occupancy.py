"""Share, in percent, of truncated Prim's search-loop lane-iterations that
did useful work (``core/msf.py``): the program's own counters summed over
the window's answered solves, ``prim_lane_steps`` (iterations of the
searches from real vertices) over ``prim_lane_steps_run`` (every
lane-iteration the vmapped loop ran, each chunk until its longest search
ended).  None where no solve reports them."""


def read(ctx):
    steps = run = 0
    for rec in ctx.window.records:
        if not rec.answered:
            continue
        for st in rec.stats or []:
            steps += st.get("prim_lane_steps", 0)
            run += st.get("prim_lane_steps_run", 0)
    if not run:
        return None
    return 100.0 * steps / run
