"""Share, in percent, of the matching fixpoint's edge lanes that did live
work (``core/matching.py``): the program's own counters summed over the
window's answered solves, ``mm_lane_steps`` (the unresolved edges each
wave ran on) over ``mm_lane_steps_run`` (every edge lane of every wave).
None where no solve reports them."""


def read(ctx):
    steps = run = 0
    for rec in ctx.window.records:
        if not rec.answered:
            continue
        for st in rec.stats or []:
            steps += st.get("mm_lane_steps", 0)
            run += st.get("mm_lane_steps_run", 0)
    if not run:
        return None
    return 100.0 * steps / run
