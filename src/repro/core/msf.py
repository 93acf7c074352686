"""Minimum spanning forest in constant adaptive rounds (paper Section 3).

Pieces:
  * ``truncated_prim``  — Algorithm 1: per-vertex rank-truncated Prim search,
    vmapped over all vertices (each vertex = one AMPC "machine task"); three
    stopping conditions (budget, exhaustion, lower-rank hook).
  * ``pointer_jump``    — Proposition 3.2 forest contraction (in-round
    doubling on the immutable hook snapshot).
  * ``contract_edges``  — relabel + self-loop removal + min-weight dedup.
  * ``boruvka_inround`` — DenseMSF stand-in: Borůvka hook-and-contract run
    entirely inside one launch (AMPC adaptivity), used for the dense phase.
  * ``msf_ampc``        — Algorithm 2 driver (5 materialized shuffles, matching
    the paper's Table 3 accounting: SortGraph, PrimSearch, PointerJump,
    Contract, DenseMSF).
  * ``msf_mpc_boruvka`` — the paper's MPC baseline (red/blue Borůvka,
    3 shuffles per phase, O(log n) phases).

All functions return a boolean mask over the *original* edge ids.

The ``msf_ampc`` / ``msf_mpc_boruvka`` drivers are deprecated shims over
``repro.ampc.solvers``; the jitted primitives live here.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.coo import UGraph
from .rounds import RoundLedger

INF = jnp.float32(jnp.inf)
_PRIM_STATE_BYTES = 1 << 28   # per-chunk frontier state of truncated Prim


# --------------------------------------------------------------------------
# Algorithm 1: truncated Prim
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("capacity", "n_edges"))
def truncated_prim_capped(nbr, nbw, nbe, rank, budget, capacity: int,
                          n_edges: int, n_real=None):
    """``truncated_prim`` with the buffer *capacity* decoupled from the
    stopping *budget*.

    The buffers (visited set, output slots, frontier) are sized by the static
    ``capacity`` while the stopping condition compares against the traced
    ``budget`` (an int32 scalar, ``budget <= capacity``).  With
    ``capacity == budget`` the trajectory is identical to ``truncated_prim``;
    with ``capacity > budget`` the extra slots stay at their -1/inf fill and
    never win the frontier argmin, so outputs are still bit-identical.  This
    is what lets a vmapped batch of graphs share one compiled program while
    each lane keeps its own n-dependent budget.

    Besides ``truncated_prim``'s outputs it counts the search loop's lanes,
    one int32 per chunk of vertices (below): ``lane_steps``, the loop
    iterations of the searches from real vertices (those below ``n_real``,
    a traced count that defaults to ``n``: a padded bucket passes its
    graph's own vertex count), and ``lane_steps_run``, ``chunk`` times the
    longest search of the chunk: every lane-iteration the vmapped loop ran,
    padding lanes included.  Their sums' ratio is the share of the loop's
    lanes that did useful work.
    """
    n, D = nbr.shape
    F = D * capacity  # frontier capacity
    budget = jnp.asarray(budget, jnp.int32)
    # rows are gathered column by column: a TPU keeps an (n, 3) array with
    # n on the lanes, and a row gather (or a flat reshape) would first copy
    # it row-major, tiling the 3 columns out to 128 lanes (~43x its size)
    cols = [[a[:, k] for k in range(D)] for a in (nbr, nbw, nbe)]

    def row(x):
        return [jnp.stack([c[x] for c in a]) for a in cols]

    slots = jnp.arange(F)            # frontier slot ids
    marks = jnp.arange(capacity)     # visited / out slot ids

    def per_vertex(v):
        nbr_v, nbw_v, nbe_v = row(v)
        visited = jnp.full((capacity,), -1, jnp.int32).at[0].set(v)
        fdst = jnp.full((F,), -1, jnp.int32).at[:D].set(nbr_v)
        fw = jnp.full((F,), INF).at[:D].set(nbw_v)
        feid = jnp.full((F,), -1, jnp.int32).at[:D].set(nbe_v)
        out = jnp.full((capacity,), -1, jnp.int32)
        st = dict(visited=visited, vcount=jnp.int32(1), fdst=fdst, fw=fw,
                  feid=feid, fsize=jnp.int32(D), out=out, ocount=jnp.int32(0),
                  hook=jnp.int32(-1), case=jnp.int32(0), queries=jnp.int32(1),
                  steps=jnp.int32(0))

        def cond(s):
            return s["case"] == 0

        def body(s):
            # every per-vertex read and write is a dense select over the
            # vertex's own slots: a TPU runs those at full width, while the
            # gathers and scatters of indexed updates serialize per element
            idx = jnp.argmin(s["fw"])
            at = slots == idx
            best_w = jnp.min(s["fw"])
            dst = jnp.sum(jnp.where(at, s["fdst"], 0))
            eid = jnp.sum(jnp.where(at, s["feid"], 0))
            exhausted = jnp.isinf(best_w)
            # consume the frontier entry
            fw = jnp.where(at, INF, s["fw"])
            fdst = jnp.where(at, -1, s["fdst"])
            already = (s["visited"] == dst).any()
            lower = rank[jnp.clip(dst, 0, n - 1)] < rank[v]
            # the four outcomes: exhausted (stop, case 2), already seen
            # (skip), lower-rank hook (stop, case 3), or add dst and grow
            # the frontier by its row.  No lax.switch: its branches would
            # carry nbr/nbw/nbe as operands, and under the vmap over
            # vertices that batches (broadcasts) the whole adjacency.
            fresh = jnp.logical_not(exhausted | already)
            hook = fresh & lower
            add = fresh & jnp.logical_not(lower)
            took = hook | add
            nbr_d, nbw_d, nbe_d = row(jnp.clip(dst, 0, n - 1))
            vcount = s["vcount"] + add.astype(jnp.int32)
            case = jnp.where(
                exhausted, jnp.int32(2),
                jnp.where(hook, jnp.int32(3),
                          jnp.where(add & (vcount >= budget), jnp.int32(1),
                                    s["case"])))
            # dst's row lands in frontier slots fsize .. fsize + D - 1
            k = slots - s["fsize"]

            def grow(a, r):
                placed = jnp.select([k == j for j in range(D)],
                                    [r[j] for j in range(D)], a)
                return jnp.where(add, placed, a)

            return {
                "visited": jnp.where(add & (marks == s["vcount"]), dst,
                                     s["visited"]),
                "vcount": vcount,
                "fdst": grow(fdst, nbr_d),
                "fw": grow(fw, nbw_d),
                "feid": grow(s["feid"], nbe_d),
                "fsize": s["fsize"] + jnp.where(add, D, 0),
                "out": jnp.where(took & (marks == s["ocount"]), eid,
                                 s["out"]),
                "ocount": s["ocount"] + took.astype(jnp.int32),
                "hook": jnp.where(hook, dst, s["hook"]),
                "case": case,
                "queries": s["queries"] + took.astype(jnp.int32),
                "steps": s["steps"] + 1,
            }

        s = jax.lax.while_loop(cond, body, st)
        return s["out"], s["hook"], s["case"], s["queries"], s["steps"]

    # vertices run in vmapped chunks, so the live search state stays near
    # _PRIM_STATE_BYTES however many vertices the graph has, and each
    # chunk's discovered edges are folded into one (n_edges,) mask (all at
    # once, a scale-20 Graph500 graph's ~30M ternarized vertices would hold
    # ~100 GB of frontiers and ~9 GB of per-vertex edge lists)
    lane_bytes = 4 * (2 * capacity + 3 * F + 8)
    chunk = max(1, min(n, _PRIM_STATE_BYTES // lane_bytes))
    n_chunks = -(-n // chunk)
    verts = jnp.arange(n_chunks * chunk, dtype=jnp.int32).reshape(
        n_chunks, chunk)

    real_n = n if n_real is None else n_real

    def run_chunk(found, vs):
        out, hook, case, queries, steps = jax.vmap(per_vertex)(
            jnp.minimum(vs, n - 1))
        live = (vs < n)[:, None] & (out >= 0)
        found = found.at[jnp.where(live, out, n_edges).ravel()].set(
            True, mode="drop")
        # a chunk's sums stay below 2^31 (chunk x 3 x capacity steps at
        # most); the per-chunk values are summed on the host
        lane_steps = jnp.where(vs < real_n, steps, 0).sum()
        return found, (hook, case, queries, lane_steps, chunk * steps.max())

    found, (hooks, cases, queries, lane_steps, lane_steps_run) = \
        jax.lax.scan(run_chunk, jnp.zeros((n_edges,), bool), verts)
    return (found, hooks.reshape(-1)[:n], cases.reshape(-1)[:n],
            queries.reshape(-1)[:n], lane_steps, lane_steps_run)


@functools.partial(jax.jit, static_argnames=("budget", "n_edges"))
def truncated_prim(nbr, nbw, nbe, rank, budget: int, n_edges: int):
    """Run rank-truncated Prim from every vertex of a Δ<=3 graph.

    nbr/nbw/nbe: (n, D) padded adjacency (ids / weights / edge ids), -1 / inf pad.
    rank: (n,) distinct float ranks (the random permutation π).
    n_edges: number of edge ids (every id in nbe is below it).
    Returns (found (n_edges,) bool — the edges some search took, hooks (n,),
    cases (n,), queries (n,), lane_steps (chunks,), lane_steps_run
    (chunks,)); the last two count the search loop's lanes (see
    ``truncated_prim_capped``).
    cases: 1 = budget hit, 2 = component exhausted, 3 = lower-rank hook.
    """
    return truncated_prim_capped(nbr, nbw, nbe, rank,
                                 jnp.int32(budget), budget, n_edges)


# --------------------------------------------------------------------------
# Proposition 3.2: forest contraction by pointer jumping (in-round)
# --------------------------------------------------------------------------
@jax.jit
def pointer_jump(parent: jnp.ndarray):
    """Iterated doubling to the root; returns (roots, num_doublings)."""
    def cond(s):
        p, _ = s
        return jnp.any(p[p] != p)

    def body(s):
        p, it = s
        nxt = p[p]
        # gate the counter on actual progress so a vmapped lane that has
        # already converged stops counting (sequentially the body only runs
        # while cond holds, so the gate is a no-op there)
        return nxt, it + jnp.any(nxt != p).astype(jnp.int32)

    p, iters = jax.lax.while_loop(cond, body, (parent, jnp.int32(0)))
    return p, iters


# --------------------------------------------------------------------------
# Contraction: relabel edges, drop self-loops, dedup (min weight per pair)
# --------------------------------------------------------------------------
@jax.jit
def contract_edges(u, v, w, eid, valid, labels):
    """Relabel endpoints by ``labels``; self-loops invalidated; duplicate
    (cu, cv) pairs keep only the minimum-weight edge. Shapes are static; a
    boolean ``valid`` mask tracks liveness.  Returns (cu, cv, w, eid, valid,
    n_live_vertices)."""
    cu = labels[u]
    cv = labels[v]
    lo = jnp.minimum(cu, cv)
    hi = jnp.maximum(cu, cv)
    valid = valid & (lo != hi)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    klo = jnp.where(valid, lo, big)
    khi = jnp.where(valid, hi, big)
    # jnp.lexsort((w, khi, klo)) as three stable single-key sorts, least
    # significant key first: the same permutation, and the TPU compiler
    # builds three one-key sorts ~3x faster than one three-key sort
    order = jnp.argsort(w, stable=True)
    order = order[jnp.argsort(khi[order], stable=True)]
    order = order[jnp.argsort(klo[order], stable=True)]
    slo, shi = klo[order], khi[order]
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (slo[1:] != slo[:-1]) | (shi[1:] != shi[:-1])])
    keep = jnp.zeros_like(valid).at[order].set(first) & valid
    # live vertex count: labels that appear as an endpoint of a live edge
    live = jnp.zeros(labels.shape[0], jnp.int32)
    live = live.at[jnp.where(keep, lo, 0)].max(keep.astype(jnp.int32), mode="drop")
    live = live.at[jnp.where(keep, hi, 0)].max(keep.astype(jnp.int32), mode="drop")
    return cu, cv, w, eid, keep, live.sum()


# --------------------------------------------------------------------------
# DenseMSF stand-in: in-round Borůvka (min-edge hooking + doubling)
# --------------------------------------------------------------------------
def _component_min_edge(lu, lv, w, eid, valid, n):
    """For each component label, the (weight, lane)-lexicographic minimum
    incident cross edge.  Lanes (edge positions) are unique even when edge
    ids repeat (ternarization dummy edges all carry eid=-1), so the choice is
    unambiguous and two components hooking each other always agree on the
    same edge.  Returns (min_eid (n,), partner (n,), has (n,))."""
    E = w.shape[0]
    cross = valid & (lu != lv)
    wbig = jnp.where(cross, w, INF)
    both_l = jnp.concatenate([lu, lv])
    seg_w = jax.ops.segment_min(jnp.concatenate([wbig, wbig]), both_l,
                                num_segments=n)
    lane = jnp.arange(E, dtype=jnp.int32)
    big = jnp.int32(2**30)
    lane_u = jnp.where(cross & (w <= seg_w[lu]), lane, big)
    lane_v = jnp.where(cross & (w <= seg_w[lv]), lane, big)
    seg_lane = jax.ops.segment_min(jnp.concatenate([lane_u, lane_v]), both_l,
                                   num_segments=n)
    has = seg_lane < big
    sl = jnp.clip(seg_lane, 0, E - 1)
    min_eid = jnp.where(has, eid[sl], -1)
    comp = jnp.arange(n, dtype=jnp.int32)
    plu, plv = lu[sl], lv[sl]
    partner = jnp.where(plu == comp, plv, plu)
    partner = jnp.where(has, partner, comp)
    return min_eid, partner, has


def boruvka_core(u, v, w, eid, valid, n_labels: int, max_eid: int):
    """Borůvka run to completion inside one program (while_loop).
    Traceable core — call inside other jitted programs; use
    ``boruvka_inround`` for a standalone launch.

    Returns (msf_mask over [0, max_eid), labels, phases)."""
    n = n_labels
    labels0 = jnp.arange(n, dtype=jnp.int32)
    mask0 = jnp.zeros((max_eid,), bool)

    def cond(s):
        labels, mask, it, done = s
        return ~done

    def body(s):
        labels, mask, it, done_prev = s
        lu, lv = labels[u], labels[v]
        min_eid, partner, has = _component_min_edge(lu, lv, w, eid, valid, n)
        parent = jnp.where(has, partner, labels0)
        # break 2-cycles: keep the hook only on the smaller label
        two = (parent[parent] == labels0) & (parent != labels0)
        parent = jnp.where(two & (labels0 > parent), labels0, parent)
        roots, _ = pointer_jump(parent)
        # an edge is selected if it was some component's min edge; invalid
        # lanes (no edge / dummy eid=-1) scatter out-of-bounds and are dropped
        sel = jnp.where(has & (min_eid >= 0), min_eid, max_eid)
        selected_mask = jnp.zeros((max_eid,), bool).at[sel].set(True, mode="drop")
        mask = mask | selected_mask
        labels = roots[labels]
        done = ~jnp.any(has)
        # gate the phase counter on the carried-in done flag: sequentially
        # cond guarantees done_prev is False (so the gate is a no-op), but
        # under vmap a finished lane keeps executing the body until the
        # slowest lane converges and must stop counting phases
        return labels, mask, it + (~done_prev).astype(jnp.int32), done

    labels, mask, phases, _ = jax.lax.while_loop(
        cond, body, (labels0, mask0, jnp.int32(0), jnp.asarray(False)))
    return mask, labels, phases


boruvka_inround = functools.partial(jax.jit, static_argnames=("n_labels", "max_eid"))(
    boruvka_core)


# --------------------------------------------------------------------------
# MPC baseline: red/blue Borůvka, 3 shuffles per phase (paper Section 5.5)
# --------------------------------------------------------------------------
@jax.jit
def _mpc_boruvka_phase(u, v, w, eid, valid, labels, color, max_eid_mask):
    """One red/blue Borůvka phase (paper Section 5.5): each *blue* component
    computes its overall minimum incident cross edge and contracts into the
    partner only if the partner is *red*."""
    n = labels.shape[0]
    lu, lv = labels[u], labels[v]
    min_eid, partner, has = _component_min_edge(lu, lv, w, eid, valid, n)
    ids = jnp.arange(n, dtype=jnp.int32)
    hook = has & color[ids] & ~color[partner]        # I am blue, partner red
    parent = jnp.where(hook, partner, ids)           # depth 1, acyclic
    sel = jnp.where(hook & (min_eid >= 0), min_eid, max_eid_mask.shape[0])
    selected = jnp.zeros_like(max_eid_mask).at[sel].set(True, mode="drop")
    labels = parent[labels]
    new_valid = valid & (labels[u] != labels[v])
    remaining = new_valid.sum()
    return labels, selected, new_valid, remaining




# --------------------------------------------------------------------------
# Deprecated shims — the drivers moved to repro.ampc.solvers; prefer
# AmpcEngine().solve(g, "msf") / .solve(g, "msf-mpc").
# --------------------------------------------------------------------------
def msf_ampc(g: UGraph, epsilon: float = 0.5, seed: int = 0,
             ledger: Optional[RoundLedger] = None,
             skip_ternarize_if_dense: bool = True) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.msf_ampc."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.msf.msf_ampc", 'AmpcEngine().solve(g, "msf")')
    return solvers.msf_ampc(g, epsilon=epsilon, seed=seed, ledger=ledger,
                            skip_ternarize_if_dense=skip_ternarize_if_dense)


def msf_mpc_boruvka(g: UGraph, seed: int = 0,
                    ledger: Optional[RoundLedger] = None,
                    max_phases: int = 200) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.msf_mpc_boruvka."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.msf.msf_mpc_boruvka",
              'AmpcEngine().solve(g, "msf-mpc")')
    return solvers.msf_mpc_boruvka(g, seed=seed, ledger=ledger,
                                   max_phases=max_phases)
