"""Maximal matching (paper Section 4, Theorem 2).

All variants compute the exact random-greedy (lexicographically-first)
maximal matching over a random edge permutation π — identical to the
sequential oracle.

``mm_ampc``            — the implementation evaluated in Section 5.4: one
  shuffle builds the edge-sorted graph + writes the DHT; one launch resolves
  all edges by an in-round dependency fixpoint (an edge joins when it is the
  minimum-rank unresolved edge at *both* endpoints; an edge dies when an
  endpoint is matched).  Per-vertex caching is modelled by the dedup query
  counters (the paper's per-vertex cache stores exactly the resolution
  frontier per vertex).

``mm_ampc_levels``     — Algorithm 4: O(log log Δ) levels of geometric edge
  sampling; level i materializes one launch.  Union of the level matchings is
  the LFMM of G (Lemma 4.4/4.5).

``mm_ampc_vertex_process`` — Theorem 2 part 2: per-vertex query budget n^ε
  per launch; frozen vertices postpone the resolution of their edges to the
  next launch; O(1/ε) launches empirically, total queries O(m + n^{1+ε}).

``mm_mpc_rootset``     — the MPC baseline of Section 5.4 (2 shuffles/phase).

The driver functions are deprecated shims over ``repro.ampc.solvers``; the
jitted fixpoint primitives (``_mm_wave``, ``_mm_fixpoint``) live here.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.coo import UGraph
from .rounds import RoundLedger

UNKNOWN, IN, OUT = 0, 1, 2
BIGF = jnp.float32(jnp.inf)


def _mm_wave(estatus, u, v, erank, n, active_edge=None):
    """One fixpoint wave. Returns (new_estatus, resolved_now)."""
    m = erank.shape[0]
    unk = estatus == UNKNOWN
    # endpoints already matched by earlier waves — their edges can never join
    prev_in = estatus == IN
    pmatch = jnp.zeros((n,), jnp.int32)
    pmatch = pmatch.at[jnp.where(prev_in, u, n)].set(1, mode="drop")
    pmatch = pmatch.at[jnp.where(prev_in, v, n)].set(1, mode="drop")
    wbig = jnp.where(unk, erank, BIGF)
    vmin = jax.ops.segment_min(jnp.concatenate([wbig, wbig]),
                               jnp.concatenate([u, v]),
                               num_segments=n)
    is_min = (unk & (pmatch[u] == 0) & (pmatch[v] == 0)
              & (erank <= vmin[u]) & (erank <= vmin[v]))
    if active_edge is not None:
        is_min = is_min & active_edge
    new = jnp.where(is_min, IN, estatus)
    matched = pmatch
    matched = matched.at[jnp.where(is_min, u, n)].set(1, mode="drop")
    matched = matched.at[jnp.where(is_min, v, n)].set(1, mode="drop")
    die = (new == UNKNOWN) & ((matched[u] == 1) | (matched[v] == 1))
    if active_edge is not None:
        die = die & active_edge
    new = jnp.where(die, OUT, new)
    return new, matched


@functools.partial(jax.jit, static_argnames=("n",))
def _mm_fixpoint(u, v, erank, n: int, estatus0):
    """Run the LFMM fixpoint to completion inside one program.

    Returns ``(estatus, waves, q0, q1, lane_steps, lane_steps_run)``.
    Beside the paper's query counts ``q0``/``q1`` it counts the waves' edge
    lanes, for speed and not for the query accounting: ``lane_steps``, the
    unresolved edges summed over waves (edges that start OUT, such as a
    batch's padding, never count), and ``lane_steps_run``, the lanes every
    wave runs (the padded width) times the waves.  Their ratio is the
    share of the waves' lanes that did live work.  The counters are int32:
    ``lane_steps_run`` overflows past 2**31 - 1 lane-waves, e.g. 3.8M
    edges over 565 waves.
    """
    lanes = erank.shape[0]

    def cond(s):
        return jnp.any(s[0] == UNKNOWN)

    def body(s):
        estatus, it, q0, q1, steps, steps_run = s
        new, _ = _mm_wave(estatus, u, v, erank, n)
        unk = estatus == UNKNOWN
        unresolved = unk.sum()
        # queries: each unresolved edge probes both endpoint frontiers
        scanned = 2 * unresolved
        probe = jnp.zeros((n,), jnp.int32)
        probe = probe.at[jnp.where(unk, u, n)].set(1, mode="drop")
        probe = probe.at[jnp.where(unk, v, n)].set(1, mode="drop")
        # gate the wave counters on live work so per-lane counts stay exact
        # when this fixpoint runs as one lane of a vmapped solve_many bucket
        live = unk.any().astype(jnp.int32)
        return (new, it + live, q0 + scanned, q1 + probe.sum(),
                steps + unresolved, steps_run + live * lanes)

    zero = jnp.int32(0)
    return jax.lax.while_loop(cond, body,
                              (estatus0, zero, zero, zero, zero, zero))


# --------------------------------------------------------------------------
# Deprecated shims — the drivers moved to repro.ampc.solvers; prefer
# AmpcEngine().solve(g, "matching") and friends.
# --------------------------------------------------------------------------
def mm_ampc(g: UGraph, seed: int = 0,
            ledger: Optional[RoundLedger] = None,
            caching: bool = True,
            erank: "np.ndarray | None" = None) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.mm_ampc.

    ``erank`` is the Corollary-4.1 rank-injection point (weighted matching
    passes decreasing-weight ranks); omitted = random permutation from seed.
    """
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.matching.mm_ampc",
              'AmpcEngine().solve(g, "matching")')
    return solvers.mm_ampc(g, seed=seed, ledger=ledger, caching=caching,
                           erank=erank)


def mm_ampc_levels(g: UGraph, seed: int = 0,
                   ledger: Optional[RoundLedger] = None) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.mm_ampc_levels."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.matching.mm_ampc_levels",
              'AmpcEngine().solve(g, "matching-levels")')
    return solvers.mm_ampc_levels(g, seed=seed, ledger=ledger)


def mm_ampc_vertex_process(g: UGraph, epsilon: float = 0.5, seed: int = 0,
                           ledger: Optional[RoundLedger] = None,
                           ) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.mm_ampc_vertex_process."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.matching.mm_ampc_vertex_process",
              'AmpcEngine().solve(g, "matching-vertex-process")')
    return solvers.mm_ampc_vertex_process(g, epsilon=epsilon, seed=seed,
                                          ledger=ledger)


def mm_mpc_rootset(g: UGraph, seed: int = 0,
                   ledger: Optional[RoundLedger] = None,
                   max_phases: int = 500) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.mm_mpc_rootset."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.matching.mm_mpc_rootset",
              'AmpcEngine().solve(g, "matching-mpc")')
    return solvers.mm_mpc_rootset(g, seed=seed, ledger=ledger,
                                  max_phases=max_phases)
