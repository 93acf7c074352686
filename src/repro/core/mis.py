"""Maximal independent set (paper Proposition 4.2 / Section 5.3 case study).

Both implementations compute the *lexicographically-first MIS* over a random
vertex permutation π — identical output to the sequential greedy (oracle).

``mis_ampc``  — the AMPC algorithm of Figure 1: one shuffle builds the
  rank-directed graph and writes it to the DHT; one launch then resolves every
  vertex by adaptive queries against that immutable snapshot.  The per-machine
  recursion of Yoshida et al. becomes an in-round dependency-fixpoint: a
  vertex joins when all lower-rank neighbours are OUT; a vertex is OUT when a
  neighbour is IN.  Fischer–Noever gives O(log n) fixpoint iterations w.h.p.;
  all iterations read the same snapshot, so this is 2 AMPC rounds total.
  Query/byte counters reproduce the paper's Fig 3/4/9 measurements, including
  the caching (dedup) savings.

``mis_mpc_rootset`` — the MPC baseline of Figure 2: the same rule, but each
  phase is a materialized launch with 2 shuffles (join + removal), O(log n)
  phases.
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


@functools.partial(jax.jit, static_argnames=("n",))
def _mis_fixpoint_masked(senders, receivers, rank, n: int, edge_ok):
    """LFMIS fixpoint with an edge-validity mask (the batched-solve core).

    ``edge_ok`` marks the real directed edges; masked lanes (the padding a
    ``solve_many`` shape bucket introduces) never contribute to blocking,
    joining, or query counts, so each batch lane reproduces exactly the
    trajectory of the unpadded sequential fixpoint.  Padding vertices have
    no valid edges and resolve to IN on the first wave.

    Precondition: the valid edges are symmetric — every valid (s, r) has a
    valid (r, s).  ``UGraph.symmetric``, ``GraphBatch.padded_symmetric`` and
    a snapshot's ``sym_*`` entries all concatenate both directions under
    one mask.

    Returns (status(n,), iters, queries_nodedup, queries_dedup).
    Query accounting per wave: every undecided vertex fetches the status of
    each of its neighbours (no-dedup count); with caching each *distinct*
    neighbour is fetched once per machine — we model the per-wave dedup as
    one fetch per distinct queried vertex (paper Section 5.3).  By symmetry
    the distinct vertices queried are those with a valid edge to an
    undecided vertex.

    The waves use no scatter: indexed updates serialize per element on the
    TPU.  Once per solve the valid edges are sorted by sender (masked lanes
    keyed ``n``, past every vertex), so vertex v owns the contiguous
    segment ``[bounds[v], bounds[v+1])``; each wave then gathers the
    receivers' status once and reads every per-vertex sum as a difference
    of prefix sums.  Only undecided vertices change, and all edges of an
    undecided sender are scanned, so the sender's status is never gathered.
    """
    key = jnp.where(edge_ok, senders, n).astype(jnp.int32)
    # order inside a segment is immaterial: an unstable two-operand sort
    # compiles in a fraction of the time of a stable or wider one
    key, recv = jax.lax.sort((key, receivers), num_keys=1, is_stable=False)
    lower = rank[recv] < rank[jnp.minimum(key, n - 1)]
    bounds = jnp.searchsorted(key, jnp.arange(n + 1, dtype=jnp.int32))
    okdeg = jnp.diff(bounds)            # valid edges per vertex
    status0 = jnp.zeros((n,), jnp.int32)

    def cond(s):
        status, it, q0, q1 = s
        return jnp.any(status == UNKNOWN)

    def body(s):
        status, it, q0, q1 = s
        sr = status[recv]
        # per edge: a lower-rank neighbour not OUT (blocks the sender), an
        # IN neighbour (the sender goes OUT), an undecided neighbour (the
        # sender is queried by it); sums over each sender's segment
        flags = jnp.stack([lower & (sr != OUT), sr == IN, sr == UNKNOWN])
        csum = jnp.pad(jnp.cumsum(flags.astype(jnp.int32), axis=1),
                       ((0, 0), (1, 0)))
        n_block, n_in, n_unk = jnp.diff(csum[:, bounds], axis=1)
        unk = status == UNKNOWN
        status = jnp.where(unk & (n_in > 0), OUT, status)
        status = jnp.where(unk & (n_in == 0) & (n_block == 0), IN, status)
        # queries: edges scanned this wave (sender undecided)
        scanned = jnp.where(unk, okdeg, 0).sum()
        # dedup: distinct vertices queried this wave
        distinct = (n_unk > 0).sum()
        # gate the wave counter on this lane actually having work: under a
        # vmapped while_loop a finished batch lane may still execute the
        # body, and the query counters are already zero then (no unk)
        live = unk.any().astype(jnp.int32)
        return status, it + live, q0 + scanned, q1 + distinct

    status, iters, q0, q1 = jax.lax.while_loop(
        cond, body, (status0, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
    return status, iters, q0, q1


def _mis_fixpoint(senders, receivers, rank, n: int):
    """Run the LFMIS fixpoint to completion inside one program.

    The unmasked (single-graph) entry point: every edge lane is valid.
    Returns (status(n,), iters, queries_nodedup, queries_dedup); see
    :func:`_mis_fixpoint_masked` for the query-accounting model.
    """
    return _mis_fixpoint_masked(senders, receivers, rank, n,
                                jnp.ones(senders.shape, bool))


# --------------------------------------------------------------------------
# Deprecated shims — the drivers moved to repro.ampc.solvers; prefer
# AmpcEngine().solve(g, "mis") / .solve(g, "mis-mpc").
# --------------------------------------------------------------------------
def mis_ampc(g: UGraph, seed: int = 0,
             ledger: Optional[RoundLedger] = None,
             caching: bool = True) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.mis_ampc."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.mis.mis_ampc", 'AmpcEngine().solve(g, "mis")')
    return solvers.mis_ampc(g, seed=seed, ledger=ledger, caching=caching)


def mis_mpc_rootset(g: UGraph, seed: int = 0,
                    ledger: Optional[RoundLedger] = None,
                    max_phases: int = 500) -> Tuple[np.ndarray, dict]:
    """Deprecated shim over repro.ampc.solvers.mis_mpc_rootset."""
    from ..ampc import solvers
    from ..ampc.deprecation import warn_once
    warn_once("repro.core.mis.mis_mpc_rootset",
              'AmpcEngine().solve(g, "mis-mpc")')
    return solvers.mis_mpc_rootset(g, seed=seed, ledger=ledger,
                                   max_phases=max_phases)
