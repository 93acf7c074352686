"""Normalized AMPC / MPC solver drivers — the engine's algorithm layer.

Every driver that used to live at module level in ``core.mis`` /
``core.matching`` / ``core.msf`` / ``core.connectivity`` /
``core.weighted_matching`` / ``core.one_vs_two`` now lives here with a
*normalized* surface:

  * the jitted numerical primitives (fixpoints, truncated Prim, Borůvka,
    pointer jumping, walks) stay in their ``core`` modules;
  * each driver accepts the same cross-cutting keywords (``seed``,
    ``ledger``, and — for AMPC solvers with array outputs — an optional
    ``dht`` backend for the final CollectOutputs snapshot read);
  * each driver is registered with :mod:`repro.ampc.registry` so
    ``AmpcEngine.solve(graph, "<problem>")`` reaches it uniformly;
  * batch-safe problems additionally register a ``@batched_impl`` adapter
    (bottom of this module) that runs one vmapped launch per
    ``solve_many`` shape bucket with outputs identical to the sequential
    driver.

The old ``core`` module functions remain as thin deprecated shims that
delegate here, so pre-engine call sites keep working unchanged.

The ``dht`` parameter realizes the paper's last step of every AMPC round:
machines read their outputs back from the immutable DHT snapshot.  With the
``local`` backend that read is a device gather; with the ``routed`` backend
it is a real dedup + all_to_all exchange.  Both report through the same
ledger path, so ``AmpcResult.ledger`` is backend-independent except for the
collect-read traffic itself.
"""
from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.coo import UGraph
from ..core.rounds import RoundLedger, harvest_many, nbytes_of
from ..core.ternarize import ternarize, ternarize_batch
from ..core.mis import _mis_fixpoint, _mis_fixpoint_masked, IN, OUT, UNKNOWN
from ..core.matching import _mm_fixpoint, _mm_wave, BIGF
from ..core.msf import (truncated_prim, truncated_prim_capped, pointer_jump,
                        contract_edges, boruvka_core, boruvka_inround,
                        _mpc_boruvka_phase)
from ..core.connectivity import (_canonicalize, _cc_fixpoint_masked,
                                 _h2m_phase)
from ..core.one_vs_two import cycle_adjacency, _walk_and_count, \
    _walk_and_count_batch, _local_contraction_phase
from ..obs.trace import current_tracer
from . import registry
from .registry import batched_impl, problem


def _collect_dev(dht, ledger, values, keys=None, dedup: bool = False):
    """CollectOutputs: read an output snapshot back through the DHT backend.

    ``dht=None`` (legacy call sites) returns the device array unchanged.
    With a backend, the read is a genuine lookup (local gather or routed
    all_to_all) whose queries/bytes land in the ledger — as deferred
    device records under a ``deferred`` ledger.  The result stays on the
    device: the caller materializes it through the solve's single
    :meth:`RoundLedger.harvest`.
    """
    if dht is None:
        return values
    if keys is None:
        keys = jnp.arange(values.shape[0], dtype=jnp.int32)
    return dht.lookup(values, keys, ledger=ledger, dedup=dedup)


# ==========================================================================
# MIS (paper Proposition 4.2 / Section 5.3)
# ==========================================================================
def mis_ampc(g: UGraph, seed: int = 0,
             ledger: Optional[RoundLedger] = None,
             caching: bool = True, dht=None,
             snapshot=None) -> Tuple[np.ndarray, dict]:
    """Returns (in_mis bool(n,), stats).

    ``snapshot`` (a :class:`~repro.ampc.session.GraphSnapshot`) replaces
    shuffle 1 with a read of the session's cached graph-KV image: cold it
    records one ``WriteGraphKV`` shuffle, warm it records none — the rank
    permutation is still drawn per solve, so outputs stay bit-identical to
    the snapshot-free path.
    """
    ledger = ledger if ledger is not None else RoundLedger("ampc_mis")
    n = g.n
    rng = np.random.default_rng(seed)
    with ledger.host("rank"):
        rank = rng.permutation(n).astype(np.float32)

    snap_stat = None
    if snapshot is not None:
        entries, snap_hit = snapshot.materialize(ledger)
        senders = entries["sym_senders"]
        receivers = entries["sym_receivers"]
        with ledger.host("to_device"):
            jrank = jnp.asarray(rank)
        snap_stat = snapshot.stat(snap_hit)
    else:
        # shuffle 1: build the rank-directed graph, write to the DHT
        # (Fig 1 step 1-2)
        with ledger.shuffle("DirectEdges+WriteKV", nbytes_of(g.edges) * 2):
            with ledger.host("symmetric"):
                s, r, _, _ = g.symmetric()
            with ledger.host("to_device"):
                senders = jnp.asarray(s); receivers = jnp.asarray(r)
                jrank = jnp.asarray(rank)

    # shuffle 2: IsInMIS search — adaptive queries against the snapshot
    with ledger.shuffle("IsInMIS", n * 4):
        status_dev, iters, q0, q1 = _mis_fixpoint(senders, receivers, jrank, n)
        out_dev = _collect_dev(dht, ledger, status_dev)
        # the solve's one transfer: outputs + every deferred counter record
        status, it, qn, qd = ledger.harvest((out_dev, iters, q0, q1))
        with ledger.host("assemble"):
            status = np.asarray(status)
            it, qn, qd = int(it), int(qn), int(qd)
    with ledger.host("assemble"):
        queries = qd if caching else qn
        row_bytes = 8  # nodeid + status
        ledger.record_queries(queries, queries * row_bytes, waves=it,
                              deduped_away=(qn - qd) if caching else 0)
        assert not (status == UNKNOWN).any()
        stats = {"fixpoint_iters": it, "queries_nodedup": qn,
                 "queries_dedup": qd,
                 "cache_savings_factor": qn / max(qd, 1)}
        if snap_stat is not None:
            stats["snapshot"] = snap_stat
    return status == IN, stats


def mis_mpc_rootset(g: UGraph, seed: int = 0,
                    ledger: Optional[RoundLedger] = None,
                    max_phases: int = 500) -> Tuple[np.ndarray, dict]:
    ledger = ledger if ledger is not None else RoundLedger("mpc_mis")
    n = g.n
    rng = np.random.default_rng(seed)
    rank = jnp.asarray(rng.permutation(n).astype(np.float32))
    s, r, _, _ = g.symmetric()
    senders = jnp.asarray(s); receivers = jnp.asarray(r)

    @jax.jit
    def phase(status):
        s_unk = status[senders] == UNKNOWN
        lower = rank[receivers] < rank[senders]
        blocked = s_unk & lower & (status[receivers] != OUT)
        has_block = jax.ops.segment_max(blocked.astype(jnp.int32), senders,
                                        num_segments=n)
        nbr_in = s_unk & (status[receivers] == IN)
        has_in = jax.ops.segment_max(nbr_in.astype(jnp.int32), senders,
                                     num_segments=n)
        unk = status == UNKNOWN
        status = jnp.where(unk & (has_in > 0), OUT, status)
        status = jnp.where(unk & (has_in <= 0) & (has_block <= 0), IN, status)
        return status, (status == UNKNOWN).sum()

    status = jnp.zeros((n,), jnp.int32)
    phases = 0
    nb = nbytes_of(g.edges) * 2
    remaining = n
    while remaining > 0 and phases < max_phases:
        # paper Fig 2: 2 shuffles per phase (mark-to-remove join, removal join)
        with ledger.shuffle(f"rootset_mark_{phases}", nb):
            status, rem = phase(status)
        with ledger.shuffle(f"rootset_remove_{phases}", nb):
            remaining = int(jax.device_get(rem))
        phases += 1
    status = np.asarray(jax.device_get(status))
    return status == IN, {"phases": phases}


# ==========================================================================
# Maximal matching (paper Section 4, Theorem 2)
# ==========================================================================
def mm_ampc(g: UGraph, seed: int = 0,
            ledger: Optional[RoundLedger] = None,
            caching: bool = True, erank: Optional[np.ndarray] = None,
            dht=None, snapshot=None) -> Tuple[np.ndarray, dict]:
    """Greedy maximal matching over the rank permutation ``erank``.

    ``erank`` is the rank-injection point (Corollary 4.1): when omitted it
    is a fresh random permutation drawn from ``seed``; weighted matching
    passes decreasing-weight ranks instead.  ``snapshot`` reuses a
    session's cached graph-KV image in place of the ``SortEdges+WriteKV``
    shuffle (see :func:`mis_ampc`).  Returns (in_mm bool(m,), stats).
    """
    ledger = ledger if ledger is not None else RoundLedger("ampc_mm")
    n, m = g.n, g.m
    if erank is None:
        rng = np.random.default_rng(seed)
        with ledger.host("rank"):
            erank = rng.permutation(m).astype(np.float32)
    else:
        erank = np.asarray(erank, np.float32)
        assert erank.shape == (m,), "erank must be one rank per edge"

    snap_stat = None
    if snapshot is not None:
        entries, snap_hit = snapshot.materialize(ledger)
        u = entries["edge_u"]; v = entries["edge_v"]
        with ledger.host("to_device"):
            jrank = jnp.asarray(erank)
        snap_stat = snapshot.stat(snap_hit)
    else:
        with ledger.shuffle("SortEdges+WriteKV", nbytes_of(g.edges) * 2):
            with ledger.host("to_device"):
                u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
                jrank = jnp.asarray(erank)

    with ledger.shuffle("IsInMM", m):
        estatus_dev, iters, q0, q1, steps, steps_run = _mm_fixpoint(
            u, v, jrank, n, jnp.zeros((m,), jnp.int32))
        out_dev = _collect_dev(dht, ledger, estatus_dev)
        estatus, it, qn, qd, steps_h, steps_run_h = ledger.harvest(
            (out_dev, iters, q0, q1, steps, steps_run))
        with ledger.host("assemble"):
            estatus = np.asarray(estatus)
            it, qn, qd = int(it), int(qn), int(qd)
    with ledger.host("assemble"):
        queries = qd if caching else qn
        ledger.record_queries(queries, queries * 12, waves=it,
                              deduped_away=(qn - qd) if caching else 0)
        stats = {"fixpoint_iters": it, "queries_nodedup": qn,
                 "queries_dedup": qd, "erank": erank,
                 **_lane_stats(steps_h, steps_run_h, "mm")}
        if snap_stat is not None:
            stats["snapshot"] = snap_stat
    return estatus == IN, stats


def mm_ampc_levels(g: UGraph, seed: int = 0,
                   ledger: Optional[RoundLedger] = None) -> Tuple[np.ndarray, dict]:
    """Algorithm 4: O(log log Δ) geometric sampling levels."""
    ledger = ledger if ledger is not None else RoundLedger("ampc_mm_levels")
    n, m = g.n, g.m
    rng = np.random.default_rng(seed)
    erank01 = rng.permutation(m).astype(np.float64) / max(m, 1)  # π(e) in [0,1)
    delta = int(g.degrees().max()) if m else 1
    k = int(np.ceil(np.log2(max(np.log2(max(delta, 2)), 1.000001)))) + 1
    u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
    jrank = jnp.asarray(erank01.astype(np.float32))
    estatus = jnp.zeros((m,), jnp.int32)
    level_stats = []
    steps_h = steps_run_h = 0
    ten_log_n = 10 * np.log(max(n, 2))
    for i in range(1, k + 1):
        # current degree of the residual graph
        unk = estatus == UNKNOWN
        deg = np.zeros(n, np.int64)
        eun = np.asarray(jax.device_get(unk))
        np.add.at(deg, g.edges[eun, 0], 1)
        np.add.at(deg, g.edges[eun, 1], 1)
        cur_delta = int(deg.max()) if eun.any() else 0
        if cur_delta == 0:
            break
        if cur_delta > ten_log_n:
            thresh = float(delta) ** (-(0.5 ** i))
        else:
            thresh = 1.1  # H_i = G_i
        in_h = jnp.asarray(erank01 <= thresh) & unk
        with ledger.shuffle(f"level_{i}_greedyMM", nbytes_of(g.edges)):
            # resolve the sampled subgraph completely (one AMPC launch)
            st, iters, _, _, steps, steps_run = _mm_fixpoint(
                u, v, jnp.where(in_h, jrank, BIGF), n,
                jnp.where(in_h, jnp.int32(UNKNOWN), jnp.int32(OUT)))
            # edges of H_i resolved; commit IN edges, kill touched vertices
            new_in = (st == IN) & in_h
            estatus = jnp.where(new_in, IN, estatus)
            matched = jnp.zeros((n,), jnp.int32)
            matched = matched.at[jnp.where(estatus == IN, u, n)].set(1, mode="drop")
            matched = matched.at[jnp.where(estatus == IN, v, n)].set(1, mode="drop")
            dead = (estatus == UNKNOWN) & ((matched[u] == 1) | (matched[v] == 1))
            estatus = jnp.where(dead, OUT, estatus)
            # H_i \ M_i edges whose endpoints survive go back to G_{i+1}
        it, steps, steps_run = jax.device_get((iters, steps, steps_run))
        steps_h += int(steps)
        steps_run_h += int(steps_run)
        level_stats.append({"level": i, "delta": cur_delta,
                            "threshold": thresh, "iters": int(it)})
    st = np.asarray(jax.device_get(estatus))
    return st == IN, {"levels": level_stats, "k": k,
                      "erank": erank01.astype(np.float32),
                      **_lane_stats(steps_h, steps_run_h, "mm")}


def mm_ampc_vertex_process(g: UGraph, epsilon: float = 0.5, seed: int = 0,
                           ledger: Optional[RoundLedger] = None,
                           ) -> Tuple[np.ndarray, dict]:
    """Theorem 2 part 2: vertex-started truncated query process.

    Each launch gives every vertex a fresh budget of n^ε queries; decisions on
    an edge are applied only while at least one endpoint still has budget, so
    resolution is delayed — never altered — and the output is the exact LFMM.
    """
    ledger = ledger if ledger is not None else RoundLedger("ampc_mm_vertex")
    n, m = g.n, g.m
    rng = np.random.default_rng(seed)
    erank = rng.permutation(m).astype(np.float32)
    u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
    jrank = jnp.asarray(erank)
    budget = max(4, int(np.ceil(n ** epsilon)))

    @functools.partial(jax.jit, static_argnames=())
    def launch(estatus):
        qcount0 = jnp.zeros((n,), jnp.int32)

        def cond(s):
            estatus, qcount, it, q = s
            unk = estatus == UNKNOWN
            active = (qcount[u] < budget) | (qcount[v] < budget)
            return jnp.any(unk & active) & (it < 4 * budget)

        def body(s):
            estatus, qcount, it, q = s
            active = (qcount[u] < budget) | (qcount[v] < budget)
            new, _ = _mm_wave(estatus, u, v, jrank, n, active_edge=active)
            unk = estatus == UNKNOWN
            # each unresolved active edge costs one query at each live endpoint
            cost = jnp.zeros((n,), jnp.int32)
            live = unk & active
            cost = cost.at[jnp.where(live, u, n)].add(1, mode="drop")
            cost = cost.at[jnp.where(live, v, n)].add(1, mode="drop")
            return new, qcount + cost, it + 1, q + live.sum()

        return jax.lax.while_loop(cond, body,
                                  (estatus, qcount0, jnp.int32(0), jnp.int32(0)))

    estatus = jnp.zeros((m,), jnp.int32)
    launches, total_q = 0, 0
    while bool(jax.device_get(jnp.any(estatus == UNKNOWN))) and launches < 64:
        with ledger.shuffle(f"vertex_process_{launches}", m):
            estatus, qcount, iters, q = launch(estatus)
            total_q += int(jax.device_get(q))
        launches += 1
    ledger.record_queries(total_q, total_q * 12, waves=launches)
    st = np.asarray(jax.device_get(estatus))
    return st == IN, {"launches": launches, "budget": budget,
                      "queries": total_q, "erank": erank}


def mm_mpc_rootset(g: UGraph, seed: int = 0,
                   ledger: Optional[RoundLedger] = None,
                   max_phases: int = 500) -> Tuple[np.ndarray, dict]:
    ledger = ledger if ledger is not None else RoundLedger("mpc_mm")
    n, m = g.n, g.m
    rng = np.random.default_rng(seed)
    erank = rng.permutation(m).astype(np.float32)
    u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
    jrank = jnp.asarray(erank)

    @jax.jit
    def phase(estatus):
        new, _ = _mm_wave(estatus, u, v, jrank, n)
        return new, (new == UNKNOWN).sum()

    estatus = jnp.zeros((m,), jnp.int32)
    phases, remaining = 0, m
    nb = nbytes_of(g.edges)
    while remaining > 0 and phases < max_phases:
        with ledger.shuffle(f"rootset_mark_{phases}", nb):
            estatus, rem = phase(estatus)
        with ledger.shuffle(f"rootset_remove_{phases}", nb):
            remaining = int(jax.device_get(rem))
        phases += 1
    st = np.asarray(jax.device_get(estatus))
    return st == IN, {"phases": phases, "erank": erank}


# ==========================================================================
# Corollary 4.1 applications of the MM black box
# ==========================================================================
def mwm_greedy_ampc(g: UGraph, seed: int = 0,
                    ledger: Optional[RoundLedger] = None,
                    dht=None, snapshot=None) -> Tuple[np.ndarray, dict]:
    """1/2-approx maximum weight matching: greedy by decreasing weight
    (ties broken by a random permutation), via the AMPC MM fixpoint with
    weight-derived ranks injected through ``mm_ampc(erank=...)``.
    Returns (in_matching bool(m,), stats)."""
    assert g.weights is not None
    ledger = ledger if ledger is not None else RoundLedger("ampc_mwm")
    with ledger.host("rank"):
        rng = np.random.default_rng(seed)
        tie = rng.permutation(g.m).astype(np.float64) / max(g.m, 1)
        # rank: ascending = processed first => sort by decreasing weight
        order = np.argsort(np.lexsort((tie, -g.weights.astype(np.float64))))
        erank = order.astype(np.float32)
    in_mm, st = mm_ampc(g, seed=seed, ledger=ledger, erank=erank, dht=dht,
                        snapshot=snapshot)
    w = float(g.weights[in_mm].sum())
    return in_mm, {"weight": w, **st}


def vertex_cover_2approx(g: UGraph, seed: int = 0,
                         ledger: Optional[RoundLedger] = None,
                         dht=None, snapshot=None) -> Tuple[np.ndarray, dict]:
    """2-approx minimum vertex cover = endpoints of a maximal matching."""
    in_mm, stats = mm_ampc(g, seed=seed, ledger=ledger, dht=dht,
                           snapshot=snapshot)
    cover = np.zeros(g.n, bool)
    cover[g.edges[in_mm, 0]] = True
    cover[g.edges[in_mm, 1]] = True
    return cover, {"cover_size": int(cover.sum()), **stats}


# ==========================================================================
# MSF (paper Section 3, Algorithm 2)
# ==========================================================================
def _msf_assemble(orig_eid, m, dmask, found_h, q_h, jump_h, live_h, phases_h,
                  cases_h, budget, nt):
    """Sparse-path output assembly shared by the 5-shuffle and the fused
    session paths: union the Prim-discovered edges (the ``found`` mask over
    tern eids, mapped back through ``orig_eid``) into the dense-phase mask,
    and build the stats."""
    total_q = int(q_h)
    orig = orig_eid[np.flatnonzero(found_h)]
    orig = orig[orig >= 0]
    mask = dmask.copy()
    if m:
        mask[orig] = True
    live_v = int(live_h)
    stats = {
        "path": "sparse",
        "budget": budget,
        "n_tern": nt,
        "queries": total_q,
        "avg_queries_per_vertex": total_q / max(nt, 1),
        "pointer_jump_iters": int(jump_h),
        "contracted_vertices": live_v,
        "shrink_factor": nt / max(live_v, 1),
        "dense_phases": int(phases_h),
        "stop_cases": {int(k): int(c) for k, c in zip(
            *np.unique(np.asarray(cases_h), return_counts=True))},
    }
    return mask, stats


def _lane_stats(lane_steps_h, lane_steps_run_h, kind: str = "prim") -> dict:
    """A loop's lane counters summed into ``stats`` keys
    ``<kind>_lane_steps`` (the lane-iterations that did useful work) and
    ``<kind>_lane_steps_run`` (all it ran): truncated Prim's per chunk (see
    ``truncated_prim_capped``), the matching fixpoint's per launch (see
    ``_mm_fixpoint``)."""
    return {f"{kind}_lane_steps": int(np.sum(lane_steps_h, dtype=np.int64)),
            f"{kind}_lane_steps_run": int(np.sum(lane_steps_run_h,
                                                 dtype=np.int64))}


def msf_ampc(g: UGraph, epsilon: float = 0.5, seed: int = 0,
             ledger: Optional[RoundLedger] = None,
             skip_ternarize_if_dense: bool = True,
             dht=None, snapshot=None) -> Tuple[np.ndarray, dict]:
    """Compute the MSF mask over g.edges.  Returns (mask, stats).

    ``snapshot`` switches to the fused session path: the ternarized
    adjacency (or the dense edge image) comes from the session's cached KV
    view — cold it is built under one ``WriteTernKV`` / ``WriteGraphKV``
    shuffle, warm it is free — and the whole solve then runs in a single
    ``MSF`` round (2 shuffles cold, 1 warm, vs the cold path's 5).  The
    rank permutation is still the *first* per-solve draw from ``seed``, so
    outputs are bit-identical to the snapshot-free path.
    """
    ledger = ledger if ledger is not None else RoundLedger("ampc_msf")
    assert g.weights is not None
    n, m = g.n, g.m
    rng = np.random.default_rng(seed)

    dense = skip_ternarize_if_dense and m >= n ** (1.0 + epsilon / 2.0)
    if dense:
        # Proposition 3.1 path: run the dense routine directly.
        if snapshot is not None:
            entries, snap_hit = snapshot.materialize_dense(ledger)
            u, v, w = entries["edge_u"], entries["edge_v"], entries["edge_w"]
            shuffle_nbytes = 0  # the write was accounted at view build
        else:
            with ledger.host("to_device"):
                u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
                w = jnp.asarray(g.weights)
            shuffle_nbytes = nbytes_of(g.edges, g.weights)
        eid = jnp.arange(m, dtype=jnp.int32)
        valid = jnp.ones((m,), bool)
        with ledger.shuffle("DenseMSF", shuffle_nbytes):
            mask_dev, _, phases = boruvka_inround(u, v, w, eid, valid, n, m)
            col_dev = _collect_dev(dht, ledger, mask_dev.astype(jnp.int32))
            mask, phases_h = ledger.harvest((col_dev, phases))
            with ledger.host("assemble"):
                mask = np.asarray(mask).astype(bool)
        stats = {"phases": int(phases_h), "path": "dense"}
        if snapshot is not None:
            stats["snapshot"] = snapshot.stat(snap_hit)
        return mask, stats

    if snapshot is not None:
        # fused session path: read the ternarized view from the snapshot
        # cache, then run Prim -> jump -> contract -> Borůvka in ONE round
        entries, snap_hit = snapshot.materialize_tern(ledger)
        tg = entries["tg"]
        nt = tg.g.n
        with ledger.host("rank"):
            rank = rng.permutation(nt).astype(np.float32)
        budget = max(2, int(np.ceil(nt ** (epsilon / 2.0))))
        with ledger.shuffle("MSF", 0):
            with ledger.host("to_device"):
                jn_rank = jnp.asarray(rank)
            found, hooks, cases, queries, steps, steps_run = truncated_prim(
                entries["nbr"], entries["nbw"], entries["nbe"],
                jn_rank, budget, tg.g.m)
            q_sum = queries.sum()
            ledger.record_queries_deferred(q_sum, q_sum * 36, waves=1)
            parent = jnp.where(hooks >= 0, hooks,
                               jnp.arange(nt, dtype=jnp.int32))
            roots, jump_iters = pointer_jump(parent)
            ledger.record_queries_deferred(jump_iters * nt,
                                           jump_iters * nt * 4, waves=1)
            cu, cv, cw, ceid, cvalid, live = contract_edges(
                entries["tu"], entries["tv"], entries["tw"],
                entries["teid"], jnp.ones((tg.g.m,), bool), roots)
            dmask_dev, _, phases = boruvka_inround(cu, cv, cw, ceid, cvalid,
                                                   nt, max(m, 1))
            col_dev = _collect_dev(dht, ledger, dmask_dev.astype(jnp.int32))
            (dmask, found_h, q_h, jump_h, live_h, phases_h, cases_h,
             steps_h, steps_run_h) = ledger.harvest(
                (col_dev, found, q_sum, jump_iters, live, phases, cases,
                 steps, steps_run))
            with ledger.host("assemble"):
                dmask = np.asarray(dmask).astype(bool)
        with ledger.host("assemble"):
            mask, stats = _msf_assemble(tg.orig_eid, m, dmask, found_h, q_h,
                                        jump_h, live_h, phases_h, cases_h,
                                        budget, nt)
            stats.update(_lane_stats(steps_h, steps_run_h))
            stats["snapshot"] = snapshot.stat(snap_hit)
        return mask, stats

    # --- shuffle 1: SortGraph (ternarize + build sorted adjacency, write DHT)
    with ledger.shuffle("SortGraph", nbytes_of(g.edges, g.weights)):
        with ledger.host("ternarize"):
            tg = ternarize(g)
        with ledger.host("adjacency"):
            nbr, nbw, nbe = tg.g.padded_adj(3)
        nt = tg.g.n
        with ledger.host("rank"):
            rank = rng.permutation(nt).astype(np.float32)
        budget = max(2, int(np.ceil(nt ** (epsilon / 2.0))))
    ledger.record_queries(0, 0, waves=0)

    # --- shuffle 2: PrimSearch (adaptive queries against the DHT snapshot)
    with ledger.host("to_device"):
        jn_nbr, jn_nbw, jn_nbe = (jnp.asarray(nbr), jnp.asarray(nbw),
                                  jnp.asarray(nbe))
        jn_rank = jnp.asarray(rank)
    with ledger.shuffle("PrimSearch", 0):
        found, hooks, cases, queries, steps, steps_run = truncated_prim(
            jn_nbr, jn_nbw, jn_nbe, jn_rank, budget, tg.g.m)
        q_sum = queries.sum()
    row_bytes = 3 * (4 + 4 + 4)
    ledger.record_queries_deferred(q_sum, q_sum * row_bytes, waves=1)

    # --- shuffle 3: PointerJump (contract the hook forest, Prop 3.2)
    with ledger.shuffle("PointerJump", nbytes_of(hooks)):
        parent = jnp.where(hooks >= 0, hooks, jnp.arange(nt, dtype=jnp.int32))
        roots, jump_iters = pointer_jump(parent)
    ledger.record_queries_deferred(jump_iters * nt, jump_iters * nt * 4,
                                   waves=1)

    # --- shuffle 4: Contract (relabel + dedup on the ternarized edge list)
    with ledger.host("to_device"):
        tu = jnp.asarray(tg.g.edges[:, 0]); tv = jnp.asarray(tg.g.edges[:, 1])
        tw = jnp.asarray(tg.g.weights); teid = jnp.asarray(tg.orig_eid)
    with ledger.shuffle("Contract", nbytes_of(tg.g.edges, tg.g.weights)):
        cu, cv, cw, ceid, cvalid, live = contract_edges(
            tu, tv, tw, teid, jnp.ones((tg.g.m,), bool), roots)

    # --- shuffle 5: DenseMSF on the contracted graph, then the solve's
    # single harvest: every output array and deferred counter, one transfer
    with ledger.shuffle("DenseMSF", 0):
        dmask_dev, dlabels, phases = boruvka_inround(cu, cv, cw, ceid, cvalid,
                                                     nt, max(m, 1))
        col_dev = _collect_dev(dht, ledger, dmask_dev.astype(jnp.int32))
        (dmask, found_h, q_h, jump_h, live_h, phases_h, cases_h, steps_h,
         steps_run_h) = ledger.harvest(
            (col_dev, found, q_sum, jump_iters, live, phases, cases, steps,
             steps_run))
        with ledger.host("assemble"):
            dmask = np.asarray(dmask).astype(bool)
    with ledger.host("assemble"):
        mask, stats = _msf_assemble(tg.orig_eid, m, dmask, found_h, q_h,
                                    jump_h, live_h, phases_h, cases_h,
                                    budget, nt)
        stats.update(_lane_stats(steps_h, steps_run_h))
    return mask, stats


def msf_mpc_boruvka(g: UGraph, seed: int = 0,
                    ledger: Optional[RoundLedger] = None,
                    max_phases: int = 200) -> Tuple[np.ndarray, dict]:
    ledger = ledger if ledger is not None else RoundLedger("mpc_msf")
    n, m = g.n, g.m
    rng = np.random.default_rng(seed)
    u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
    w = jnp.asarray(g.weights); eid = jnp.arange(m, dtype=jnp.int32)
    valid = jnp.ones((m,), bool)
    labels = jnp.arange(n, dtype=jnp.int32)
    mask = np.zeros(m, bool)
    phase_bytes = nbytes_of(g.edges, g.weights)
    phases = 0
    remaining = m
    while remaining > 0 and phases < max_phases:
        color = jnp.asarray(rng.random(n) < 0.5)
        # the paper's MPC algorithm performs 3 shuffles per contraction phase
        with ledger.shuffle(f"boruvka_minedge_{phases}", phase_bytes):
            pass
        with ledger.shuffle(f"boruvka_hook_{phases}", n * 4):
            labels, selected, valid, rem = _mpc_boruvka_phase(
                u, v, w, eid, valid, labels, color,
                jnp.zeros((m,), bool))
        with ledger.shuffle(f"boruvka_relabel_{phases}", phase_bytes):
            mask |= np.asarray(jax.device_get(selected))
            remaining = int(jax.device_get(rem))
        phases += 1
    return mask, {"phases": phases}


# ==========================================================================
# Connectivity (paper Theorem 1)
# ==========================================================================
def cc_ampc(g: UGraph, epsilon: float = 0.5, seed: int = 0,
            ledger: Optional[RoundLedger] = None,
            dht=None, snapshot=None) -> Tuple[np.ndarray, dict]:
    """Connected components; returns (labels(n,) canonical, stats).

    ``snapshot`` switches to the fused session path (see :func:`msf_ampc`):
    the unit-weight ternarization + first-slot map come from the session's
    ``tern_cc`` KV view (one ``WriteTernKV`` shuffle, cold only) and the
    solve runs in a single ``Connectivity`` round — 2 shuffles cold, 1
    warm, bit-identical labels.
    """
    ledger = ledger if ledger is not None else RoundLedger("ampc_cc")
    n, m = g.n, g.m
    if m == 0:
        stats = {"queries": 0}
        if snapshot is not None:
            # nothing to materialize; the trivial answer never hits the KV
            stats["snapshot"] = snapshot.stat(False)
        return np.arange(n, dtype=np.int64), stats
    rng = np.random.default_rng(seed)

    if snapshot is not None:
        entries, snap_hit = snapshot.materialize_tern(ledger, unit=True)
        tg = entries["tg"]
        nt = tg.g.n
        with ledger.host("rank"):
            rank = rng.permutation(nt).astype(np.float32)
        budget = max(2, int(np.ceil(nt ** (epsilon / 2.0))))
        with ledger.shuffle("Connectivity", 0):
            with ledger.host("to_device"):
                jn_rank = jnp.asarray(rank)
            _, hooks, cases, queries, steps, steps_run = truncated_prim(
                entries["nbr"], entries["nbw"], entries["nbe"],
                jn_rank, budget, tg.g.m)
            q_sum = queries.sum()
            ledger.record_queries_deferred(q_sum, q_sum * 36, waves=1)
            parent = jnp.where(hooks >= 0, hooks,
                               jnp.arange(nt, dtype=jnp.int32))
            roots, jump_iters = pointer_jump(parent)
            cu, cv, cw, ceid, cvalid, live = contract_edges(
                entries["tu"], entries["tv"], entries["tw"],
                entries["teid"], jnp.ones((tg.g.m,), bool), roots)
            _, dlabels, phases = boruvka_inround(cu, cv, cw, ceid, cvalid,
                                                 nt, max(m, 1))
            # compose contractions: two genuine DHT reads of the label maps
            if dht is not None:
                final_tern = dht.lookup(dlabels, roots, ledger=ledger)
                orig_dev = dht.lookup(final_tern, entries["first_slot"],
                                      ledger=ledger)
            else:
                final_tern = jnp.take(dlabels, roots)
                orig_dev = jnp.take(final_tern, entries["first_slot"])
            orig_labels, q_h, jump_h, phases_h, steps_h, steps_run_h = \
                ledger.harvest((orig_dev, q_sum, jump_iters, phases, steps,
                                steps_run))
            with ledger.host("assemble"):
                orig_labels = np.asarray(orig_labels).astype(np.int64)
        with ledger.host("assemble"):
            labels = _canonicalize(orig_labels)
            stats = {
                "queries": int(q_h),
                "pointer_jump_iters": int(jump_h),
                "dense_phases": int(phases_h),
                "num_components": int(len(np.unique(labels))),
                **_lane_stats(steps_h, steps_run_h),
                "snapshot": snapshot.stat(snap_hit),
            }
        return labels, stats

    gw = UGraph(n, g.edges, np.arange(m, dtype=np.float32))  # unit-ish distinct
    with ledger.shuffle("SortGraph", nbytes_of(gw.edges)):
        with ledger.host("ternarize"):
            tg = ternarize(gw)
        with ledger.host("adjacency"):
            nbr, nbw, nbe = tg.g.padded_adj(3)
        nt = tg.g.n
        with ledger.host("rank"):
            rank = rng.permutation(nt).astype(np.float32)
        budget = max(2, int(np.ceil(nt ** (epsilon / 2.0))))
        # first tern slot of each original vertex (node_of is sorted)
        with ledger.host("adjacency"):
            first_slot = np.searchsorted(tg.node_of, np.arange(n))

    with ledger.shuffle("PrimSearch", 0):
        with ledger.host("to_device"):
            adj = [jnp.asarray(a) for a in (nbr, nbw, nbe, rank)]
        _, hooks, cases, queries, steps, steps_run = truncated_prim(
            *adj, budget, tg.g.m)
        q_sum = queries.sum()
    ledger.record_queries_deferred(q_sum, q_sum * 36, waves=1)

    with ledger.shuffle("PointerJump", nbytes_of(hooks)):
        parent = jnp.where(hooks >= 0, hooks, jnp.arange(nt, dtype=jnp.int32))
        roots, jump_iters = pointer_jump(parent)

    with ledger.host("to_device"):
        tu = jnp.asarray(tg.g.edges[:, 0]); tv = jnp.asarray(tg.g.edges[:, 1])
        tw = jnp.asarray(tg.g.weights); teid = jnp.asarray(tg.orig_eid)
    with ledger.shuffle("Contract", nbytes_of(tg.g.edges)):
        cu, cv, cw, ceid, cvalid, live = contract_edges(
            tu, tv, tw, teid, jnp.ones((tg.g.m,), bool), roots)

    with ledger.shuffle("ForestConnectivity", 0):
        _, dlabels, phases = boruvka_inround(cu, cv, cw, ceid, cvalid, nt,
                                             max(m, 1))
        # compose contractions: two genuine DHT reads of the label maps
        if dht is not None:
            final_tern = dht.lookup(dlabels, roots, ledger=ledger)
            with ledger.host("to_device"):
                jn_first = jnp.asarray(first_slot, jnp.int32)
            orig_dev = dht.lookup(final_tern, jn_first, ledger=ledger)
        else:
            final_tern = jnp.take(dlabels, roots)
            with ledger.host("to_device"):
                jn_first = jnp.asarray(first_slot)
            orig_dev = jnp.take(final_tern, jn_first)
        orig_labels, q_h, jump_h, phases_h, steps_h, steps_run_h = \
            ledger.harvest((orig_dev, q_sum, jump_iters, phases, steps,
                            steps_run))
        with ledger.host("assemble"):
            orig_labels = np.asarray(orig_labels).astype(np.int64)

    with ledger.host("assemble"):
        labels = _canonicalize(orig_labels)
        stats = {
            "queries": int(q_h),
            "pointer_jump_iters": int(jump_h),
            "dense_phases": int(phases_h),
            "num_components": int(len(np.unique(labels))),
            **_lane_stats(steps_h, steps_run_h),
        }
    return labels, stats


def cc_mpc_hash_to_min(g: UGraph, ledger: Optional[RoundLedger] = None,
                       max_phases: int = 200) -> Tuple[np.ndarray, dict]:
    ledger = ledger if ledger is not None else RoundLedger("mpc_cc")
    n = g.n
    u = jnp.asarray(g.edges[:, 0]); v = jnp.asarray(g.edges[:, 1])
    labels = jnp.arange(n, dtype=jnp.int32)
    phases = 0
    nb = nbytes_of(g.edges)
    while phases < max_phases:
        with ledger.shuffle(f"h2m_join_{phases}", nb):
            labels, changed = _h2m_phase(u, v, labels)
        with ledger.shuffle(f"h2m_update_{phases}", n * 4):
            ch = bool(jax.device_get(changed))
        phases += 1
        if not ch:
            break
    labels = _canonicalize(np.asarray(jax.device_get(labels)).astype(np.int64))
    return labels, {"phases": phases,
                    "num_components": int(len(np.unique(labels)))}


# ==========================================================================
# 1-vs-2-Cycle (paper Section 5.6)
# ==========================================================================
def one_vs_two_ampc(g: UGraph, p: float = 1.0 / 64, seed: int = 0,
                    ledger: Optional[RoundLedger] = None,
                    max_steps: Optional[int] = None,
                    snapshot=None) -> Tuple[int, dict]:
    """Returns (num_cycles, stats).

    ``snapshot`` reads the cycle adjacency from the session's ``cycle_adj``
    KV view instead of rebuilding it under the ``WriteKV`` shuffle; the
    sample set is still drawn per solve (same rng order), so the answer is
    identical — 2 shuffles cold, 1 warm.
    """
    ledger = ledger if ledger is not None else RoundLedger("ampc_1v2c")
    n = g.n
    rng = np.random.default_rng(seed)
    snap_stat = None
    if snapshot is not None:
        entries, snap_hit = snapshot.materialize_cycle(ledger)
        nbr = entries["cycle_nbr"]
        sampled_np = rng.random(n) < p
        if not sampled_np.any():
            sampled_np[rng.integers(n)] = True
        sampled = jnp.asarray(sampled_np)
        snap_stat = snapshot.stat(snap_hit)
    else:
        with ledger.shuffle("WriteKV", nbytes_of(g.edges)):
            nbr = jnp.asarray(cycle_adjacency(g))
            sampled_np = rng.random(n) < p
            # guarantee at least one sample (paper: w.h.p. argument)
            if not sampled_np.any():
                sampled_np[rng.integers(n)] = True
            sampled = jnp.asarray(sampled_np)
    ms = max_steps or int(min(n + 1, np.ceil(8 * np.log(max(n, 2)) / p)))
    with ledger.shuffle("SampleWalk", int(sampled_np.sum()) * 4):
        ncomp, steps, ok = ledger.harvest(_walk_and_count(nbr, sampled, ms))
        ncomp, total_steps, ok = int(ncomp), int(steps), bool(ok)
    ledger.record_queries(total_steps, total_steps * 12, waves=1)
    if not ok:
        raise RuntimeError("walk budget exceeded; increase p or max_steps")
    stats = {"samples": int(sampled_np.sum()),
             "walk_steps": total_steps, "max_steps": ms}
    if snap_stat is not None:
        stats["snapshot"] = snap_stat
    return ncomp, stats


def one_vs_two_mpc(g: UGraph, seed: int = 0,
                   ledger: Optional[RoundLedger] = None) -> Tuple[int, dict]:
    """CC-LocalContraction MPC baseline (Section 5.6): each phase removes the
    rank-local-minima of every cycle and reconnects; 3 shuffles per phase,
    O(log n) phases; the residual graph is finished in memory (the paper
    switches to a single machine below 5e7 edges)."""
    ledger = ledger if ledger is not None else RoundLedger("mpc_1v2c")
    n = g.n
    rng = np.random.default_rng(seed)
    nbr = cycle_adjacency(g)
    a = jnp.asarray(nbr[:, 0]); b = jnp.asarray(nbr[:, 1])
    rank = jnp.asarray(rng.permutation(n).astype(np.float32))
    parent = jnp.arange(n, dtype=jnp.int32)
    alive = jnp.ones((n,), bool)
    phases, remaining = 0, n
    nb = nbytes_of(g.edges)
    shrink = []
    while remaining > 0 and phases < 200:
        prev = remaining
        with ledger.shuffle(f"lc_minima_{phases}", nb):
            a, b, parent, alive, rem = _local_contraction_phase(
                a, b, parent, alive, rank)
        with ledger.shuffle(f"lc_reconnect_{phases}", nb):
            remaining = int(jax.device_get(rem))
        with ledger.shuffle(f"lc_relabel_{phases}", n * 4):
            shrink.append(prev / max(remaining, 1))
        phases += 1
    # in-memory finish: pointer-jump parents to roots
    roots, _ = pointer_jump(parent)
    ncomp = int(len(np.unique(np.asarray(jax.device_get(roots)))))
    return ncomp, {"phases": phases, "shrink_per_phase": shrink}


# ==========================================================================
# Registry entries — the engine's dispatch table
# ==========================================================================
@problem("mis", model="ampc", output="vertex_mask", aliases=("ampc-mis",),
         table3_shuffles=2,
         summary="LFMIS by in-round dependency fixpoint (Fig 1)")
def _p_mis(ctx, g, **opts):
    return mis_ampc(g, seed=ctx.seed, ledger=ctx.ledger, dht=ctx.dht, **opts)


@problem("mis-mpc", model="mpc", output="vertex_mask", baseline_of="mis",
         summary="MPC rootset baseline, 2 shuffles/phase (Fig 2)")
def _p_mis_mpc(ctx, g, **opts):
    return mis_mpc_rootset(g, seed=ctx.seed, ledger=ctx.ledger, **opts)


@problem("matching", model="ampc", output="edge_mask",
         aliases=("mm", "maximal-matching"), table3_shuffles=2,
         summary="LFMM by in-round edge fixpoint (Section 5.4)")
def _p_mm(ctx, g, **opts):
    return mm_ampc(g, seed=ctx.seed, ledger=ctx.ledger, dht=ctx.dht, **opts)


@problem("matching-levels", model="ampc", output="edge_mask",
         summary="Algorithm 4: O(log log Δ) geometric sampling levels")
def _p_mm_levels(ctx, g, **opts):
    return mm_ampc_levels(g, seed=ctx.seed, ledger=ctx.ledger, **opts)


@problem("matching-vertex-process", model="ampc", output="edge_mask",
         summary="Theorem 2.2: n^ε-budget truncated vertex query process")
def _p_mm_vertex(ctx, g, **opts):
    return mm_ampc_vertex_process(g, epsilon=ctx.epsilon, seed=ctx.seed,
                                  ledger=ctx.ledger, **opts)


@problem("matching-mpc", model="mpc", output="edge_mask",
         baseline_of="matching",
         summary="MPC rootset baseline, 2 shuffles/phase")
def _p_mm_mpc(ctx, g, **opts):
    return mm_mpc_rootset(g, seed=ctx.seed, ledger=ctx.ledger, **opts)


@problem("weighted-matching", model="ampc", output="edge_mask",
         aliases=("mwm",), needs_weights=True, table3_shuffles=2,
         summary="Corollary 4.1: greedy 1/2-approx MWM via erank injection")
def _p_mwm(ctx, g, **opts):
    return mwm_greedy_ampc(g, seed=ctx.seed, ledger=ctx.ledger, dht=ctx.dht,
                           **opts)


@problem("vertex-cover", model="ampc", output="vertex_mask",
         summary="Corollary 4.1: 2-approx vertex cover = V(maximal matching)")
def _p_vc(ctx, g, **opts):
    return vertex_cover_2approx(g, seed=ctx.seed, ledger=ctx.ledger,
                                dht=ctx.dht, **opts)


@problem("msf", model="ampc", output="edge_mask", needs_weights=True,
         table3_shuffles=5,
         summary="Algorithm 2: 5-shuffle truncated-Prim MSF")
def _p_msf(ctx, g, **opts):
    return msf_ampc(g, epsilon=ctx.epsilon, seed=ctx.seed, ledger=ctx.ledger,
                    dht=ctx.dht, **opts)


@problem("msf-kkt", model="ampc", output="edge_mask", needs_weights=True,
         summary="Algorithm 3: KKT sample + F-light filter + MSF")
def _p_msf_kkt(ctx, g, **opts):
    from ..core.kkt_filter import msf_kkt
    return msf_kkt(g, epsilon=ctx.epsilon, seed=ctx.seed, ledger=ctx.ledger,
                   **opts)


@problem("msf-mpc", model="mpc", output="edge_mask", needs_weights=True,
         baseline_of="msf",
         summary="MPC red/blue Borůvka baseline, 3 shuffles/phase")
def _p_msf_mpc(ctx, g, **opts):
    return msf_mpc_boruvka(g, seed=ctx.seed, ledger=ctx.ledger, **opts)


@problem("connectivity", model="ampc", output="labels", aliases=("cc",),
         table3_shuffles=5,
         summary="Theorem 1: MSF on unit weights + forest connectivity")
def _p_cc(ctx, g, **opts):
    return cc_ampc(g, epsilon=ctx.epsilon, seed=ctx.seed, ledger=ctx.ledger,
                   dht=ctx.dht, **opts)


@problem("connectivity-mpc", model="mpc", output="labels",
         baseline_of="connectivity",
         summary="MPC hash-to-min label propagation baseline")
def _p_cc_mpc(ctx, g, **opts):
    return cc_mpc_hash_to_min(g, ledger=ctx.ledger, **opts)


@problem("one-vs-two", model="ampc", output="count", aliases=("1v2c",),
         needs_cycles=True, table3_shuffles=2,
         summary="Section 5.6: adaptive cycle walk, the AMPC/MPC separation")
def _p_1v2(ctx, g, **opts):
    return one_vs_two_ampc(g, seed=ctx.seed, ledger=ctx.ledger, **opts)


@problem("one-vs-two-mpc", model="mpc", output="count",
         baseline_of="one-vs-two", needs_cycles=True,
         summary="CC-LocalContraction MPC baseline, 3 shuffles/phase")
def _p_1v2_mpc(ctx, g, **opts):
    return one_vs_two_mpc(g, seed=ctx.seed, ledger=ctx.ledger, **opts)


# ==========================================================================
# Batched adapters — AmpcEngine.solve_many, one vmapped launch per bucket
# ==========================================================================
# Each adapter takes (bctx: engine.BatchSolveContext, batch: GraphBatch) and
# returns one (output, stats) per graph, in batch order.  Invariants:
#
#   * outputs are bit-identical to sequential ``solve`` on the same engine
#     seed: each lane pads with inert edges/vertices and uses the graph's
#     *own* (unpadded) rank permutation, so the fixpoint trajectory over the
#     real vertices/edges is exactly the sequential one;
#   * the traced solver is memoized per (problem, backend, bucket) through
#     ``bctx.cache``; all graphs after the first occupant of a bucket ride
#     the same compiled program (stats["solver_cache"]);
#   * per-graph ledgers mirror the sequential shuffle structure, with this
#     graph's own bytes and its mask's share of the batched DHT traffic.


def _cache_stat(key, hit: bool, slot: int) -> dict:
    # slot 0 of a cold bucket pays the trace; every later occupant is a hit
    return {"key": key, "hit": bool(hit or slot > 0)}


def _per_graph_ranks(batch, seed: int):
    """Per-graph vertex rank permutations, padded to n_bucket.

    Each graph draws from ``default_rng(seed)`` exactly like the sequential
    solver; padding vertices get ranks above every real rank (they are
    isolated, so the value never matters)."""
    B, nb = len(batch), batch.n_bucket
    ranks = np.zeros((B, nb), np.float32)
    for b, g in enumerate(batch.graphs):
        rng = np.random.default_rng(seed)
        ranks[b, :g.n] = rng.permutation(g.n).astype(np.float32)
        ranks[b, g.n:] = np.arange(g.n, nb, dtype=np.float32)
    return ranks


def _build_mis_solver(n: int):
    return jax.jit(jax.vmap(
        lambda s, r, rank, ok: _mis_fixpoint_masked(s, r, rank, n, ok)))


@batched_impl("mis")
def mis_ampc_batched(bctx, batch, caching: bool = True):
    """Batched MIS: one masked-fixpoint launch over the whole bucket."""
    B, nb = len(batch), batch.n_bucket
    senders, receivers, edge_ok = batch.padded_symmetric()
    ranks = _per_graph_ranks(batch, bctx.seed)
    for b, g in enumerate(batch.graphs):
        bctx.ledgers[b].record_shuffle("DirectEdges+WriteKV",
                                       nbytes_of(g.edges) * 2)
    key = bctx.solver_key(batch)
    solver, hit = bctx.cache.get_or_build(
        key, lambda: _build_mis_solver(nb), occupants=B)
    t0 = time.perf_counter()
    status_b, iters_b, q0_b, q1_b = solver(
        jnp.asarray(senders), jnp.asarray(receivers), jnp.asarray(ranks),
        jnp.asarray(edge_ok))
    # CollectOutputs: one batched DHT read, per-graph queries split by mask
    keys = np.broadcast_to(np.arange(nb, dtype=np.int32), (B, nb))
    out_b = bctx.dht.lookup_many(status_b, keys, ledgers=bctx.ledgers,
                                 key_mask=batch.node_mask)
    # the bucket's one transfer: outputs + every ledger's deferred counters
    status_h, iters, q0, q1 = harvest_many(
        bctx.ledgers, (out_b, iters_b, q0_b, q1_b))
    status_h = np.asarray(status_h)
    dt = time.perf_counter() - t0
    outs = []
    for b, g in enumerate(batch.graphs):
        led = bctx.ledgers[b]
        led.record_shuffle("IsInMIS", g.n * 4, seconds=dt / B)
        qn, qd, it = int(q0[b]), int(q1[b]), int(iters[b])
        queries = qd if caching else qn
        led.record_queries(queries, queries * 8, waves=it,
                           deduped_away=(qn - qd) if caching else 0)
        status = status_h[b, :g.n]
        assert not (status == UNKNOWN).any()
        outs.append((status == IN,
                     {"fixpoint_iters": it, "queries_nodedup": qn,
                      "queries_dedup": qd,
                      "cache_savings_factor": qn / max(qd, 1),
                      "solver_cache": _cache_stat(key, hit, b)}))
    return outs


def _build_mm_solver(n: int):
    return jax.jit(jax.vmap(
        lambda u, v, rank, st0: _mm_fixpoint(u, v, rank, n, st0)))


def _mm_batched_launch(bctx, batch, eranks, caching: bool = True):
    """Shared batched greedy-MM launch (matching / mwm / vertex-cover).

    ``eranks`` is one unpadded rank array per graph (the Corollary-4.1
    injection point); padding edges start OUT so they never join or block.
    The compiled fixpoint is shared across every problem that rides it —
    the cache key is scoped to ``"matching"``, not the caller's name.
    """
    B, nb, mb = len(batch), batch.n_bucket, batch.m_bucket
    u = batch.edges[:, :, 0]
    v = batch.edges[:, :, 1]
    ranks = np.full((B, mb), np.inf, np.float32)
    for b, er in enumerate(eranks):
        ranks[b, :er.shape[0]] = er
    estatus0 = np.where(batch.edge_mask, np.int32(UNKNOWN),
                        np.int32(OUT)).astype(np.int32)
    for b, g in enumerate(batch.graphs):
        bctx.ledgers[b].record_shuffle("SortEdges+WriteKV",
                                       nbytes_of(g.edges) * 2)
    key = ("matching", bctx.backend_name, nb, mb)
    solver, hit = bctx.cache.get_or_build(
        key, lambda: _build_mm_solver(nb), occupants=B)
    t0 = time.perf_counter()
    estatus_b, iters_b, q0_b, q1_b, steps_b, steps_run_b = solver(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(ranks),
        jnp.asarray(estatus0))
    keys = np.broadcast_to(np.arange(mb, dtype=np.int32), (B, mb))
    out_b = bctx.dht.lookup_many(estatus_b, keys, ledgers=bctx.ledgers,
                                 key_mask=batch.edge_mask)
    estatus_h, iters, q0, q1, steps_h, steps_run_h = harvest_many(
        bctx.ledgers, (out_b, iters_b, q0_b, q1_b, steps_b, steps_run_b))
    estatus_h = np.asarray(estatus_h)
    dt = time.perf_counter() - t0
    outs = []
    for b, g in enumerate(batch.graphs):
        led = bctx.ledgers[b]
        led.record_shuffle("IsInMM", g.m, seconds=dt / B)
        qn, qd, it = int(q0[b]), int(q1[b]), int(iters[b])
        queries = qd if caching else qn
        led.record_queries(queries, queries * 12, waves=it,
                           deduped_away=(qn - qd) if caching else 0)
        estatus = estatus_h[b, :g.m]
        outs.append((estatus == IN,
                     {"fixpoint_iters": it, "queries_nodedup": qn,
                      "queries_dedup": qd, "erank": eranks[b],
                      **_lane_stats(steps_h[b], steps_run_h[b], "mm"),
                      "solver_cache": _cache_stat(key, hit, b)}))
    return outs


@batched_impl("matching")
def mm_ampc_batched(bctx, batch, caching: bool = True):
    """Batched greedy maximal matching over per-graph random edge ranks."""
    eranks = []
    for g in batch.graphs:
        rng = np.random.default_rng(bctx.seed)
        eranks.append(rng.permutation(g.m).astype(np.float32))
    return _mm_batched_launch(bctx, batch, eranks, caching=caching)


@batched_impl("weighted-matching")
def mwm_greedy_ampc_batched(bctx, batch, caching: bool = True):
    """Batched 1/2-approx MWM: decreasing-weight eranks into the MM launch."""
    eranks = []
    for g in batch.graphs:
        rng = np.random.default_rng(bctx.seed)
        tie = rng.permutation(g.m).astype(np.float64) / max(g.m, 1)
        order = np.argsort(np.lexsort((tie, -g.weights.astype(np.float64))))
        eranks.append(order.astype(np.float32))
    outs = _mm_batched_launch(bctx, batch, eranks, caching=caching)
    return [(in_mm, {"weight": float(g.weights[in_mm].sum()), **st})
            for g, (in_mm, st) in zip(batch.graphs, outs)]


@batched_impl("vertex-cover")
def vertex_cover_2approx_batched(bctx, batch, caching: bool = True):
    """Batched 2-approx vertex cover: endpoints of the batched MM."""
    outs = mm_ampc_batched(bctx, batch, caching=caching)
    results = []
    for g, (in_mm, st) in zip(batch.graphs, outs):
        cover = np.zeros(g.n, bool)
        cover[g.edges[in_mm, 0]] = True
        cover[g.edges[in_mm, 1]] = True
        results.append((cover, {"cover_size": int(cover.sum()), **st}))
    return results


def _build_msf_sparse_solver(ntb: int, mtb: int, mb: int, capacity: int):
    """Vmapped sparse-MSF pipeline for one ternarized bucket shape.

    ``capacity`` is the bucket-max Prim budget: every lane shares the
    compiled buffer size while stopping at its own traced ``budget``
    (bit-identical per ``truncated_prim_capped``).  ``mtb`` is the
    bucket's tern edge capacity (the size of Prim's found-edge mask);
    ``mb`` its *original* edge capacity — the Borůvka mask is over
    original edge ids (``teid``), exactly like the sequential path."""
    def one(nbr, nbw, nbe, rank, budget, nmask, tu, tv, tw, teid, emask):
        # the lane's real tern vertices are the first nmask.sum()
        found, hooks, cases, queries, steps, steps_run = \
            truncated_prim_capped(nbr, nbw, nbe, rank, budget, capacity, mtb,
                                  nmask.sum())
        # padded tern vertices exhaust on their first frontier pop; mask
        # their unit query out of the per-graph total
        q_sum = jnp.where(nmask, queries, 0).sum()
        parent = jnp.where(hooks >= 0, hooks,
                           jnp.arange(ntb, dtype=jnp.int32))
        roots, jump_iters = pointer_jump(parent)
        cu, cv, cw, ceid, cvalid, live = contract_edges(
            tu, tv, tw, teid, emask, roots)
        dmask, _, phases = boruvka_core(cu, cv, cw, ceid, cvalid, ntb, mb)
        return (dmask.astype(jnp.int32), found, q_sum, jump_iters,
                live, phases, cases, steps, steps_run)

    return jax.jit(jax.vmap(one))


def _build_msf_dense_solver(nb: int, mb: int):
    def one(u, v, w, emask):
        eid = jnp.arange(mb, dtype=jnp.int32)
        dmask, _, phases = boruvka_core(u, v, w, eid, emask, nb, mb)
        return dmask.astype(jnp.int32), phases

    return jax.jit(jax.vmap(one))


@batched_impl("msf")
def msf_ampc_batched(bctx, batch, skip_ternarize_if_dense: bool = True):
    """Batched MSF: lanes split by the sequential dense/sparse predicate.

    Sparse lanes run one vmapped truncated-Prim -> pointer-jump ->
    contract -> Borůvka launch over a shared :func:`ternarize_batch`
    bucket; dense lanes (``m >= n^(1+eps/2)``) run one vmapped Borůvka
    launch, mirroring the sequential Proposition-3.1 shortcut.  Each lane
    pads with isolated tern vertices / invalid edges and keeps its own
    rank permutation and budget, so outputs are bit-identical to
    sequential ``solve``; per-graph ledgers mirror the sequential 5- (or
    1-) shuffle structure, and the whole bucket still materializes through
    exactly one ``harvest_many`` transfer.
    """
    B, mb = len(batch), batch.m_bucket
    eps = bctx.epsilon
    dense_set = set(
        b for b, g in enumerate(batch.graphs)
        if skip_ternarize_if_dense and g.m >= g.n ** (1.0 + eps / 2.0))
    dense_idx = sorted(dense_set)
    sparse_idx = [b for b in range(B) if b not in dense_set]

    t0 = time.perf_counter()
    sparse_extra = dense_extra = None
    if sparse_idx:
        with current_tracer().span("host:pad"):
            tb = ternarize_batch([batch.graphs[b] for b in sparse_idx])
        Bs, ntb = len(tb), tb.nt_bucket
        ranks = np.zeros((Bs, ntb), np.float32)
        budgets = np.zeros((Bs,), np.int32)
        for j, t in enumerate(tb.terns):
            nt = t.g.n
            rng = np.random.default_rng(bctx.seed)
            ranks[j, :nt] = rng.permutation(nt).astype(np.float32)
            ranks[j, nt:] = np.arange(nt, ntb, dtype=np.float32)
            budgets[j] = max(2, int(np.ceil(nt ** (eps / 2.0))))
        capacity = int(budgets.max())
        for b in sparse_idx:
            g = batch.graphs[b]
            bctx.ledgers[b].record_shuffle(
                "SortGraph", nbytes_of(g.edges, g.weights))
        skey = bctx.solver_key(batch,
                               ("sparse", ntb, tb.mt_bucket, capacity))
        ssolver, shit = bctx.cache.get_or_build(
            skey, lambda: _build_msf_sparse_solver(ntb, tb.mt_bucket, mb,
                                                   capacity),
            occupants=Bs)
        (dmask_b, found_b, q_b, jump_b, live_b, phases_b, cases_b, steps_b,
         steps_run_b) = ssolver(
            jnp.asarray(tb.nbr), jnp.asarray(tb.nbw), jnp.asarray(tb.nbe),
            jnp.asarray(ranks), jnp.asarray(budgets),
            jnp.asarray(tb.node_mask), jnp.asarray(tb.edges[:, :, 0]),
            jnp.asarray(tb.edges[:, :, 1]), jnp.asarray(tb.weights),
            jnp.asarray(tb.orig_eid), jnp.asarray(tb.edge_mask))
        # per-lane deferred traffic (prim, then pointer-jump) queued on
        # each graph's ledger before the bucket's one harvest
        for j, b in enumerate(sparse_idx):
            nt = tb.terns[j].g.n
            led = bctx.ledgers[b]
            led.record_queries_deferred(q_b[j], q_b[j] * 36, waves=1)
            led.record_queries_deferred(jump_b[j] * nt, jump_b[j] * nt * 4,
                                        waves=1)
        keys = np.broadcast_to(np.arange(mb, dtype=np.int32), (Bs, mb))
        col_b = bctx.dht.lookup_many(
            dmask_b, keys, ledgers=[bctx.ledgers[b] for b in sparse_idx],
            key_mask=batch.edge_mask[np.asarray(sparse_idx)])
        sparse_extra = (col_b, found_b, q_b, jump_b, live_b, phases_b,
                        cases_b, steps_b, steps_run_b)
    if dense_idx:
        didx = np.asarray(dense_idx)
        demask = batch.edge_mask[didx]
        dkey = bctx.solver_key(batch, ("dense",))
        dsolver, dhit = bctx.cache.get_or_build(
            dkey, lambda: _build_msf_dense_solver(batch.n_bucket, mb),
            occupants=len(dense_idx))
        dmaskd_b, dphases_b = dsolver(
            jnp.asarray(batch.edges[didx, :, 0]),
            jnp.asarray(batch.edges[didx, :, 1]),
            jnp.asarray(batch.weights[didx]), jnp.asarray(demask))
        keys = np.broadcast_to(np.arange(mb, dtype=np.int32),
                               (len(dense_idx), mb))
        dcol_b = bctx.dht.lookup_many(
            dmaskd_b, keys, ledgers=[bctx.ledgers[b] for b in dense_idx],
            key_mask=demask)
        dense_extra = (dcol_b, dphases_b)

    # the bucket's one transfer: both sub-launches' outputs and every
    # ledger's deferred counters
    sparse_h, dense_h = harvest_many(bctx.ledgers,
                                     (sparse_extra, dense_extra))
    dt = time.perf_counter() - t0

    outs = [None] * B
    if sparse_idx:
        (col_h, found_h, q_h, jump_h, live_h, phases_h, cases_h, steps_h,
         steps_run_h) = sparse_h
        col_h = np.asarray(col_h)
        found_h = np.asarray(found_h)
        cases_h = np.asarray(cases_h)
        for j, b in enumerate(sparse_idx):
            g = batch.graphs[b]
            t = tb.terns[j]
            nt = t.g.n
            led = bctx.ledgers[b]
            led.record_queries(0, 0, waves=0)
            led.record_shuffle("PrimSearch", 0)
            led.record_shuffle("PointerJump", nt * 4)
            led.record_shuffle("Contract", nbytes_of(t.g.edges, t.g.weights))
            led.record_shuffle("DenseMSF", 0, seconds=dt / B)
            mask, stats = _msf_assemble(
                t.orig_eid, g.m, col_h[j, :g.m].astype(bool),
                found_h[j, :t.g.m], q_h[j], jump_h[j], live_h[j], phases_h[j],
                cases_h[j, :nt], int(budgets[j]), nt)
            stats.update(_lane_stats(steps_h[j], steps_run_h[j]))
            stats["solver_cache"] = _cache_stat(skey, shit, j)
            outs[b] = (mask, stats)
    if dense_idx:
        dcol_h, dphases_h = dense_h
        dcol_h = np.asarray(dcol_h)
        for j, b in enumerate(dense_idx):
            g = batch.graphs[b]
            bctx.ledgers[b].record_shuffle(
                "DenseMSF", nbytes_of(g.edges, g.weights), seconds=dt / B)
            outs[b] = (dcol_h[j, :g.m].astype(bool),
                       {"phases": int(dphases_h[j]), "path": "dense",
                        "solver_cache": _cache_stat(dkey, dhit, j)})
    return outs


def _build_cc_solver(n: int):
    return jax.jit(jax.vmap(
        lambda u, v, ok: _cc_fixpoint_masked(u, v, ok, n)))


@batched_impl("connectivity")
def cc_ampc_batched(bctx, batch):
    """Batched connectivity via in-round min-label doubling (2 shuffles).

    The sequential solver runs the paper's 5-shuffle truncated-Prim
    pipeline; that pipeline's per-graph ternarized shapes do not bucket, so
    the batched path instead resolves labels by masked hash-to-min run to
    fixpoint against one snapshot.  Outputs are identical after
    canonicalization (component labels are min-vertex-id in both paths);
    the ledger reflects the 2-shuffle batched pipeline.
    """
    B, nb = len(batch), batch.n_bucket
    u = batch.edges[:, :, 0]
    v = batch.edges[:, :, 1]
    for b, g in enumerate(batch.graphs):
        bctx.ledgers[b].record_shuffle("SortGraph+WriteKV",
                                       nbytes_of(g.edges))
    key = bctx.solver_key(batch)
    solver, hit = bctx.cache.get_or_build(
        key, lambda: _build_cc_solver(nb), occupants=B)
    t0 = time.perf_counter()
    labels_b, iters_b, q0_b, q1_b = solver(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(batch.edge_mask))
    keys = np.broadcast_to(np.arange(nb, dtype=np.int32), (B, nb))
    out_b = bctx.dht.lookup_many(labels_b, keys, ledgers=bctx.ledgers,
                                 key_mask=batch.node_mask)
    labels_h, iters, q0, q1 = harvest_many(
        bctx.ledgers, (out_b, iters_b, q0_b, q1_b))
    labels_h = np.asarray(labels_h)
    dt = time.perf_counter() - t0
    outs = []
    for b, g in enumerate(batch.graphs):
        led = bctx.ledgers[b]
        led.record_shuffle("LabelFixpoint", g.n * 4, seconds=dt / B)
        qn, qd, it = int(q0[b]), int(q1[b]), int(iters[b])
        led.record_queries(qd, qd * 8, waves=it, deduped_away=qn - qd)
        labels = _canonicalize(labels_h[b, :g.n].astype(np.int64))
        outs.append((labels,
                     {"label_prop_iters": it, "queries": qd,
                      "queries_nodedup": qn,
                      "num_components": int(len(np.unique(labels))),
                      "solver_cache": _cache_stat(key, hit, b)}))
    return outs


def _build_1v2_solver(n: int, max_steps: int):
    return jax.jit(
        lambda nbr, sampled: _walk_and_count_batch(nbr, sampled, max_steps, n))


@batched_impl("one-vs-two")
def one_vs_two_ampc_batched(bctx, batch, p: float = 1.0 / 64,
                            max_steps: Optional[int] = None):
    """Batched 1-vs-2-cycle: one vmapped walk launch per bucket.

    Padding vertices self-loop and are marked sampled, so each contributes
    exactly 2 walk steps and 1 component — both subtracted per graph.  The
    static walk budget is the bucket maximum of the per-graph budgets (it
    only bounds the in-round chase; successful walks stop at the next
    sample regardless), and is part of the solver cache key.
    """
    B, nb = len(batch), batch.n_bucket
    nbrs = np.zeros((B, nb, 2), np.int32)
    sampled = np.zeros((B, nb), bool)
    n_samples = np.zeros(B, np.int64)
    ms = 1
    for b, g in enumerate(batch.graphs):
        nbrs[b, :g.n] = cycle_adjacency(g)
        pads = np.arange(g.n, nb, dtype=np.int32)
        nbrs[b, g.n:, 0] = pads
        nbrs[b, g.n:, 1] = pads
        rng = np.random.default_rng(bctx.seed)
        s = rng.random(g.n) < p
        if not s.any():
            s[rng.integers(g.n)] = True
        sampled[b, :g.n] = s
        sampled[b, g.n:] = True
        n_samples[b] = int(s.sum())
        ms = max(ms, max_steps or
                 int(min(g.n + 1, np.ceil(8 * np.log(max(g.n, 2)) / p))))
        bctx.ledgers[b].record_shuffle("WriteKV", nbytes_of(g.edges))
    key = bctx.solver_key(batch, ("max_steps", ms))
    solver, hit = bctx.cache.get_or_build(
        key, lambda: _build_1v2_solver(nb, ms), occupants=B)
    t0 = time.perf_counter()
    ncomp_b, steps_b, ok_b = solver(jnp.asarray(nbrs), jnp.asarray(sampled))
    ncomp, steps, ok = harvest_many(bctx.ledgers, (ncomp_b, steps_b, ok_b))
    ncomp, steps, ok = np.asarray(ncomp), np.asarray(steps), np.asarray(ok)
    dt = time.perf_counter() - t0
    outs = []
    for b, g in enumerate(batch.graphs):
        if not bool(ok[b]):
            raise RuntimeError("walk budget exceeded; increase p or "
                               f"max_steps (graph {batch.indices[b]})")
        n_pad = nb - g.n
        real_steps = int(steps[b]) - 2 * n_pad
        led = bctx.ledgers[b]
        led.record_shuffle("SampleWalk", int(n_samples[b]) * 4,
                           seconds=dt / B)
        led.record_queries(real_steps, real_steps * 12, waves=1)
        outs.append((int(ncomp[b]) - n_pad,
                     {"samples": int(n_samples[b]),
                      "walk_steps": real_steps, "max_steps": ms,
                      "solver_cache": _cache_stat(key, hit, b)}))
    return outs
