"""Round / query / byte accounting for AMPC and MPC executions.

The paper measures (Table 3, Fig 3, Fig 9):
  * shuffles  — materialized rounds (Flume stages writing to durable storage);
  * bytes shuffled — data written by shuffles;
  * DHT communication — bytes of key-value store queries + answers;
  * query count — number of KV lookups.

Here a "shuffle" is a materialized jitted-program launch whose outputs are
committed (and, under the fault-tolerant runtime, checkpointed).  Adaptive
in-round query waves performed via ``lax.while_loop`` count queries/DHT bytes
but not shuffles — exactly the AMPC accounting.  MPC baselines call
``ledger.shuffle`` once per phase instead.

Observability wiring (``repro.obs``): a ledger may carry a ``tracer`` and a
``metrics`` registry.  Every shuffle then becomes a span (named
``shuffle:<name>``, carrying its bytes) and every counter update lands in
the engine-wide metric series (``shuffles_total``, ``dht_queries_total``,
…) labeled by ``algorithm``.  Both default to disabled no-ops, so a bare
``RoundLedger`` behaves exactly as before.  Beside the shuffles, a traced
ledger opens ``host:<stage>`` spans around the solve's host work
(:meth:`RoundLedger.host`) and a ``device:wait`` span around each harvest
transfer, where the host blocks on the device; neither counts anything.

Raw-string event accumulation is gated behind ``record_events``: the
structured trace supersedes the strings, and long-lived engines serving
``solve_many`` traffic must not grow an unbounded list per solve (the
engine creates bucket-loop ledgers with ``record_events=False``).

Deferred (device-resident) accounting: a ledger created with
``deferred=True`` queues DHT-traffic records on the device instead of
host-syncing per lookup.  ``ShardedDHT`` and the solvers hand
:meth:`RoundLedger.record_queries_deferred` raw device scalars
(``n_unique``, overflow counts, iteration counters) without calling
``device_get``/``int()`` on them; the engine materializes every pending
record — together with the solver outputs — in **one** ``jax.device_get``
per solve (:meth:`RoundLedger.harvest`) or per ``solve_many`` bucket
(:func:`harvest_many`).  Harvest folds each record through the same
counter/trace/metrics apply path the eager ``record_queries`` uses, with
``dht_queries`` events back-filled onto the span that was open at record
time, so the resulting ledger and trace are bit-identical to the eager
path.  A bare ``RoundLedger()`` keeps ``deferred=False`` and behaves
exactly as before: counters readable immediately after every lookup.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

from ..obs.trace import NOOP_SPAN, current_tracer

# Test hook for the one-harvest-per-solve rule: when set, called with the
# ledger (or ledger list) each time a harvest performs its single
# ``jax.device_get``.  Tests install a counting callback to assert a warm
# solve syncs exactly once.
HARVEST_HOOK: Any = None


def _device_wait(tracer):
    """The span ``device:wait`` around one transfer to the host: the host
    blocked on the device.  The shared no-op span without a tracer."""
    if tracer is not None and tracer.enabled:
        return tracer.span("device:wait")
    return NOOP_SPAN


def _get_each(extra, tracer):
    """``extra``'s host copy, one blocking transfer per leaf (the eager
    ledgers' sync pattern), each under its own ``device:wait``."""
    import jax

    leaves, treedef = jax.tree.flatten(extra)
    host = []
    for leaf in leaves:
        with _device_wait(tracer):
            host.append(jax.device_get(leaf))
    return jax.tree.unflatten(treedef, host)


class DeviceCounters:
    """Pending on-device DHT-traffic records for one ledger.

    Each hot-path call queues one record — five scalars (queries, nbytes,
    waves, deduped_away, overflow), any of which may still be an unread
    device array — plus the tracer span open at record time.  Nothing
    touches the host until :meth:`RoundLedger.harvest` /
    :func:`harvest_many` drains every record in a single transfer.

    Records are kept individually (rather than folded into one running
    device vector) so harvest can replay them one-by-one through the
    eager apply path: per-wave ``dht_queries`` trace events and metric
    increments come out identical to eager mode, not collapsed into one.
    """

    __slots__ = ("records",)

    def __init__(self):
        # [((queries, nbytes, waves, deduped_away, overflow), span)]
        self.records: List = []

    def add(self, record, span=None) -> None:
        self.records.append((record, span))

    def drain(self) -> List:
        records, self.records = self.records, []
        return records

    def __len__(self):
        return len(self.records)

    def __repr__(self):
        return f"DeviceCounters(pending={len(self.records)})"


@dataclasses.dataclass
class RoundLedger:
    algorithm: str = ""
    shuffles: int = 0
    bytes_shuffled: int = 0
    dht_queries: int = 0
    dht_bytes: int = 0
    dht_query_waves: int = 0
    dedup_savings: int = 0  # queries avoided by the caching optimization
    dht_overflows: int = 0  # routed-router capacity overflows (0 = exact)
    wall_time_s: float = 0.0
    phase_times: Dict[str, float] = dataclasses.field(default_factory=dict)
    events: List[str] = dataclasses.field(default_factory=list)
    # observability hooks (repro.obs); None => disabled
    tracer: Any = dataclasses.field(repr=False, compare=False, default=None)
    metrics: Any = dataclasses.field(repr=False, compare=False, default=None)
    record_events: bool = dataclasses.field(compare=False, default=True)
    # deferred accounting: queue device scalars, harvest once per solve
    deferred: bool = dataclasses.field(compare=False, default=False)
    device: DeviceCounters = dataclasses.field(
        repr=False, compare=False, default_factory=DeviceCounters)

    # -- shuffle (materialized round) -------------------------------------
    @contextlib.contextmanager
    def shuffle(self, name: str, nbytes: int = 0):
        tracer = self.tracer
        t0 = time.perf_counter()
        if tracer is not None and tracer.enabled:
            with tracer.span(f"shuffle:{name}", algorithm=self.algorithm,
                             nbytes=int(nbytes)):
                yield
        else:
            yield
        self._count_shuffle(name, nbytes, time.perf_counter() - t0)

    def host(self, stage: str):
        """A span ``host:<stage>`` around host work of the solve: rank
        draws, ternarize, adjacency, host-to-device copies, assembly after
        the harvest.  It counts nothing (no shuffle, no ``phase_times``);
        without a tracer it is the shared no-op span."""
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            return tracer.span(f"host:{stage}")
        return NOOP_SPAN

    def record_shuffle(self, name: str, nbytes: int = 0,
                       seconds: float = 0.0):
        """Record one materialized round without timing a ``with`` block.

        Used by batched (``solve_many``) launches, where one physical launch
        serves many per-graph ledgers: each ledger records its own shuffle
        entry with its share of the bytes and wall time.  With a tracer the
        share becomes a retroactive span under the current open span.
        """
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            tracer.record_span(f"shuffle:{name}", dur_s=seconds,
                               algorithm=self.algorithm, nbytes=int(nbytes))
        self._count_shuffle(name, nbytes, seconds)

    def _count_shuffle(self, name: str, nbytes: int, seconds: float):
        self.shuffles += 1
        self.bytes_shuffled += int(nbytes)
        self.wall_time_s += seconds
        self.phase_times[name] = self.phase_times.get(name, 0.0) + seconds
        if self.record_events:
            self.events.append(f"shuffle:{name}:{nbytes}B:{seconds:.4f}s")
        if self.metrics is not None:
            self.metrics.counter(
                "shuffles_total", labelnames=("algorithm",)).inc(
                    1, algorithm=self.algorithm)
            self.metrics.counter(
                "bytes_shuffled_total", labelnames=("algorithm",)).inc(
                    int(nbytes), algorithm=self.algorithm)

    # -- DHT traffic -------------------------------------------------------
    def record_queries(self, n_queries: int, nbytes: int, waves: int = 1,
                       deduped_away: int = 0, overflow: int = 0):
        """Eagerly record one wave of DHT traffic (host values)."""
        self._apply_queries(int(n_queries), int(nbytes), int(waves),
                            int(deduped_away), int(overflow))

    def record_queries_deferred(self, n_queries, nbytes, waves=1,
                                deduped_away=0, overflow=0):
        """Record DHT traffic without leaving the device.

        Arguments may be raw device scalars; on a ``deferred=True`` ledger
        they are queued untouched and materialized later by
        :meth:`harvest` in one transfer.  On an eager ledger this
        degrades to an immediate :meth:`record_queries` (one transfer
        now), preserving bare-ledger semantics — counters are readable
        right after the lookup that produced them.
        """
        record = (n_queries, nbytes, waves, deduped_away, overflow)
        if not self.deferred:
            import jax  # host-sync: ok — eager ledger, sync by contract
            self._apply_queries(*(int(x) for x in jax.device_get(record)))
            return
        span = None
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            span = tracer.current_span()
        self.device.add(record, span)

    def harvest(self, extra=None):
        """Materialize every pending deferred record in one transfer.

        ``extra`` is an optional pytree of device arrays the caller wants
        pulled in the same ``jax.device_get`` (solver outputs, iteration
        counters); its host copy is returned.  This is the *one* host
        sync a deferred solve performs, under a ``device:wait`` span;
        :data:`HARVEST_HOOK` fires once per actual transfer so tests can
        count syncs.  With nothing pending and no ``extra`` the call is
        free — no transfer at all.

        On an eager (``deferred=False``) ledger this instead mirrors the
        pre-deferral sync pattern: one blocking ``jax.device_get`` per
        ``extra`` leaf, exactly like the per-value ``int(device_get(...))``
        / ``np.asarray(device_get(...))`` calls the solvers used to make.
        That keeps ``deferred_accounting=False`` a faithful "today's hot
        path" baseline for the ``dht_hot_path`` benchmark rather than a
        half-deferred hybrid that batches the final transfer anyway.
        """
        import jax

        records = self.device.drain()
        if not records and extra is None:
            return None
        if HARVEST_HOOK is not None:
            HARVEST_HOOK(self)
        if not self.deferred and extra is not None:
            # records were already applied eagerly at record time, so only
            # extra remains; transfer leaf by leaf (seed sync pattern)
            return _get_each(extra, self.tracer)
        with _device_wait(self.tracer):
            host_records, host_extra = jax.device_get(
                ([rec for rec, _ in records], extra))
        for host_rec, (_, span) in zip(host_records, records):
            self._apply_queries(*(int(x) for x in host_rec), span=span)
        return host_extra

    def _apply_queries(self, n_queries: int, nbytes: int, waves: int,
                       deduped_away: int, overflow: int, span=None):
        """Fold one wave of host-side counts into counters/trace/metrics.

        ``span`` is the span that was open when a deferred record was
        queued: the ``dht_queries`` event is back-filled onto it so a
        harvested trace matches the eager one event-for-event.
        """
        self.dht_queries += n_queries
        self.dht_bytes += nbytes
        self.dht_query_waves += waves
        self.dedup_savings += deduped_away
        self.dht_overflows += overflow
        tracer = self.tracer
        if tracer is not None and tracer.enabled:
            kw = dict(queries=n_queries, nbytes=nbytes, waves=waves,
                      deduped_away=deduped_away, overflow=overflow)
            if span is not None:
                span.event("dht_queries", **kw)
            else:
                tracer.event("dht_queries", **kw)
        m = self.metrics
        if m is not None:
            labels = {"labelnames": ("algorithm",)}
            kw = {"algorithm": self.algorithm}
            m.counter("dht_queries_total", **labels).inc(n_queries, **kw)
            m.counter("dht_bytes_total", **labels).inc(nbytes, **kw)
            m.counter("dht_query_waves_total", **labels).inc(waves, **kw)
            if deduped_away:
                m.counter("dedup_savings_total", **labels).inc(
                    deduped_away, **kw)
            if overflow:
                m.counter("dht_overflows_total", **labels).inc(
                    overflow, **kw)

    def summary(self) -> Dict:
        if self.device.records:  # safety net: a forgotten harvest
            self.harvest()
        return {
            "algorithm": self.algorithm,
            "shuffles": self.shuffles,
            "bytes_shuffled": self.bytes_shuffled,
            "dht_queries": self.dht_queries,
            "dht_bytes": self.dht_bytes,
            "dht_query_waves": self.dht_query_waves,
            "dedup_savings": self.dedup_savings,
            "dht_overflows": self.dht_overflows,
            "wall_time_s": round(self.wall_time_s, 4),
            "phase_times": {k: round(v, 4) for k, v in self.phase_times.items()},
        }


def harvest_many(ledgers: Sequence[Optional[RoundLedger]], extra=None):
    """Harvest several deferred ledgers in one ``jax.device_get``.

    The ``solve_many`` counterpart of :meth:`RoundLedger.harvest`: one
    bucket launch accumulates pending records on every per-graph ledger,
    and the engine drains them all — plus the batched outputs in
    ``extra`` — with a single transfer.  Returns ``extra``'s host copy.
    Bucket ledgers carry no tracer, so the transfer's ``device:wait`` span
    goes to the tracer of the span open on this thread (the ``bucket``).
    """
    import jax

    ledgers = [led for led in ledgers if led is not None]
    pending = [led.device.drain() for led in ledgers]
    if not any(pending) and extra is None:
        return None
    if HARVEST_HOOK is not None:
        HARVEST_HOOK(ledgers)
    if not any(pending) and not any(led.deferred for led in ledgers):
        # all-eager bucket: mirror the pre-deferral per-leaf sync pattern
        # (see RoundLedger.harvest) so eager solve_many stays a faithful
        # baseline
        return _get_each(extra, current_tracer())
    with _device_wait(current_tracer()):
        host_pending, host_extra = jax.device_get(
            ([[rec for rec, _ in records] for records in pending], extra))
    for led, host_records, records in zip(ledgers, host_pending, pending):
        for host_rec, (_, span) in zip(host_records, records):
            led._apply_queries(*(int(x) for x in host_rec), span=span)
    return host_extra


def nbytes_of(*arrays) -> int:
    total = 0
    for a in arrays:
        if a is None:
            continue
        total += a.size * a.dtype.itemsize
    return int(total)
