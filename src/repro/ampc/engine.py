"""``AmpcEngine`` — one entry point for every AMPC algorithm in the repo.

    from repro.ampc import AmpcEngine
    eng = AmpcEngine(dht_backend="local", epsilon=0.5, seed=0)
    res = eng.solve(graph, "mis")
    res.output                  # bool (n,) membership mask
    res.ledger["shuffles"]      # Table-3 materialized round count
    res.stats                   # algorithm-specific stats, stable key names

The engine owns the three things every pre-engine call site threaded by
hand: the ``RoundLedger`` (created per solve, summarized on the result),
the DHT backend (local gather vs routed all_to_all — pluggable, identical
accounting), and the seed/epsilon defaults.  Problems are resolved through
:mod:`repro.ampc.registry`, so a new algorithm becomes engine-callable by
decorating its adapter with ``@problem(...)``.

For serving many graphs per call, :meth:`AmpcEngine.solve_many` pads the
fleet into power-of-two shape buckets and runs each bucket as one vmapped
launch, memoizing the traced solver per ``(problem, backend, bucket)`` in
an engine-level :class:`~repro.ampc.cache.SolverCache`
(see :meth:`AmpcEngine.cache_info`).

Observability (``repro.obs``): ``AmpcEngine(trace=True)`` records every
solve as a span tree (``AmpcResult.trace``; export with
``repro.obs.export.write_chrome_trace``), and the engine reports counters
and latency histograms into a metrics registry —
:meth:`AmpcEngine.metrics_report` renders it.  Both hooks default to
disabled/no-op paths that cost essentially nothing per solve.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..core.rounds import RoundLedger
from ..graph import batching
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import registry
from .async_engine import AsyncEngineMixin
from .backends import DhtBackend, resolve_backend
from .cache import CacheInfo, SolverCache
from .session import GraphSession


def _field_eq(a, b) -> bool:
    """Equality that tolerates numpy arrays nested in outputs/stats."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return np.array_equal(a, b)
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and \
            all(_field_eq(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and \
            all(_field_eq(x, y) for x, y in zip(a, b))
    return a == b


@dataclasses.dataclass(eq=False)
class AmpcResult:
    """Uniform result of ``AmpcEngine.solve`` / ``AmpcEngine.solve_many``.

    ``output`` follows the problem's declared kind: ``vertex_mask`` (bool
    (n,)), ``edge_mask`` (bool (m,)), ``labels`` (int (n,)), or ``count``
    (int).  ``ledger`` is the ``RoundLedger.summary()`` dict —
    ``ledger["shuffles"]`` is the paper's Table-3 round count.

    ``raw_ledger`` keeps the live ledger for phase-time inspection; it is
    excluded from equality (``compare=False``), and ``__eq__`` compares the
    remaining fields with array-aware semantics, so results holding numpy
    outputs compare cleanly instead of raising.

    >>> from repro.ampc import AmpcEngine
    >>> from repro.graph import generators as gen
    >>> res = AmpcEngine(seed=0).solve(gen.erdos_renyi(64, 3.0, seed=1), "mis")
    >>> res.problem, res.model, res.backend
    ('mis', 'ampc', 'local')
    >>> res.shuffles == res.ledger["shuffles"] == 2
    True
    >>> bool(res.output.any())
    True
    """

    problem: str
    model: str                      # "ampc" | "mpc"
    backend: str                    # DHT backend name used for the solve
    output: Any
    stats: Dict[str, Any]
    ledger: Dict[str, Any]
    wall_time_s: float
    raw_ledger: Optional[RoundLedger] = dataclasses.field(
        repr=False, compare=False, default=None)
    # obs.trace.Span for this solve when the engine traces (compare=False:
    # outputs stay bit-identical with tracing on vs off, and == agrees)
    trace: Optional[Any] = dataclasses.field(
        repr=False, compare=False, default=None)

    @property
    def shuffles(self) -> int:
        return self.ledger["shuffles"]

    def __eq__(self, other):
        if not isinstance(other, AmpcResult):
            return NotImplemented
        return all(_field_eq(getattr(self, f.name), getattr(other, f.name))
                   for f in dataclasses.fields(self) if f.compare)

    def __repr__(self):
        return (f"AmpcResult(problem={self.problem!r}, model={self.model!r}, "
                f"backend={self.backend!r}, shuffles={self.shuffles}, "
                f"dht_queries={self.ledger['dht_queries']}, "
                f"wall_time_s={self.wall_time_s:.3f})")


@dataclasses.dataclass
class SolveContext:
    """Cross-cutting state handed to every registered solver."""

    ledger: RoundLedger
    dht: DhtBackend
    seed: int
    epsilon: float
    mesh: Any = None


@dataclasses.dataclass
class BatchSolveContext:
    """Cross-cutting state handed to a batch adapter for one bucket launch.

    ``ledgers`` holds one ``RoundLedger`` per graph in the batch (batch
    order): the single physical launch is attributed per graph — each ledger
    records the bucket's shuffle structure with that graph's own bytes and
    its own share of the DHT query counts (split by mask).
    """

    ledgers: List[RoundLedger]
    dht: DhtBackend
    seed: int
    epsilon: float
    cache: SolverCache
    problem: str = ""
    backend_name: str = ""
    mesh: Any = None

    def solver_key(self, batch, *extra):
        """Cache key for this bucket's compiled solver.  ``extra`` captures
        options that change the traced program (e.g. a static walk budget)."""
        return (self.problem, self.backend_name,
                batch.n_bucket, batch.m_bucket, *extra)


class AmpcEngine(AsyncEngineMixin):
    """Session object for AMPC graph solves.

    Parameters
    ----------
    mesh:         optional jax mesh handed to the routed backend (a 1-D mesh
                  over all devices is built when omitted).
    dht_backend:  ``"local"`` | ``"routed"`` | a ``DhtBackend`` instance.
    epsilon:      the paper's space exponent (per-machine space n^ε).
    seed:         default randomness for rank permutations / sampling.
    trace:        ``True`` → record every solve as a span tree on a fresh
                  tracer (``engine.tracer``); ``False`` → tracing off; a
                  ``repro.obs.Tracer`` instance to share one tracer across
                  engines; ``None`` (default) → the process-default tracer
                  (no-op unless a harness installed one, e.g.
                  ``benchmarks.run --trace``).
    metrics:      a ``repro.obs.MetricsRegistry``, ``False`` to disable, or
                  ``None`` (default) for the process-wide registry.
    record_events:force the ``RoundLedger.events`` raw-string log on/off for
                  every solve; ``None`` (default) keeps it on for ``solve``
                  and **off inside ``solve_many`` bucket loops**, so
                  long-lived serving sessions don't accumulate strings.
    max_workers:  size of the async worker pool behind ``engine.submit``
                  (lazy: no threads exist until the first submit).
    queue_depth:  bound on the submit queue before ``submit`` blocks for
                  backpressure; default ``2 * max_workers``.
    serialize_launches: hold one engine-wide lock around every device
                  launch, so concurrent async solves overlap host-side
                  phases but never race on the device (the AMPC accounting
                  model runs one materialized round at a time).  Disable
                  only for experiments on multi-controller setups.
    deferred_accounting: ``True`` (default) → per-solve ledgers queue DHT
                  counters on the device and the solve performs exactly one
                  ``jax.device_get`` harvest at result materialization
                  (once per bucket under ``solve_many``); counter values
                  and traces are bit-identical to the eager path.
                  ``False`` → the pre-deferral behavior: every lookup
                  syncs its counts to the host immediately.

    >>> from repro.ampc import AmpcEngine
    >>> from repro.graph import generators as gen
    >>> eng = AmpcEngine(dht_backend="local", epsilon=0.5, seed=0)
    >>> fleet = [gen.erdos_renyi(48, 3.0, seed=s) for s in range(3)]
    >>> results = eng.solve_many(fleet, "mis")
    >>> [r.problem for r in results]
    ['mis', 'mis', 'mis']
    >>> sequential = eng.solve(fleet[0], "mis")
    >>> bool((results[0].output == sequential.output).all())
    True
    >>> eng.cache_info().misses >= 1
    True

    Tracing is one flag away; the per-solve span lands on the result:

    >>> eng = AmpcEngine(seed=0, trace=True)
    >>> res = eng.solve(gen.erdos_renyi(32, 2.0, seed=2), "mis")
    >>> res.trace.name, res.trace.attributes["problem"]
    ('solve', 'mis')
    >>> [c.name for c in res.trace.children]  # doctest: +NORMALIZE_WHITESPACE
    ['wait:launch_lock', 'host:rank', 'shuffle:DirectEdges+WriteKV',
     'shuffle:IsInMIS', 'host:assemble']

    Async serving (``submit`` -> future) and snapshot reuse on one graph
    (``session``; see ``repro.ampc.session``):

    >>> with AmpcEngine(seed=0) as eng:
    ...     g = gen.erdos_renyi(48, 3.0, seed=3)
    ...     fut = eng.submit(g, "mis")
    ...     async_res = fut.result(timeout=60)
    ...     sess = eng.session(g)
    ...     cold = sess.solve("mis")
    ...     warm = sess.solve("matching")
    >>> bool((async_res.output == cold.output).all())
    True
    >>> cold.stats["snapshot"]["hit"], warm.stats["snapshot"]["hit"]
    (False, True)
    >>> warm.ledger["shuffles"]   # the WriteKV shuffle was skipped
    1
    """

    def __init__(self, mesh=None, dht_backend="local", epsilon: float = 0.5,
                 seed: int = 0, *, trace=None, metrics=None,
                 record_events: Optional[bool] = None, max_workers: int = 4,
                 queue_depth: Optional[int] = None,
                 serialize_launches: bool = True,
                 deferred_accounting: bool = True):
        self.mesh = mesh
        self.dht = resolve_backend(dht_backend, mesh=mesh)
        self.epsilon = float(epsilon)
        self.seed = int(seed)
        self.deferred_accounting = bool(deferred_accounting)
        self.tracer = obs_trace.as_tracer(trace)
        self.metrics = obs_metrics.as_registry(metrics)
        self.record_events = record_events
        self._solver_cache = SolverCache(metrics=self.metrics)
        # snapshot store for GraphSessions; separate from the solver cache
        # so solver hit/miss accounting stays comparable across versions
        self._snapshot_cache = SolverCache()
        self._launch_lock = (threading.RLock() if serialize_launches
                             else contextlib.nullcontext())
        self._init_async(max_workers, queue_depth)

    # ------------------------------------------------------------------
    def _ledger(self, spec, record_events: bool) -> RoundLedger:
        tracer = self.tracer
        return RoundLedger(
            f"{spec.model}_{spec.name}",
            tracer=tracer if tracer.enabled else None,
            metrics=self.metrics, record_events=record_events,
            deferred=self.deferred_accounting)

    def _observe_solve(self, spec, wall: float, mode: str) -> None:
        m = self.metrics
        if m is None:
            return
        m.histogram("solve_latency_s",
                    labelnames=("problem", "backend")).observe(
                        wall, problem=spec.name, backend=self.dht.name)
        m.counter("solves_total",
                  labelnames=("problem", "backend", "mode")).inc(
                      1, problem=spec.name, backend=self.dht.name, mode=mode)

    @contextlib.contextmanager
    def _locked(self, tracer):
        """Hold the launch lock, its acquisition timed as the span
        ``wait:launch_lock`` (the time a solve queues behind other device
        work, which the async pool's ``queue_wait`` does not see)."""
        with tracer.span("wait:launch_lock"):
            self._launch_lock.__enter__()
        try:
            yield
        finally:
            self._launch_lock.__exit__(None, None, None)

    # ------------------------------------------------------------------
    def _validate(self, spec, graph) -> None:
        if spec.needs_weights and getattr(graph, "weights", None) is None:
            raise ValueError(
                f"problem {spec.name!r} needs edge weights; call "
                "g.with_random_weights()/g.with_degree_weights() first")
        if spec.needs_cycles and not (graph.degrees() == 2).all():
            raise ValueError(
                f"problem {spec.name!r} needs a disjoint union of cycles "
                "(every vertex must have degree 2)")

    # ------------------------------------------------------------------
    def solve(self, graph, problem: str, *, seed: Optional[int] = None,
              epsilon: Optional[float] = None,
              record_events: Optional[bool] = None, **opts) -> AmpcResult:
        """Run ``problem`` on ``graph`` and return an ``AmpcResult``.

        ``**opts`` are forwarded to the registered solver (e.g.
        ``skip_ternarize_if_dense=False`` for msf, ``p=1/64`` for
        one-vs-two).  ``seed``/``epsilon``/``record_events`` override the
        engine defaults for this solve only.
        """
        spec = registry.get(problem)
        self._validate(spec, graph)
        if record_events is None:
            record_events = self.record_events
        ledger = self._ledger(spec, True if record_events is None
                              else record_events)
        ctx = SolveContext(
            ledger=ledger, dht=self.dht,
            seed=self.seed if seed is None else int(seed),
            epsilon=self.epsilon if epsilon is None else float(epsilon),
            mesh=self.mesh)
        tracer = self.tracer
        span = None
        t0 = time.perf_counter()
        # the launch lock serializes device work across async workers; the
        # wait for it is the solve's wait:launch_lock span
        if tracer.enabled:
            with tracer.span("solve", problem=spec.name, model=spec.model,
                             backend=self.dht.name, n=int(graph.n),
                             m=int(graph.m)) as span:
                with self._locked(tracer):
                    output, stats = spec.fn(ctx, graph, **opts)
        else:
            with self._launch_lock:
                output, stats = spec.fn(ctx, graph, **opts)
        wall = time.perf_counter() - t0
        self._observe_solve(spec, wall, "solve")
        return AmpcResult(problem=spec.name, model=spec.model,
                          backend=self.dht.name, output=output, stats=stats,
                          ledger=ledger.summary(), wall_time_s=wall,
                          raw_ledger=ledger, trace=span)

    # ------------------------------------------------------------------
    def solve_many(self, graphs: Sequence[Any], problem: str, *,
                   seed: Optional[int] = None,
                   epsilon: Optional[float] = None,
                   record_events: Optional[bool] = None,
                   **opts) -> List[AmpcResult]:
        """Solve ``problem`` on a fleet of graphs, one result per graph.

        Graphs are padded into power-of-two ``(n_bucket, m_bucket)`` shape
        buckets (:mod:`repro.graph.batching`); each bucket runs as a single
        vmapped/jitted launch whose traced solver is memoized in the
        engine's :class:`SolverCache`, so repeated traffic on same-sized
        graphs skips tracing entirely.  Outputs are unpadded back to
        per-graph ``AmpcResult`` objects identical to sequential ``solve``
        outputs; ``wall_time_s`` is the bucket launch amortized over its
        occupants.

        Bucket-loop ledgers default to ``record_events=False`` (the
        structured trace supersedes the raw strings; pass
        ``record_events=True`` to keep them).  With tracing enabled each
        bucket launch is one ``bucket`` span whose per-graph ``graph[i]``
        children carry that graph's ledger attribution (phase shares from
        ``RoundLedger.record_shuffle``); ``result.trace`` points at the
        graph's own span.

        Problems without a registered batch adapter (see
        ``src/repro/ampc/README.md`` for the list) fall back to sequential
        ``solve`` calls — same results, no batching speedup.
        """
        graphs = list(graphs)
        spec = registry.get(problem)
        for g in graphs:
            self._validate(spec, g)
        if record_events is None:
            record_events = self.record_events
        rec = False if record_events is None else record_events
        if spec.batch_fn is None:
            return [self.solve(g, problem, seed=seed, epsilon=epsilon,
                               record_events=rec, **opts)
                    for g in graphs]
        tracer = self.tracer
        results: List[Optional[AmpcResult]] = [None] * len(graphs)
        root = tracer.span("solve_many", problem=spec.name,
                           backend=self.dht.name, n_graphs=len(graphs)) \
            if tracer.enabled else None
        if root is not None:
            root.__enter__()
        try:
            with tracer.span("host:pad"):
                buckets = batching.bucketize(graphs)
            for batch in buckets.values():
                self._solve_bucket(spec, batch, results, rec,
                                   seed=seed, epsilon=epsilon, **opts)
        finally:
            if root is not None:
                root.__exit__(None, None, None)
        return results

    def _solve_bucket(self, spec, batch, results, rec, *, seed, epsilon,
                      **opts) -> None:
        """One bucket launch of ``solve_many``: run, attribute, trace."""
        tracer = self.tracer
        # tracer=None on bucket ledgers: one physical launch must not emit
        # B copies of every shuffle span — the per-graph share is attached
        # retroactively below, from each ledger's phase_times.
        ledgers = [RoundLedger(f"{spec.model}_{spec.name}",
                               metrics=self.metrics, record_events=rec,
                               deferred=self.deferred_accounting)
                   for _ in range(len(batch))]
        bctx = BatchSolveContext(
            ledgers=ledgers, dht=self.dht,
            seed=self.seed if seed is None else int(seed),
            epsilon=self.epsilon if epsilon is None else float(epsilon),
            cache=self._solver_cache, problem=spec.name,
            backend_name=self.dht.name, mesh=self.mesh)
        bspan = tracer.span(
            "bucket", problem=spec.name, n_bucket=batch.n_bucket,
            m_bucket=batch.m_bucket, batch_size=len(batch)) \
            if tracer.enabled else None
        t0 = time.perf_counter()
        if bspan is not None:
            with bspan:
                with self._locked(tracer):
                    outs = spec.batch_fn(bctx, batch, **opts)
        else:
            with self._launch_lock:
                outs = spec.batch_fn(bctx, batch, **opts)
        wall = time.perf_counter() - t0
        assert len(outs) == len(batch), \
            f"batch adapter for {spec.name!r} returned {len(outs)} " \
            f"results for {len(batch)} graphs"
        per_graph_wall = wall / max(len(batch), 1)
        for slot, (idx, (output, stats)) in enumerate(
                zip(batch.indices, outs)):
            stats.setdefault("batch", {
                "bucket": batch.key, "batch_size": len(batch),
                "slot": slot})
            ledger = ledgers[slot]
            gspan = None
            if bspan is not None:
                gspan = tracer.record_span(
                    f"graph[{idx}]", dur_s=per_graph_wall, parent=bspan,
                    problem=spec.name, bucket=batch.key, slot=slot)
                for phase, secs in ledger.phase_times.items():
                    tracer.record_span(f"shuffle:{phase}", dur_s=secs,
                                       parent=gspan,
                                       algorithm=ledger.algorithm)
            self._observe_solve(spec, per_graph_wall, "solve_many")
            results[idx] = AmpcResult(
                problem=spec.name, model=spec.model,
                backend=self.dht.name, output=output, stats=stats,
                ledger=ledger.summary(),
                wall_time_s=per_graph_wall, raw_ledger=ledger,
                trace=gspan)

    # ------------------------------------------------------------------
    def session(self, graph) -> GraphSession:
        """A :class:`~repro.ampc.session.GraphSession` on ``graph``: solves
        through it share one DHT graph-KV snapshot (built on first use,
        reported in ``AmpcResult.stats["snapshot"]``)."""
        return GraphSession(self, graph)

    def cache_info(self, kind: str = "solver") -> CacheInfo:
        """Hit/miss/size counters of an engine cache.

        ``kind="solver"`` (default): the compiled-solver cache — one miss
        per solver actually traced; one hit per graph served by an
        already-traced solver (so a cold bucket of ``B`` graphs counts
        ``1`` miss and ``B - 1`` hits).  ``kind="snapshot"``: the
        GraphSession snapshot store — one miss per snapshot built, one hit
        per solve that reused it.
        """
        if kind == "solver":
            return self._solver_cache.info()
        if kind == "snapshot":
            return self._snapshot_cache.info()
        raise ValueError(
            f"kind must be 'solver' or 'snapshot', got {kind!r}")

    def clear_cache(self) -> None:
        """Drop every memoized solver and graph snapshot, and reset both
        caches' hit/miss counters."""
        self._solver_cache.clear()
        self._snapshot_cache.clear()

    def metrics_report(self) -> str:
        """Plain-text dump of this engine's metrics registry.

        One line per labeled series (``name{labels} value``); histograms
        show count/sum/percentiles.  Empty string when ``metrics=False``.
        """
        from ..obs.export import metrics_report
        return metrics_report(self.metrics)

    # ------------------------------------------------------------------
    def problems(self, model: Optional[str] = None):
        """Names of every solvable problem (optionally one model only)."""
        return registry.names(model)

    def baseline_for(self, problem: str) -> Optional[str]:
        """Name of the MPC baseline registered for an AMPC problem."""
        for spec in registry.specs("mpc"):
            if spec.baseline_of == registry.get(problem).name:
                return spec.name
        return None

    def __repr__(self):
        return (f"AmpcEngine(dht_backend={self.dht.name!r}, "
                f"epsilon={self.epsilon}, seed={self.seed})")
