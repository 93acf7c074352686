"""The spans of a solve, their identity, and truncated Prim's lane counters.

* a traced solve holds the host stages (``host:*``), the harvest's
  ``device:wait`` and the ``wait:launch_lock`` span, nested as
  ``docs/architecture.md`` draws them, every span carrying its parent's id
  and its root's id as ``request_id``;
* with tracing off no span is allocated;
* with ``annotate_device=True`` every span is one host event of a
  ``jax.profiler`` capture, carrying the span's ids as stats;
* truncated Prim's ``lane_steps`` / ``lane_steps_run`` equal a numpy
  re-count of the search loop, chunked or not, and every path that runs
  Prim reports the same ``prim_lane_steps``.
"""
import glob
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ampc import AmpcEngine
from repro.core import msf, rounds
from repro.core.ternarize import ternarize
from repro.graph import generators as gen
from repro.obs import Tracer
from repro.obs import trace as obs_trace

REPO = Path(__file__).resolve().parents[1]


def _graph(problem):
    g = gen.rmat(7, 5.0, seed=3)
    weighted = problem in ("msf", "weighted-matching")
    return g.with_random_weights(4) if weighted else g


def _solve(eng, problem):
    opts = {"skip_ternarize_if_dense": False} if problem == "msf" else {}
    return eng.solve(_graph(problem), problem, **opts)


def _parents(root):
    out = {root.span_id: None}
    for sp in root.walk():
        for c in sp.children:
            out[c.span_id] = sp
    return out


# (problem, {span name: name of the span it sits directly under})
TREES = [
    ("msf", {"wait:launch_lock": "solve",
             "host:ternarize": "shuffle:SortGraph",
             "host:adjacency": "shuffle:SortGraph",
             "host:rank": "shuffle:SortGraph",
             "host:to_device": "solve",
             "device:wait": "shuffle:DenseMSF",
             "host:assemble": "solve"}),
    ("mis", {"wait:launch_lock": "solve",
             "host:rank": "solve",
             "host:symmetric": "shuffle:DirectEdges+WriteKV",
             "host:to_device": "shuffle:DirectEdges+WriteKV",
             "device:wait": "shuffle:IsInMIS",
             "host:assemble": "solve"}),
    ("connectivity", {"wait:launch_lock": "solve",
                      "host:ternarize": "shuffle:SortGraph",
                      "host:adjacency": "shuffle:SortGraph",
                      "host:rank": "shuffle:SortGraph",
                      "host:to_device": "shuffle:PrimSearch",
                      "device:wait": "shuffle:ForestConnectivity",
                      "host:assemble": "solve"}),
    ("matching", {"wait:launch_lock": "solve",
                  "host:rank": "solve",
                  "host:to_device": "shuffle:SortEdges+WriteKV",
                  "device:wait": "shuffle:IsInMM",
                  "host:assemble": "solve"}),
    # the weight ranks' lexsort is labelled like every other rank draw
    ("weighted-matching", {"wait:launch_lock": "solve",
                           "host:rank": "solve",
                           "host:to_device": "shuffle:SortEdges+WriteKV",
                           "device:wait": "shuffle:IsInMM",
                           "host:assemble": "solve"}),
]


@pytest.mark.parametrize("problem,under", TREES, ids=[t[0] for t in TREES])
def test_traced_solve_holds_host_wait_and_lock_spans(problem, under):
    eng = AmpcEngine(seed=0, trace=Tracer())
    root = _solve(eng, problem).trace
    parents = _parents(root)
    spans = list(root.walk())
    for name, parent_name in under.items():
        found = [sp for sp in spans if sp.name == name]
        assert found, f"{problem}: no span {name}"
        assert parent_name in {parents[sp.span_id].name for sp in found}, \
            (problem, name, [parents[sp.span_id].name for sp in found])
    assert len([sp for sp in spans if sp.name == "device:wait"]) == 1
    assert len([sp for sp in spans if sp.name == "wait:launch_lock"]) == 1
    # identity: every span names its parent and its request (the root)
    assert root.parent_id is None and root.request_id == root.span_id
    for sp in spans[1:]:
        assert sp.parent_id == parents[sp.span_id].span_id
        assert sp.request_id == root.span_id
    # host spans never nest in each other
    for sp in spans:
        if sp.name.startswith("host:"):
            assert not [c for c in sp.walk() if c is not sp
                        and c.name.startswith("host:")]
            p = parents[sp.span_id]
            while p is not None:
                assert not p.name.startswith("host:"), (sp.name, p.name)
                p = parents[p.span_id]


def test_ids_are_inherited_by_retroactive_spans_and_exported():
    from repro.obs.export import span_to_dict, to_chrome_trace
    tr = Tracer()
    with tr.span("request") as root:
        with tr.span("inner") as inner:
            pass
        late = tr.record_span("late", dur_s=0.001, parent=inner)
    lone = tr.record_span("lone")
    assert (late.parent_id, late.request_id) == (inner.span_id, root.span_id)
    assert (lone.parent_id, lone.request_id) == (None, lone.span_id)
    args = {e["name"]: e["args"] for e in to_chrome_trace(tr)["traceEvents"]
            if e["ph"] == "X"}
    assert args["late"] == {"span_id": late.span_id,
                            "parent_id": inner.span_id,
                            "request_id": root.span_id}
    assert span_to_dict(inner)["request_id"] == root.span_id
    # an event with no open span is dropped, not kept
    tr.event("nowhere")
    assert not hasattr(tr, "orphan_events")


@pytest.mark.parametrize("problem", ["msf", "mis", "connectivity"])
def test_noop_tracer_solve_allocates_no_span(monkeypatch, problem):
    eng = AmpcEngine(seed=0, trace=False)
    _solve(eng, problem)                         # compile outside the count
    made = []
    init = obs_trace.Span.__init__

    def counting(self, *a, **k):
        made.append(a[1] if len(a) > 1 else k.get("name"))
        init(self, *a, **k)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting)
    res = _solve(eng, problem)
    assert made == [] and res.trace is None
    led = rounds.RoundLedger("x")
    assert led.host("rank") is obs_trace.NOOP_SPAN


def _profile_events(log_dir):
    from jax.profiler import ProfileData
    f = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                  recursive=True)
    pd = ProfileData.from_file(sorted(f, key=os.path.getmtime)[-1])
    return [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_profiler_capture_has_one_event_per_span(tmp_path):
    eng = AmpcEngine(seed=0, trace=Tracer(annotate_device=True))
    _solve(eng, "msf")                           # compile outside the capture
    eng.tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    res = _solve(eng, "msf")
    jax.profiler.stop_trace()
    spans = list(res.trace.walk())
    names = {sp.name for sp in spans}
    events = [(n, st) for n, st in _profile_events(str(tmp_path))
              if n in names]
    assert len(events) == len(spans)
    by_id = {st["span_id"]: (n, st) for n, st in events}
    assert len(by_id) == len(spans)
    for sp in spans:
        name, st = by_id[sp.span_id]
        assert name == sp.name
        assert st["request_id"] == res.trace.span_id
        assert st.get("parent_id") == sp.parent_id


# ------------------------------------------------------------ Prim lanes
def _prim_inputs():
    g = gen.rmat(8, 6.0, seed=3).with_random_weights(4)
    tg = ternarize(g)
    nt = tg.g.n
    adj = tg.g.padded_adj(3)
    rank = np.random.default_rng(0).permutation(nt).astype(np.float32)
    return tg, adj, rank, int(np.ceil(nt ** 0.25))


def _numpy_steps(adj, rank, budget):
    """The search loop of ``truncated_prim_capped`` replayed in numpy, one
    vertex at a time: how many iterations each vertex's search runs."""
    nbr, nbw, _ = adj
    n, D = nbr.shape
    F = D * budget
    steps = np.zeros(n, np.int64)
    for v in range(n):
        fw = np.full(F, np.inf, np.float32)
        fdst = np.full(F, -1, np.int64)
        fw[:D], fdst[:D] = nbw[v], nbr[v]
        visited, fsize, case = {v}, D, 0
        while case == 0:
            steps[v] += 1
            idx = int(np.argmin(fw))
            best, dst = fw[idx], int(fdst[idx])
            fw[idx], fdst[idx] = np.inf, -1
            if np.isinf(best):
                case = 2
            elif dst in visited:
                continue
            elif rank[dst] < rank[v]:
                case = 3
            else:
                visited.add(dst)
                fw[fsize:fsize + D], fdst[fsize:fsize + D] = nbw[dst], nbr[dst]
                fsize += D
                if len(visited) >= budget:
                    case = 1
    return steps


@pytest.fixture(scope="module")
def prim_case():
    tg, adj, rank, budget = _prim_inputs()
    return tg, adj, rank, budget, _numpy_steps(adj, rank, budget)


@pytest.mark.parametrize("chunk", [None, 7, 100, 500])
def test_prim_lane_counters_match_a_numpy_recount(monkeypatch, prim_case,
                                                   chunk):
    tg, adj, rank, budget, steps = prim_case
    nt = tg.g.n
    args = [jnp.asarray(a) for a in adj] + [jnp.asarray(rank)]

    def run():
        jax.clear_caches()                       # re-read the chunk size
        return [np.asarray(x) for x in msf.truncated_prim_capped(
            *args, budget, budget, tg.g.m)]

    whole = run()
    if chunk is not None:
        lane_bytes = 4 * (2 * budget + 3 * 3 * budget + 8)
        monkeypatch.setattr(msf, "_PRIM_STATE_BYTES", chunk * lane_bytes)
    out = run()
    size = nt if chunk is None else chunk
    for name, a, b in zip(("found", "hooks", "cases", "queries"), whole, out):
        assert np.array_equal(a, b), f"{name} differs at chunk {chunk}"
    lane_steps, lane_steps_run = out[4], out[5]
    n_chunks = -(-nt // size)
    assert lane_steps.shape == lane_steps_run.shape == (n_chunks,)
    # padding lanes run vertex nt - 1's search again, and count only as run
    pad = n_chunks * size - nt
    real = np.r_[steps, np.zeros(pad, int)].reshape(n_chunks, size)
    ran = np.r_[steps, np.full(pad, steps[-1])].reshape(n_chunks, size)
    assert np.array_equal(lane_steps, real.sum(1))
    assert np.array_equal(lane_steps_run, size * ran.max(1))
    assert lane_steps.sum() == steps.sum()
    assert (lane_steps <= lane_steps_run).all()


@pytest.mark.parametrize("problem", ["msf", "connectivity"])
def test_every_prim_path_reports_the_same_lane_steps(problem):
    g = _graph(problem)
    opts = {"skip_ternarize_if_dense": False} if problem == "msf" else {}
    eng = AmpcEngine(seed=0)
    seq = eng.solve(g, problem, **opts)
    sess = eng.session(g)
    sess.solve(problem, **opts)
    warm = sess.solve(problem, **opts)
    assert warm.stats["snapshot"]["hit"] is True
    paths = [seq, warm]
    if problem == "msf":
        other = gen.rmat(6, 4.0, seed=1).with_random_weights(2)
        batched = eng.solve_many([g, other], problem, **opts)
        assert np.array_equal(batched[0].output, seq.output)
        assert batched[1].stats["prim_lane_steps"] == \
            eng.solve(other, problem, **opts).stats["prim_lane_steps"]
        paths.append(batched[0])
    for res in paths:
        assert np.array_equal(res.output, seq.output)
        st = res.stats
        assert st["prim_lane_steps"] == seq.stats["prim_lane_steps"] > 0
        assert st["prim_lane_steps"] <= st["prim_lane_steps_run"]


@pytest.fixture
def harvest_log():
    calls = []
    rounds.HARVEST_HOOK = lambda who: calls.append(who)
    try:
        yield calls
    finally:
        rounds.HARVEST_HOOK = None


@pytest.mark.parametrize("problem", ["msf", "mis", "connectivity"])
def test_traced_warm_solve_still_harvests_once(harvest_log, problem):
    eng = AmpcEngine(seed=0, trace=Tracer())
    _solve(eng, problem)
    harvest_log.clear()
    res = _solve(eng, problem)
    assert len(harvest_log) == 1
    assert len(res.trace.find("device:wait")) == 1


def test_host_sync_lint_still_passes():
    out = subprocess.run([sys.executable,
                          str(REPO / "scripts" / "lint_host_sync.py")],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
