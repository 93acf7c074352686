"""The ``g500-s18.matching`` cell: its readers on hand-made traces and
records, the program name its device-time reader matches, and the cell run
on the CPU at a tiny scale, plain and traced, with its control."""
import json
import re
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from repro.ampc import AmpcEngine  # noqa: E402
from repro.core import matching  # noqa: E402

from chipbench import control, inputs, run, trace  # noqa: E402
from chipbench.tests.tiny import tiny_bench  # noqa: E402
from chipbench.workload import Record, Request, Window  # noqa: E402

CELL = "g500-s18.matching"
METRICS = ROOT / "chipbench" / "metrics"
LAYER_METRICS = {"mm_fixpoint_device_ms", "mm_lane_occupancy",
                 "device_idle_share.analytics", "compiles_in_window.analytics",
                 "host_prep_ms.analytics"}


def _reader(name):
    return run._load_module(METRICS / f"{name}.py")


def _window(stats, answered=None):
    """One record per entry of ``stats``; ``answered[i]`` False leaves
    record i without an answer."""
    answered = answered or [True] * len(stats)
    recs = []
    for st, ok in zip(stats, answered):
        rec = Record(Request("matching", [], 0), start=0.0)
        if ok:
            rec.end, rec.stats = 1.0, [st]
        recs.append(rec)
    return Window(recs, t0=0.0, t_close=1.0, t_end=1.0)


def _ctx(window, tr):
    return run.Context(window, tr, [])


def _trace(programs):
    return trace.Trace(ops=[[("op", 0, 10)]], programs=[programs], host=[],
                       window=(1_000_000.0, 9_000_000.0))


PROGRAMS = [("jit__mm_fixpoint", 1_000_000, 4_000_000),       # 3 ms
            ("jit__mis_fixpoint_masked", 4_000_000, 5_000_000),
            ("jit__mm_fixpoint", 5_000_000, 6_000_000),       # 1 ms
            ("jit__mm_fixpoint", 8_500_000, 9_500_000)]       # 0.5 ms inside


@pytest.mark.parametrize("answered,want_ms", [
    ([True], 4.5), ([True, True], 2.25), ([True, True, True], 1.5),
    ([True, False, True], 2.25)])
def test_mm_fixpoint_device_ms_is_its_program_time_per_answered_solve(
        answered, want_ms):
    read = _reader("mm_fixpoint_device_ms").read
    ctx = _ctx(_window([{}] * len(answered), answered), _trace(PROGRAMS))
    assert read(ctx) == pytest.approx(want_ms)


def test_mm_fixpoint_device_ms_is_none_without_its_program():
    read = _reader("mm_fixpoint_device_ms").read
    assert read(_ctx(_window([{}]), None)) is None
    assert read(_ctx(_window([{}]), _trace(PROGRAMS[1:2]))) is None
    assert read(_ctx(_window([{}], [False]), _trace(PROGRAMS))) is None


def test_mm_lane_occupancy_is_live_over_run_lanes():
    read = _reader("mm_lane_occupancy").read
    stats = [{"mm_lane_steps": 27, "mm_lane_steps_run": 200},
             {"mm_lane_steps": 13, "mm_lane_steps_run": 100},
             {"mm_lane_steps": 90, "mm_lane_steps_run": 100}]
    assert read(_ctx(_window(stats[:1]), None)) == pytest.approx(13.5)
    assert read(_ctx(_window(stats[:2]), None)) == pytest.approx(40 / 3)
    # an unanswered solve adds nothing
    assert read(_ctx(_window(stats, [True, True, False]), None)) == \
        pytest.approx(40 / 3)


def test_mm_lane_occupancy_is_none_without_counters():
    """The parent's solves report no lane counters: the reader is silent
    and does not raise."""
    read = _reader("mm_lane_occupancy").read
    assert read(_ctx(_window([{"fixpoint_iters": 10}]), None)) is None
    assert read(_ctx(_window([]), None)) is None
    assert read(_ctx(_window([{"mm_lane_steps": 5, "mm_lane_steps_run": 9}],
                             [False]), None)) is None


def test_program_name_still_matches_the_device_time_reader():
    """A rename of the jitted fixpoint fails here instead of silencing
    ``mm_fixpoint_device_ms``."""
    m, n = 8, 16
    text = matching._mm_fixpoint.lower(
        jnp.zeros(m, jnp.int32), jnp.zeros(m, jnp.int32),
        jnp.arange(m, dtype=jnp.float32), n,
        jnp.zeros(m, jnp.int32)).as_text()
    module = re.search(r"module @([\w.]+)", text).group(1)
    assert any(re.search(p, module)
               for p in _reader("mm_fixpoint_device_ms").PROGRAMS), module


def test_the_cell_reports_the_new_metrics_and_edges_per_s():
    parts = run.load_cell(run.BENCH_DIR, CELL)
    assert parts["cell"]["chips"] == 1
    assert parts["traffic"]["problem"] == "matching"
    assert {m["name"] for m in parts["end_to_end"]} == \
        {"edges_per_s", "setup_s"}
    assert {m["name"] for m, _ in parts["per_layer"]} == LAYER_METRICS


def test_the_matching_config_describes_the_mis_cells_graph():
    """The two configurations hold one graph: the mis and matching cells
    share their input pipeline only while these keys stay equal."""
    def cfg(name):
        return json.loads((ROOT / "chipbench" / "configs"
                           / f"{name}.json").read_text())
    mis, mm = cfg("graph500-s18"), cfg("graph500-s18-matching")
    for key in ("generator", "initiator", "graphs", "source_sizes"):
        assert mm[key] == mis[key], key
    assert mm["assumed"]["dedup"] == mis["assumed"]["dedup"]
    assert mm["source"].startswith(mis["source"])
    assert set(mm["reduced"]) == set(mis["reduced"])


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # the persistent cache is for the chip; tests keep JAX's config as is
    monkeypatch.setattr(run, "enable_cache", lambda cache_dir: None)


def _run(bench, trace_on):
    return run.run_cell(CELL, 2**31 + 7, 0.6, trace_on, bench_dir=bench,
                        t_start=time.perf_counter(), require_chip=False,
                        log=lambda s: None)


def test_the_cell_runs_end_to_end_on_the_cpu(bench):
    res = _run(bench, False)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"edges_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["checks"]) == {"unanswered", "matching.shared_vertices",
                                  "matching.free_edges"}
    assert all(c["value"] == 0 for c in res["checks"].values())


def test_traced_run_on_the_cpu_reads_the_lane_counters(bench):
    """Off the chip the trace has no TPU plane, so the device readers stay
    silent; the lane counters and the compile count come from the program
    and are read."""
    res = _run(bench, True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"mm_lane_occupancy",
                                   "compiles_in_window.analytics"}
    assert res["metrics"]["compiles_in_window.analytics"]["value"] >= 0
    occ = res["metrics"]["mm_lane_occupancy"]
    assert occ["unit"] == "%" and 0 < occ["value"] < 100
    assert np.isfinite(occ["value"])


def test_every_run_seed_does_the_same_matching_work(bench):
    """Two run seeds give each request the same rank seed and the same
    edge positions under their own vertex names, so the engine matches the
    same positions in the same waves over the same live lanes."""
    parts = run.load_cell(bench, CELL)
    graphs = inputs.base_graphs(parts["config"])
    plans = [parts["driver"].plan(graphs, parts["traffic"],
                                  np.random.default_rng(s), 1.0)
             for s in (1, 2**31 + 11)]
    assert [r.seed for r in plans[0]] == [r.seed for r in plans[1]]
    assert not np.array_equal(plans[0][0].graphs[0].edges,
                              plans[1][0].graphs[0].edges)
    eng = AmpcEngine(dht_backend="local", seed=0)
    seen = set()
    for ra, rb in zip(plans[0][:3], plans[1][:3]):
        a = eng.solve(ra.graphs[0], "matching", seed=ra.seed)
        b = eng.solve(rb.graphs[0], "matching", seed=rb.seed)
        assert np.array_equal(a.output, b.output)
        for key in ("fixpoint_iters", "queries_nodedup", "queries_dedup",
                    "mm_lane_steps", "mm_lane_steps_run"):
            assert a.stats[key] == b.stats[key], key
        seen.add(a.output.tobytes())
    assert len(seen) == 3  # and each request has ranks of its own


def test_control_in_the_programs_place_is_not_correct(bench):
    """The matching fixpoint stopped after two waves leaves free edges on
    every seed (PERF.md gives the readings at the cell's own size)."""
    for seed in (1, 2, 3):
        totals = control.control_checks(CELL, seed, 8, 1.0, bench_dir=bench)
        assert totals["matching.free_edges"] > 0, (seed, totals)
