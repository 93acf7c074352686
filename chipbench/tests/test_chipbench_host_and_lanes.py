"""The readers of ``host_prep_ms`` and ``prim_lane_occupancy`` on hand-made
traces and records, a traced run of the analytics cells on the CPU, and the
program names that the device-time readers match."""
import re
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from repro.core import mis, msf  # noqa: E402

from chipbench import run, trace  # noqa: E402
from chipbench.tests.tiny import tiny_bench  # noqa: E402
from chipbench.workload import Record, Request, Window  # noqa: E402

METRICS = ROOT / "chipbench" / "metrics"


def _reader(name):
    return run._load_module(METRICS / f"{name}.py")


def _window(stats, answered=None):
    """One record per entry of ``stats``; ``answered[i]`` False leaves
    record i without an answer."""
    answered = answered or [True] * len(stats)
    recs = []
    for st, ok in zip(stats, answered):
        rec = Record(Request("msf", [], 0), start=0.0)
        if ok:
            rec.end, rec.stats = 1.0, [st]
        recs.append(rec)
    return Window(recs, t0=0.0, t_close=1.0, t_end=1.0)


def _trace(host):
    return trace.Trace(ops=[[("op", 0, 10)]], programs=[[]], host=host,
                       window=(100.0, 2_000_100.0))


def _ctx(window, tr):
    return run.Context(window, tr, [])


HOST = [("solve", 0, 2_100_000),
        ("host:ternarize", 50, 400_100),          # clipped to 400,000
        ("shuffle:SortGraph", 40, 600_000),
        ("host:adjacency", 500_100, 600_100),      # 100,000
        ("device:wait", 600_100, 1_000_000),       # not host prep
        ("host:assemble", 1_900_100, 2_200_000)]   # clipped to 100,000


@pytest.mark.parametrize("solves,want_ms", [(1, 0.6), (2, 0.3), (3, 0.2)])
def test_host_prep_sums_host_spans_in_the_window_per_solve(solves, want_ms):
    read = _reader("host_prep_ms").read
    ctx = _ctx(_window([{}] * solves + [{}], [True] * solves + [False]),
               _trace(HOST))
    assert read(ctx) == pytest.approx(want_ms)


def test_host_prep_is_none_without_a_trace_or_host_spans():
    read = _reader("host_prep_ms").read
    assert read(_ctx(_window([{}]), None)) is None
    no_host = [h for h in HOST if not h[0].startswith("host:")]
    assert read(_ctx(_window([{}]), _trace(no_host))) is None
    assert read(_ctx(_window([{}], [False]), _trace(HOST))) is None


def test_prim_lane_occupancy_is_useful_over_run_lanes():
    read = _reader("prim_lane_occupancy").read
    stats = [{"prim_lane_steps": 30, "prim_lane_steps_run": 100},
             {"prim_lane_steps": 10, "prim_lane_steps_run": 60},
             {"prim_lane_steps": 99, "prim_lane_steps_run": 100}]
    assert read(_ctx(_window(stats[:1]), None)) == pytest.approx(30.0)
    assert read(_ctx(_window(stats[:2]), None)) == pytest.approx(25.0)
    # an unanswered solve adds nothing
    assert read(_ctx(_window(stats, [True, True, False]), None)) == \
        pytest.approx(25.0)


def test_prim_lane_occupancy_is_none_without_counters():
    read = _reader("prim_lane_occupancy").read
    assert read(_ctx(_window([{"fixpoint_iters": 3}]), None)) is None
    assert read(_ctx(_window([]), None)) is None


def test_program_names_still_match_the_device_time_readers():
    """The device-time readers match XLA program names; a rename of these
    jitted functions fails here instead of silencing a metric."""
    n, d = 16, 3
    adj = (jnp.zeros((n, d), jnp.int32), jnp.zeros((n, d), jnp.float32),
           jnp.zeros((n, d), jnp.int32))
    rank = jnp.arange(n, dtype=jnp.float32)
    prim = msf.truncated_prim.lower(*adj, rank, 2, 24).as_text()
    fix = mis._mis_fixpoint_masked.lower(
        jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32), rank, n,
        jnp.ones(8, bool)).as_text()
    for text, reader in ((prim, "prim_device_ms"),
                         (fix, "mis_fixpoint_device_ms")):
        module = re.search(r"module @([\w.]+)", text).group(1)
        assert any(re.search(p, module)
                   for p in _reader(reader).PROGRAMS), (reader, module)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell,want", [
    ("g500-s15.msf", {"compiles_in_window.analytics", "prim_lane_occupancy"}),
    ("g500-s18.mis", {"compiles_in_window.analytics"})])
def test_traced_analytics_run_on_the_cpu(bench, monkeypatch, cell, want):
    """Off the chip the trace has no TPU plane, so the trace readers stay
    silent; the lane counters come from the program and are read."""
    monkeypatch.setattr(run, "enable_cache", lambda cache_dir: None)
    res = run.run_cell(cell, 2**31 + 5, 0.6, True, bench_dir=bench,
                       t_start=time.perf_counter(), require_chip=False,
                       log=lambda s: None)
    assert res["correct"] is True
    assert set(res["metrics"]) == want
    if "prim_lane_occupancy" in want:
        assert 0 < res["metrics"]["prim_lane_occupancy"]["value"] <= 100
        assert res["metrics"]["prim_lane_occupancy"]["unit"] == "%"
    assert np.isfinite([m["value"] for m in res["metrics"].values()]).all()
