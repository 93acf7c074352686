"""The harness end to end on the CPU at tiny sizes: every driver, the
refusal without a chip, discovery of new files by name, and ``correct``
turning false when the timed path is broken underneath."""
import filecmp
import io
import json
import shutil
import sys
import time
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from repro.ampc import AmpcEngine  # noqa: E402

from chipbench import control, run  # noqa: E402
from chipbench.tests.tiny import CELLS, tiny_bench  # noqa: E402

@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny_bench(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    # the persistent cache is for the chip; tests keep JAX's config as is
    monkeypatch.setattr(run, "enable_cache", lambda cache_dir: None)


def _run(bench, cell, trace=False, seconds=0.6, seed=2**31 + 3):
    return run.run_cell(cell, seed, seconds, trace, bench_dir=bench,
                        t_start=time.perf_counter(), require_chip=False,
                        log=lambda s: None)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_end_to_end(bench, cell):
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    res = _run(bench, cell)
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])


def test_traced_run_on_the_cpu_reads_what_it_can(bench):
    """Off the chip the trace has no TPU plane: the device metrics stay
    silent, the metrics read from the window do not."""
    res = _run(bench, "fleet.submit-poisson", trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"compiles_in_window.fleet"}
    assert res["metrics"]["compiles_in_window.fleet"]["value"] == 0
    assert "busy_s" not in res["device"]


def test_main_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "g500-s18.mis", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "refusing" in out.err


def test_a_new_cell_is_found_by_its_names(tmp_path):
    """A configuration, a traffic mix and a metric added as files, with
    their entries in BENCHMARK.json: the harness runs the new cell and no
    file that was there changes."""
    bench = tiny_bench(tmp_path)
    before = tmp_path / "before"
    shutil.copytree(bench, before / "chipbench")
    (bench / "configs" / "path-fleet.json").write_text(json.dumps({
        "name": "path-fleet", "generator": "graph500_kronecker",
        "initiator": [0.45, 0.25, 0.15],
        "graphs": [{"scale": 6, "edge_factor": 4, "topology_seed": 3}],
        "reduced": {}}))
    (bench / "traffic" / "solve-matching.json").write_text(json.dumps({
        "driver": "solve_loop", "problem": "matching", "graph": 0,
        "pool": 4}))
    (bench / "metrics" / "answers_per_window.py").write_text(
        "def read(ctx):\n    return len(ctx.window.records)\n")
    spec_path = bench.parent / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["configs"].append({"name": "path-fleet", "source": "test",
                            "file": "chipbench/configs/path-fleet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.matching", "config": "path-fleet",
                              "traffic": "solve-matching", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("tiny.matching")
    spec["per_layer"].append({
        "name": "answers_per_window", "unit": "answers", "better": "higher",
        "source": "host_clock", "layer": "harness", "moves": "edges_per_s",
        "workloads": ["tiny.matching"]})
    spec_path.write_text(json.dumps(spec))
    plain, traced = _run(bench, "tiny.matching"), \
        _run(bench, "tiny.matching", trace=True)
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"edges_per_s", "setup_s"}
    assert traced["metrics"]["answers_per_window"]["value"] > 0
    cmp = filecmp.dircmp(before / "chipbench", bench)
    assert not cmp.diff_files and not cmp.left_only
    assert not any(cmp.subdirs[d].diff_files for d in cmp.subdirs)


# ------------------------------------------------------------ the faults
def _altered(out):
    out = np.array(out)
    out[np.argmax(out != 0) if out.dtype == bool else 0] ^= \
        True if out.dtype == bool else 1
    return out


def _patch_solve(monkeypatch, change):
    real = AmpcEngine.solve

    def solve(self, graph, problem, **kw):
        res = real(self, graph, problem, **kw)
        res.output = change(res.output)
        return res
    monkeypatch.setattr(AmpcEngine, "solve", solve)


def _patch_solve_many(monkeypatch, change):
    real = AmpcEngine.solve_many

    def solve_many(self, graphs, problem, **kw):
        return change(real(self, graphs, problem, **kw))
    monkeypatch.setattr(AmpcEngine, "solve_many", solve_many)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(bench, monkeypatch, cell, fault):
    """An answer altered where it is produced, or a solve that returns its
    initial state (nothing chosen, every vertex its own component)."""
    if fault == "answer_altered":
        change = _altered
    else:
        def change(out):
            out = np.asarray(out)
            return np.arange(out.shape[0]) if out.dtype != bool \
                else np.zeros_like(out)
    if cell == "fleet.batch64":
        def change_many(results):
            results[len(results) // 2].output = change(
                results[len(results) // 2].output)
            return results
        _patch_solve_many(monkeypatch, change_many)
    else:
        _patch_solve(monkeypatch, change)
    res = _run(bench, cell)
    assert res["correct"] is False
    assert res["failed"] > 0
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_half_the_batch_left_out_is_not_correct(bench, monkeypatch):
    _patch_solve_many(monkeypatch, lambda results: results[:len(results) // 2])
    res = _run(bench, "fleet.batch64")
    assert res["correct"] is False
    assert res["checks"]["unanswered"]["value"] > 0


def test_wrong_answers_miss_the_latency_limits(bench, monkeypatch):
    """A wrong answer counts as failed and at the drain's end in the
    latency percentiles."""
    _patch_solve(monkeypatch, _altered)
    res = _run(bench, "fleet.submit-poisson")
    assert res["failed"] == res["attempted"]
    from chipbench.drivers import submit_open
    assert res["metrics"]["latency_p50_ms"]["value"] >= \
        1e3 * submit_open.DRAIN_S


def test_result_line_is_json_on_the_last_line(bench, monkeypatch):
    monkeypatch.setattr(run, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(run, "BENCH_DIR", bench)
    out, err = io.StringIO(), io.StringIO()
    real = run.run_cell
    monkeypatch.setattr(run, "run_cell", lambda *a, **k: real(
        *a, bench_dir=bench, **k))
    with redirect_stdout(out), redirect_stderr(err):
        assert run.main(["--workload", "g500-s18.mis", "--seed", "5",
                         "--seconds", "0.5", "--trace", "0"]) == 0
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last["correct"] is True
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(bench, cell):
    """The control answers a few requests of the plan a run makes, and
    fails the comparison on every seed.  msf runs at its cell's own size,
    where rounding the weights 1..m to bfloat16 ties thousands of them;
    the others run tiny (PERF.md gives their readings at the cells' own
    sizes)."""
    where = run.BENCH_DIR if cell == "g500-s15.msf" else bench
    for seed in (1, 2, 3):
        totals = control.control_checks(cell, seed, 8, 1.0, bench_dir=where)
        assert sum(totals.values()) > 0, (seed, totals)


def test_sweep_reports_each_rate(bench):
    from chipbench import sweep
    rows = list(sweep.sweep("fleet.submit-poisson", 4, 0.5, [10, 40],
                            bench_dir=bench, require_chip=False))
    assert [r["rate_per_s"] for r in rows] == [10, 40]
    assert [r["requests"] for r in rows] == [5, 20]
    assert all(r["correct"] and r["latency_p95_ms"] > 0 for r in rows)
