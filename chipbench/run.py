"""Chip benchmark of the AMPC engine: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything particular to one of them is a file found by its name:

  <config file named in BENCHMARK.json>  generator, sizes, topology seed
  chipbench/traffic/<mix>.json           a driver and its parameters
  chipbench/drivers/<kind>.py            plan / warmup / window / END_TO_END
  chipbench/metrics/<metric>.py          read(ctx) -> value or None; a
                                         metric ``<name>.<part>`` is read
                                         by ``<name>.py``

A run makes its inputs from the configuration and ``--seed``, warms up one
request of each shape the window uses, measures for ``--seconds``, checks
every answer of the window against the plain references
(``chipbench/reference.py``) off the clock, and prints one JSON line last.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()      # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.ampc import AmpcEngine  # noqa: E402
from repro.obs import Tracer  # noqa: E402

from chipbench import inputs, reference  # noqa: E402
from chipbench import trace as tracemod  # noqa: E402
from chipbench.compiles import CompileWatch  # noqa: E402
from chipbench.workload import Window  # noqa: E402

LEDGER_KEYS = ("shuffles", "dht_queries", "dht_bytes", "dht_query_waves")


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""

    window: Window
    trace: Optional[tracemod.Trace]
    compiles_in_window: list


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(entries: list, cell: str) -> list:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def load_cell(bench_dir: Path, name: str) -> dict:
    """The cell ``name`` and everything its names point at."""
    root = bench_dir.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    traffic = json.loads(
        (bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    return {
        "cell": cell,
        "config": json.loads((root / cfg["file"]).read_text()),
        "traffic": traffic,
        "driver": _load_module(bench_dir / "drivers"
                               / f"{traffic['driver']}.py"),
        "end_to_end": _for_cell(spec["end_to_end"], name),
        "per_layer": [(m, _load_module(bench_dir / "metrics"
                                       / f"{m['name'].split('.')[0]}.py"))
                      for m in _for_cell(spec["per_layer"], name)],
    }


def device_info(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX sees {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache(cache_dir: Path) -> None:
    """Every program in the persistent cache at a fixed path inside the
    checkout, whatever its compile time, so only a cell's first run in a
    checkout compiles."""
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# -------------------------------------------------------------- checking
def check_window(window: Window) -> dict:
    """Every answer of the window against the references.  Returns the
    summed violation counts per check name, with the answers never given
    under ``unanswered``, and sets each record's ``wrong``: its answers
    that never came or fail a check."""
    totals = {"unanswered": 0}
    for rec in window.records:
        req = rec.request
        for name in reference.CHECKS[req.problem]:
            totals.setdefault(name, 0)
        if not rec.answered or len(rec.outputs) != len(req.graphs):
            totals["unanswered"] += len(req.graphs)
            rec.wrong = len(req.graphs)
            continue
        rec.wrong = 0
        for g, out in zip(req.graphs, rec.outputs):
            found = reference.check(req.problem, g, out)
            rec.wrong += any(found.values())
            for k, v in found.items():
                totals[k] += v
    return totals


def _ledger_totals(window: Window) -> dict:
    tot = dict.fromkeys(LEDGER_KEYS, 0)
    for rec in window.records:
        for led in rec.ledgers or []:
            for k in LEDGER_KEYS:
                tot[k] += int(led.get(k, 0))
    return tot


# ------------------------------------------------------------------- run
def run_cell(name: str, seed: int, seconds: float, trace: bool,
             bench_dir: Path = BENCH_DIR, t_start: float = _T_START,
             require_chip: bool = True, log=None) -> dict:
    """One run of the cell ``name``; returns the result line as a dict.
    ``require_chip=False`` skips the look for a TPU (tests on the CPU)."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    parts = load_cell(bench_dir, name)
    cell, driver = parts["cell"], parts["driver"]
    device = device_info(cell["chips"]) if require_chip else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    enable_cache(bench_dir.parent / ".jax_cache")
    watch = CompileWatch()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        rng = np.random.default_rng(seed)
        graphs = inputs.base_graphs(parts["config"])
        requests = driver.plan(graphs, parts["traffic"], rng, seconds)
        log(f"cell {name}: base graphs {[(g.n, g.m) for g in graphs]}, "
            f"{len(requests)} requests planned")
        tracer = Tracer(annotate_device=True) if trace else False
        with AmpcEngine(dht_backend="local", seed=0, trace=tracer) as engine:
            driver.warmup(engine, requests)
            setup_s = time.perf_counter() - t_start
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                with jax.profiler.TraceAnnotation(tracemod.WINDOW):
                    window = driver.window(engine, requests, seconds)
                jax.profiler.stop_trace()
            else:
                window = driver.window(engine, requests, seconds)
        memory_peak = peak_bytes() if device["platform"] == "tpu" else 0
        compiles = watch.between(window.t0, window.t_end)
        tr = None
        if trace:
            names = {s.name for s in engine.tracer.all_spans()}
            tr = tracemod.load(trace_dir, names)
    finally:
        watch.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    took = [r.end - r.start for r in window.records if r.end is not None]
    log(f"window: {len(window.records)} requests, {window.elapsed:.3f} s "
        f"(closed {window.t_close - window.t0:.3f} s after opening), "
        f"request seconds min/median/max "
        f"{min(took, default=0):.4f}/{float(np.median(took or [0])):.4f}/"
        f"{max(took, default=0):.4f}, backend compiles inside: "
        f"{len(compiles)} ({sum(compiles):.3f} s), notes {window.notes}")
    slowest = sorted((r for r in window.records if r.end is not None),
                     key=lambda r: r.start - r.end)[:5]
    log("slowest requests (problem, n, due-to-answer s, engine s, queue "
        "wait s): " + str([
            (r.request.problem, r.request.graphs[0].n,
             round(r.end - r.start, 4), r.solve_s and round(r.solve_s, 4),
             r.stats and r.stats[0].get("async", {}).get("queue_wait_s"))
            for r in slowest]))
    log(f"ledger totals over the window (program counts): "
        f"{_ledger_totals(window)}")
    errors = [r.error for r in window.records if r.error]
    if errors:
        log(f"errors: {len(errors)}, first: {errors[0]}")

    checks = check_window(window)
    attempted = sum(len(r.request.graphs) for r in window.records)
    failed = sum(r.wrong for r in window.records)
    correct = all(v <= 0 for v in checks.values())

    units = {m["name"]: m["unit"] for m in parts["end_to_end"]}
    metrics: dict[str, Any] = {}
    if not trace:
        for m in parts["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            else:
                metrics[m["name"]] = {
                    "value": driver.END_TO_END[m["name"]](window),
                    "unit": units[m["name"]]}
    else:
        ctx = Context(window, tr, compiles)
        for m, mod in parts["per_layer"]:
            value = mod.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if tr is not None:
            device["busy_s"] = tracemod.busy_ns(tr) / 1e9
            device["window_s"] = tr.window_ns / 1e9
    device["memory_peak_bytes"] = memory_peak

    result: dict[str, Any] = {"correct": correct, "attempted": attempted,
                              "failed": failed, "metrics": metrics,
                              "device": device}
    if tr is not None:
        result["breakdown"] = tracemod.breakdown(tr)
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k}: {v} (limit 0)")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; refusing to run", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
