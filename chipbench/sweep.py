"""The knee of an open-loop cell: the highest offered rate the engine
sustains without a growing backlog.

    python chipbench/sweep.py --workload <open-loop cell> --seed 1 \\
        --seconds 30 --rates 10 15 20 25

Runs the cell's traffic at each rate in one process (every shape compiles
once) and prints one JSON line per rate: the latency median and 95th
percentile, how long after the window closed the last answer came, and
the median latency of the last quarter of the requests over the first
quarter.  A backlog that grows through the window shows as a drain of
seconds and a ratio well above 1.  The cell's rate is fixed in its traffic
file from this sweep; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
for _p in (str(BENCH_DIR.parent), str(BENCH_DIR.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from repro.ampc import AmpcEngine  # noqa: E402

from chipbench import inputs, run  # noqa: E402


def sweep(cell: str, seed: int, seconds: float, rates, bench_dir=BENCH_DIR,
          require_chip: bool = True):
    parts = run.load_cell(bench_dir, cell)
    if require_chip:
        run.device_info(parts["cell"]["chips"])
        run.enable_cache(bench_dir.parent / ".jax_cache")
    driver = parts["driver"]
    graphs = inputs.base_graphs(parts["config"])
    with AmpcEngine(dht_backend="local", seed=0, trace=False) as engine:
        for rate in rates:
            traffic = dict(parts["traffic"], rate_per_s=float(rate))
            requests = driver.plan(graphs, traffic,
                                   np.random.default_rng(seed), seconds)
            driver.warmup(engine, requests)
            w = driver.window(engine, requests, seconds)
            checks = run.check_window(w)
            lat = np.array([1e3 * (r.end - r.start) if r.good else np.inf
                            for r in w.records])
            q = max(len(lat) // 4, 1)
            yield {"rate_per_s": rate, "requests": len(lat),
                   "correct": all(v == 0 for v in checks.values()),
                   "failed": int(sum(r.wrong for r in w.records)),
                   "latency_p50_ms": float(np.percentile(lat, 50)),
                   "latency_p95_ms": float(np.percentile(lat, 95)),
                   "drain_s": w.t_end - w.t_close,
                   "last_over_first_quarter": float(
                       np.median(lat[-q:]) / np.median(lat[:q])),
                   **w.notes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        for row in sweep(args.workload, args.seed, args.seconds, args.rates):
            row["elapsed_s"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)
    except run.NoChip as e:
        print(f"sweep: {e}; refusing to run", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
