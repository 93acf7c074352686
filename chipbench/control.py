"""The control of ``correct``: the reference with one guarantee broken, put
in the program's place, must fail the comparison a run makes.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 --requests N

For each seed it plans the cell's inputs exactly as a run does, answers the
first ``N`` requests a window would send (cycling as the closed loops do)
with :func:`chipbench.reference.control_answer`, and prints the summed
check numbers beside their limits.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
for _p in (str(BENCH_DIR.parent), str(BENCH_DIR.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import inputs, reference  # noqa: E402
from chipbench.run import load_cell  # noqa: E402


def control_checks(cell: str, seed: int, requests: int, seconds: float,
                   bench_dir: Path = BENCH_DIR) -> dict:
    parts = load_cell(bench_dir, cell)
    graphs = inputs.base_graphs(parts["config"])
    plan = parts["driver"].plan(graphs, parts["traffic"],
                                np.random.default_rng(seed), seconds)
    totals: dict = {}
    for i in range(requests or len(plan)):
        req = plan[i % len(plan)]
        for g in req.graphs:
            found = reference.check(
                req.problem, g,
                reference.control_answer(req.problem, g, req.seed))
            for k, v in found.items():
                totals[k] = totals.get(k, 0) + v
    return totals


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to answer per seed (0: the whole plan)")
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        totals = control_checks(args.workload, seed, args.requests,
                                args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": {k: {"value": v, "limit": 0}
                                      for k, v in totals.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
