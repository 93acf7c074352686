"""A tiny copy of the benchmark for tests on the CPU: the configurations of
``BENCHMARK.json`` at small scales, plus the open-loop and batch cells of
``data/fleet_cells.json``, so that every driver has a cell."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
FLEET = Path(__file__).resolve().parent / "data" / "fleet_cells.json"
TINY_SCALES = {15: 7, 18: 8}
CELLS = ["g500-s15.msf", "g500-s18.mis", "fleet.submit-poisson",
         "fleet.batch64"]


def tiny_bench(tmp: Path) -> Path:
    """Write the tiny benchmark under ``tmp``; returns its benchmark
    directory."""
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for g in cfg["graphs"]:
            g["scale"] = TINY_SCALES[g["scale"]]
        c["file"] = c["file"].replace(".json", "-tiny.json")
        (tmp / c["file"]).write_text(json.dumps(cfg))
    fleet = json.loads(FLEET.read_text())
    for name, cfg in fleet["configs"].items():
        (tmp / "chipbench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, traffic in fleet["traffic"].items():
        (tmp / "chipbench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
    for key, entries in fleet["benchmark"].items():
        spec[key] += entries
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp / "chipbench"
