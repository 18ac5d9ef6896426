"""A throwaway benchmark checkout for the CPU tests: the benchmark's own
files, plus a tiny deployment and two cells of it, in a temporary
directory."""

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CELLS = {
    "tiny.closed": {"loop": "closed", "clients": 3, "budgets": [1, 3],
                    "popularity": "uniform"},
    "tiny.open": {"loop": "open", "rate_per_s": 4.0, "burst_mean": 2,
                  "budgets": [1, 3], "popularity": {"zipf": 1.1}},
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout holding BENCHMARK.json with the tiny cells and the
    benchmark's files; the program itself comes from the test run's
    import path."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIXTURES / "tiny_tables.py", root / "bench" / "tables"
                / "tiny.py")
    shutil.copy(FIXTURES / "tiny_config.json", root / "bench" / "configs"
                / "tiny.json")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = []
    for name, mix in CELLS.items():
        traffic = name.split(".")[1]
        (root / "bench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(mix))
        spec["workloads"].append({"name": name, "config": "tiny",
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
