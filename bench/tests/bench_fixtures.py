"""Shared set-up of the benchmark's CPU tests.

The fixture cells run the whole harness, without its look for a chip, on
a small Quest corpus (``fixtures/configs/quest-tiny.json``).  They are
added the way a later change adds a cell: a configuration file, a traffic
file and a metric reader beside the benchmark's own, and entries in a
copy of BENCHMARK.json.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from mba_bench import harness  # noqa: E402

FIXTURES = os.path.join(TESTS, "fixtures")
MINE, SERVE = "tiny-mine", "tiny-serve"
SEED = 2 ** 31 + 17            # larger than 32 signed bits hold


def use_fixture_files(monkeypatch, tmp_path) -> None:
    """Point the harness at copies of its configs, traffic and metric
    readers with the fixture files added beside them."""
    for sub, attr in (("configs", "CONFIG_DIR"), ("traffic", "TRAFFIC_DIR"),
                      ("metrics", "METRIC_DIR")):
        dst = tmp_path / sub
        shutil.copytree(os.path.join(BENCH, sub), dst)
        for name in os.listdir(os.path.join(FIXTURES, sub)):
            shutil.copy(os.path.join(FIXTURES, sub, name), dst / name)
        monkeypatch.setattr(harness, attr, str(dst))


def fixture_benchmark() -> dict:
    """BENCHMARK.json with the two fixture cells and the fixture metric
    added as entries, as a later change would add them."""
    bench = harness.load_benchmark()
    bench["workloads"] += [
        {"name": MINE, "config": "quest-tiny", "traffic": "mine_loop",
         "chips": 1, "why": "test fixture"},
        {"name": SERVE, "config": "quest-tiny", "traffic": "tiny_open_loop",
         "chips": 1, "why": "test fixture"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None:
            cells += [c for real, c in (("t10i4-mine", MINE),
                                        ("t10i4-serve", SERVE))
                      if real in cells]
    bench["per_layer"].append(
        {"name": "mines_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "pipeline control plane",
         "moves": "mine_s", "workloads": [MINE]})
    return bench


def run(bench: dict, cell: str, trace: bool = False, seconds: float = 1.0,
        seed: int = SEED) -> dict:
    return harness.run_cell(bench, cell, seed, seconds, trace,
                            time.perf_counter(), require_chip=False)
