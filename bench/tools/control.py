#!/usr/bin/env python3
"""Run the control of a cell on the chip: a whole run of the cell with the
plain reference in the system's place, in bfloat16 (``mba_bench.control``),
on each seed given.  Every line printed must read ``correct: false``; its
checks are the control's readings, the upper end of each limit.

  python bench/tools/control.py --workload t10i4-mine --seconds 10 \
      --seeds 101 102 103
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mba_bench import control, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    kind = harness.find_cell(bench, args.workload).spec["kind"]
    patch = control.mine_control if kind == "mine_loop" else \
        control.serve_control
    for seed in args.seeds:
        t0 = time.perf_counter()
        with patch():
            line = harness.run_cell(bench, args.workload, seed, args.seconds,
                                    False, t0)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "checks": line["checks"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
