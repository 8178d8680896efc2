#!/usr/bin/env python3
"""Run a cell on Quest corpora other than its configuration's own: the
whole run (set-up, window, comparison with the plain reference), once per
generator seed given, with the configuration otherwise as it stands.

A cell's runs mine the one corpus of its configuration's
``generator_seed``, so that every run after the first finds its programs
compiled; this tool is the witness on other corpora, where other lattices
take other shapes (and compile them).

  python bench/tools/corpora.py --workload t10i4-mine --seconds 10 \
      --generator-seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mba_bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--generator-seeds", nargs="+", type=int, required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    load_config = harness.load_config
    for gen_seed in args.generator_seeds:
        def with_seed(name, gen_seed=gen_seed):
            cfg = load_config(name)
            cfg["generator_seed"] = gen_seed
            return cfg
        harness.load_config = with_seed
        t0 = time.perf_counter()
        try:
            line = harness.run_cell(bench, args.workload, args.seed,
                                    args.seconds, False, t0)
        finally:
            harness.load_config = load_config
        print(json.dumps({"generator_seed": gen_seed, "seed": args.seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics": line["metrics"],
                          "checks": line["checks"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
