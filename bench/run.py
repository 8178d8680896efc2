#!/usr/bin/env python3
"""Market-basket benchmark: run one cell of BENCHMARK.json once.

  python bench/run.py --workload t10i4-mine --seed 7 --seconds 30 --trace 0

Runs in one process on the chips it is started on, from the root of a
checkout.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, ``breakdown`` (traced runs only) and, last, ``checks``: each
number compared with the plain reference beside its limit.  The same
numbers are the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the system under test cannot be imported.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from mba_bench import harness  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = harness.run_cell(harness.load_benchmark(), args.workload,
                                args.seed, args.seconds, bool(args.trace),
                                T_START, log=log)
    except harness.NoChip as e:
        log(f"no result: {e}")
        return 2
    for name, c in line["checks"].items():
        print(f"[check] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
