#!/usr/bin/env python3
"""Serving knee sweep on the chip: one set-up of a serving cell, then an
open-loop window at each offered rate, each recording whether completions
kept pace with arrivals.

  python bench/tools/knee.py --workload t10i4-serve --rates 500 1000 2000 \
      --seconds 10 --out knee.json

The knee is the highest rate at which, in every window at that rate and
every rate below it, at least 99% of the requests due in the window were
answered inside it and the p99 stayed under 300 ms (no backlog built up);
a serving cell offers 0.8 x the knee.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mba_bench import harness, loops, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", nargs="+", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    device = harness.require_devices(cell.chips)
    harness.enable_compile_cache()
    model = harness.model_for(cell.cfg)
    T = model.corpus(cell.cfg["generator_seed"])
    loop = loops.OpenLoop(cell.cfg, dict(cell.spec), T, model, args.seed)
    loop.setup(args.seconds)
    out = {"device": device, "workload": args.workload,
           "setup_s": time.perf_counter() - T_START,
           "index_rows": loop.index.n_rows, "rows": []}
    import numpy as np
    for i, rate in enumerate(args.rates):
        loop.spec["rate_qps"] = rate
        rng = np.random.default_rng([args.seed, 10 + i])
        n = traffic.n_requests(loop.spec, args.seconds)
        loop.offsets = traffic.arrival_offsets(loop.spec, n, args.seconds, rng)
        loop.baskets = traffic.baskets(loop.spec, model, n, rng)
        from repro.serving import AsyncServer
        # a fresh server per rate, as a run has: the server keeps every
        # handle it was given, which would weigh on the later rates
        loop.server = AsyncServer(loop.engine)
        loop.engine.cache.clear()
        gc.collect()
        with harness.GcWatch() as watch:
            loop.window(args.seconds, harness.Tracer(False))
        e2e = loop.e2e()
        rep = loop.report
        row = {"rate_qps": rate, "offered": n, **e2e,
               "kept_pace": e2e["serve_qps"] * args.seconds / n,
               "batch_fill": rep.batch_fill, "steps": rep.n_steps,
               "bucket_counts": rep.bucket_counts,
               "hit_rate": rep.hit_rate, "gc": watch.summary()}
        out["rows"].append(row)
        print(json.dumps(row), flush=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
        if row["kept_pace"] < 0.9:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
