#!/usr/bin/env python3
"""What the program's phase spans cost with the profiler on, and which
phases lower (compile) in a warm loop.

  python bench/tools/oncost.py --seed 1 --repeats 3 --out oncost.json

Mining (``t10i4-mine``): after the set-up mine, ``--repeats`` pairs of
one mine untraced and one mine with ``jax.profiler`` tracing the whole
mine, each timed alone; the phases with ``lowerings`` of every mine, the
host seconds of each phase of each untraced mine, and the steps of ingest
timed alone are listed.  Serving (``t10i4-serve``): after the cell's set-up, ``--repeats``
pairs of a ``--seconds`` open-loop window at the cell's rate untraced and
one traced over all its arrivals, each on a fresh ``AsyncServer`` with an
empty result cache.  A traced span's host events are counted by name, so
the output shows which spans the profiler kept.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from collections import Counter

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mba_bench import harness, loops  # noqa: E402


def _spans(tracer) -> dict:
    """Host events of a traced span named like the program's phases."""
    tr = tracer.load()
    names = Counter(e.name for e in tr.host_events
                    if e.name.startswith(("mba-", "serve-")))
    return dict(sorted(names.items()))


def _lowered(res) -> list:
    return [[p.name, p.lowerings, p.compile_s]
            for p in res.report.ledger.phases if p.lowerings]


def _ingest_parts(T, n_tiles: int) -> dict:
    """Host seconds of each step ``mba-ingest`` takes, timed alone."""
    from repro.data.baskets import pad_items
    from repro.pipeline import uniform_tiles
    t0 = time.perf_counter()
    ((T == 0) | (T == 1)).all()
    t1 = time.perf_counter()
    Tp = pad_items(T.astype(np.uint8, copy=False))
    t2 = time.perf_counter()
    uniform_tiles(Tp, n_tiles)
    t3 = time.perf_counter()
    return {"validate_s": t1 - t0, "pad_items_s": t2 - t1,
            "uniform_tiles_s": t3 - t2}


def mining(bench, seed: int, repeats: int) -> dict:
    from repro.mining import make_miner
    cell = harness.find_cell(bench, "t10i4-mine")
    T = harness.model_for(cell.cfg).corpus(cell.cfg["generator_seed"])
    miner, _ = make_miner(T, config=loops._pipeline_config(cell.cfg))
    out = {"lowered": [_lowered(miner.run(T))], "untraced_s": [],
           "traced_s": [], "spans": None, "phases_host_s": [],
           "ingest_parts": _ingest_parts(T, cell.cfg["mining"]["n_tiles"])}
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = miner.run(T)
        out["untraced_s"].append(time.perf_counter() - t0)
        out["lowered"].append(_lowered(res))
        out["phases_host_s"].append(
            [[p.name, p.host_time_s] for p in res.report.ledger.phases]
            + [["unspanned", res.report.wall_time_s - sum(
                p.host_time_s for p in res.report.ledger.phases)]])
        tracer = harness.Tracer(True)
        tracer.start()
        t0 = time.perf_counter()
        res = miner.run(T)
        out["traced_s"].append(time.perf_counter() - t0)
        tracer.stop()
        out["lowered"].append(_lowered(res))
        out["spans"] = _spans(tracer)
    return out


def serving(bench, seed: int, repeats: int, seconds: float) -> dict:
    from repro.serving import AsyncServer
    cell = harness.find_cell(bench, "t10i4-serve")
    model = harness.model_for(cell.cfg)
    T = model.corpus(cell.cfg["generator_seed"])
    loop = loops.OpenLoop(cell.cfg, cell.spec, T, model, seed)
    loop.setup(seconds)
    loops.TRACE_SECONDS = float("inf")      # trace the whole window
    out = {"untraced": [], "traced": [], "spans": None}
    for _ in range(repeats):
        for traced in (False, True):
            loop.server = AsyncServer(loop.engine)
            loop.engine.cache.clear()
            gc.collect()
            tracer = harness.Tracer(traced)
            loop.window(seconds, tracer)
            row = dict(loop.e2e(), lowerings=sum(
                p.lowerings for p in loop.report.ledger.phases))
            out["traced" if traced else "untraced"].append(row)
            if traced:
                out["spans"] = _spans(tracer)
            print(json.dumps({"traced": traced, **row}), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    out = {"device": harness.require_devices(1),
           "cache": harness.enable_compile_cache()}
    out["mine"] = mining(bench, args.seed, args.repeats)
    print(json.dumps(out["mine"]), flush=True)
    out["serve"] = serving(bench, args.seed, args.repeats, args.seconds)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("device", "mine")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
