#!/usr/bin/env python3
"""Support-grid sweep on the chip: mine each configuration once cold and
once warm at each support of the paper's Fig. 4 grid, and record the
lattice (levels, candidates per level, itemsets, rules), the host
transfers and the warm mine's wall.

  python bench/tools/grid.py --configs quest-t10i4d100k --out grid.json

Supports run from high to low; a configuration stops going lower once a
warm mine takes more than ``--max-warm-s``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from mba_bench import harness  # noqa: E402

GRID = (0.02, 0.015, 0.01, 0.0075, 0.005, 0.0033, 0.0025)


def lattice(result) -> dict:
    rep = result.report
    led = rep.ledger
    return {
        "levels": [[r.k, r.n_candidates, r.n_frequent, r.m_padded]
                   for r in rep.rounds],
        "itemsets": len(result.supports), "rules": len(result.rules),
        "h2d_mb": led.total_h2d_bytes / 1e6,
        "syncs": led.total_syncs,
        "phases_host_s": {p.name: p.host_time_s for p in led.phases},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--configs", nargs="+", required=True)
    ap.add_argument("--supports", nargs="+", type=float, default=GRID)
    ap.add_argument("--max-warm-s", type=float, default=6.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    device = harness.require_devices(1)
    harness.enable_compile_cache()
    from repro.mining import make_miner
    from repro.pipeline import PipelineConfig
    from repro.kernels.autotune.cache import LAST_DISPATCH

    out = {"device": device, "grid": {}}
    for name in args.configs:
        cfg = harness.load_config(name)
        t0 = time.perf_counter()
        T = harness.model_for(cfg).corpus(cfg["generator_seed"])
        gen_s = time.perf_counter() - t0
        rows = []
        out["grid"][name] = {"corpus_s": gen_s,
                             "mean_basket": float(T.sum(1).mean()),
                             "supports": rows}
        for s in args.supports:
            pc = PipelineConfig(min_support=s)
            miner, _ = make_miner(T, config=pc)
            t0 = time.perf_counter()
            miner.run(T)
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            res = miner.run(T)
            warm = time.perf_counter() - t0
            row = {"min_support": s, "cold_s": cold, "warm_s": warm,
                   "dispatch": {k: {kk: vv for kk, vv in v.items()}
                                for k, v in LAST_DISPATCH.items()},
                   **lattice(res)}
            rows.append(row)
            print(json.dumps({"config": name, **{k: row[k] for k in (
                "min_support", "cold_s", "warm_s", "itemsets", "rules",
                "h2d_mb", "levels")}}), flush=True)
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1, default=str)
            if warm > args.max_warm_s:
                break

    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
