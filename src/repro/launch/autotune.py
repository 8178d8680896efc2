"""Sweep the kernel tile spaces and (re)write the autotune winner cache.

  PYTHONPATH=src python -m repro.launch.autotune            # full lattice
  PYTHONPATH=src python -m repro.launch.autotune --smoke    # CI: tiny sweep
  PYTHONPATH=src python -m repro.launch.autotune --out /tmp/cache.json

Every candidate config is measured (synced warmup + median of ``--reps``
synced repetitions) *and* verified bit-identical against the Python
oracle before it may win; configs that disagree are excluded from the
argmin, so a cache entry is both the fastest and a correct configuration
for its (kernel, shape-bucket, device kind).  The default ``--out`` is
the checked-in cache the ops wrappers read
(:data:`repro.kernels.autotune.cache.DEFAULT_CACHE_PATH`) — refresh it on
the device class ``bench/run.py`` measures on.

``--smoke`` sweeps one small shape per kernel with 2 candidate configs
and writes to a scratch path by default: it exists to exercise the whole
tune → verify → cache → resolve loop in CI, not to produce good tiles.
"""
from __future__ import annotations

import argparse

from repro.kernels.autotune.cache import (DEFAULT_CACHE_PATH, AutotuneCache,
                                          device_kind)
from repro.kernels.autotune.tuner import standard_shapes, tune_into
from repro.launch.common import add_seed_arg, enable_compile_cache
from repro.launch.tuning import TUNABLE_KERNELS


def autotune(out: str = DEFAULT_CACHE_PATH, smoke: bool = False,
             reps: int = 3, max_configs: int = 0, seed: int = 0,
             kernels: tuple = TUNABLE_KERNELS):
    """Run the sweep and write the cache; returns the AutotuneCache."""
    if smoke and not max_configs:
        max_configs = 2
    cache = AutotuneCache.load(out)
    if cache.load_error:
        print(f"[autotune] starting fresh: {cache.load_error}")
    print(f"[autotune] device={device_kind()} smoke={smoke} "
          f"reps={reps} max_configs={max_configs or 'all'}")
    for kernel in kernels:
        shapes = standard_shapes(kernel, smoke=smoke)
        tune_into(cache, kernel, shapes, log=print, reps=reps,
                  max_configs=max_configs, seed=seed)
    path = cache.save(out)
    print(f"[autotune] wrote {len(cache)} entries to {path}")
    return cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_CACHE_PATH,
                    help="cache file to update (default: the checked-in "
                         "cache the ops wrappers read)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: one small shape per kernel, 2 configs "
                         "— exercises the tune/verify/cache loop only")
    ap.add_argument("--reps", type=int, default=3,
                    help="synced repetitions per config (median wins)")
    ap.add_argument("--max-configs", type=int, default=0,
                    help="truncate the roofline-ordered candidate list "
                         "(0 = sweep all)")
    add_seed_arg(ap)                # shared with the other launch CLIs
    ap.add_argument("--kernel", action="append", default=None,
                    choices=list(TUNABLE_KERNELS),
                    help="restrict to one kernel (repeatable)")
    args = ap.parse_args()
    enable_compile_cache()
    autotune(args.out, smoke=args.smoke, reps=args.reps,
             max_configs=args.max_configs, seed=args.seed,
             kernels=tuple(args.kernel) if args.kernel else TUNABLE_KERNELS)


if __name__ == "__main__":
    main()
