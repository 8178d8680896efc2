#!/usr/bin/env python3
"""Smoke test of the market-basket main path on a TPU chip.

  python chip_smoke.py                # one chip: kernels, Apriori, Eclat, serving
  python chip_smoke.py --four-chips   # sharded Apriori over a 4-chip mesh

Every phase goes through the entry points a user calls (``make_miner``,
``RuleIndex``, ``AsyncServer``) on a corpus of 100,000 transactions over
1,000 items (the D and N of the classic T10I4D100K benchmark), generated
from ``--seed`` by ``data.baskets.generate_baskets``:

  kernels  each main-path Pallas kernel, both variants, at tiles pinned so
           that every call runs several row blocks, and support_count at
           the checked-in cache's config for two row counts off the
           sweep's lattice; equal to the jnp refs.
  apriori  ``data_plane="auto"`` must resolve to compiled Pallas; supports
           and rules bit-identical to the same mine with ``data_plane="ref"``.
  eclat    the same for ``algorithm="eclat"`` (the ``intersect_count``
           kernel), and equal to the Apriori result.
  serve    a ``RuleIndex`` of the mined rules behind an ``AsyncServer``;
           every answer equals ``serving/oracle.py``.

``--four-chips`` runs only the sharded Apriori mine over a 4-device mesh
and the single-device pipeline it must equal, and checks that the shards
live on 4 distinct devices.

Each phase prints its resolved kernel variant, tiles and config source,
its item and rule counts, and a one-off wall time (a smoke timing that
includes compilation, not a metric).  The last line of stdout is
``{"ok": true, "device": {...}}``.  The script exits non-zero without
that line when the platform is not a TPU, when a phase falls back to the
jnp reference or to interpret mode, or when a parity check fails.
Everything runs in this one process: the chip belongs to one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.itemsets import itemsets_to_bitmap  # noqa: E402
from repro.data.baskets import BasketConfig, generate_baskets  # noqa: E402
from repro.data.sparse import pack_tid_columns  # noqa: E402
from repro.distributed.mining import (ShardedMiner, make_shard_mesh,  # noqa: E402
                                      mesh_profile)
from repro.kernels.autotune.cache import LAST_DISPATCH  # noqa: E402
from repro.kernels.rule_match.ops import rule_topk  # noqa: E402
from repro.kernels.support_count.ops import (intersect_count,  # noqa: E402
                                             support_count)
from repro.kernels.support_count.ref import (intersect_count_ref,  # noqa: E402
                                             support_count_ref)
from repro.launch.common import enable_compile_cache  # noqa: E402
from repro.mining import make_miner  # noqa: E402
from repro.pipeline import MarketBasketPipeline, PipelineConfig  # noqa: E402
from repro.serving import (AsyncServer, Query, RecommendationEngine,  # noqa: E402
                           RuleIndex, ServingConfig, recommend_bruteforce)

N_TX, N_ITEMS, MIN_SUPPORT = 100_000, 1_000, 0.01
ROW_TILE = 512          # pinned kernel row tile in the kernels phase
# row counts the kernels phase also counts at the cached config: a 32-tile
# round's tile of 120,000 rows and a four-chip shard of 100,000
OFF_LATTICE_ROWS = (3752, 25000)


class SmokeFailure(RuntimeError):
    """A phase fell back, or its result disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(devices) -> dict:
    """The device record of the last line; refuses anything but a TPU."""
    d = devices[0]
    check(d.platform == "tpu",
          f"platform is {d.platform!r}, not 'tpu': no accelerator found")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def dispatched(*kernels: str) -> dict:
    """What the ops wrappers last launched for ``kernels``."""
    return {k: dict(LAST_DISPATCH[k]) for k in kernels if k in LAST_DISPATCH}


def check_compiled(rec: dict) -> None:
    """Refuse a phase that ran on the jnp reference or in interpret mode."""
    check(rec["backend"] == "pallas",
          f"{rec['phase']}: backend resolved to {rec['backend']!r}")
    check(bool(rec["dispatch"]), f"{rec['phase']}: no kernel was dispatched")
    for kernel, d in rec["dispatch"].items():
        check(not d["interpret"],
              f"{rec['phase']}: {kernel} ran in interpret mode")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def corpus(n_tx: int, n_items: int, seed: int) -> np.ndarray:
    return generate_baskets(BasketConfig(n_tx=n_tx, n_items=n_items,
                                         seed=seed))


# ---------------------------------------------------------------------------
# phases — each returns a record; main() prints it and checks it
# ---------------------------------------------------------------------------

def phase_apriori(T: np.ndarray, min_support: float,
                  data_plane: str = "auto", interpret=None):
    cfg = PipelineConfig(min_support=min_support, data_plane=data_plane,
                         interpret=interpret)
    LAST_DISPATCH.clear()
    miner, _ = make_miner(T, config=cfg)
    got, wall = _timed(lambda: miner.run(T))
    rec = {"phase": "apriori", "backend": got.report.backend,
           "dispatch": dispatched("support_count"), "wall_s": wall}
    ref_cfg = PipelineConfig(min_support=min_support, data_plane="ref")
    want = make_miner(T, config=ref_cfg)[0].run(T)
    check(got.supports == want.supports,
          "apriori: supports differ from data_plane='ref'")
    check(got.rules == want.rules, "apriori: rules differ from data_plane='ref'")
    levels = max(len(s) for s in got.supports)
    check(levels >= 3, f"apriori: only {levels} levels mined")
    rec.update(itemsets=len(got.supports), rules=len(got.rules),
               levels=levels)
    return got, rec


def phase_eclat(T: np.ndarray, min_support: float, apriori,
                data_plane: str = "auto", interpret=None) -> dict:
    cfg = PipelineConfig(min_support=min_support, algorithm="eclat",
                         data_plane=data_plane, interpret=interpret)
    LAST_DISPATCH.clear()
    miner, _ = make_miner(T, config=cfg)
    got, wall = _timed(lambda: miner.run(T))
    rec = {"phase": "eclat", "backend": got.report.backend,
           "dispatch": dispatched("intersect_count"), "wall_s": wall}
    ref_cfg = PipelineConfig(min_support=min_support, algorithm="eclat",
                             data_plane="ref")
    want = make_miner(T, config=ref_cfg)[0].run(T)
    check(got.supports == want.supports,
          "eclat: supports differ from data_plane='ref'")
    check(got.rules == want.rules, "eclat: rules differ from data_plane='ref'")
    check(got.supports == apriori.supports and got.rules == apriori.rules,
          "eclat: result differs from Apriori")
    rec.update(itemsets=len(got.supports), rules=len(got.rules))
    return rec


def phase_kernels(T: np.ndarray, supports, rules, n_queries: int,
                  interpret=None) -> dict:
    """Each main-path kernel and variant at pinned tiles that give every
    grid axis several blocks — above all several row blocks, the case in
    which an output block is revisited — against its jnp reference."""
    n_rows = (T.shape[0] // ROW_TILE) * ROW_TILE
    check(n_rows >= 2 * ROW_TILE, "kernels: corpus too small for 2 row tiles")
    Tj = jnp.asarray(T[:n_rows])
    n_items = T.shape[1]
    C = itemsets_to_bitmap(sorted(s for s in supports if len(s) >= 2),
                           n_items)
    Cj = jnp.asarray(C)
    want = np.asarray(support_count_ref(Tj, Cj))
    blocks = {}
    t0 = time.perf_counter()
    for cfg in ({"variant": "mxu", "bn": ROW_TILE, "bm": 128, "bi": 512},
                {"variant": "packed", "bn": ROW_TILE, "bm": 128}):
        got = np.asarray(support_count(Tj, Cj, interpret=interpret,
                                       tuning=cfg))
        check(np.array_equal(got, want),
              f"kernels: support_count {cfg} differs from the jnp ref")
        blocks[f"support_count/{cfg['variant']}"] = n_rows // ROW_TILE
    # the checked-in cache's config, fitted to row counts no sweep measured
    for rows in OFF_LATTICE_ROWS:
        got = np.asarray(support_count(Tj[:rows], Cj, interpret=interpret))
        check(np.array_equal(got, np.asarray(support_count_ref(Tj[:rows],
                                                               Cj))),
              f"kernels: support_count at {rows} rows differs from the "
              f"jnp ref")
        blocks[f"support_count/cache@{rows}"] = \
            rows // LAST_DISPATCH["support_count"]["config"]["bn"]
    # Eclat: candidate pairs' tid-lists, several row and word blocks
    cols = pack_tid_columns(T)
    pairs = sorted(s for s in supports if len(s) == 2)
    A = jnp.asarray(cols[[a for a, _ in pairs]])
    B = jnp.asarray(cols[[b for _, b in pairs]])
    got = np.asarray(intersect_count(A, B, interpret=interpret,
                                     tuning={"variant": "packed", "bm": 128,
                                             "bw": 128}))
    check(np.array_equal(got, np.asarray(intersect_count_ref(A, B))),
          "kernels: intersect_count differs from the jnp ref")
    blocks["intersect_count/packed"] = -(-len(pairs) // 128)
    # serving: a batch of baskets against the compiled rule index
    index = RuleIndex.build(rules, n_items)
    Q = corpus(n_queries, n_items, seed=7)
    args = (jnp.asarray(np.pad(Q, ((0, 0), (0, index.n_items_padded
                                             - n_items)))),
            jnp.asarray(index.ante), jnp.asarray(index.sizes),
            jnp.asarray(index.conf), jnp.asarray(index.cons))
    want_i, want_s = rule_topk(*args, k=5, n_items=n_items, backend="ref")
    for cfg in ({"variant": "mxu", "bb": 64, "br": 128, "bi": 512},
                {"variant": "packed", "bb": 64, "br": 128}):
        got_i, got_s = rule_topk(*args, k=5, n_items=n_items,
                                 backend="pallas", interpret=interpret,
                                 tuning=cfg)
        check(np.array_equal(np.asarray(got_i), np.asarray(want_i))
              and np.array_equal(np.asarray(got_s), np.asarray(want_s)),
              f"kernels: rule_match {cfg} differs from the jnp ref")
        blocks[f"rule_match/{cfg['variant']}"] = -(-n_queries // 64)
    return {"phase": "kernels", "backend": "pallas",
            "dispatch": dispatched("support_count", "intersect_count",
                                   "rule_match"),
            "row_blocks": blocks, "candidates": int(C.shape[0]),
            "wall_s": time.perf_counter() - t0}


def phase_serve(rules, n_items: int, n_queries: int,
                data_plane: str = "auto", interpret=None) -> dict:
    index = RuleIndex.build(rules, n_items)
    engine = RecommendationEngine(
        index, config=ServingConfig(k=5, batch_buckets=(1, 8, 64),
                                    data_plane=data_plane,
                                    interpret=interpret))
    LAST_DISPATCH.clear()
    baskets = corpus(n_queries, n_items, seed=11)
    t0 = time.perf_counter()
    server = AsyncServer(engine)              # warms every bucket first
    handles = [server.submit(Query.of(row), arrival_s=0.0)
               for row in baskets]
    server.drain()
    wall = time.perf_counter() - t0
    answered = 0
    for h, row in zip(handles, baskets):
        check(h.status == "done", f"serve: request {h.rid} is {h.status}")
        want = recommend_bruteforce(rules, np.nonzero(row)[0].tolist(), 5)
        check(h.result() == want,
              f"serve: basket {np.nonzero(row)[0].tolist()} got "
              f"{h.result()}, oracle {want}")
        answered += bool(want)
    check(answered > 0, "serve: no basket matched any rule")
    report = server.take_report()
    return {"phase": "serve", "backend": engine.backend,
            "dispatch": dispatched("rule_match"), "wall_s": wall,
            "queries": len(handles), "answered": answered,
            "index_rules": index.n_rules, "index_rows": index.n_rows,
            "index_rows_padded": index.n_rows_padded,
            "steps": report.n_steps}


def phase_sharded(T: np.ndarray, min_support: float, n_devices: int,
                  data_plane: str = "auto", interpret=None) -> dict:
    """Sharded Apriori over an n-device mesh vs the single-device
    pipeline; every rank's slab must sit on its own device."""
    cfg = PipelineConfig(min_support=min_support, data_plane=data_plane,
                         interpret=interpret)
    mesh = make_shard_mesh(n_devices)
    LAST_DISPATCH.clear()
    miner = ShardedMiner(mesh=mesh, profile=mesh_profile(n_devices),
                         config=cfg)
    got, wall = _timed(lambda: miner.run(T))
    rec = {"phase": "sharded", "backend": got.report.backend,
           "dispatch": dispatched("support_count"), "wall_s": wall,
           "shard_devices": got.report.shard_devices,
           "shard_rows": got.report.shard_rows}
    check(len(set(got.report.shard_devices)) == n_devices,
          f"sharded: shards on devices {got.report.shard_devices}, "
          f"not {n_devices} distinct ones")
    want = MarketBasketPipeline(config=cfg).run(T)
    check(got.supports == want.supports,
          "sharded: supports differ from the single-device pipeline")
    check(got.rules == want.rules,
          "sharded: rules differ from the single-device pipeline")
    rec.update(itemsets=len(got.supports), rules=len(got.rules))
    return rec


# ---------------------------------------------------------------------------

def describe(rec: dict) -> str:
    parts = [f"[smoke] {rec['phase']}: backend={rec['backend']}"]
    for kernel, d in rec["dispatch"].items():
        cfg = ",".join(f"{k}={v}" for k, v in sorted(d["config"].items()))
        parts.append(f"{kernel}[{cfg}] from {d['source']} at shape "
                     f"{list(d['shape'])}, interpret={d['interpret']}")
    extra = {k: v for k, v in rec.items()
             if k not in ("phase", "backend", "dispatch", "wall_s")}
    if extra:
        parts.append(json.dumps(extra, sort_keys=True))
    parts.append(f"smoke timing {rec['wall_s']:.3f} s (one-off wall incl. "
                 f"compilation; not a metric)")
    return " | ".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Apriori mine over a 4-chip "
                         "mesh and the single-device pipeline it must equal")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=512,
                    help="baskets answered through the AsyncServer")
    args = ap.parse_args(argv)
    try:
        device = require_tpu(jax.devices())
        if args.four_chips:
            check(device["count"] >= 4,
                  f"--four-chips needs 4 devices, found {device['count']}")
        print(f"[smoke] device {device} | compile cache "
              f"{enable_compile_cache()}", flush=True)
        T, wall = _timed(lambda: corpus(N_TX, N_ITEMS, args.seed))
        print(f"[smoke] corpus {T.shape[0]} x {T.shape[1]} items "
              f"(seed {args.seed}), mean basket {T.sum(1).mean():.2f}, "
              f"generated in {wall:.2f} s", flush=True)
        if args.four_chips:
            rec = phase_sharded(T, MIN_SUPPORT, 4)
            print(describe(rec), flush=True)
            check_compiled(rec)
        else:
            apriori, rec = phase_apriori(T, MIN_SUPPORT)
            print(describe(rec), flush=True)
            check_compiled(rec)
            for phase in (
                    lambda: phase_eclat(T, MIN_SUPPORT, apriori),
                    lambda: phase_kernels(T, apriori.supports, apriori.rules,
                                          args.queries),
                    lambda: phase_serve(apriori.rules, N_ITEMS,
                                        args.queries)):
                rec = phase()
                print(describe(rec), flush=True)
                check_compiled(rec)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
