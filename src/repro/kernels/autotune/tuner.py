"""The autotune sweep: measure every candidate config, verify it
bit-identical, cache the argmin.

Measurement discipline (the same fix applied to ``bench_kernels``): the
warm-up call is ``block_until_ready``-synced so compile time never leaks
into the first rep, then the config's cost is the **median of >= 3
synced reps** — tile decisions made on one noisy dispatch are how a
tuner ends up *pessimizing* a kernel.

Correctness discipline: a config may only win if its output is exactly
equal to the jnp oracle's (int32 counts / f32 confidence-weighted
scores — both exact, so equality is bit-equality).  Mismatching configs
are recorded (``matched=False``) and excluded from the argmin; the
differential-fuzz harness (`tests/test_kernel_fuzz.py`) holds the whole
candidate space to the same bar.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.autotune.cache import AutotuneCache, device_kind
from repro.kernels.rule_match.fused import rule_scores_fused
from repro.kernels.rule_match.kernel import rule_scores_pallas
from repro.kernels.rule_match.ref import rule_scores_ref
from repro.kernels.support_count.fused import support_count_fused
from repro.kernels.support_count.intersect import intersect_count_pallas
from repro.kernels.support_count.kernel import support_count_mxu
from repro.kernels.support_count.ref import (intersect_count_ref,
                                             support_count_ref)
from repro.launch.tuning import kernel_candidates, seed_order


@dataclass
class SweptConfig:
    config: Dict[str, Any]
    cost_us: float
    matched: bool                     # bit-identical to the oracle


@dataclass
class TuneResult:
    kernel: str
    shape: Tuple[int, ...]
    device: str
    best: Dict[str, Any]
    cost_us: float
    swept: List[SweptConfig] = field(default_factory=list)

    def summary(self) -> str:
        return (f"{self.kernel} {self.shape} [{self.device}]: "
                f"{self.best} @ {self.cost_us:.1f}us "
                f"({len(self.swept)} configs swept)")


# ---------------------------------------------------------------------------
# synthetic inputs + per-kernel runners (kernel entry points, not the ops
# wrappers — the tuner must pin tiles exactly, not re-enter the resolver)
# ---------------------------------------------------------------------------

def make_inputs(kernel: str, shape: Tuple[int, ...], seed: int = 0
                ) -> Dict[str, jnp.ndarray]:
    """Padded synthetic inputs at the sweep shape, density matched to the
    planes (sparse transactions/baskets, 1-4 item candidates/antecedents,
    a tail of never-match padding rows on the serving side)."""
    rng = np.random.default_rng(seed)
    if kernel == "intersect_count":
        # two random packed tid-slabs (every bit pattern is a legal
        # tid-list, so uniform uint32 words exercise the full popcount)
        m, w = shape
        bits = rng.integers(0, 2**32, size=(2, m, w), dtype=np.uint32)
        return {"A": jnp.asarray(bits[0]), "B": jnp.asarray(bits[1])}
    n, m, i = shape
    X = (rng.random((n, i)) < 0.3).astype(np.int8)
    A = np.zeros((m, i), np.int8)
    for r in range(m):
        A[r, rng.choice(i, size=1 + r % 4, replace=False)] = 1
    if kernel == "support_count":
        return {"T": jnp.asarray(X), "C": jnp.asarray(A)}
    # rule_match: last eighth of the rows are index padding (sizes=-1)
    pad_from = m - max(m // 8, 1)
    sizes = A.astype(np.float32).sum(axis=1)
    conf = rng.random(m).astype(np.float32) * 0.9 + 0.1
    A[pad_from:] = 0
    sizes[pad_from:] = -1.0
    conf[pad_from:] = 0.0
    return {"Q": jnp.asarray(X), "A": jnp.asarray(A),
            "sizes": jnp.asarray(sizes[None, :]),
            "conf": jnp.asarray(conf[None, :])}


def run_config(kernel: str, config: Dict[str, Any],
               inputs: Dict[str, jnp.ndarray],
               interpret: bool) -> jnp.ndarray:
    cfg = dict(config)
    variant = cfg.pop("variant")
    if kernel == "intersect_count":
        return intersect_count_pallas(inputs["A"], inputs["B"],
                                      bm=cfg["bm"], bw=cfg["bw"],
                                      interpret=interpret)
    if kernel == "support_count":
        T, C = inputs["T"], inputs["C"]
        if variant == "packed":
            return support_count_fused(T, C, bn=cfg["bn"], bm=cfg["bm"],
                                       interpret=interpret)
        return support_count_mxu(T, C, bn=cfg["bn"], bm=cfg["bm"],
                                 bi=cfg["bi"], interpret=interpret)
    Q, A = inputs["Q"], inputs["A"]
    sizes, conf = inputs["sizes"], inputs["conf"]
    if variant == "packed":
        return rule_scores_fused(Q, A, sizes, conf, bb=cfg["bb"],
                                 br=cfg["br"], interpret=interpret)
    return rule_scores_pallas(Q, A, sizes, conf, bb=cfg["bb"], br=cfg["br"],
                              bi=cfg["bi"], interpret=interpret)


def oracle(kernel: str, inputs: Dict[str, jnp.ndarray]) -> np.ndarray:
    if kernel == "intersect_count":
        return np.asarray(intersect_count_ref(inputs["A"], inputs["B"])
                          )[None, :].astype(np.int32)
    if kernel == "support_count":
        return np.asarray(support_count_ref(inputs["T"], inputs["C"])
                          )[None, :].astype(np.int32)
    return np.asarray(rule_scores_ref(inputs["Q"], inputs["A"],
                                      inputs["sizes"][0], inputs["conf"][0]))


# ---------------------------------------------------------------------------
# measurement + the sweep
# ---------------------------------------------------------------------------

def measure_us(fn: Callable[[], Any], reps: int = 3,
               timer: Callable[[], float] = time.perf_counter) -> float:
    """Median wall µs over ``reps`` fully-synced calls (warm-up synced
    too, so compilation never pollutes rep 0)."""
    reps = max(int(reps), 3)
    jax.block_until_ready(fn())                  # compile + warm, synced
    walls = []
    for _ in range(reps):
        t0 = timer()
        jax.block_until_ready(fn())
        walls.append(timer() - t0)
    return float(np.median(walls)) * 1e6


def tune(kernel: str, shape: Tuple[int, ...], *,
         configs: Optional[Sequence[Dict[str, Any]]] = None,
         max_configs: int = 0, reps: int = 3, seed: int = 0,
         interpret: Optional[bool] = None,
         timer: Callable[[], float] = time.perf_counter) -> TuneResult:
    """Sweep one (kernel, shape): returns the measured argmin config.

    ``max_configs > 0`` truncates the roofline-ordered candidate list —
    the CI smoke mode (2 configs per kernel) still measures the configs
    the seed model believes in.  Raises if *no* config reproduces the
    oracle (a correctness bug, not a tuning failure).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cands = list(configs) if configs is not None \
        else seed_order(kernel, shape, kernel_candidates(kernel, shape))
    if max_configs > 0:
        cands = cands[:max_configs]
    inputs = make_inputs(kernel, shape, seed=seed)
    want = oracle(kernel, inputs)

    swept: List[SweptConfig] = []
    for cfg in cands:
        out = np.asarray(run_config(kernel, cfg, inputs, interpret))
        matched = out.shape == want.shape and np.array_equal(out, want)
        cost = measure_us(
            lambda c=cfg: run_config(kernel, c, inputs, interpret),
            reps=reps, timer=timer) if matched else float("inf")
        swept.append(SweptConfig(config=dict(cfg), cost_us=cost,
                                 matched=matched))
    ok = [s for s in swept if s.matched]
    if not ok:
        raise RuntimeError(f"autotune {kernel} {shape}: no candidate "
                           f"matched the oracle ({len(swept)} swept)")
    best = min(ok, key=lambda s: s.cost_us)
    return TuneResult(kernel=kernel, shape=tuple(shape),
                      device=device_kind(), best=best.config,
                      cost_us=best.cost_us, swept=swept)


def standard_shapes(kernel: str, smoke: bool = False
                    ) -> List[Tuple[int, int, int]]:
    """The sweep lattice: one shape per bucket the planes actually hit
    (mining tiles 64-1024 rows x 128-2048 candidates; serving buckets
    1-64 queries x 128-512 index rows), nearest-bucket lookup covers the
    rest.  ``smoke`` shrinks to one tiny shape for the CI sweep leg.

    support_count also sweeps the tiles a 100,000 x 1,000 Quest corpus
    (``bench/configs/quest-t10i4d100k.json``) puts through it: a 32-tile
    round's row tile against the k=2 level (113,050 candidates) and
    against a deep one, and ``chip_smoke.py``'s kernels phase.  Those
    are sized for the chip; interpret mode would take hours there.

    rule_match also sweeps the shapes the benchmark's serving cells
    launch over 1,000 items (1,024 lanes) at the 8- and 64-basket
    buckets: the 0.5%-support index (6,186 rows, 6,272 padded) and the
    1% one (32 rows, 128 padded), so each resolves a measured entry of
    its own."""
    if kernel == "support_count":
        if smoke:
            return [(64, 128, 128)]
        return [(n, m, 128) for n in (64, 256, 1024)
                for m in (128, 256, 512, 2048)] + [
            (3128, 113152, 1024), (3128, 128, 1024), (99840, 384, 1024)]
    if kernel == "intersect_count":
        # Eclat rounds: candidate count varies widely, word axis is
        # W = ceil(n_tx/32) padded to 128 lanes (128 words ≈ 4k tx)
        if smoke:
            return [(128, 128)]
        return [(m, w) for m in (128, 512, 2048) for w in (128, 256)]
    if smoke:
        return [(8, 128, 128)]
    return [(b, r, 128) for b in (8, 64) for r in (128, 512)] + [
        (b, r, 1024) for r in (6272, 128) for b in (8, 64)]


def tune_into(cache: AutotuneCache, kernel: str,
              shapes: Optional[Sequence[Tuple[int, ...]]] = None,
              log: Optional[Callable[[str], None]] = None,
              **tune_kwargs) -> List[TuneResult]:
    """Sweep a shape list into a cache (entries keyed per shape bucket)."""
    results = []
    for shape in shapes if shapes is not None else standard_shapes(kernel):
        res = tune(kernel, shape, **tune_kwargs)
        cache.put(kernel, res.shape, res.best, res.cost_us,
                  swept=[{"config": s.config, "cost_us":
                          (None if s.cost_us == float("inf")
                           else round(s.cost_us, 3)),
                          "matched": s.matched} for s in res.swept],
                  device=res.device)
        if log:
            log(res.summary())
        results.append(res)
    return results
