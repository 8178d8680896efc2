"""Bitmap Apriori — the paper's Market Basket Analysis steps 1–2 as
MapReduce rounds over a packed transaction bitmap.

Data plane (JAX / Pallas): transactions are a dense 0/1 matrix
``T ∈ uint8[n_tx, n_items]`` (item-minor, padded to 128 lanes); support of a
candidate bitmask row c is ``Σ_t 1[T_t ∧ c = c]``, computed on the MXU as
``dot(T, cᵀ) == |c|`` — see ``repro.kernels.support_count``.

Control plane (host): level-k candidate *generation* (the classic
F_{k-1}⋈F_{k-1} join + downward-closure prune) is tiny serial work — the
paper's "single-threaded task", which the MB Scheduler routes to one core
while gating the rest (power model hook).

``apriori`` below is the minimal reference driver (used by the property
tests); the production path with full scheduling/energy
accounting, data-plane batching and rule extraction is
``repro.pipeline.MarketBasketPipeline``, which shares this module's
candidate generation.  Behavioral changes to round semantics belong in
both, and each is pinned to the same brute-force oracle by tests.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.hetero import HeterogeneityProfile
from repro.core.mapreduce import FailureEvent, MapReduceJob, SimulatedCluster
from repro.core.scheduler import MBScheduler, TaskSpec


# ---------------------------------------------------------------------------
# support counting (data plane)
# ---------------------------------------------------------------------------

def support_counts_ref(T: jnp.ndarray, C: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle.  T: [N, I] uint8 0/1; C: [M, I] uint8 0/1 -> [M] int32."""
    dots = jnp.dot(T.astype(jnp.int32), C.astype(jnp.int32).T)   # [N, M]
    sizes = C.astype(jnp.int32).sum(axis=1)                      # [M]
    return (dots == sizes[None, :]).astype(jnp.int32).sum(axis=0)


def support_counts(T, C, use_pallas: bool = False) -> jnp.ndarray:
    if use_pallas:
        from repro.kernels.support_count.ops import support_count as sc
        return sc(T, C)
    return support_counts_ref(T, C)


# ---------------------------------------------------------------------------
# candidate generation (control plane, classic Apriori)
# ---------------------------------------------------------------------------

def generate_candidates(frequent: List[Tuple[int, ...]]) -> List[Tuple[int, ...]]:
    """F_{k-1} ⋈ F_{k-1} join + downward-closure prune.  Itemsets are sorted
    tuples of item ids."""
    if not frequent:
        return []
    k = len(frequent[0]) + 1
    fset = set(frequent)
    out: List[Tuple[int, ...]] = []
    by_prefix: Dict[Tuple[int, ...], List[int]] = {}
    for t in frequent:
        by_prefix.setdefault(t[:-1], []).append(t[-1])
    for prefix, lasts in by_prefix.items():
        lasts = sorted(lasts)
        for i, a in enumerate(lasts):
            for b in lasts[i + 1:]:
                cand = prefix + (a, b)
                # prune: every (k-1)-subset must be frequent
                if all(cand[:j] + cand[j + 1:] in fset for j in range(k)):
                    out.append(cand)
    return sorted(out)


def itemsets_to_bitmap(itemsets: Sequence[Tuple[int, ...]], n_items: int) -> np.ndarray:
    C = np.zeros((len(itemsets), n_items), dtype=np.uint8)
    for i, s in enumerate(itemsets):
        C[i, list(s)] = 1
    return C


# ---------------------------------------------------------------------------
# the level-wise Apriori driver (paper §V steps 1-2)
# ---------------------------------------------------------------------------

def frequent_itemsets(supports: Dict[Tuple[int, ...], int],
                      k: Optional[int] = None) -> List[Tuple[int, ...]]:
    """Sorted frequent itemsets from a supports dict, optionally one level."""
    items = supports.keys()
    if k is not None:
        items = (s for s in items if len(s) == k)
    return sorted(items)


@dataclass
class AprioriResult:
    supports: Dict[Tuple[int, ...], int]      # itemset -> absolute support
    n_tx: int
    levels: int
    reports: list = field(default_factory=list)

    def frequent(self, k: Optional[int] = None) -> List[Tuple[int, ...]]:
        return frequent_itemsets(self.supports, k)


def _tile_rows(T: np.ndarray, n_tiles: int) -> List[np.ndarray]:
    return [np.ascontiguousarray(t) for t in np.array_split(T, n_tiles) if len(t)]


def apriori(T: np.ndarray, min_support: int, *,
            cluster: Optional[SimulatedCluster] = None,
            n_tiles: int = 8,
            max_k: int = 0,
            use_pallas: bool = False,
            failures: Optional[List[FailureEvent]] = None) -> AprioriResult:
    """Level-wise frequent-itemset mining over a transaction bitmap.

    Each level is one MapReduce round: the map phase counts candidate
    supports on row-tiles of T, the reduce phase sums the count vectors
    (a psum tree on hardware; the combiner here).  min_support is absolute.
    """
    n_tx, n_items = T.shape
    if cluster is None:
        cluster = SimulatedCluster(HeterogeneityProfile.paper())
    # hoist the tile uploads: one h2d per tile for the whole mine, and all
    # per-tile map results stay device-resident until the round's single
    # np.asarray readback below (same contract as the pipeline plane)
    tiles = [jnp.asarray(t) for t in _tile_rows(T, n_tiles)]
    supports: Dict[Tuple[int, ...], int] = {}
    reports = []

    # ---- step 1: item frequency (<item, count>) ----
    job1 = MapReduceJob(
        name="mba-step1-item-counts",
        map_fn=lambda tile: tile.sum(axis=0, dtype=jnp.int32),
        combine_fn=lambda a, b: a + b,
        zero_fn=lambda: jnp.zeros(n_items, dtype=jnp.int32),
    )
    counts, rep = cluster.run(job1, tiles, failures=failures)
    counts = np.asarray(counts, dtype=np.int64)
    reports.append(("k=1", rep))
    frequent = [(int(i),) for i in np.nonzero(counts >= min_support)[0]]
    for (i,) in frequent:
        supports[(i,)] = int(counts[i])

    # ---- step 2 loop: candidate generation + support counting ----
    k = 2
    while frequent and (max_k == 0 or k <= max_k):
        cands = generate_candidates(frequent)
        if not cands:
            break
        C = itemsets_to_bitmap(cands, n_items)
        Cj = jnp.asarray(C)

        def map_fn(tile, Cj=Cj):
            return support_counts(tile, Cj, use_pallas=use_pallas)

        job = MapReduceJob(
            name=f"mba-step2-support-k{k}",
            map_fn=map_fn,
            combine_fn=lambda a, b: a + b,
            zero_fn=lambda m=len(cands): jnp.zeros(m, dtype=jnp.int32),
        )
        sup, rep = cluster.run(job, tiles, failures=failures)
        sup = np.asarray(sup, dtype=np.int64)   # the round's one readback
        reports.append((f"k={k}", rep))
        frequent = []
        for c, s in zip(cands, sup):
            if s >= min_support:
                supports[c] = int(s)
                frequent.append(c)
        k += 1

    return AprioriResult(supports=supports, n_tx=n_tx, levels=k - 1,
                         reports=reports)


# ---------------------------------------------------------------------------
# brute-force oracle for tests
# ---------------------------------------------------------------------------

def apriori_bruteforce(T: np.ndarray, min_support: int, max_k: int = 4) -> Dict[Tuple[int, ...], int]:
    n_tx, n_items = T.shape
    out: Dict[Tuple[int, ...], int] = {}
    frequent_items = [i for i in range(n_items) if T[:, i].sum() >= min_support]
    for k in range(1, max_k + 1):
        any_f = False
        for comb in itertools.combinations(frequent_items, k):
            s = int(np.all(T[:, list(comb)] == 1, axis=1).sum())
            if s >= min_support:
                out[tuple(comb)] = s
                any_f = True
        if not any_f:
            break
    return out
