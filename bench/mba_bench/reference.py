"""The plain reference: the same semantics as the system under test,
written out directly, importing nothing of it.

* Frequent itemsets: level-wise.  Level 1 counts items; level 2 counts
  every pair of frequent items as one co-occurrence matrix ``Fᵀ F``; level
  k >= 3 joins the frequent (k-1)-sets that share their first k-2 items,
  keeps the candidates whose every (k-1)-subset is frequent, and counts
  each candidate c as the rows t with ``t · c == |c|``.  Counts run on
  the default JAX device in integer arithmetic, in blocks of candidates.
* Rules: for every frequent itemset S and non-empty proper subset A,
  ``A => S - A`` with support s(S)/n, confidence s(S)/s(A) >= the
  minimum, and lift conf / (s(S - A)/n), all in Python floats.
* Served top-k: score(item) is the largest confidence (as float32) of the
  rules whose antecedent lies in the basket and whose consequent holds
  the item; items in the basket and items that score 0 are left out;
  ties go to the lower item id.

``count_dtype`` and ``score_dtype`` exist for the control: the same
reference with counts or scores kept in bfloat16 must read as wrong.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

Itemset = Tuple[int, ...]
RuleKey = Tuple[Itemset, Itemset]

CAND_BLOCK = 1024      # candidates counted per call at levels >= 3
QUERY_BLOCK = 4096     # baskets scored per call


@dataclass
class MinedReference:
    supports: Dict[Itemset, int]
    rules: Dict[RuleKey, Tuple[float, float, float]]   # support, conf, lift
    # per level k >= 2: (candidates counted, distinct items among them)
    levels: List[Tuple[int, int]] = field(default_factory=list)


def _lanes(n: int) -> int:
    return n + (-n) % 128


@partial(jax.jit, static_argnames=("dtype",))
def _pair_counts(T, dtype):
    if dtype == jnp.int32:
        return jnp.dot(T.T, T, preferred_element_type=jnp.int32)
    return jnp.dot(T.T.astype(dtype), T.astype(dtype),
                   preferred_element_type=dtype)


@partial(jax.jit, static_argnames=("k", "dtype"))
def _block_counts(T, C, k, dtype):
    dots = jnp.dot(T, C.T, preferred_element_type=jnp.int32)
    hit = (dots == k)
    return hit.astype(dtype).sum(axis=0, dtype=dtype)


def _join(frequent: List[Itemset]) -> List[Itemset]:
    """Candidates of the next level from the sorted frequent itemsets."""
    fset = set(frequent)
    groups: Dict[Itemset, List[int]] = {}
    for s in frequent:
        groups.setdefault(s[:-1], []).append(s[-1])
    out = []
    for prefix, lasts in groups.items():
        lasts.sort()
        for a, b in itertools.combinations(lasts, 2):
            cand = prefix + (a, b)
            if all(cand[:d] + cand[d + 1:] in fset
                   for d in range(len(cand) - 2)):
                out.append(cand)
    out.sort()
    return out


def frequent_itemsets(T: np.ndarray, min_sup: int,
                      count_dtype=jnp.int32) -> Tuple[Dict[Itemset, int],
                                                      List[Tuple[int, int]]]:
    """Supports of every itemset with support >= ``min_sup`` (absolute),
    and per level k >= 2 the number of candidates and their distinct
    items."""
    n, n_items = T.shape
    item_counts = T.sum(axis=0, dtype=np.int64)
    if count_dtype != jnp.int32:
        item_counts = np.asarray(jnp.asarray(item_counts, jnp.float32)
                                 .astype(count_dtype).astype(jnp.float32),
                                 np.int64)
    f1 = [int(i) for i in np.nonzero(item_counts >= min_sup)[0]]
    supports = {(i,): int(item_counts[i]) for i in f1}
    levels: List[Tuple[int, int]] = []
    if len(f1) < 2:
        return supports, levels
    Tf = jnp.asarray(T[:, f1].astype(np.int8))
    pairs = np.asarray(_pair_counts(Tf, count_dtype)).astype(np.int64)
    del Tf
    levels.append((len(f1) * (len(f1) - 1) // 2, len(f1)))
    frequent = []
    for a in range(len(f1)):
        for b in np.nonzero(pairs[a, a + 1:] >= min_sup)[0] + a + 1:
            s = (f1[a], f1[int(b)])
            supports[s] = int(pairs[a, b])
            frequent.append(s)
    Tj = jnp.asarray(np.pad(T.astype(np.int8),
                            ((0, 0), (0, _lanes(n_items) - n_items))))
    k = 3
    while frequent:
        cands = _join(frequent)
        if not cands:
            break
        levels.append((len(cands), len({i for c in cands for i in c})))
        counts = []
        for lo in range(0, len(cands), CAND_BLOCK):
            block = cands[lo:lo + CAND_BLOCK]
            C = np.zeros((CAND_BLOCK, Tj.shape[1]), np.int8)
            rows = np.repeat(np.arange(len(block)), k)
            C[rows, np.asarray(block).reshape(-1)] = 1
            got = np.asarray(_block_counts(Tj, jnp.asarray(C), k,
                                           count_dtype))
            counts.append(got[:len(block)].astype(np.int64))
        counts = np.concatenate(counts)
        frequent = []
        for c, s in zip(cands, counts):
            if s >= min_sup:
                supports[c] = int(s)
                frequent.append(c)
        k += 1
    return supports, levels


def rules(supports: Dict[Itemset, int], n_tx: int,
          min_confidence: float) -> Dict[RuleKey, Tuple[float, float, float]]:
    out: Dict[RuleKey, Tuple[float, float, float]] = {}
    n = float(n_tx)
    for itemset, s in supports.items():
        for r in range(1, len(itemset)):
            for ante in itertools.combinations(itemset, r):
                conf = s / supports[ante]
                if conf < min_confidence:
                    continue
                cons = tuple(i for i in itemset if i not in ante)
                out[(ante, cons)] = (s / n, conf, conf / (supports[cons] / n))
    return out


def mine(T: np.ndarray, min_support: float, min_confidence: float,
         count_dtype=jnp.int32) -> MinedReference:
    """``min_support`` <= 1 is a share of the transactions, else a count."""
    n = T.shape[0]
    min_sup = (max(1, int(min_support * n)) if min_support <= 1.0
               else int(min_support))
    supports, levels = frequent_itemsets(T, min_sup, count_dtype)
    return MinedReference(supports, rules(supports, n, min_confidence),
                          levels)


@partial(jax.jit, static_argnames=("n_items", "k", "dtype"))
def _topk_block(Q, A, sizes, conf, cons, n_items, k, dtype):
    dots = jnp.dot(Q, A.T, preferred_element_type=jnp.int32)
    score = jnp.where(dots == sizes[None, :], conf.astype(dtype)[None, :],
                      jnp.zeros((), dtype)).astype(jnp.float32)
    width = Q.shape[1]
    per_item = jnp.zeros((Q.shape[0], width + 1), jnp.float32)
    per_item = per_item.at[:, cons].max(score)[:, :width]
    allowed = (Q == 0) & (jnp.arange(width)[None, :] < n_items)
    per_item = jnp.where(allowed, per_item, -1.0)
    return jax.lax.top_k(per_item, k)


def topk(baskets: np.ndarray, rule_map: Dict[RuleKey, Tuple[float, float,
                                                            float]],
         n_items: int, k: int,
         score_dtype=jnp.float32) -> List[List[Tuple[int, float]]]:
    """Top-k (item, score) for each basket row of the 0/1 ``baskets``."""
    width = _lanes(n_items)
    rows = [(ante, item, conf) for (ante, cons), (_, conf, _) in
            sorted(rule_map.items()) for item in cons]
    n_rows = _lanes(max(len(rows), 1))
    A = np.zeros((n_rows, width), np.int8)
    sizes = np.full(n_rows, -1, np.int32)
    conf = np.zeros(n_rows, np.float32)
    cons = np.full(n_rows, width, np.int32)
    for r, (ante, item, c) in enumerate(rows):
        A[r, list(ante)] = 1
        sizes[r] = len(ante)
        conf[r] = c
        cons[r] = item
    dev = [jnp.asarray(x) for x in (A, sizes, conf, cons)]
    out: List[List[Tuple[int, float]]] = []
    for lo in range(0, len(baskets), QUERY_BLOCK):
        blk = baskets[lo:lo + QUERY_BLOCK]
        Q = np.zeros((QUERY_BLOCK, width), np.int8)
        Q[:len(blk), :n_items] = blk
        scores, items = _topk_block(jnp.asarray(Q), *dev, n_items=n_items,
                                    k=k, dtype=score_dtype)
        scores, items = np.asarray(scores), np.asarray(items)
        for r in range(len(blk)):
            out.append([(int(i), float(s)) for i, s in
                        zip(items[r], scores[r]) if s > 0.0])
    return out


def mismatches(got: Dict, want: Dict) -> int:
    """Keys in one map and not the other, plus keys whose values differ."""
    return (len(got.keys() ^ want.keys())
            + sum(1 for key in got.keys() & want.keys()
                  if got[key] != want[key]))
