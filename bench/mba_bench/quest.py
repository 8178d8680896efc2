"""The IBM Quest synthetic basket generator, after Agrawal & Srikant,
"Fast Algorithms for Mining Association Rules", VLDB 1994, section 2.4.3.

A corpus is named ``T<|T|>I<|I|>D<|D|>`` by its parameters:

  |D|  transactions            |T|  mean transaction size
  |L|  potentially large itemsets ("patterns")
  |I|  mean pattern size       N    items

Patterns: sizes are Poisson(|I|) (at least 1).  The first pattern's items
are drawn at random; each later pattern takes a fraction of its items from
the previous pattern, the fraction exponentially distributed with mean 0.5
(the correlation level), and the rest at random.  Each pattern has a
weight, exponential with unit mean and normalised to sum 1, which is the
probability that it is picked, and a corruption level drawn from a normal
distribution with mean 0.5 and variance 0.1.

Transactions: sizes are Poisson(|T|).  A transaction is filled with picked
patterns.  Before a pattern goes in, items are dropped from it for as long
as a uniform draw stays below its corruption level.  A pattern that does
not fit is put in anyway in half the cases and carried over to the next
transaction in the other half; either way the transaction is closed.

"Fits" compares the transaction's target size with the items already
placed plus the pattern's size, counted with repeats (as the reference
generator does); the bitmap then holds their union, so the mean basket is
a little under |T|.

Everything is drawn from one ``numpy`` generator per stream, so a seed gives
the same corpus on every machine.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CORRELATION_MEAN = 0.5
CORRUPTION_MEAN = 0.5
CORRUPTION_VAR = 0.1


@dataclass(frozen=True)
class QuestParams:
    n_transactions: int       # |D|
    mean_transaction: float   # |T|
    mean_pattern: float       # |I|
    n_patterns: int           # |L|
    n_items: int              # N

    @classmethod
    def from_config(cls, quest: dict) -> "QuestParams":
        return cls(n_transactions=int(quest["D"]),
                   mean_transaction=float(quest["T"]),
                   mean_pattern=float(quest["I"]),
                   n_patterns=int(quest["L"]),
                   n_items=int(quest["N"]))


@dataclass(frozen=True)
class Patterns:
    """The potentially large itemsets: ``items[j, :sizes[j]]`` holds pattern
    j's item ids (sorted, -1 beyond its size)."""

    items: np.ndarray        # int32 [L, max size]
    sizes: np.ndarray        # int32 [L]
    weights: np.ndarray      # float64 [L], sums to 1
    corruption: np.ndarray   # float64 [L], clipped into [0, 1)


def make_patterns(p: QuestParams, rng: np.random.Generator) -> Patterns:
    sizes = np.maximum(rng.poisson(p.mean_pattern, p.n_patterns), 1)
    sizes = np.minimum(sizes, p.n_items)
    items = np.full((p.n_patterns, int(sizes.max())), -1, np.int32)
    prev = None
    for j, size in enumerate(sizes):
        if prev is None:
            chosen = rng.choice(p.n_items, size, replace=False)
        else:
            frac = rng.exponential(CORRELATION_MEAN)
            n_prev = min(int(frac * size + 0.5), size, len(prev))
            kept = rng.choice(prev, n_prev, replace=False)
            free = np.setdiff1d(np.arange(p.n_items), kept)
            fresh = rng.choice(free, size - n_prev, replace=False)
            chosen = np.concatenate([kept, fresh])
        chosen = np.sort(chosen)
        items[j, :size] = chosen
        prev = chosen
    weights = rng.exponential(1.0, p.n_patterns)
    weights /= weights.sum()
    corruption = np.clip(rng.normal(CORRUPTION_MEAN, np.sqrt(CORRUPTION_VAR),
                                    p.n_patterns), 0.0, 0.999)
    return Patterns(items=items, sizes=sizes.astype(np.int32),
                    weights=weights, corruption=corruption)


def _picks(pat: Patterns, n: int, rng: np.random.Generator):
    """n corrupted pattern picks: (pattern ids, keep mask [n, max size])."""
    j = rng.choice(len(pat.sizes), n, p=pat.weights)
    sizes = pat.sizes[j]
    # items dropped = run of uniform draws below c: failures before the
    # first "stop" of a geometric with success 1 - c
    n_drop = np.minimum(rng.geometric(1.0 - pat.corruption[j]) - 1, sizes)
    width = pat.items.shape[1]
    slot = np.arange(width)[None, :]
    valid = slot < sizes[:, None]
    keys = np.where(valid, rng.random((n, width)), np.inf)
    rank = np.argsort(np.argsort(keys, axis=1), axis=1)
    keep = valid & (rank >= n_drop[:, None])
    return j, keep


def _fill(pat: Patterns, n_tx: int, mean_size: float,
          rng: np.random.Generator, n_items: int) -> np.ndarray:
    """``n_tx`` Quest transactions as a 0/1 uint8 bitmap [n_tx, n_items]."""
    tgt = rng.poisson(mean_size, n_tx).tolist()
    mean_kept = max(float((pat.sizes * pat.weights).sum()) * 0.5, 0.5)
    chunk = int(n_tx * (mean_size / mean_kept) * 1.5) + 64
    pick_j, keep_rows, tx_rows = [], [], []
    t, used = 0, 0
    while t < n_tx:
        j, keep = _picks(pat, chunk, rng)
        kept = keep.sum(axis=1).tolist()
        coin = (rng.random(2 * chunk) < 0.5).tolist()
        tx_of = np.full(chunk, -1, np.int64)
        i, flips = 0, 0
        while i < chunk and t < n_tx:
            s = kept[i]
            if used + s <= tgt[t]:
                tx_of[i] = t
                used += s
                i += 1
                continue
            if flips == len(coin):
                break
            if coin[flips]:         # put in anyway
                tx_of[i] = t
                i += 1
            flips += 1              # either way the transaction closes
            t += 1
            used = 0
        done = tx_of >= 0
        pick_j.append(j[done])
        keep_rows.append(keep[done])
        tx_rows.append(tx_of[done])
    j = np.concatenate(pick_j)
    keep = np.concatenate(keep_rows)
    tx = np.concatenate(tx_rows)
    items = pat.items[j]
    rows = np.broadcast_to(tx[:, None], items.shape)[keep]
    T = np.zeros((n_tx, n_items), np.uint8)
    T[rows, items[keep]] = 1
    return T


@dataclass(frozen=True)
class QuestModel:
    """A configuration's patterns, from which corpora and baskets are drawn."""

    params: QuestParams
    patterns: Patterns

    @classmethod
    def build(cls, params: QuestParams, seed: int) -> "QuestModel":
        rng = np.random.default_rng([int(seed), 0])
        return cls(params, make_patterns(params, rng))

    def corpus(self, seed: int) -> np.ndarray:
        """The |D| x N bitmap that the generator seed gives."""
        rng = np.random.default_rng([int(seed), 1])
        return _fill(self.patterns, self.params.n_transactions,
                     self.params.mean_transaction, rng, self.params.n_items)

    def baskets(self, n: int, rng: np.random.Generator) -> np.ndarray:
        """n further transactions of the same model, from ``rng``."""
        return _fill(self.patterns, n, self.params.mean_transaction, rng,
                     self.params.n_items)
