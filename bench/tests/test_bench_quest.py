"""The Quest generator: deterministic per seed, and its mean basket and
pattern sizes near |T| and |I| (Agrawal & Srikant, VLDB 1994, 2.4.3)."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mba_bench.quest import QuestModel, QuestParams  # noqa: E402


def model(T=10, I=4, D=4000, L=200, N=500, seed=3):
    return QuestModel.build(QuestParams(D, T, I, L, N), seed)


def test_same_seed_same_corpus_and_baskets():
    a, b = model(), model()
    assert np.array_equal(a.corpus(3), b.corpus(3))
    ra, rb = np.random.default_rng(9), np.random.default_rng(9)
    assert np.array_equal(a.baskets(300, ra), b.baskets(300, rb))


def test_other_seed_other_corpus():
    assert not np.array_equal(model(seed=3).corpus(3),
                              model(seed=4).corpus(4))


@pytest.mark.parametrize("T,I", [(10, 4), (20, 6)])
def test_mean_sizes_near_parameters(T, I):
    m = model(T=T, I=I)
    C = m.corpus(3)
    assert C.shape == (4000, 500) and C.dtype == np.uint8
    assert set(np.unique(C)) <= {0, 1}
    assert abs(C.sum(1).mean() - T) < 0.1 * T
    assert abs(m.patterns.sizes.mean() - I) < 0.1 * I


def test_patterns_share_items_with_their_predecessor():
    pat = model(L=400).patterns
    shared = [len(set(pat.items[j, :pat.sizes[j]])
                  & set(pat.items[j - 1, :pat.sizes[j - 1]]))
              for j in range(1, len(pat.sizes))]
    # exponential share with mean 0.5 of ~4 items: ~2 shared on average,
    # where independent draws over 500 items would share ~0.03
    assert 1.0 < np.mean(shared) < 3.0


def test_weights_and_corruption():
    pat = model().patterns
    assert np.isclose(pat.weights.sum(), 1.0)
    assert (pat.corruption >= 0).all() and (pat.corruption < 1).all()
    assert abs(pat.corruption.mean() - 0.5) < 0.1
