"""The control: the plain reference put in the place of the system under
test, computed in a precision that breaks a guarantee the configuration
states, which the comparison has to read as wrong.

* ``mine_control``: every mine the window drives is the reference with
  its support counts kept in bfloat16 (exact only up to 256), the step
  that would tempt a later change to the counting kernels.
* ``serve_control``: every scoring step the server runs is the reference
  top-k with rule confidences in bfloat16.

Neither is part of a benchmark run; ``tools/control.py`` runs them on the
chip and the tests run them at a small size.
"""
from __future__ import annotations

import contextlib
from collections import namedtuple
from types import SimpleNamespace

import numpy as np

import jax.numpy as jnp

from mba_bench import reference

Rule = namedtuple("Rule", "antecedent consequent support confidence lift")


class ReferenceMiner:
    def __init__(self, config, count_dtype):
        self.config = config
        self.count_dtype = count_dtype

    def run(self, T):
        got = reference.mine(np.asarray(T), self.config.min_support,
                             self.config.min_confidence, self.count_dtype)
        rules = [Rule(a, c, *v) for (a, c), v in got.rules.items()]
        return SimpleNamespace(supports=got.supports, rules=rules,
                               report=None)


@contextlib.contextmanager
def mine_control(count_dtype=jnp.bfloat16):
    import repro.mining
    real = repro.mining.make_miner

    def make_miner(baskets, config=None, **_):
        return ReferenceMiner(config, count_dtype), None

    repro.mining.make_miner = make_miner
    try:
        yield
    finally:
        repro.mining.make_miner = real


@contextlib.contextmanager
def serve_control(score_dtype=jnp.bfloat16):
    from repro.serving.engine import RecommendationEngine
    real = RecommendationEngine._score_batch
    rule_maps = {}        # index version -> its rows as reference rules

    def _score_batch(self, rows, bucket):
        index = self.index
        if index.version not in rule_maps:
            rule_maps[index.version] = {
                (tuple(int(i) for i in np.nonzero(index.ante[r])[0]),
                 (int(index.cons[r]),)): (0.0, float(index.conf[r]), 0.0)
                for r in range(index.n_rows)}
        return reference.topk(np.stack(rows), rule_maps[index.version],
                              index.n_items, self.config.k,
                              score_dtype=score_dtype)

    RecommendationEngine._score_batch = _score_batch
    try:
        yield
    finally:
        RecommendationEngine._score_batch = real
