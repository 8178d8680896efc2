"""Device-resident round execution: the transfer ledger is exact for a
scripted two-round mine, the pipelined path makes exactly one d2h sync per
counting round (the per_tile baseline makes one per tile), the on-device
candidate join/prune matches the host generate_candidates bit for bit
(including the guarded host fallback), and both execution modes mine
identical supports/rules on Apriori and Eclat."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.itemsets import (apriori_bruteforce, generate_candidates,
                                 itemsets_to_bitmap)
from repro.data.baskets import BasketConfig, generate_baskets
from repro.mining import EclatMiner
from repro.pipeline import MarketBasketPipeline, PipelineConfig
from repro.pipeline.dataplane import pad_candidates
from repro.pipeline.devgen import DeviceLattice


def _mk_cfg(**kw):
    base = dict(min_support=0.05, min_confidence=0.5, n_tiles=4,
                data_plane="ref")
    base.update(kw)
    return PipelineConfig(**base)


# ---------------------------------------------------------------------------
# ledger exactness: every byte and sync of a scripted 2-round mine
# ---------------------------------------------------------------------------

def test_two_round_mine_transfer_ledger_is_exact():
    T = generate_baskets(BasketConfig(n_tx=256, n_items=24, seed=3))
    cfg = _mk_cfg(max_k=2)
    res = MarketBasketPipeline(config=cfg).run(T)
    rounds = res.report.rounds
    assert len(rounds) == 2 and rounds[1].n_frequent > 0, \
        "fixture must mine two full rounds with surviving pairs"
    led = res.report.ledger
    by_name = {p.name: p for p in led.phases}
    n_items_pad = 128                       # 24 raw items, lane-padded
    f1 = rounds[0].n_frequent
    f1_cap = max(cfg.m_bucket, -(-f1 // cfg.m_bucket) * cfg.m_bucket)
    m_cap = rounds[1].m_padded
    f2 = rounds[1].n_frequent

    # the one-time upload of the raw bitmap (256 uint8 rows of 24 items;
    # the device pads the lanes) is its own phase; ingest before it moves
    # nothing across the boundary
    ing, up = by_name["mba-ingest"], by_name["mba-upload"]
    assert ing.h2d_bytes == ing.d2h_bytes == ing.syncs == 0
    assert up.h2d_bytes == 256 * 24
    assert up.d2h_bytes == 0 and up.syncs == 0

    # round 1: no upload; the single readback is the padded int64
    # item-count vector
    r1 = by_name["mba-round1-item-counts"]
    assert r1.h2d_bytes == 0
    assert r1.d2h_bytes == n_items_pad * 8
    assert r1.syncs == 1

    # candgen k=2: the frequent-item seed upload ([f1_cap, 1] int32) is
    # consumed here; the device join itself transfers nothing
    cg = by_name["mba-candgen-k2"]
    assert cg.h2d_bytes == f1_cap * 4
    assert cg.d2h_bytes == 0 and cg.syncs == 0

    # round 2: no upload (candidates were born on device); the one d2h is
    # the packed [m_cap + 1] int32 counts-plus-join-size vector
    r2 = by_name["mba-round2-support"]
    assert r2.h2d_bytes == 0
    assert r2.d2h_bytes == (m_cap + 1) * 4
    assert r2.syncs == 1

    # rules: one decode per mined level >= 2 — here one [f2, 2] int32 read
    ru = by_name["mba-rules"]
    assert ru.h2d_bytes == 0
    assert ru.d2h_bytes == f2 * 2 * 4
    assert ru.syncs == 1

    assert led.total_h2d_bytes == up.h2d_bytes + cg.h2d_bytes
    assert led.total_d2h_bytes == (r1.d2h_bytes + r2.d2h_bytes
                                   + ru.d2h_bytes)
    assert led.total_syncs == 3


# ---------------------------------------------------------------------------
# the one-sync-per-round contract (asserted, not just benched)
# ---------------------------------------------------------------------------

def test_pipelined_syncs_once_per_round_per_tile_syncs_per_tile():
    T = generate_baskets(BasketConfig(n_tx=512, n_items=32, seed=5))
    runs = {}
    for rexec in ("pipelined", "per_tile"):
        res = MarketBasketPipeline(config=_mk_cfg(round_execution=rexec)
                                   ).run(T)
        maps = res.report.ledger.by_kind("map")
        assert maps, "mine must run at least one counting round"
        if rexec == "pipelined":
            assert all(p.syncs == 1 for p in maps), \
                [(p.name, p.syncs) for p in maps]
        else:
            assert all(p.syncs == p.n_tiles == 4 for p in maps), \
                [(p.name, p.syncs) for p in maps]
        runs[rexec] = res

    # both modes mine the same answer, and it is the oracle's
    want = apriori_bruteforce(T, max(1, int(0.05 * 512)), max_k=8)
    assert runs["pipelined"].supports == runs["per_tile"].supports == want
    assert runs["pipelined"].rules == runs["per_tile"].rules


def test_round_execution_knob_is_validated():
    with pytest.raises(ValueError):
        MarketBasketPipeline(config=_mk_cfg(round_execution="bogus"))


# ---------------------------------------------------------------------------
# on-device candidate generation vs the host reference
# ---------------------------------------------------------------------------

def _decoded(C, valid_c):
    Ch, v = np.asarray(C), np.asarray(valid_c)
    return [tuple(int(x) for x in row) for row in Ch[v]]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("lat_kw", [{}, {"max_join_rows": 0}],
                         ids=["device-join", "host-fallback"])
def test_device_join_prune_matches_generate_candidates(seed, lat_kw):
    rng = np.random.default_rng(seed)
    n_items, min_sup = 16, 5
    lat = DeviceLattice(n_items, m_bucket=8, **lat_kw)
    items = np.sort(rng.choice(n_items, size=9, replace=False))
    lat.seed_items(items)
    frequent = [(int(i),) for i in items]
    expect_supports = {}
    for k in (2, 3, 4, 5):
        want = generate_candidates(frequent)
        gen = lat.join()
        if not want:
            # every pair pruned (or J = 0): both the device join — which
            # reads back the survivor count before sizing the round — and
            # the host fallback report the round dry
            assert gen is None
            break
        assert gen is not None
        C, valid_c, bitmap, m_cap = gen
        assert _decoded(C, valid_c) == want
        ref_bitmap = pad_candidates(itemsets_to_bitmap(want, n_items), m_cap)
        assert (np.asarray(bitmap) == ref_bitmap).all()

        # fabricate this round's counts and close it through the real
        # finalize/advance protocol (order is positional — the invariant
        # the device join guarantees)
        counts = rng.integers(0, 10, size=len(want))
        acc = jnp.zeros(m_cap, jnp.int32).at[:len(want)].set(
            jnp.asarray(counts, jnp.int32))
        packed, Fn, vn = lat.finalize(acc, C, valid_c, min_sup)
        m_true, f_true = lat.advance(np.asarray(packed), Fn, vn, min_sup)
        frequent = [c for c, s in zip(want, counts) if s >= min_sup]
        assert m_true == len(want) and f_true == len(frequent)
        expect_supports.update(
            {c: int(s) for c, s in zip(want, counts) if s >= min_sup})
        if not frequent:
            break
    assert lat.decode_supports() == expect_supports


# ---------------------------------------------------------------------------
# cross-mode parity on both algorithms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["apriori", "eclat"])
@pytest.mark.parametrize("policy", ["static", "dynamic"])
def test_both_modes_mine_identically(algorithm, policy):
    T = generate_baskets(BasketConfig(n_tx=384, n_items=28, seed=9))
    results = []
    for rexec in ("pipelined", "per_tile"):
        cfg = _mk_cfg(algorithm=algorithm, policy=policy,
                      round_execution=rexec)
        miner = (EclatMiner(config=cfg) if algorithm == "eclat"
                 else MarketBasketPipeline(config=cfg))
        results.append(miner.run(T))
    want = apriori_bruteforce(T, max(1, int(0.05 * 384)), max_k=8)
    assert results[0].supports == results[1].supports == want
    assert results[0].rules == results[1].rules
