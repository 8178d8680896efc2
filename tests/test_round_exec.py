"""Device-resident round execution: the transfer ledger is exact for a
scripted two-round mine, every counting round of the Apriori, Eclat and
streaming miners makes exactly one d2h sync, an Apriori round is one call
into the data plane whose counts equal the per-tile fold's, the on-device
candidate join/prune matches the host generate_candidates bit for bit
(including the guarded host fallback), and Apriori and Eclat mine the
oracle's supports/rules under every switching policy."""
import functools

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.itemsets import (AprioriResult, apriori_bruteforce,
                                 generate_candidates, itemsets_to_bitmap)
from repro.core.rules import generate_rules
from repro.data.baskets import (BasketConfig, generate_baskets,
                                stationary_baskets)
from repro.mining import EclatMiner
from repro.pipeline import MarketBasketPipeline, PipelineConfig
from repro.pipeline import pipeline as pipeline_module
from repro.pipeline.dataplane import (DataPlane, ResidentTiles, device_tiles,
                                      pad_candidates)
from repro.pipeline.devgen import DeviceLattice
from repro.streaming import StreamingConfig, StreamingMiner, TransactionStream


def _mk_cfg(**kw):
    base = dict(min_support=0.05, min_confidence=0.5, n_tiles=4,
                data_plane="ref")
    base.update(kw)
    return PipelineConfig(**base)


def _oracle(T, cfg):
    """Brute-force supports and the rules derived from them."""
    n_tx = T.shape[0]
    supports = apriori_bruteforce(T, cfg.abs_support(n_tx), max_k=8)
    levels = max(len(c) for c in supports)
    rules = generate_rules(AprioriResult(supports=supports, n_tx=n_tx,
                                         levels=levels),
                           cfg.min_confidence, min_lift=cfg.min_lift)
    return supports, rules


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

def test_apriori_syncs_once_per_round_and_mines_the_oracle():
    T = generate_baskets(BasketConfig(n_tx=512, n_items=32, seed=5))
    cfg = _mk_cfg()
    res = MarketBasketPipeline(config=cfg).run(T)
    maps = res.report.ledger.by_kind("map")
    assert len(maps) >= 2, "mine must run at least two counting rounds"
    assert all(p.syncs == 1 for p in maps), \
        [(p.name, p.syncs) for p in maps]
    want_supports, want_rules = _oracle(T, cfg)
    assert res.supports == want_supports
    assert res.rules == want_rules


def test_eclat_round_reads_back_once():
    T = generate_baskets(BasketConfig(n_tx=512, n_items=32, seed=5))
    cfg = _mk_cfg(algorithm="eclat")
    res = EclatMiner(config=cfg).run(T)
    rounds = [p for p in res.report.ledger.by_kind("map")
              if p.name.startswith("eclat-round")]
    assert {p.name for p in rounds} >= {"eclat-round1-item-counts",
                                        "eclat-round2-intersect"}
    assert all(p.syncs == 1 for p in rounds), \
        [(p.name, p.syncs) for p in rounds]
    assert res.supports == _oracle(T, cfg)[0]


def test_streaming_delta_and_validation_read_back_once():
    # a stationary stream, then a shifted one: deltas on every batch and
    # re-validations (k >= 2) both at warm-up and after the shift
    T = np.vstack([stationary_baskets(512, 32, n_patterns=4, seed=5),
                   stationary_baskets(512, 32, n_patterns=4, seed=6)])
    cfg = StreamingConfig(window=256, batch_size=64, min_support=0.15,
                          min_confidence=0.5, n_tiles=4, data_plane="ref",
                          power="none")
    report = StreamingMiner(32, config=cfg).run(
        TransactionStream(T, cfg.batch_size))
    phases = report.ledger.phases
    delta = [p for p in phases if p.name.startswith("stream-delta-")]
    validate = [p for p in phases if p.name.startswith("stream-validate-k")]
    assert len(delta) == report.n_batches == 16
    assert len(validate) >= 2 and report.n_revalidations >= 2
    assert all(p.syncs == 1 for p in delta + validate), \
        [(p.name, p.syncs) for p in delta + validate if p.syncs != 1]


# ---------------------------------------------------------------------------
# a counting round is one call into the data plane
# ---------------------------------------------------------------------------

# the data planes a round program runs on here: the jitted jnp oracle, and
# the Pallas kernels in interpret mode
PLANES = {"ref": dict(kind="ref"),
          "pallas": dict(kind="pallas", interpret=True)}


def _pair_candidates(T, min_sup, source):
    """The k=2 candidate bitmap (device) and its itemsets, from the device
    join, the host join (the device join's fallback past
    ``max_candidates``) or a host bitmap staged with ``prepare``."""
    n_items = T.shape[1] + (-T.shape[1]) % 128
    items = np.nonzero(T.sum(axis=0) >= min_sup)[0]
    want = generate_candidates([(int(i),) for i in items])
    if source == "host-prepare":
        return itemsets_to_bitmap(want, n_items), want
    lat = DeviceLattice(n_items, max_candidates=0 if source == "host-join"
                        else 1 << 17)
    lat.seed_items(items)
    C, valid_c, bitmap, _ = lat.join()
    assert _decoded(C, valid_c) == want
    return bitmap, want


@pytest.mark.parametrize("source", ["device-join", "host-join",
                                    "host-prepare"])
@pytest.mark.parametrize("plane", sorted(PLANES))
@pytest.mark.parametrize("n_tiles", [1, 3, 32])
def test_round_program_counts_equal_the_tile_fold_and_the_oracle(
        n_tiles, plane, source):
    # 203 rows: a multiple of no tiling here, so the last tile is padded
    T = generate_baskets(BasketConfig(n_tx=203, n_items=20, seed=n_tiles))
    min_sup = 8
    tiles = ResidentTiles(device_tiles(jnp.asarray(T.reshape(-1)), T.shape,
                                       n_tiles))
    assert len(tiles) == n_tiles
    C, cands = _pair_candidates(T, min_sup, source)
    dp = DataPlane(**PLANES[plane])
    if source == "host-prepare":
        dp.prepare(C)
    else:
        dp.prepare_device(C)

    fused = np.asarray(dp.round_counts(tiles))
    fold = sum(np.asarray(dp.tile_counts_device(t)) for t in tiles.stack)
    np.testing.assert_array_equal(fused, fold)
    assert fused.shape == (dp.m_padded,)
    # each candidate's containment count, and the frequent ones are the
    # brute-force oracle's pairs
    want = np.array([int(T[:, list(c)].all(axis=1).sum()) for c in cands])
    np.testing.assert_array_equal(fused[:len(cands)], want)
    oracle = {c: s for c, s in apriori_bruteforce(T, min_sup,
                                                  max_k=2).items()
              if len(c) == 2}
    assert {c: int(s) for c, s in zip(cands, fused)
            if s >= min_sup} == oracle


@pytest.mark.parametrize("join", ["device", "host"])
@pytest.mark.parametrize("plane", sorted(PLANES))
@pytest.mark.parametrize("n_tiles", [1, 3, 32])
def test_every_pipeline_round_is_one_data_plane_call(n_tiles, plane, join,
                                                     monkeypatch):
    if join == "host":           # every level past the device join's limit
        monkeypatch.setattr(pipeline_module, "DeviceLattice",
                            functools.partial(DeviceLattice,
                                              max_candidates=0))
    T = generate_baskets(BasketConfig(n_tx=203, n_items=20, seed=7))
    cfg = _mk_cfg(n_tiles=n_tiles, data_plane=PLANES[plane]["kind"],
                  interpret=PLANES[plane].get("interpret"))
    res = MarketBasketPipeline(config=cfg).run(T)
    counted = [r for r in res.report.rounds if r.n_tiles]
    assert len(counted) >= 3, "fixture must count three levels"
    assert [r.map_calls for r in counted] == [1] * len(counted)
    assert all(r.n_tiles == n_tiles and sum(r.tiles_per_device) == n_tiles
               for r in counted)
    assert "calls" in res.report.summary()
    want_supports, want_rules = _oracle(T, cfg)
    assert res.supports == want_supports
    assert res.rules == want_rules


def test_eclat_rounds_fold_per_tile():
    T = generate_baskets(BasketConfig(n_tx=512, n_items=32, seed=5))
    res = EclatMiner(config=_mk_cfg(algorithm="eclat")).run(T)
    # Eclat tiles each round's candidate rows: one call per tile
    counted = [r for r in res.report.rounds if r.n_tiles]
    assert max(r.n_tiles for r in counted) > 1
    assert [r.map_calls for r in counted] == [r.n_tiles for r in counted]


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
# oracle parity on both algorithms under both switching policies
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["apriori", "eclat"])
@pytest.mark.parametrize("policy", ["static", "dynamic"])
def test_every_algorithm_and_policy_mines_the_oracle(algorithm, policy):
    T = generate_baskets(BasketConfig(n_tx=384, n_items=28, seed=9))
    cfg = _mk_cfg(algorithm=algorithm, policy=policy)
    miner = (EclatMiner(config=cfg) if algorithm == "eclat"
             else MarketBasketPipeline(config=cfg))
    res = miner.run(T)
    want_supports, want_rules = _oracle(T, cfg)
    assert res.supports == want_supports
    assert res.rules == want_rules
