"""End-to-end MarketBasketPipeline: oracle equality, data-plane agreement,
report invariants, ingestion parity, and failure accounting."""
import numpy as np
import pytest

from repro.core.hetero import HeterogeneityProfile
from repro.core.itemsets import apriori_bruteforce
from repro.core.mapreduce import FailureEvent
from repro.core.rules import generate_rules
from repro.core.itemsets import AprioriResult
from repro.data.baskets import BasketConfig, generate_baskets, pack_transactions
from repro.pipeline import MarketBasketPipeline, PipelineConfig


def small_db(n_tx=300, n_items=24, seed=5):
    return generate_baskets(BasketConfig(n_tx=n_tx, n_items=n_items,
                                         n_patterns=4, pattern_len=3,
                                         pattern_prob=0.5, seed=seed))


def test_end_to_end_matches_bruteforce_oracle():
    T = small_db()
    cfg = PipelineConfig(min_support=0.05, min_confidence=0.6, n_tiles=4)
    res = MarketBasketPipeline(config=cfg).run(T)

    min_sup = cfg.abs_support(len(T))
    want = apriori_bruteforce(T, min_sup, max_k=T.shape[1])
    assert res.supports == want

    # rules must equal direct generation over the oracle supports
    oracle = AprioriResult(supports=want, n_tx=len(T), levels=0)
    want_rules = generate_rules(oracle, 0.6, min_lift=0.0)
    assert res.rules == want_rules
    assert res.report.n_rules == len(want_rules)


def test_pallas_and_ref_data_planes_agree():
    T = small_db(seed=11)
    base = dict(min_support=0.05, n_tiles=4)
    ref = MarketBasketPipeline(
        config=PipelineConfig(data_plane="ref", **base)).run(T)
    pallas = MarketBasketPipeline(
        config=PipelineConfig(data_plane="pallas", interpret=True,
                              **base)).run(T)
    assert pallas.report.backend == "pallas"
    assert ref.report.backend == "ref"
    assert pallas.supports == ref.supports
    assert pallas.rules == ref.rules


def test_report_tile_counts_sum_to_job_size():
    T = small_db(n_tx=500, seed=2)
    res = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.04, n_tiles=8)).run(T)
    rep = res.report
    assert rep.tiles_invariant_ok()
    for r in rep.rounds:
        assert sum(r.tiles_per_device) == r.n_tiles
        # every counting round spreads work across the paper's four cores
        assert len(r.tiles_per_device) == 4


def test_report_accounting_nonzero():
    T = small_db(n_tx=400, seed=3)
    res = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.05, n_tiles=4)).run(T)
    rep = res.report
    assert rep.n_rounds >= 2
    assert rep.total_time_s > 0
    assert rep.total_energy_j > 0
    assert rep.n_itemsets == len(res.supports) > 0
    # serial phases gate every core except the chosen one
    for r in rep.rounds:
        if r.serial is not None:
            assert r.serial.device not in r.serial.gated
            assert len(r.serial.gated) == 3
            assert r.serial.energy_j > 0
    # candidate batches are bucketed to kernel lane multiples
    for m in rep.kernel_batches:
        assert m % 128 == 0
    assert "rounds" in rep.summary() or "round" in rep.summary()


def test_ingestion_from_transaction_lists():
    T = small_db(seed=7)
    tx_lists = [list(np.nonzero(row)[0]) for row in T]
    cfg = PipelineConfig(min_support=0.05, n_tiles=4)
    from_bitmap = MarketBasketPipeline(config=cfg).run(T)
    from_lists = MarketBasketPipeline(config=cfg).run(tx_lists)
    assert from_lists.supports == from_bitmap.supports
    assert from_lists.rules == from_bitmap.rules


def test_pack_transactions_sets_semantics():
    T = pack_transactions([[0, 2, 2], [], [1]], n_items=4)
    assert T.tolist() == [[1, 0, 1, 0], [0, 0, 0, 0], [0, 1, 0, 0]]
    with pytest.raises(ValueError):
        pack_transactions([[0, -1]], n_items=4)
    with pytest.raises(ValueError):
        pack_transactions([[0, 7]], n_items=4)


def test_report_uses_raw_shapes_and_fraction_boundary():
    T = small_db(n_tx=200, n_items=20, seed=1)   # pads 20 -> 128 internally
    cfg = PipelineConfig(min_support=0.05, n_tiles=4)
    rep = MarketBasketPipeline(config=cfg).run(T).report
    assert rep.n_items == 20
    assert rep.n_tx == 200
    assert rep.rounds[0].n_candidates == 20
    # min_support == 1.0 means "in every transaction", not absolute 1
    assert PipelineConfig(min_support=1.0).abs_support(200) == 200
    assert PipelineConfig(min_support=50).abs_support(200) == 50


def test_failure_replan_keeps_result_and_counts_switches():
    T = small_db(n_tx=400, seed=9)
    cfg = PipelineConfig(min_support=0.05, n_tiles=8)
    clean = MarketBasketPipeline(config=cfg).run(T)
    failed = MarketBasketPipeline(config=cfg).run(
        T, failures=[FailureEvent(device=3, at_time=0.0)])
    # the dead core's tiles are re-planned: same answer, switches charged
    assert failed.supports == clean.supports
    assert failed.report.total_switches > 0
    assert failed.report.total_energy_j != clean.report.total_energy_j
    # tiles_per_device reflects execution: the dead core ran nothing, the
    # survivors ran everything, and the job-size invariant still holds
    for r in failed.report.rounds:
        if r.n_tiles:
            assert r.tiles_per_device[3] == 0
            assert sum(r.tiles_per_device) == r.n_tiles


def test_non_binary_bitmap_rejected_before_cast():
    pipe = MarketBasketPipeline(config=PipelineConfig(min_support=0.2,
                                                      n_tiles=2))
    with pytest.raises(ValueError):
        pipe.run(np.array([[2, 0], [0, 1]]))          # counts, not bits
    with pytest.raises(ValueError):
        pipe.run(np.array([[0.9, 0.0], [0.9, 0.9]]))  # floats truncate to 0
    with pytest.raises(ValueError):
        pipe.run(np.ones(8, np.uint8))                # 1-D


@pytest.mark.parametrize("bad, match", [
    (np.array([[2, 0], [0, 1]], np.uint8), "only 0/1"),
    (np.array([[-1, 0], [0, 1]], np.int8), "only 0/1"),
    (np.array([[2, 0], [0, 1]], np.int64), "only 0/1"),
    (np.array([[0.9, 0.0], [0.9, 0.9]]), "only 0/1"),
    (np.ones(8, np.uint8), "2-D"),
], ids=["uint8-2", "int8-neg", "int64-2", "float-0.9", "1-D"])
def test_non_binary_bitmap_rejected_by_dtype(bad, match):
    """Each dtype's one-pass check rejects what the elementwise check did,
    with the same message."""
    pipe = MarketBasketPipeline(config=PipelineConfig(min_support=0.2,
                                                      n_tiles=2))
    with pytest.raises(ValueError, match=match):
        pipe.run(bad)


@pytest.mark.parametrize("dtype", [np.bool_, np.int64, np.float32])
def test_binary_bitmap_of_any_dtype_mines_alike(dtype):
    T = small_db(seed=13)
    cfg = PipelineConfig(min_support=0.05, n_tiles=4)
    want = MarketBasketPipeline(config=cfg).run(T)
    got = MarketBasketPipeline(config=cfg).run(T.astype(dtype))
    assert got.supports == want.supports and got.rules == want.rules


@pytest.mark.parametrize("n_items", [24, 128, 1000])
@pytest.mark.parametrize("n_tx, n_tiles", [(100, 32), (999, 32), (7, 32),
                                           (96, 4)])
def test_device_tiles_match_host_tiling(n_items, n_tx, n_tiles):
    """The device-built tiles are byte for byte the host tiling of the
    lane-padded bitmap, stacked and in the kernels' int8: same count and
    tile shape (7 rows in 32 tiles clamps the count to the rows)."""
    import jax.numpy as jnp
    from repro.data.baskets import pad_items
    from repro.pipeline.dataplane import device_tiles, uniform_tiles
    rng = np.random.default_rng(n_items * 1000 + n_tx)
    T = (rng.random((n_tx, n_items)) < 0.1).astype(np.uint8)
    want = uniform_tiles(pad_items(T), n_tiles)
    got = device_tiles(jnp.asarray(T.reshape(-1)), T.shape, n_tiles)
    assert got.shape == (len(want),) + want[0].shape
    assert got.dtype == jnp.int8
    for g, w in zip(np.asarray(got), want):
        np.testing.assert_array_equal(g, w.astype(np.int8))


def test_failure_energy_bills_replanned_core_as_active():
    """A planned-idle core that executes orphaned tiles must be charged
    active watts, and the dead core gated watts (zero busy seconds)."""
    T = small_db(n_tx=400, seed=9)
    cfg = PipelineConfig(min_support=0.05, n_tiles=2)
    res = MarketBasketPipeline(config=cfg).run(
        T, failures=[FailureEvent(device=3, at_time=0.0)])
    for r in res.report.rounds:
        if r.n_tiles:
            # dead core executed nothing; survivors ran every tile
            assert r.map_busy_s[3] == 0.0
            assert sum(1 for b in r.map_busy_s if b > 0) >= 1
            assert r.energy_j > 0


def test_midround_death_charges_gated_tail_not_idle():
    """A core that dies after finishing some tiles is active for its busy
    seconds and gated — not idle — for the rest of the round."""
    T = small_db(n_tx=400, seed=9)
    cfg = PipelineConfig(min_support=0.05, n_tiles=8)
    pipe = MarketBasketPipeline(config=cfg)
    # death late enough that core 3 completes at least one tile first
    # (tiles are 50 rows x 128 padded items = 6400 work units; core 3 runs
    # at speed 400 => 16 simulated seconds per tile)
    res = pipe.run(T, failures=[FailureEvent(device=3, at_time=20.0)])
    rounds = [r for r in res.report.rounds
              if 3 in r.failed_devices and r.map_busy_s[3] > 0]
    assert rounds, "expected core 3 to die mid-round with work done"
    r = rounds[0]
    # recompute what idle-tail billing would have charged: must be more
    # (idle watts exceed gated watts in the cpu calibration)
    power = pipe.power
    idle_billing = power.energy(
        np.array(r.map_busy_s), r.map_makespan_s,
        gated=[d for d, b in enumerate(r.map_busy_s) if b == 0.0],
        switches=r.switches + r.reissued)   # every migration is priced
    assert r.energy_j < idle_billing


def test_preused_scheduler_switch_counter_not_recounted():
    """A scheduler with prior rebalance history must not inflate per-round
    switch counts (ExecReport.switches is per-run; the scheduler's lifetime
    counter is tracked separately on the scheduler itself)."""
    from repro.core.scheduler import MBScheduler
    profile = HeterogeneityProfile.paper()
    sched = MBScheduler(profile)
    sched.switches = 5                      # pretend prior rebalances
    T = small_db(n_tx=300, seed=1)
    res = MarketBasketPipeline(
        profile, PipelineConfig(min_support=0.05, n_tiles=4),
        scheduler=sched).run(T)
    assert res.report.total_switches == 0   # clean run: no moves happened


def test_policy_equal_is_no_faster_than_lpt():
    T = small_db(n_tx=600, seed=4)
    times = {}
    for split in ("equal", "lpt"):
        res = MarketBasketPipeline(
            HeterogeneityProfile.paper(),
            PipelineConfig(min_support=0.05, n_tiles=16,
                           split=split)).run(T)
        times[split] = res.report.total_time_s
    assert times["lpt"] <= times["equal"] + 1e-9
