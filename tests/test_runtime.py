"""Shared scheduling runtime: policy planning/feedback, ledger accounting,
speculative-move application, serial-constraint surfacing, and the
closed-loop dynamic-vs-static comparison under an injected straggler."""
import numpy as np
import pytest

from repro.core.hetero import HeterogeneityProfile
from repro.core.power import PowerModel
from repro.core.scheduler import MBScheduler, TaskSpec
from repro.runtime import (CostModelPolicy, DynamicPolicy, MeasuredPhase,
                           Runtime, StaticPolicy, resolve_policy)


def modeled_executor():
    """Executor that lets the runtime model busy seconds from the plan."""
    def execute(asg, costs):
        return MeasuredPhase(result="ok")
    return execute


def true_speed_executor(true_speeds):
    """Executor that measures walls under the *true* rates — the believed
    profile only drives planning.  Feeds work_done so DynamicPolicy's EWMA
    loop can learn the real speeds."""
    true_speeds = np.asarray(true_speeds, dtype=np.float64)

    def execute(asg, costs):
        load = np.array([costs[ts].sum() if ts else 0.0
                         for ts in asg.tiles_of])
        busy = load / true_speeds
        return MeasuredPhase(result=None, busy_s=busy,
                             makespan=float(busy.max()), work_done=load)
    return execute


# ---------------------------------------------------------------------------
# serial phases + constraint surfacing (satellite: no silent fallback)
# ---------------------------------------------------------------------------

def test_run_serial_records_energy_and_picks_best_core():
    profile = HeterogeneityProfile.paper()
    rt = Runtime(profile, power="cpu")
    val, rec = rt.run_serial("phase", cost=400.0, fn=lambda: 42)
    assert val == 42
    assert rec.device == 3 and rec.sim_time_s == pytest.approx(1.0)
    assert sorted(rec.gated) == [0, 1, 2]
    assert not rec.constraint_violated
    # energy: chosen core active for 1s, the rest gated for 1s
    pm = rt.power
    want = pm.p_active[3] * 1.0 + sum(pm.p_gated[d] for d in (0, 1, 2))
    assert rec.energy_j == pytest.approx(want)
    assert rt.ledger.phases == [rec]


def test_min_speed_violation_is_flagged_not_hidden():
    profile = HeterogeneityProfile.paper()          # fastest core: 400
    rt = Runtime(profile, power="none")
    _, ok = rt.run_serial("fits", cost=1.0, min_speed=300.0)
    assert ok.device == 3 and not ok.constraint_violated
    _, bad = rt.run_serial("too-demanding", cost=1.0, min_speed=1000.0)
    assert bad.device == 3                          # fastest fallback...
    assert bad.constraint_violated                  # ...but flagged
    assert len(rt.ledger.constraint_violations()) == 1
    # pinning below min_speed is a violation too
    sched = MBScheduler(profile)
    asg = sched.assign_serial(TaskSpec("pinned", 1.0, parallel=False,
                                       min_speed=100.0), device=0)
    assert asg.serial_device == 0 and asg.constraint_violated


# ---------------------------------------------------------------------------
# static map phases: accounting matches the power model exactly once
# ---------------------------------------------------------------------------

def test_static_phase_energy_matches_manual_pricing():
    profile = HeterogeneityProfile.paper()
    rt = Runtime(profile, policy="static", power="cpu")
    costs = np.full(16, 100.0)
    task = TaskSpec("t", float(costs.sum()), parallel=True, n_tiles=16)
    _, rec = rt.run_phase(task, modeled_executor(), tile_costs=costs)
    busy = np.asarray(rec.busy_s)
    want = rt.power.energy(busy, rec.sim_time_s,
                           gated=[d for d in range(4) if busy[d] == 0.0],
                           switches=rec.switches + rec.reissued)
    assert rec.energy_j == pytest.approx(want)
    assert rec.policy == "static" and rec.kind == "map"
    assert sum(rec.tiles_done) == 16
    assert rt.ledger.total_energy_j == pytest.approx(rec.energy_j)


def test_pinned_assignment_gates_zero_cost_ranks():
    profile = HeterogeneityProfile.homogeneous(4, 100.0)
    rt = Runtime(profile, power="cpu")
    costs = np.array([100.0, 0.0, 100.0, 100.0])    # rank 1: dead/empty
    task = TaskSpec("pinned", 300.0, parallel=True, n_tiles=4)
    _, rec = rt.run_phase(task, modeled_executor(), tile_costs=costs,
                          assignment=rt.pinned_assignment(costs))
    assert rec.busy_s[1] == 0.0 and 1 in rec.gated
    assert rec.energy_j > 0
    assert rec.tiles_done == [1, 0, 1, 1]


# ---------------------------------------------------------------------------
# dynamic policy: the closed loop (EWMA feedback + speculation)
# ---------------------------------------------------------------------------

def _run_phases(policy, n_phases, believed, true_speeds, costs):
    rt = Runtime(believed.copy(), policy=policy, split="lpt", power="cpu")
    execute = true_speed_executor(true_speeds)
    total = 0.0
    for i in range(n_phases):
        task = TaskSpec("bench", float(costs.sum()), parallel=True,
                        n_tiles=len(costs))
        _, rec = rt.run_phase(task, execute, tile_costs=costs)
        total += rec.sim_time_s
    return total, rt


def test_dynamic_beats_static_under_injected_straggler():
    believed = HeterogeneityProfile(np.full(4, 100.0))
    true_speeds = np.array([20.0, 100.0, 100.0, 100.0])  # core 0 straggles
    rng = np.random.default_rng(0)
    costs = rng.uniform(50.0, 150.0, 64)
    t_static, _ = _run_phases("static", 6, believed, true_speeds, costs)
    t_dynamic, rt = _run_phases("dynamic", 6, believed, true_speeds, costs)
    assert t_dynamic < t_static * 0.8
    # the EWMA loop learned the straggler's true rate
    assert rt.profile.speeds[0] < 40.0
    assert rt.profile.speeds[1] == pytest.approx(100.0)


def test_dynamic_speculation_reissues_straggler_tiles():
    """equal split on the paper's cores: the 80-core lags the planned
    checkpoint, so its tail tiles re-issue to already-finished cores."""
    profile = HeterogeneityProfile.paper()
    rt_s = Runtime(profile.copy(), policy="static", split="equal",
                   power="cpu")
    rt_d = Runtime(profile.copy(), policy="dynamic", split="equal",
                   power="cpu")
    costs = np.full(32, 100.0)
    task = TaskSpec("t", float(costs.sum()), parallel=True, n_tiles=32)
    _, rec_s = rt_s.run_phase(task, modeled_executor(), tile_costs=costs)
    _, rec_d = rt_d.run_phase(task, modeled_executor(), tile_costs=costs)
    assert rec_d.reissued > 0
    assert rec_d.sim_time_s < rec_s.sim_time_s
    # re-issues migrate work: no tile lost, none duplicated
    assert sum(rec_d.tiles_done) == 32
    # energy priced the migrations
    assert rec_d.switches + rec_d.reissued > 0


def test_dynamic_rebalance_counts_owner_changes_as_switches():
    believed = HeterogeneityProfile(np.full(4, 100.0))
    true_speeds = np.array([25.0, 100.0, 100.0, 100.0])
    _, rt = _run_phases("dynamic", 3, believed, true_speeds,
                        np.full(32, 100.0))
    led = rt.ledger
    # the corrected speeds moved tiles off the straggler in later phases
    assert led.total_switches > 0
    assert rt.scheduler.switches >= led.total_switches - led.total_reissued


# ---------------------------------------------------------------------------
# costmodel policy: roofline seeding instead of raw byte counts
# ---------------------------------------------------------------------------

def test_costmodel_seeds_from_tile_flops():
    profile = HeterogeneityProfile.paper()
    policy = CostModelPolicy(peak_flops=1e12, hbm_bw=1e9)
    rt = Runtime(profile, policy=policy, power="none")
    bytes_ = np.full(8, 1e6)
    # tile 0 is violently compute-bound; the rest are memory-bound
    flops = np.array([1e12] + [1.0] * 7)
    seeded = policy.tile_costs(rt, None, bytes_, flops)
    assert seeded.sum() == pytest.approx(bytes_.sum())   # same work total
    assert seeded[0] > seeded[1] * 100                   # intensity skew
    # uniform intensity degenerates to the byte seeding
    flat = policy.tile_costs(rt, None, bytes_, bytes_ * 2.0)
    np.testing.assert_allclose(flat, bytes_)


def test_costmodel_phase_assignment_differs_from_static():
    profile = HeterogeneityProfile.paper()
    bytes_ = np.full(8, 1e6)
    flops = np.array([1e12] + [1.0] * 7)
    task = TaskSpec("t", float(bytes_.sum()), parallel=True, n_tiles=8)
    rt_s = Runtime(profile, policy="static", power="none")
    rt_c = Runtime(profile, policy=CostModelPolicy(peak_flops=1e12,
                                                   hbm_bw=1e9), power="none")
    seen = {}
    for name, rt in (("static", rt_s), ("costmodel", rt_c)):
        def execute(asg, costs):
            return MeasuredPhase(result=asg)
        asg, rec = rt.run_phase(task, execute, tile_costs=bytes_,
                                tile_flops=flops)
        seen[name] = asg
        assert sorted(t for ts in asg.tiles_of for t in ts) == list(range(8))
    # the compute-bound tile dominates under costmodel: it lands alone on
    # the fastest core, which a byte-uniform static plan never does
    owner = {t: d for d, ts in enumerate(seen["costmodel"].tiles_of)
             for t in ts}
    assert owner[0] == 3
    assert seen["costmodel"].tiles_of != seen["static"].tiles_of


def test_costmodel_from_hlo_derives_intensity():
    hlo = """
HloModule m

ENTRY %main (p0: f32[128,128], p1: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128] parameter(0)
  %p1 = f32[128,128] parameter(1)
  ROOT %dot = f32[128,128] dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    policy = CostModelPolicy.from_hlo(hlo)
    # 2*128^3 flops over (result + 2 operand) f32[128,128] buffers
    want = (2.0 * 128 ** 3) / (3 * 128 * 128 * 4)
    assert policy.flops_per_byte == pytest.approx(want)


# ---------------------------------------------------------------------------
# apply_moves (satellite): speculation must mutate the assignment
# ---------------------------------------------------------------------------

def test_apply_moves_rehomes_tiles_and_stops_repeat_reissue():
    profile = HeterogeneityProfile.homogeneous(4, 100.0)
    sched = MBScheduler(profile, policy="equal")
    costs = np.full(16, 10.0)
    task = TaskSpec("t", 160.0, parallel=True, n_tiles=16)
    asg = sched.assign_parallel(task, costs)
    progress = np.array([0.1, 1.0, 1.0, 1.0])       # device 0 straggles
    moves = sched.speculate(asg, progress)
    assert moves
    first = {t for t, _ in moves}
    applied = sched.apply_moves(asg, moves, costs)
    # exact partition: nothing lost, nothing duplicated
    assert sorted(t for ts in applied.tiles_of for t in ts) == list(range(16))
    # every move changed the owner
    before, after = asg.owner_of(), applied.owner_of()
    assert sum(1 for t in after if after[t] != before[t]) == len(moves)
    # the bug this satellite fixes: a second speculation must not re-issue
    # the same tiles (they left the straggler's queue)
    again = sched.speculate(applied, progress)
    assert first.isdisjoint({t for t, _ in again})


def test_apply_moves_rejects_unassigned_tiles():
    profile = HeterogeneityProfile.homogeneous(2, 1.0)
    sched = MBScheduler(profile)
    asg = sched.assign_parallel(TaskSpec("t", 2.0, parallel=True, n_tiles=2),
                                np.ones(2))
    with pytest.raises(ValueError):
        sched.apply_moves(asg, [(99, 0)], np.ones(2))


# ---------------------------------------------------------------------------
# ledger + resolve helpers
# ---------------------------------------------------------------------------

def test_ledger_slices_isolate_runs():
    profile = HeterogeneityProfile.paper()
    rt = Runtime(profile, power="cpu")
    rt.run_serial("a", cost=100.0)
    mark = rt.ledger.mark()
    _, rec = rt.run_serial("b", cost=100.0)
    run2 = rt.ledger.since(mark)
    assert run2.n_phases == 1 and run2.phases[0] is rec
    assert rt.ledger.n_phases == 2
    assert "phases" in rt.ledger.summary()
    # take_since hands the slice to the run report AND compacts the live
    # ledger, so long-lived planes don't accumulate records forever
    taken = rt.ledger.take_since(mark)
    assert taken.n_phases == 1 and taken.phases[0] is rec
    assert rt.ledger.n_phases == mark


def test_serving_engine_ledger_does_not_grow_across_calls():
    from repro.data.baskets import BasketConfig, generate_baskets
    from repro.pipeline import MarketBasketPipeline, PipelineConfig
    from repro.serving import (Query, RecommendationEngine, RuleIndex,
                               ServingConfig)
    T = generate_baskets(BasketConfig(n_tx=400, n_items=24, seed=2))
    res = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.05, min_confidence=0.5,
                              n_tiles=4)).run(T)
    engine = RecommendationEngine(
        RuleIndex.build(res.rules, T.shape[1]),
        config=ServingConfig(k=3, batch_buckets=(8,), data_plane="ref",
                             cache_size=0))
    queries = [Query.of(list(np.nonzero(row)[0])) for row in T[:16]]
    _, rep1 = engine.serve(queries)
    _, rep2 = engine.serve(queries)
    assert rep1.ledger.n_phases > 0 and rep2.ledger.n_phases > 0
    # each call took its slice; nothing is retained in the live ledger
    assert engine.runtime.ledger.n_phases == 0


def test_resolve_policy_names_and_errors():
    assert isinstance(resolve_policy("static"), StaticPolicy)
    assert isinstance(resolve_policy("dynamic"), DynamicPolicy)
    assert isinstance(resolve_policy(None), StaticPolicy)
    inst = DynamicPolicy()
    assert resolve_policy(inst) is inst
    with pytest.raises(ValueError):
        resolve_policy("nope")
    with pytest.raises(ValueError):
        Runtime(HeterogeneityProfile.paper(), power="warp-drive")


def test_planes_share_report_semantics():
    """The two simulated planes expose the same ledger-backed totals."""
    from repro.data.baskets import BasketConfig, generate_baskets
    from repro.pipeline import MarketBasketPipeline, PipelineConfig
    T = generate_baskets(BasketConfig(n_tx=256, n_items=24, seed=3))
    res = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.05, n_tiles=4,
                              policy="dynamic")).run(T)
    rep = res.report
    assert rep.policy == "dynamic" and rep.split == "lpt"
    assert rep.ledger is not None
    assert rep.total_energy_j == pytest.approx(rep.ledger.total_energy_j)
    assert rep.total_time_s == pytest.approx(rep.ledger.total_time_s)
    # every phase in the ledger is either a serial or a map record
    assert {p.kind for p in rep.ledger.phases} <= {"serial", "map"}
    # two runs on one pipeline must not bleed into each other's ledger
    res2 = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.05, n_tiles=4)).run(T)
    assert res2.report.ledger.n_phases == len(res2.report.ledger.phases)


# ---------------------------------------------------------------------------
# measurement at the chokepoint: every phase timed, spanned and counted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,span", [
    ("serve-score-17", "serve-score"), ("serve-admit-0", "serve-admit"),
    ("mba-round2-support", "mba-round2-support"),
    ("mba-candgen-k3", "mba-candgen-k3"), ("mba-ingest", "mba-ingest"),
    ("42", "42")])
def test_span_name_drops_only_a_trailing_step_counter(name, span):
    from repro.runtime.runtime import span_name
    assert span_name(name) == span


def test_every_mine_phase_is_timed_and_tiles_land_on_upload():
    from repro.data.baskets import BasketConfig, generate_baskets
    from repro.pipeline import MarketBasketPipeline, PipelineConfig
    T = generate_baskets(BasketConfig(n_tx=256, n_items=24, seed=3))
    res = MarketBasketPipeline(config=PipelineConfig(
        min_support=0.05, n_tiles=4, data_plane="ref")).run(T)
    phases = res.report.ledger.phases
    names = [p.name for p in phases]
    assert names[:3] == ["mba-ingest", "mba-upload",
                         "mba-round1-item-counts"]
    assert "mba-round2-support" in names and names[-1] == "mba-rules"
    assert all(p.host_time_s > 0 for p in phases), \
        [(p.name, p.host_time_s) for p in phases if p.host_time_s <= 0]
    # the phases lie inside the mine's own wall
    assert sum(p.host_time_s for p in phases) <= res.report.wall_time_s
    # the one-time upload: the raw 256 x 24 bitmap, which the device pads
    # and cuts into 4 tiles of 64 rows x 128 lanes
    assert phases[1].h2d_bytes == 256 * 24
    assert phases[0].h2d_bytes == phases[2].h2d_bytes == 0
    # ingest and upload are priced like every other serial phase
    assert all(p.kind == "serial" and p.sim_time_s > 0 and p.energy_j > 0
               for p in phases[:2])


def test_phase_that_lowers_a_fresh_shape_counts_it_and_a_repeat_does_not():
    import jax
    import jax.numpy as jnp
    rt = Runtime(HeterogeneityProfile.paper(), power="none")
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones(77, jnp.float32)
    _, first = rt.run_serial("lower", cost=1.0, fn=lambda: f(x))
    _, repeat = rt.run_serial("lower", cost=1.0, fn=lambda: f(x))
    assert first.lowerings >= 1 and first.compile_s > 0
    assert repeat.lowerings == 0 and repeat.compile_s == 0.0

    # a map phase counts the same way, and between-phase work lands on
    # the next phase, as transfers do
    g = jax.jit(lambda x: x - 5)
    g(jnp.ones(78, jnp.float32))             # between phases
    task = TaskSpec("map", 4.0, parallel=True, n_tiles=4)
    _, rec = rt.run_phase(task, modeled_executor())
    assert rec.lowerings >= 1 and rec.host_time_s > 0
    _, rec = rt.run_phase(task, modeled_executor())
    assert rec.lowerings == 0


def test_steady_mine_lowers_nothing():
    from repro.data.baskets import BasketConfig, generate_baskets
    from repro.pipeline import MarketBasketPipeline, PipelineConfig
    T = generate_baskets(BasketConfig(n_tx=256, n_items=24, seed=3))
    pipe = MarketBasketPipeline(config=PipelineConfig(
        min_support=0.05, n_tiles=4, data_plane="ref"))
    # the first mine compiles its lattice; a round keeps its counts inside
    # its one program (no pooled slab to zero), so every later mine
    # lowers nothing
    runs = [pipe.run(T) for _ in range(3)]
    assert [sum(p.lowerings for p in r.report.ledger.phases)
            for r in runs[1:]] == [0, 0]


def test_second_mine_stages_without_lowering():
    """A repeat mine of the same bitmap shape reuses the device tiling
    program: nothing lowers in ingest or upload."""
    from repro.data.baskets import BasketConfig, generate_baskets
    from repro.pipeline import MarketBasketPipeline, PipelineConfig
    T = generate_baskets(BasketConfig(n_tx=200, n_items=40, seed=4))
    pipe = MarketBasketPipeline(config=PipelineConfig(
        min_support=0.05, n_tiles=4, data_plane="ref"))
    pipe.run(T)
    staged = {p.name: p.lowerings for p in pipe.run(T).report.ledger.phases
              if p.name in ("mba-ingest", "mba-upload")}
    assert staged == {"mba-ingest": 0, "mba-upload": 0}
