"""Distributed mining plane: sharded-vs-single-device parity, run_sharded
vs SimulatedCluster parity, energy on the sharded path (priced by the
shared Runtime ledger), switching-policy independence of the mined result,
and device_loss → shard re-planning.  Device-backed checks run in a
subprocess with 8 forced host devices (like test_distributed); plan math
is tested host-side."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.hetero import HeterogeneityProfile

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp

from repro.core.hetero import HeterogeneityProfile
from repro.core.mapreduce import (MapReduceJob, SimulatedCluster, run_sharded)
from repro.core.power import PowerModel
from repro.core.scheduler import TaskSpec
from repro.data.baskets import BasketConfig, generate_baskets
from repro.distributed.fault import FaultEvent, FaultPlan
from repro.distributed.mining import ShardedMiner, make_shard_mesh, mesh_profile
from repro.pipeline import MarketBasketPipeline, PipelineConfig
from repro.runtime import MeasuredPhase, Runtime

out = {}

# ---- 1. run_sharded vs SimulatedCluster: same job, same tiles, same value
n_dev = 8
profile = HeterogeneityProfile.homogeneous(n_dev, 100.0)
rng = np.random.default_rng(0)
tiles = [rng.integers(0, 16, 32).astype(np.int32) for _ in range(n_dev)]
job = MapReduceJob("wc",
    map_fn=lambda t: jnp.bincount(jnp.asarray(t), length=16),
    combine_fn=lambda a, b: a + b,
    zero_fn=lambda: jnp.zeros(16, jnp.int32))
sim, sim_rep = SimulatedCluster(profile).run(job, tiles)
mesh = make_shard_mesh(n_dev)
shard, shard_rep = run_sharded(job, jnp.concatenate([jnp.asarray(t) for t in tiles]),
                               mesh, mesh.axis_names[0], profile=profile)
out["parity_value_ok"] = bool((np.asarray(sim) == np.asarray(shard)).all())

# ---- 2. sharded energy is priced by the shared Runtime (exactly once):
# drive the same shard_map job through Runtime.run_phase with the shard
# layout as a pinned assignment, as ShardedMiner does
rt = Runtime(profile, policy="static", power=PowerModel.cpu(profile))
costs = np.full(n_dev, 32.0 * 4)                 # bytes per rank
def _exec(asg, c):
    res, _ = run_sharded(job, jnp.concatenate(
        [jnp.asarray(t) for t in tiles]), mesh, mesh.axis_names[0])
    return MeasuredPhase(result=res)
shard2, rec = rt.run_phase(
    TaskSpec("wc-runtime", float(costs.sum()), parallel=True, n_tiles=n_dev),
    _exec, tile_costs=costs, assignment=rt.pinned_assignment(costs))
out["sharded_energy_ok"] = rec.energy_j > 0
out["sharded_makespan_ok"] = (rec.sim_time_s > 0
                              and bool((np.asarray(sim)
                                        == np.asarray(shard2)).all()))

# ---- 3. sharded miner == single-device pipeline, bit for bit
T = generate_baskets(BasketConfig(n_tx=1024, n_items=48, seed=7))
cfg = PipelineConfig(min_support=0.05, min_confidence=0.6)
single = MarketBasketPipeline(config=cfg).run(T)
miner = ShardedMiner(config=cfg, verify_rounds=True)
sharded = miner.run(T)
out["mining_supports_ok"] = sharded.supports == single.supports
out["mining_rules_ok"] = sharded.rules == single.rules
rep = sharded.report
out["mining_report_ok"] = (rep.execution == "sharded" and rep.n_shards == 8
                           and sum(rep.shard_rows) >= 1024
                           and rep.tiles_invariant_ok()
                           and rep.total_energy_j > 0)

# ---- 4. device_loss mid-mine -> re-plan, same answer, moves surfaced
miner2 = ShardedMiner(config=cfg, verify_rounds=True)
faulted = miner2.run(T, faults=FaultPlan([FaultEvent(2, "device_loss", 3)]))
frep = faulted.report
out["replan_result_ok"] = faulted.supports == single.supports
r2 = [r for r in frep.rounds if r.k == 2][0]
out["replan_counts_ok"] = (frep.replans == 1
                           and frep.shard_rows[3] == 0
                           and r2.reissued > 0
                           and r2.failed_devices == [3]
                           and frep.total_reissued > 0)
# the dead rank holds no real rows afterwards -> gated (zero busy seconds)
later = [r for r in frep.rounds if r.k >= 2 and r.n_tiles]
out["replan_gating_ok"] = all(r.map_busy_s[3] == 0.0 for r in later)

# ---- 5. heterogeneous split: fastest rank owns the most rows
prof = mesh_profile(8)      # cycled 80/120/200/400
miner3 = ShardedMiner(profile=prof, config=cfg)
res3 = miner3.run(T)
rows = np.asarray(res3.report.shard_rows, dtype=float)
out["hetero_split_ok"] = bool(
    res3.supports == single.supports
    and rows[np.argmax(prof.speeds)] == rows.max()
    and rows[np.argmax(prof.speeds)] > rows[np.argmin(prof.speeds)])

# ---- 6. switching-policy independence: dynamic mines bit-identically and
# the report carries the policy + a consistent ledger
miner4 = ShardedMiner(config=cfg, policy="dynamic", verify_rounds=True)
res4 = miner4.run(T)
led = res4.report.ledger
out["dynamic_parity_ok"] = (res4.supports == single.supports
                            and res4.rules == single.rules
                            and res4.report.policy == "dynamic")
out["ledger_ok"] = (led is not None
                    and abs(led.total_energy_j
                            - res4.report.total_energy_j) < 1e-9
                    and led.n_phases >= 2 * res4.report.n_rounds
                    and led.total_time_s > 0)

print("RESULT" + json.dumps({k: bool(v) for k, v in out.items()}))
'''


@pytest.fixture(scope="module")
def mining_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_run_sharded_matches_simulated_cluster(mining_results):
    assert mining_results["parity_value_ok"]


def test_run_sharded_reports_energy(mining_results):
    assert mining_results["sharded_energy_ok"]
    assert mining_results["sharded_makespan_ok"]


def test_sharded_miner_matches_single_device(mining_results):
    assert mining_results["mining_supports_ok"]
    assert mining_results["mining_rules_ok"]
    assert mining_results["mining_report_ok"]


def test_device_loss_triggers_replan(mining_results):
    assert mining_results["replan_result_ok"]
    assert mining_results["replan_counts_ok"]
    assert mining_results["replan_gating_ok"]


def test_heterogeneous_split_follows_speeds(mining_results):
    assert mining_results["hetero_split_ok"]


def test_dynamic_policy_mines_identically(mining_results):
    assert mining_results["dynamic_parity_ok"]


def test_report_totals_come_from_the_ledger(mining_results):
    assert mining_results["ledger_ok"]


# ---- host-side plan math (no devices needed) ------------------------------

def test_plan_shard_rows_proportional_and_exact():
    from repro.data.sharding import plan_shard_rows
    prof = HeterogeneityProfile.paper()          # 80/120/200/400
    rows = plan_shard_rows(prof, 2048, row_block=8)
    assert rows.sum() == 2048
    assert (rows % 8 == 0).all()
    assert rows[3] == rows.max()                 # fastest core, most rows
    # ~proportional: within one block of the exact share
    shares = prof.shares() * 2048
    assert (np.abs(rows - shares) <= 8).all()


def test_plan_shard_rows_dead_ranks_get_zero():
    from repro.data.sharding import plan_shard_rows
    prof = HeterogeneityProfile.homogeneous(4, 100.0)
    alive = np.array([True, False, True, True])
    rows = plan_shard_rows(prof, 999, row_block=8, alive=alive)
    assert rows[1] == 0
    assert rows.sum() == 1000                    # ceil to a block multiple
    with pytest.raises(RuntimeError):
        plan_shard_rows(prof, 100, alive=np.zeros(4, bool))


def test_shard_bitmap_layout_and_count_moves():
    from repro.distributed.mining import (count_moves, plan_shards,
                                          shard_bitmap)
    prof = HeterogeneityProfile.paper()
    T = np.arange(64 * 4, dtype=np.uint8).reshape(64, 4) % 2
    plan = plan_shards(prof, 64, row_block=8)
    S = shard_bitmap(T, plan)
    assert S.shape == (plan.n_shards * plan.width, 4)
    # zero-padding is inert: global column sums survive the re-layout
    assert (S.sum(axis=0) == T.sum(axis=0)).all()
    # kill the fastest rank: its blocks re-issue, others may switch
    alive = np.array([True, True, True, False])
    plan2 = plan_shards(prof, 64, row_block=8, alive=alive)
    switches, reissued = count_moves(plan, plan2)
    assert reissued == plan.rows[3] // plan.row_block
    assert plan2.rows[3] == 0
    S2 = shard_bitmap(T, plan2)
    assert (S2.sum(axis=0) == T.sum(axis=0)).all()
