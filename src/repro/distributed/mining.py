"""Distributed mining plane — shard_map Apriori over a heterogeneous mesh.

The single-device pipeline *simulates* the paper's cluster; this module
*executes* it: the packed transaction bitmap is partitioned across a
data-parallel mesh axis, `support_count` runs per shard inside `shard_map`
as the map phase, and partial support vectors reduce through the psum
combiner tree in :func:`repro.core.mapreduce.run_sharded`.

Heterogeneity shows up as shard *composition*, not shard shape: every rank
owns one static ``[width, n_items]`` slab (a jit-cache requirement), but the
number of *real* transaction rows inside it is planned ∝ core speed by
:func:`repro.data.sharding.plan_shard_rows` — padding rows are all-zero and
therefore inert for support counting.  A failure (``device_loss``) or
straggler observation re-plans that integer vector mid-mine (the paper's
dynamic core switching): the dead rank's slab becomes pure padding (gated
watts in the power model) and its row blocks re-issue to survivors, with
the move counts surfaced in the :class:`PipelineReport`.

Scheduling and accounting run on the shared :class:`repro.runtime.Runtime`:
the shard layout is handed to ``run_phase`` as a *pinned* assignment (rank
d owns tile d with its planned row bytes), shard-re-plan moves are charged
as this phase's switches/re-issues, and time/energy come off the same
ledger the simulated and serving planes use.  Serial phases (candidate
generation, rule extraction) run host-side on the driver process, which is
co-located with mesh rank 0 — they are routed there via
``Runtime.run_serial(device=0)``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hetero import HeterogeneityProfile
from repro.core.mapreduce import MapReduceJob, run_sharded
from repro.core.itemsets import (AprioriResult, generate_candidates,
                                 itemsets_to_bitmap)
from repro.core.power import PowerModel
from repro.core.scheduler import MBScheduler, TaskSpec
from repro.data.baskets import pad_items
from repro.data.sharding import plan_shard_rows
from repro.data.sparse import SparseSlab, density_stats
from repro.distributed.fault import FaultPlan
from repro.kernels.support_count.ref import support_count_ref
from repro.pipeline.dataplane import pad_candidates, resolve_backend
from repro.pipeline.pipeline import (Baskets, PipelineConfig, PipelineResult,
                                     ingest_baskets)
from repro.pipeline.report import PipelineReport, RoundReport
from repro.runtime import (MeasuredPhase, Runtime, SwitchingPolicy,
                           autotuned_costmodel)
from repro.core.rules import generate_rules

DEFAULT_AXIS = "shards"


# ---------------------------------------------------------------------------
# mesh + profile helpers
# ---------------------------------------------------------------------------

def make_shard_mesh(n_shards: Optional[int] = None,
                    axis: str = DEFAULT_AXIS) -> Mesh:
    """1-D mesh over the first `n_shards` local devices (default: all)."""
    devs = jax.devices()
    n = n_shards or len(devs)
    if not 1 <= n <= len(devs):
        raise ValueError(f"n_shards={n} but only {len(devs)} devices visible "
                         "(set XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for simulated multi-device CPU meshes)")
    return Mesh(np.asarray(devs[:n]), (axis,))


def mesh_profile(n: int,
                 base: Optional[HeterogeneityProfile] = None
                 ) -> HeterogeneityProfile:
    """Cycle a base profile's speeds (default: the paper's 80/120/200/400)
    out to an n-rank mesh — the paper's core mix at pod scale."""
    base = base or HeterogeneityProfile.paper()
    speeds = np.resize(base.speeds, n)
    names = [f"{base.names[i % base.n]}.{i // base.n}" for i in range(n)]
    return HeterogeneityProfile(speeds, names=names,
                                ewma_alpha=base.ewma_alpha)


def partition_miner(mesh: Optional[Mesh] = None,
                    config: Optional[PipelineConfig] = None,
                    base_profile: Optional[HeterogeneityProfile] = None,
                    policy: Union[str, "SwitchingPolicy", None] = None,
                    row_block: int = 8,
                    verify_rounds: bool = False) -> "ShardedMiner":
    """Per-partition entry point for the SON out-of-core plane: one
    :class:`ShardedMiner` sized to ``mesh`` (profile cycled from
    ``base_profile``) that the SON driver reuses across every partition
    sharing a local config — so the compiled shard_map programs and the
    shard planner's jit caches are built once, not once per partition.
    ``config.algorithm`` must already be resolved (SON decides ``auto``
    once, globally, before the first partition)."""
    mesh = mesh if mesh is not None else make_shard_mesh()
    n = mesh.shape[mesh.axis_names[0]]
    return ShardedMiner(mesh=mesh, profile=mesh_profile(n, base_profile),
                        config=config, policy=policy, row_block=row_block,
                        verify_rounds=verify_rounds)


# ---------------------------------------------------------------------------
# shard planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardPlan:
    """Static-shape shard layout: rank d owns rows[d] real rows inside a
    zero-padded ``[width, n_items]`` slab."""

    rows: np.ndarray          # [n_shards] real rows per rank (row_block ·)
    width: int                # padded rows per shard (static, = max rows)
    row_block: int
    alive: np.ndarray         # [n_shards] bool

    @property
    def n_shards(self) -> int:
        return len(self.rows)

    @property
    def n_blocks(self) -> int:
        return int(self.rows.sum()) // self.row_block

    def block_owners(self) -> np.ndarray:
        """owner rank of each row block, in global block order (blocks are
        assigned contiguously, so a re-plan is comparable block-by-block)."""
        return np.repeat(np.arange(self.n_shards),
                         self.rows // self.row_block)

    def shard_costs(self, n_items: int) -> np.ndarray:
        """Per-rank work units (bytes of *real* transaction data) — the same
        units the simulated pipeline's tile costs use."""
        return self.rows.astype(np.float64) * n_items


def plan_shards(profile: HeterogeneityProfile, n_rows: int,
                row_block: int = 8,
                alive: Optional[np.ndarray] = None) -> ShardPlan:
    """Heterogeneity-aware shard plan over the alive ranks."""
    alive = (np.ones(profile.n, dtype=bool) if alive is None
             else np.asarray(alive, dtype=bool))
    rows = plan_shard_rows(profile, n_rows, row_block=row_block, alive=alive)
    width = int(rows.max())
    return ShardPlan(rows=rows, width=width, row_block=row_block,
                     alive=alive.copy())


def shard_bitmap(T: np.ndarray, plan: ShardPlan) -> np.ndarray:
    """Lay T out rank-major per the plan: rank d's slab holds its contiguous
    row range zero-padded to `width`.  Shape [n_shards * width, n_items]."""
    n_tx, n_items = T.shape
    out = np.zeros((plan.n_shards * plan.width, n_items), dtype=T.dtype)
    start = 0
    for d in range(plan.n_shards):
        r = min(int(plan.rows[d]), max(n_tx - start, 0))
        out[d * plan.width:d * plan.width + r] = T[start:start + r]
        start += int(plan.rows[d])
    return out


def count_moves(old: ShardPlan, new: ShardPlan) -> Tuple[int, int]:
    """(switches, reissued) between two plans over the same bitmap:
    `switches` = row blocks that changed owner between two live ranks,
    `reissued` = row blocks re-issued away from a rank that died."""
    a, b = old.block_owners(), new.block_owners()
    assert len(a) == len(b), "plans cover different bitmaps"
    moved = a != b
    from_dead = moved & ~new.alive[a]
    return int((moved & ~from_dead).sum()), int(from_dead.sum())


# ---------------------------------------------------------------------------
# jax-traceable map bodies (module-level: stable identities keep the
# run_sharded program cache warm across rounds and runs)
# ---------------------------------------------------------------------------

def _item_counts_map(shard):
    return shard.sum(axis=0, dtype=jnp.int32)


def _support_map_ref(shard, C):
    return support_count_ref(shard, C)


def _support_map_pallas(shard, C):
    from repro.kernels.support_count.ops import support_count
    return support_count(shard, C)


def _eclat_item_counts_map(shard):
    """shard: [width, n_items] word-major packed tid matrix (uint32) —
    per-item counts are plain column popcount sums; padding words are 0."""
    return jnp.sum(jax.lax.population_count(shard).astype(jnp.int32), axis=0)


def _eclat_support_map(shard, Cidx):
    """Stateless k-way AND over base item columns, per shard.

    ``Cidx [M, k] int32`` holds each candidate's item ids.  Unlike the
    single-device Eclat plane's pairwise (k-1)-slab cascade, the sharded
    round recomputes each candidate's tidset from the *base* columns —
    carrying per-rank intermediate slabs through shard re-plans would
    couple the fault path to mining state; k is small (≤ a handful of
    levels) so the extra ANDs are cheap and every round stays a pure
    function of (data, Cidx).  Both formulations count identical bits.
    """
    g = jnp.take(shard, Cidx[:, 0], axis=1)            # [width, M]
    for j in range(1, Cidx.shape[1]):                  # k is static
        g = g & jnp.take(shard, Cidx[:, j], axis=1)
    return jnp.sum(jax.lax.population_count(g).astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# the miner
# ---------------------------------------------------------------------------

class ShardedMiner:
    """MarketBasketPipeline semantics, executed over a real device mesh.

    Produces the same ``PipelineResult`` (bit-identical supports and rules —
    tested against the single-device plane) with a report whose map phases
    were *executed* under shard_map + psum rather than event-simulated.
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 profile: Optional[HeterogeneityProfile] = None,
                 config: Optional[PipelineConfig] = None,
                 scheduler: Optional[MBScheduler] = None,
                 power: Optional[PowerModel] = None,
                 policy: Union[str, SwitchingPolicy, None] = None,
                 row_block: int = 8,
                 verify_rounds: bool = False):
        self.mesh = mesh if mesh is not None else make_shard_mesh()
        self.axis = self.mesh.axis_names[0]
        n = self.mesh.shape[self.axis]
        self.profile = profile or mesh_profile(n)
        if self.profile.n != n:
            raise ValueError(f"profile has {self.profile.n} ranks but mesh "
                             f"axis {self.axis!r} has {n}")
        self.config = config or PipelineConfig()
        policy = policy if policy is not None else self.config.policy
        if policy == "costmodel" and self.config.autotune:
            # measured kernel walls replace the datasheet constants (the
            # kernel the chosen formulation actually dispatches to)
            policy = autotuned_costmodel(
                "intersect_count" if self.config.algorithm == "eclat"
                else "support_count")
        self.runtime = Runtime(
            self.profile,
            policy=policy,
            split=self.config.split,
            power=power if power is not None else self.config.power,
            scheduler=scheduler)
        self.scheduler = self.runtime.scheduler
        self.power = self.runtime.power
        self.backend = resolve_backend(self.config.data_plane)
        # rank d's slab lives on mesh device d: the shard_map input layout
        self.data_sharding = NamedSharding(self.mesh, P(self.axis))
        self.row_block = row_block
        self.verify_rounds = verify_rounds
        # stable job objects -> run_sharded's compiled-program cache hits
        # whenever a later round (or run) repeats a batch shape
        self._item_jobs: dict = {}
        self._support_jobs: dict = {}
        self._eclat_jobs: dict = {}
        # the auto-selector's decision for the last run() (None when the
        # algorithm was explicit) — the CLI surfaces it
        self.algorithm_choice = None

    # ------------------------------------------------------------------
    def _item_job(self, n_items: int) -> MapReduceJob:
        job = self._item_jobs.get(n_items)
        if job is None:
            job = MapReduceJob(
                name=f"sharded-round1-item-counts-{n_items}",
                map_fn=_item_counts_map,
                combine_fn=lambda a, b: a + b,
                zero_fn=lambda m=n_items: jnp.zeros(m, jnp.int32))
            self._item_jobs[n_items] = job
        return job

    def _support_job(self, m_padded: int) -> MapReduceJob:
        job = self._support_jobs.get(m_padded)
        if job is None:
            map_fn = (_support_map_pallas if self.backend == "pallas"
                      else _support_map_ref)
            job = MapReduceJob(
                name=f"sharded-support-m{m_padded}",
                map_fn=map_fn,
                combine_fn=lambda a, b: a + b,
                zero_fn=lambda m=m_padded: jnp.zeros(m, jnp.int32))
            self._support_jobs[m_padded] = job
        return job

    def _eclat_job(self, m_padded: int, k: int) -> MapReduceJob:
        """One job per (candidate bucket, level arity): the k-way AND body
        specializes on Cidx's static column count."""
        job = self._eclat_jobs.get((m_padded, k))
        if job is None:
            job = MapReduceJob(
                name=f"eclat-sharded-intersect-m{m_padded}-k{k}",
                map_fn=(_eclat_item_counts_map if k == 1
                        else _eclat_support_map),
                combine_fn=lambda a, b: a + b,
                zero_fn=lambda m=m_padded: jnp.zeros(m, jnp.int32))
            self._eclat_jobs[(m_padded, k)] = job
        return job

    # ------------------------------------------------------------------
    def _sharded_round(self, job: MapReduceJob, data: jnp.ndarray,
                       plan: ShardPlan, n_items: int,
                       extra_args: Tuple = (),
                       switches: int = 0, reissued: int = 0):
        """One shard_map round through the shared runtime.  The shard plan
        *is* the assignment (rank d owns tile d, cost = its real-row bytes);
        re-plan moves are charged to this phase; busy/energy are modeled on
        the ledger exactly as for the other planes."""
        costs = plan.shard_costs(n_items)
        task = TaskSpec(job.name, float(costs.sum()), parallel=True,
                        n_tiles=self.profile.n)

        def execute(_asg, _costs):
            result, _ = run_sharded(job, data, self.mesh, self.axis,
                                    extra_args=extra_args)
            # the psum-reduced vector comes back host-side here, inside the
            # phase, so the round's single sync lands on this map record
            result = self.runtime.meter.d2h(result, dtype=np.int64)
            return MeasuredPhase(result=result)

        return self.runtime.run_phase(
            task, execute, tile_costs=costs,
            assignment=self.runtime.pinned_assignment(costs),
            extra_switches=switches, extra_reissued=reissued)

    def _stage(self, slab: np.ndarray, report: PipelineReport) -> jax.Array:
        """Upload a rank-major slab so each device receives only its own
        rank's rows; the report records which device holds each rank."""
        data = self.runtime.meter.h2d(slab, sharding=self.data_sharding)
        report.shard_devices = [
            int(s.device.id) for s in
            sorted(data.addressable_shards,
                   key=lambda s: s.index[0].start or 0)]
        return data

    def _serial(self, name: str, cost: float, fn=None):
        # driver phases execute on the host co-located with rank 0
        return self.runtime.run_serial(name, cost, fn=fn, device=0)

    # ------------------------------------------------------------------
    def _apply_faults(self, k: int, faults: Optional[FaultPlan],
                      alive: np.ndarray, plan: ShardPlan, T: np.ndarray,
                      report: PipelineReport,
                      row_block: Optional[int] = None
                      ) -> Tuple[ShardPlan, Optional[jnp.ndarray],
                                 int, int, List[int]]:
        """Consume round-k fault events; returns the (possibly new) plan,
        re-laid-out device data (or None if unchanged), and this round's
        (switches, reissued, newly_dead).  ``T`` is whatever row matrix
        the plane shards (transaction rows for Apriori, packed tid words
        for Eclat — ``row_block`` overrides the transaction-row blocking
        for the latter, where one row already covers 32 transactions)."""
        row_block = self.row_block if row_block is None else row_block
        events = faults.at(k) if faults else []
        newly_dead: List[int] = []
        replan = False
        for e in events:
            if e.kind == "device_loss" and alive[e.device]:
                alive[e.device] = False
                newly_dead.append(e.device)
                replan = True
            elif e.kind == "straggler":
                # observed rate = current speed / slowdown, EWMA'd into the
                # profile -> the re-plan gives the straggler proportionally
                # fewer row blocks (severity 1.0 = no slowdown, no change)
                self.profile.observe(
                    e.device,
                    work_done=float(self.profile.speeds[e.device]),
                    seconds=float(e.severity))
                replan = True
        if not replan:
            return plan, None, 0, 0, newly_dead
        new_plan = plan_shards(self.profile, T.shape[0],
                               row_block=row_block, alive=alive)
        switches, reissued = count_moves(plan, new_plan)
        self.scheduler.switches += switches + reissued
        report.replans += 1
        report.shard_rows = [int(r) for r in new_plan.rows]
        return (new_plan, self._stage(shard_bitmap(T, new_plan), report),
                switches, reissued, newly_dead)

    def _check_round(self, k: int, T: np.ndarray, C_padded: Optional[np.ndarray],
                     counts: np.ndarray) -> None:
        """Cross-shard invariant: the psum-reduced global support vector must
        equal the single-device oracle on the unsharded bitmap."""
        if C_padded is None:                       # k=1 column sums
            want = T.sum(axis=0, dtype=np.int64)[:len(counts)]
        else:
            want = np.asarray(support_count_ref(
                jnp.asarray(T), jnp.asarray(C_padded)),
                dtype=np.int64)[:len(counts)]
        if not np.array_equal(counts, want):
            bad = int(np.flatnonzero(counts != want)[0])
            raise RuntimeError(
                f"cross-shard invariant violated at round k={k}: "
                f"candidate {bad} counted {counts[bad]} sharded vs "
                f"{want[bad]} single-device")

    # ------------------------------------------------------------------
    @staticmethod
    def _round_view(rec, plan: ShardPlan, k: int, n_candidates: int,
                    n_frequent: int, dead: List[int],
                    serial=None, m_padded: int = 0) -> RoundReport:
        """Per-round view with shard-plan tile semantics: "tiles" are row
        blocks (Σ blocks == n_tiles invariant), not the per-rank slabs the
        pinned assignment schedules."""
        return RoundReport(
            k=k, n_candidates=n_candidates, n_frequent=n_frequent,
            n_tiles=plan.n_blocks,
            tiles_per_device=[int(b) for b in plan.rows // plan.row_block],
            map_makespan_s=rec.sim_time_s, map_busy_s=list(rec.busy_s),
            switches=rec.switches, reissued=rec.reissued,
            energy_j=rec.energy_j, serial=serial, m_padded=m_padded,
            failed_devices=dead)

    def run(self, baskets: Baskets,
            faults: Optional[FaultPlan] = None) -> PipelineResult:
        """Dispatch on ``config.algorithm`` (apriori | eclat | auto) —
        every formulation produces bit-identical supports and rules."""
        algorithm = self.config.algorithm
        self.algorithm_choice = None
        if algorithm == "auto":
            from repro.mining.select import select_algorithm
            stats = density_stats(baskets)
            self.algorithm_choice = select_algorithm(
                baskets, self.config.abs_support(stats.n_tx), stats=stats)
            algorithm = self.algorithm_choice.algorithm
        if algorithm == "eclat":
            return self._run_eclat(baskets, faults)
        if algorithm != "apriori":
            raise ValueError(f"unknown mining algorithm {algorithm!r}")
        return self._run_apriori(baskets, faults)

    def _run_apriori(self, baskets: Baskets,
                     faults: Optional[FaultPlan] = None) -> PipelineResult:
        cfg = self.config
        rt = self.runtime
        t_start = time.perf_counter()
        # a run that raised mid-way (invariant check, scoring error) leaves
        # orphaned records; this plane owns its runtime, so anything still
        # live belongs to no report — drop it before marking
        rt.ledger.take_since(0)
        mark = rt.ledger.mark()

        T, n_items_raw, n_tx_raw = ingest_baskets(baskets)
        T = pad_items(T)
        n_tx, n_items = T.shape                    # lane-padded (internal)
        min_sup = cfg.abs_support(n_tx_raw)
        n = self.profile.n

        alive = np.ones(n, dtype=bool)
        plan = plan_shards(self.profile, n_tx, row_block=self.row_block,
                           alive=alive)
        report = PipelineReport(
            backend=self.backend, policy=rt.policy.name, split=rt.split,
            profile_speeds=[float(s) for s in self.profile.speeds],
            n_tx=n_tx_raw, n_items=n_items_raw,
            n_tiles=plan.n_blocks, min_support=min_sup,
            execution="sharded", n_shards=n,
            shard_rows=[int(r) for r in plan.rows])
        data = self._stage(shard_bitmap(T, plan), report)
        supports = {}

        # ---- round k=1: item frequency (<item, count>) ----------------
        plan, new_data, sw, re, dead = self._apply_faults(
            1, faults, alive, plan, T, report)
        if new_data is not None:
            data = new_data
        counts, rec = self._sharded_round(
            self._item_job(n_items), data, plan, n_items,
            switches=sw, reissued=re)
        if self.verify_rounds:
            self._check_round(1, T, None, counts)
        frequent = [(int(i),) for i in np.nonzero(
            counts[:n_items_raw] >= min_sup)[0]]
        for (i,) in frequent:
            supports[(i,)] = int(counts[i])
        report.rounds.append(self._round_view(
            rec, plan, k=1, n_candidates=n_items_raw,
            n_frequent=len(frequent), dead=dead))

        # ---- rounds k>=2: serial candidate-gen + sharded counting -----
        k = 2
        while frequent and (cfg.max_k == 0 or k <= cfg.max_k):
            plan, new_data, sw, re, dead = self._apply_faults(
                k, faults, alive, plan, T, report)
            if new_data is not None:
                data = new_data
            cands, serial = self._serial(
                f"mba-candgen-k{k}",
                cost=max(1.0, len(frequent) * k * cfg.serial_unit_cost),
                fn=lambda fr=frequent: generate_candidates(fr))
            if not cands:
                # a replan consumed this round but no map phase will run to
                # carry its moves: charge them (counts AND joules) to the
                # serial record so the ledger still accounts every
                # migration exactly once
                rt.charge_moves(serial, sw, re)
                view = RoundReport.from_phases(
                    k=k, n_candidates=0, n_frequent=0, map_phase=None,
                    serial=serial, n_devices=n)
                view.switches, view.reissued = sw, re
                view.failed_devices = dead
                report.rounds.append(view)
                break

            C = pad_candidates(itemsets_to_bitmap(cands, n_items),
                               cfg.m_bucket)
            Cj = rt.meter.h2d(C)
            sup_all, rec = self._sharded_round(
                self._support_job(C.shape[0]), data, plan, n_items,
                extra_args=(Cj,), switches=sw, reissued=re)
            # padded candidate rows are all-zero masks and would match every
            # transaction — slice to the true count, never trust padding
            sup = sup_all[:len(cands)]
            if self.verify_rounds:
                self._check_round(k, T, C, sup)
            frequent = []
            for c, s in zip(cands, sup):
                if s >= min_sup:
                    supports[c] = int(s)
                    frequent.append(c)
            report.rounds.append(self._round_view(
                rec, plan, k=k, n_candidates=len(cands),
                n_frequent=len(frequent), dead=dead, serial=serial,
                m_padded=int(C.shape[0])))
            k += 1

        # ---- step 3: association rules (driver, rank 0) ---------------
        rules, rules_rec = self._serial(
            "mba-rules",
            cost=max(1.0, len(supports) * cfg.serial_unit_cost),
            fn=lambda: generate_rules(
                AprioriResult(supports=supports, n_tx=n_tx_raw, levels=k - 1),
                cfg.min_confidence, min_lift=cfg.min_lift))
        report.rules_phase = rules_rec

        report.n_itemsets = len(supports)
        report.n_rules = len(rules)
        report.wall_time_s = time.perf_counter() - t_start
        report.ledger = rt.ledger.take_since(mark)
        return PipelineResult(supports=supports, rules=rules, report=report,
                              n_tx=n_tx_raw)

    # ------------------------------------------------------------------
    # vertical (Eclat) execution: the packed tid matrix sharded over the
    # WORD axis — each rank owns a contiguous band of 32-transaction word
    # rows, every round is a stateless k-way AND over base item columns
    # ------------------------------------------------------------------
    def _run_eclat(self, baskets: Baskets,
                   faults: Optional[FaultPlan] = None) -> PipelineResult:
        cfg = self.config
        rt = self.runtime
        t_start = time.perf_counter()
        rt.ledger.take_since(0)
        mark = rt.ledger.mark()
        n = self.profile.n

        # ---- columnize on the driver (rank 0), then shard word-major ---
        def columnize():
            if isinstance(baskets, SparseSlab):
                return (baskets.tid_columns(), baskets.n_items,
                        baskets.n_tx)
            from repro.data.sparse import pack_tid_columns
            T, ni, ntx = ingest_baskets(baskets)
            return pack_tid_columns(T), ni, ntx

        stats = density_stats(baskets)
        (cols, n_items_raw, n_tx_raw), _ = self._serial(
            "eclat-columnize", cost=max(1.0, 4.0 * stats.nnz), fn=columnize)
        min_sup = cfg.abs_support(n_tx_raw)
        n_items_pad = cols.shape[0]
        # word-major [W_pad, n_items_pad]: the shardable leading axis is
        # words (32 tx each); one "row block" is one word row
        Tw = np.ascontiguousarray(cols.T)
        # the smoke path re-counts every round against the dense oracle;
        # only then is the dense bitmap ever materialized on this plane
        T_dense = (pad_items(ingest_baskets(baskets)[0])
                   if self.verify_rounds else None)

        alive = np.ones(n, dtype=bool)
        plan = plan_shards(self.profile, Tw.shape[0], row_block=1,
                           alive=alive)
        word_bytes = 4 * n_items_pad              # cost units: real-row bytes

        report = PipelineReport(
            backend=self.backend, policy=rt.policy.name,
            algorithm="eclat", split=rt.split,
            profile_speeds=[float(s) for s in self.profile.speeds],
            n_tx=n_tx_raw, n_items=n_items_raw,
            n_tiles=plan.n_blocks, min_support=min_sup,
            execution="sharded", n_shards=n,
            shard_rows=[int(r) for r in plan.rows])
        data = self._stage(shard_bitmap(Tw, plan), report)
        supports = {}

        # ---- round k=1: per-item column popcounts ----------------------
        plan, new_data, sw, re, dead = self._apply_faults(
            1, faults, alive, plan, Tw, report, row_block=1)
        if new_data is not None:
            data = new_data
        counts, rec = self._sharded_round(
            self._eclat_job(n_items_pad, 1), data, plan, word_bytes,
            switches=sw, reissued=re)
        if self.verify_rounds:
            self._check_round(1, T_dense, None, counts[:n_items_raw])
        frequent = [(int(i),) for i in np.nonzero(
            counts[:n_items_raw] >= min_sup)[0]]
        for (i,) in frequent:
            supports[(i,)] = int(counts[i])
        report.rounds.append(self._round_view(
            rec, plan, k=1, n_candidates=n_items_raw,
            n_frequent=len(frequent), dead=dead))

        # ---- rounds k>=2: serial join + sharded k-way AND-popcount -----
        k = 2
        while frequent and (cfg.max_k == 0 or k <= cfg.max_k):
            plan, new_data, sw, re, dead = self._apply_faults(
                k, faults, alive, plan, Tw, report, row_block=1)
            if new_data is not None:
                data = new_data
            cands, serial = self._serial(
                f"eclat-candgen-k{k}",
                cost=max(1.0, len(frequent) * k * cfg.serial_unit_cost),
                fn=lambda fr=frequent: generate_candidates(fr))
            if not cands:
                rt.charge_moves(serial, sw, re)
                view = RoundReport.from_phases(
                    k=k, n_candidates=0, n_frequent=0, map_phase=None,
                    serial=serial, n_devices=n)
                view.switches, view.reissued = sw, re
                view.failed_devices = dead
                report.rounds.append(view)
                break

            # candidate item-id matrix, zero-padded to the bucket shape
            # (padding rows AND item 0's column with itself — junk counts
            # that are sliced away, never trusted)
            Cidx = np.zeros((-(-len(cands) // cfg.m_bucket) * cfg.m_bucket,
                             k), dtype=np.int32)
            Cidx[:len(cands)] = np.asarray(cands, dtype=np.int32)
            sup_all, rec = self._sharded_round(
                self._eclat_job(Cidx.shape[0], k), data, plan, word_bytes,
                extra_args=(rt.meter.h2d(Cidx),), switches=sw, reissued=re)
            sup = sup_all[:len(cands)]
            if self.verify_rounds:
                self._check_round(
                    k, T_dense,
                    itemsets_to_bitmap(cands, T_dense.shape[1]), sup)
            frequent = []
            for c, s in zip(cands, sup):
                if s >= min_sup:
                    supports[c] = int(s)
                    frequent.append(c)
            report.rounds.append(self._round_view(
                rec, plan, k=k, n_candidates=len(cands),
                n_frequent=len(frequent), dead=dead, serial=serial,
                m_padded=int(Cidx.shape[0])))
            k += 1

        # ---- association rules (driver, rank 0) ------------------------
        rules, rules_rec = self._serial(
            "mba-rules",
            cost=max(1.0, len(supports) * cfg.serial_unit_cost),
            fn=lambda: generate_rules(
                AprioriResult(supports=supports, n_tx=n_tx_raw, levels=k - 1),
                cfg.min_confidence, min_lift=cfg.min_lift))
        report.rules_phase = rules_rec

        report.n_itemsets = len(supports)
        report.n_rules = len(rules)
        report.wall_time_s = time.perf_counter() - t_start
        report.ledger = rt.ledger.take_since(mark)
        return PipelineResult(supports=supports, rules=rules, report=report,
                              n_tx=n_tx_raw)
