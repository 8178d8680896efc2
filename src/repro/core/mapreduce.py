"""MapReduce engine — Hadoop semantics, TPU-native execution.

Two runtimes share one :class:`MapReduceJob` definition:

* :class:`SimulatedCluster` — deterministic event simulation over a
  :class:`HeterogeneityProfile` (the paper's 4-core system, a straggler-laden
  pod, ...).  Computes the *real* result (every tile mapped exactly once,
  combined associatively) and a timing/energy report under the MB Scheduler,
  including failures (tiles of a dead device re-planned — "dynamic core
  switching") and speculative re-issue.
* :func:`run_sharded` — `shard_map` execution over a JAX mesh axis: map
  runs on-device per shard, the reduce is a `psum` combiner tree.  This is
  the path the pod actually executes; the simulator is the scheduler's
  planning/evaluation model (and the benchmark harness for the paper's
  claims, since this container has one real device).
"""
from __future__ import annotations

import functools
import heapq
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.hetero import HeterogeneityProfile
from repro.core.power import PowerModel
from repro.core.scheduler import Assignment, MBScheduler, TaskSpec


@dataclass(frozen=True)
class MapReduceJob:
    """map: tile -> value; combine: value × value -> value (associative).

    ``round_fn`` computes the whole round at once, ``tiles -> value``: a
    job whose tiles stay resident on one device gives it, so a round is one
    call into the device instead of a host fold over the tiles.  Without
    it the round is the per-tile fold of ``map_fn`` / ``combine_fn`` from
    ``zero_fn()``.  Either way every tile is counted exactly once, so the
    value is the same."""

    name: str
    map_fn: Optional[Callable[[Any], Any]] = None
    combine_fn: Optional[Callable[[Any, Any], Any]] = None
    zero_fn: Optional[Callable[[], Any]] = None
    cost_fn: Optional[Callable[[Any], float]] = None   # work units per tile
    round_fn: Optional[Callable[[Sequence[Any]], Any]] = None

    def __post_init__(self):
        if self.round_fn is None and None in (self.map_fn, self.combine_fn,
                                              self.zero_fn):
            raise ValueError(f"job {self.name!r} needs a round_fn or all of "
                             f"map_fn, combine_fn and zero_fn")

    def tile_cost(self, tile) -> float:
        """Work units of one tile: ``cost_fn``, else its bytes (an array's
        ``nbytes``, or ``size`` × item size of a shape-and-dtype spec)."""
        if self.cost_fn is not None:
            return float(self.cost_fn(tile))
        if hasattr(tile, "nbytes"):
            return float(tile.nbytes)
        if hasattr(tile, "dtype") and hasattr(tile, "size"):
            return float(tile.size * np.dtype(tile.dtype).itemsize)
        return 1.0

    def run_round(self, tiles: Sequence[Any]) -> Tuple[Any, int]:
        """The round's value and the number of calls it made: 1 for
        ``round_fn``, one ``map_fn`` call per tile for the fold."""
        if self.round_fn is not None:
            return self.round_fn(tiles), 1
        result = self.zero_fn()
        for t in tiles:
            result = self.combine_fn(result, self.map_fn(t))
        return result, len(tiles)


@dataclass
class ExecReport:
    makespan: float
    busy_s: np.ndarray
    waves: int = 1
    switches: int = 0
    reissued: int = 0
    failed_devices: List[int] = field(default_factory=list)
    energy_j: Optional[float] = None
    assignment: Optional[Assignment] = None
    tiles_done: Optional[List[int]] = None   # tiles *executed* per device
    # (differs from assignment.tiles_of after failures: orphaned tiles are
    # counted at the survivor that re-ran them)
    map_calls: int = 0     # host calls the round made (MapReduceJob.run_round)


@dataclass
class FailureEvent:
    device: int
    at_time: float


class SimulatedCluster:
    """Event-driven simulation of a heterogeneous cluster executing a job."""

    def __init__(self, profile: HeterogeneityProfile,
                 scheduler: Optional[MBScheduler] = None,
                 power: Optional[PowerModel] = None):
        self.profile = profile
        self.scheduler = scheduler or MBScheduler(profile)
        self.power = power

    # ------------------------------------------------------------------
    def run(self, job: MapReduceJob, tiles: Sequence[Any],
            failures: Optional[List[FailureEvent]] = None,
            speculate: bool = True,
            assignment: Optional[Assignment] = None) -> Tuple[Any, ExecReport]:
        """`assignment` pins a pre-planned placement (the shared Runtime
        plans through its SwitchingPolicy and passes the result here);
        otherwise this cluster's scheduler plans statically."""
        tile_costs = np.array([job.tile_cost(t) for t in tiles], dtype=np.float64)
        if assignment is not None:
            asg = assignment
        else:
            task = TaskSpec(job.name, float(tile_costs.sum()), parallel=True,
                            n_tiles=len(tiles))
            asg = self.scheduler.assign_parallel(task, tile_costs)
        report = self._simulate(asg, tile_costs, failures or [], speculate)
        report.assignment = asg
        if self.power is not None:
            # same joule definition as Runtime.run_phase: cores that ran
            # nothing are gated, and every migration (switch OR re-issue)
            # is priced
            gated = [d for d in range(self.profile.n)
                     if report.busy_s[d] == 0.0]
            report.energy_j = self.power.energy(
                report.busy_s, report.makespan, gated=gated,
                switches=report.switches + report.reissued)
        # --- actual computation: every tile exactly once ---
        result, report.map_calls = job.run_round(tiles)
        return result, report

    # ------------------------------------------------------------------
    def _simulate(self, asg: Assignment, tile_costs: np.ndarray,
                  failures: List[FailureEvent], speculate: bool) -> ExecReport:
        D = self.profile.n
        speeds = self.profile.speeds
        fail_at = {f.device: f.at_time for f in failures}
        queues: List[List[int]] = [list(ts) for ts in asg.tiles_of]
        busy = np.zeros(D)
        clock = np.zeros(D)                      # per-device current time
        done: set = set()
        alive = [d for d in range(D)]
        switches, reissued = 0, 0
        pending = {t for q in queues for t in q}
        done_by = [0] * D

        def run_queue(d: int):
            nonlocal switches
            q = queues[d]
            while q:
                t = q[0]
                dt = tile_costs[t] / speeds[d]
                if d in fail_at and clock[d] + dt > fail_at[d]:
                    return False                  # dies mid-tile
                q.pop(0)
                clock[d] += dt
                busy[d] += dt
                done.add(t)
                done_by[d] += 1
                pending.discard(t)
            return True

        # first pass
        dead: List[int] = []
        for d in list(alive):
            ok = run_queue(d)
            if not ok:
                dead.append(d)
                alive.remove(d)
                clock[d] = fail_at[d]
        # dynamic re-planning of orphaned tiles (paper: dynamic switching)
        orphans = sorted(pending)
        while orphans:
            if not alive:
                raise RuntimeError("all devices failed")
            # LPT over survivors, starting at their current clocks
            for t in sorted(orphans, key=lambda t: -tile_costs[t]):
                d = min(alive, key=lambda d: clock[d] + tile_costs[t] / speeds[d])
                dt = tile_costs[t] / speeds[d]
                clock[d] += dt
                busy[d] += dt
                done.add(t)
                done_by[d] += 1
                switches += 1
            pending.difference_update(orphans)
            orphans = []
        makespan = float(clock.max())
        # speculative re-issue: if one device dominates the tail, clone its
        # last tile onto the fastest idle device and take the min finish.
        if speculate and alive:
            slowest = int(np.argmax(clock))
            others = [d for d in alive if d != slowest]
            if others and asg.tiles_of[slowest]:
                helper = max(others, key=lambda d: speeds[d])
                t = asg.tiles_of[slowest][-1]
                alt = clock[helper] + tile_costs[t] / speeds[helper]
                orig = clock[slowest]
                if alt < orig - 1e-12:
                    reissued += 1
                    makespan = float(max(np.delete(clock, slowest).max() if D > 1 else 0.0,
                                         min(orig, alt),
                                         clock[slowest] - tile_costs[t] / speeds[slowest]))
        # switches is per-run (this job's re-planned tiles only); the
        # scheduler keeps its own lifetime counter for rebalance/speculate
        return ExecReport(makespan=makespan, busy_s=busy,
                          switches=switches,
                          reissued=reissued, failed_devices=dead,
                          tiles_done=done_by)


# ---------------------------------------------------------------------------
# Real distributed execution: shard_map + psum combiner tree
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _sharded_fn(job: MapReduceJob, mesh, axis: str, n_extra: int):
    """Build (and cache) the jitted shard_map program for one job/mesh pair.

    The cache key is the *job object* (frozen dataclass → hashable): callers
    that reuse one MapReduceJob across rounds — the sharded miner's bucketed
    support jobs — hit the same compiled program whenever shapes repeat,
    exactly like the single-device DataPlane's jit-cache discipline.
    """
    def shard_body(x, *extra):
        v = job.map_fn(x, *extra)
        return jax.tree.map(lambda a: jax.lax.psum(a, axis), v)

    spec_out = jax.tree.map(lambda _: P(), job.zero_fn())
    f = jax.shard_map(shard_body, mesh=mesh,
                      in_specs=(P(axis),) + (P(),) * n_extra,
                      out_specs=spec_out, check_vma=False)
    return jax.jit(f)


def run_sharded(job: MapReduceJob, data: jnp.ndarray, mesh,
                axis: str = "data", *,
                extra_args: Tuple[Any, ...] = (),
                profile: Optional[HeterogeneityProfile] = None,
                shard_costs: Optional[np.ndarray] = None,
                ) -> Tuple[Any, ExecReport]:
    """Execute map over equal shards of `data`'s leading axis; reduce with a
    psum tree.  Returns ``(result, ExecReport)`` like ``SimulatedCluster.run``
    so simulated and sharded executions are report-comparable.

    `map_fn` must be jax-traceable, take ``(shard, *extra_args)`` and return
    a pytree of arrays with shapes independent of the shard size.
    ``extra_args`` are replicated to every shard (e.g. a candidate bitmap).

    Timing: with a `profile` (and per-rank `shard_costs` in the same work
    units the scheduler uses — defaults to an equal split of
    ``data.nbytes``), busy seconds are ``cost / speed`` per rank; without a
    profile the report carries measured wall time only.  Energy and switch
    pricing live in ``repro.runtime.Runtime.run_phase`` — the one place
    every plane's accounting happens — not here.
    """
    n_shards = mesh.shape[axis]
    f = _sharded_fn(job, mesh, axis, len(extra_args))
    t0 = time.perf_counter()
    result = f(data, *extra_args)
    result = jax.block_until_ready(result)
    wall_s = time.perf_counter() - t0

    if profile is not None:
        if profile.n != n_shards:
            raise ValueError(f"profile has {profile.n} ranks but mesh axis "
                             f"{axis!r} has {n_shards}")
        if shard_costs is None:
            shard_costs = np.full(n_shards, data.nbytes / n_shards)
        shard_costs = np.asarray(shard_costs, dtype=np.float64)
        busy = shard_costs / profile.speeds
        makespan = float(busy.max()) if len(busy) else 0.0
        rep = ExecReport(makespan=makespan, busy_s=busy,
                         tiles_done=[int(c > 0) for c in shard_costs])
    else:
        rep = ExecReport(makespan=wall_s, busy_s=np.zeros(n_shards),
                         tiles_done=[1] * n_shards)
    return result, rep
