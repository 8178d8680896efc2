"""MarketBasketPipeline — the paper end-to-end, as one object.

Composition (paper §V):

  baskets ──ingest──▶ bitmap T[n_tx, n_items] ──upload──▶ device tiles
     │        (serial phases mba-ingest, mba-upload → Runtime.run_serial)
     │
     ├─ round k=1: item-frequency MapReduceJob (tiled over the profile)
     ├─ round k≥2: serial candidate generation  → Runtime.run_serial
     │             (one core runs, the rest are power-gated)
     │             tiled support counting       → Runtime.run_phase
     │             (DataPlane: Pallas kernel on TPU, jitted ref elsewhere)
     │   every counting round runs as one device program over the resident
     │   tiles; the scheduler plans and prices the tiles, it does not set
     │   how many calls a round makes
     ├─ rules: confidence/lift pruning, serial phase on the fastest core
     ▼
  PipelineResult(supports, rules, PipelineReport)

The control plane (candidate generation, rule enumeration) is host Python
— the paper's "single-threaded tasks"; its scheduling/energy is *modeled*
through the shared :class:`repro.runtime.Runtime`, which owns the
MBScheduler + PowerModel + phase ledger and performs assignment, policy
feedback and accounting exactly once per phase.  The switching policy
(``static`` | ``dynamic`` | ``costmodel``) is a config knob; execution
stays in :class:`SimulatedCluster`, which honors whatever assignment the
policy planned.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.hetero import HeterogeneityProfile
from repro.core.itemsets import AprioriResult, frequent_itemsets
from repro.core.mapreduce import FailureEvent, MapReduceJob, SimulatedCluster
from repro.core.power import PowerModel
from repro.core.scheduler import MBScheduler, TaskSpec
from repro.core.rules import Rule, generate_rules
from repro.data.baskets import pack_transactions
from repro.data.sparse import SparseSlab
from repro.pipeline.dataplane import (DataPlane, ResidentTiles, device_tiles,
                                      item_counts)
from repro.pipeline.devgen import DeviceLattice
from repro.pipeline.report import PipelineReport, RoundReport
from repro.runtime import (MeasuredPhase, Runtime, SwitchingPolicy,
                           autotuned_costmodel, donated_add)

Baskets = Union[np.ndarray, SparseSlab, Sequence[Sequence[int]]]


def _is_binary(b: np.ndarray) -> bool:
    """Whether every entry is 0 or 1, in as few passes as the dtype allows:
    none for ``bool``, one ``max`` for unsigned integers, ``min`` and
    ``max`` for signed ones, and the elementwise check for anything else
    (floats, objects), where a reduction would pass 0.5."""
    kind = b.dtype.kind
    if kind == "b":
        return True
    if kind == "u":
        return bool(b.max() <= 1)
    if kind == "i":
        return bool(b.min() >= 0 and b.max() <= 1)
    return bool(((b == 0) | (b == 1)).all())


def ingest_baskets(baskets: Baskets) -> Tuple[np.ndarray, int, int]:
    """Validate + pack baskets into the raw 0/1 ``uint8`` bitmap.

    Returns ``(bitmap [n_tx, n_items], raw item count, raw tx count)``,
    the item axis not yet padded: the pipeline pads and tiles it on the
    device, the sharded plane pads it with ``pad_items``.  A
    :class:`SparseSlab` densifies here *explicitly* — the horizontal
    (Apriori) formulation needs the dense bitmap; the Eclat plane
    columnizes the slab without it.
    """
    if isinstance(baskets, SparseSlab):
        baskets = baskets.to_dense()
    if isinstance(baskets, np.ndarray):
        if baskets.ndim != 2:
            raise ValueError(f"bitmap must be 2-D, got {baskets.shape}")
        # validate BEFORE the uint8 cast: casting would truncate floats
        # (0.9 -> 0) and wrap negatives, hiding bad input behind an
        # empty-but-plausible mining result
        if baskets.size and not _is_binary(baskets):
            raise ValueError("bitmap must contain only 0/1 — pass "
                             "transaction lists for count-style data")
        T = (baskets.view(np.uint8) if baskets.dtype == np.bool_
             else baskets.astype(np.uint8, copy=False))
    else:
        T = pack_transactions(baskets)
    return T, T.shape[1], T.shape[0]


@dataclass(frozen=True)
class PipelineConfig:
    """Knobs for one mining run.  min_support <= 1 is a fraction of n_tx
    (1.0 = present in every transaction); values above 1 are absolute
    transaction counts."""

    min_support: float = 0.02
    min_confidence: float = 0.6
    min_lift: float = 0.0
    max_k: int = 0                  # 0 = mine until no candidates survive
    # Mining backend: "apriori" (horizontal bitmap rounds), "eclat"
    # (vertical tid-list intersections), or "auto" (the algorithm cost
    # model picks per dataset from measured density/sparsity features —
    # see repro.mining.select).  All backends are pinned bit-identical.
    algorithm: str = "apriori"
    n_tiles: int = 32
    policy: str = "static"          # switching: static | dynamic | costmodel
    split: str = "lpt"              # tile split: equal | proportional | lpt
    data_plane: str = "auto"        # auto | pallas | ref
    m_bucket: int = 128             # candidate-batch rounding (kernel lanes)
    interpret: Optional[bool] = None  # force Pallas interpret mode (tests)
    # Kernel autotuning: True = the checked-in winner cache picks the
    # Pallas variant + tile shapes (and, under the costmodel policy, its
    # measured walls replace the datasheet roofline constants); False =
    # roofline-seeded defaults everywhere.
    autotune: bool = True
    power: str = "cpu"              # cpu | tpu_v5e | none
    speculate: bool = True
    # Serial-phase cost model: work units charged per (itemset, level) pair
    # examined by the join/prune (same units as tile bytes, so serial and
    # map phases share one time axis).  Calibrated so candidate generation
    # is small-but-visible next to counting, as in the paper.
    serial_unit_cost: float = 64.0
    # Required core speed for serial phases: when no core satisfies it,
    # assign_serial falls back to the fastest core and flags the phase
    # (surfaced as PipelineReport.constraint_violations, never silent).
    serial_min_speed: float = 0.0

    def abs_support(self, n_tx: int) -> int:
        if self.min_support <= 1.0:
            return max(1, int(self.min_support * n_tx))
        return int(self.min_support)


def candgen_cost(n_frequent: int, k: int, unit_cost: float) -> float:
    """Work units for the serial F_{k-1}⋈F_{k-1} join/prune phase.

    Shared by the batch pipeline and the streaming plane's re-validation
    pass — the two Apriori drivers must price (and therefore schedule)
    identical rounds identically, or their ledgers drift."""
    return max(1.0, n_frequent * k * unit_cost)


def support_flops(tile_rows: np.ndarray, n_items: int,
                  m_padded: int) -> np.ndarray:
    """Roofline seed for a support-count map phase: the kernel's MXU work
    is 2·rows·items·candidates per tile (bytes are rows·items).  Shared
    across the Apriori drivers for the same reason as candgen_cost."""
    return 2.0 * tile_rows * n_items * max(m_padded, 1)


@dataclass
class PipelineResult:
    supports: Dict[Tuple[int, ...], int]
    rules: List[Rule]
    report: PipelineReport
    n_tx: int

    def frequent(self, k: Optional[int] = None) -> List[Tuple[int, ...]]:
        return frequent_itemsets(self.supports, k)


class MarketBasketPipeline:
    """Orchestrates the full mining run over a heterogeneity profile."""

    def __init__(self, profile: Optional[HeterogeneityProfile] = None,
                 config: Optional[PipelineConfig] = None,
                 scheduler: Optional[MBScheduler] = None,
                 power: Optional[PowerModel] = None,
                 policy: Union[str, SwitchingPolicy, None] = None):
        self.profile = profile or HeterogeneityProfile.paper()
        self.config = config or PipelineConfig()
        cfg = self.config
        policy = policy if policy is not None else cfg.policy
        if policy == "costmodel" and cfg.autotune:
            # measured kernel walls replace the datasheet constants
            policy = autotuned_costmodel("support_count")
        self.runtime = Runtime(
            self.profile,
            policy=policy,
            split=cfg.split,
            power=power if power is not None else cfg.power,
            scheduler=scheduler)
        self.scheduler = self.runtime.scheduler
        self.power = self.runtime.power
        self.cluster = SimulatedCluster(self.profile, self.scheduler,
                                        power=None)  # ledger prices energy
        self.data_plane = DataPlane(cfg.data_plane,
                                    m_bucket=cfg.m_bucket,
                                    interpret=cfg.interpret,
                                    tuning=None if cfg.autotune else False,
                                    meter=self.runtime.meter)

    # ------------------------------------------------------------------
    # phases
    # ------------------------------------------------------------------
    def _ingest(self, baskets: Baskets) -> Tuple[np.ndarray, int, int]:
        """Returns (raw bitmap, raw item count, raw tx count)."""
        return ingest_baskets(baskets)

    def _stage(self, baskets: Baskets):
        """The mine's first two serial phases.  ``mba-ingest`` validates
        and packs the baskets into the raw bitmap; ``mba-upload`` sends its
        flat bytes to the device once and builds the lane-padded int8 row
        tiles there (``device_tiles``) — every round's map phase reuses
        them, so uploading per round would redo the same transfers — and
        carries the bitmap's bytes on its record.  Returns ``(resident
        tiles, lane-padded bitmap shape, raw item count, raw tx count)``."""
        cfg, rt = self.config, self.runtime
        n_rows = (baskets.n_tx if isinstance(baskets, SparseSlab)
                  else len(baskets))
        (T, n_items_raw, n_tx_raw), _ = rt.run_serial(
            "mba-ingest", cost=max(1.0, n_rows * cfg.serial_unit_cost),
            fn=lambda: self._ingest(baskets),
            min_speed=cfg.serial_min_speed)
        tiles, _ = rt.run_serial(
            "mba-upload", cost=max(1.0, float(T.nbytes)),
            fn=lambda: ResidentTiles(device_tiles(rt.meter.h2d(T.reshape(-1)),
                                                  T.shape, cfg.n_tiles)),
            min_speed=cfg.serial_min_speed)
        return tiles, (n_tx_raw, tiles[0].shape[1]), n_items_raw, n_tx_raw

    def _map_round(self, job: MapReduceJob, tiles: ResidentTiles,
                   failures: Optional[List[FailureEvent]],
                   tile_flops: Optional[np.ndarray] = None,
                   finalize=None):
        """One tiled map phase through the shared runtime: the policy plans
        the assignment, the simulated cluster executes it, the runtime does
        the time/energy/switch accounting exactly once.  ``finalize`` runs
        on the combined result *inside* the phase — the round's single d2h
        readback happens there, so the sync lands on this phase's ledger
        record, not the next one's."""
        tile_costs = np.array([job.tile_cost(t) for t in tiles],
                              dtype=np.float64)
        # one family: every round maps the same device-resident tiles, so
        # dynamic switching tracks owner drift across rounds
        task = TaskSpec(job.name, float(tile_costs.sum()), parallel=True,
                        n_tiles=len(tiles), family="mba-map")

        def execute(asg, _costs):
            result, rep = self.cluster.run(job, tiles, failures=failures,
                                           speculate=self.config.speculate,
                                           assignment=asg)
            if finalize is not None:
                result = finalize(result)
            return MeasuredPhase.from_exec(result, rep)

        return self.runtime.run_phase(task, execute, tile_costs=tile_costs,
                                      tile_flops=tile_flops)

    # ------------------------------------------------------------------
    def run(self, baskets: Baskets,
            failures: Optional[List[FailureEvent]] = None) -> PipelineResult:
        """Mine ``baskets`` with every counting round held on device: a
        round is one device program over the resident tiles (one kernel
        launch per tile, partial counts summed in the program), candidate
        generation for the next level runs as a jitted join on the
        compacted frequent matrix, and the only device→host crossing per
        counting round is one packed ``[m_cap + 1]`` vector (counts + next
        join size) read inside the map phase.  Itemset tuples reach the
        host once, at rule time."""
        cfg = self.config
        rt = self.runtime
        t_start = time.perf_counter()
        # a run that raised mid-way (invariant check, scoring error) leaves
        # orphaned records; this plane owns its runtime, so anything still
        # live belongs to no report — drop it before marking
        rt.ledger.take_since(0)
        mark = rt.ledger.mark()

        # n_items is the lane-padded (internal) width
        tiles, (_, n_items), n_items_raw, n_tx_raw = self._stage(baskets)
        min_sup = cfg.abs_support(n_tx_raw)
        tile_rows = np.array([t.shape[0] for t in tiles], dtype=np.float64)

        report = PipelineReport(
            backend=self.data_plane.backend, policy=rt.policy.name,
            split=rt.split,
            profile_speeds=[float(s) for s in self.profile.speeds],
            n_tx=n_tx_raw, n_items=n_items_raw,
            n_tiles=len(tiles), min_support=min_sup)
        supports: Dict[Tuple[int, ...], int] = {}
        lattice = DeviceLattice(n_items, m_bucket=cfg.m_bucket,
                                meter=rt.meter)

        # ---- round k=1: item frequency, one readback ------------------
        job1 = MapReduceJob(name="mba-round1-item-counts",
                            round_fn=lambda ts: item_counts(ts.stack))
        counts, rec = self._map_round(
            job1, tiles, failures, tile_flops=tile_rows * n_items,
            finalize=lambda acc: rt.meter.d2h(acc, dtype=np.int64))
        frequent_items = np.nonzero(counts >= min_sup)[0]
        for i in frequent_items:
            supports[(int(i),)] = int(counts[i])
        report.rounds.append(RoundReport.from_phases(
            k=1, n_candidates=n_items_raw, n_frequent=len(frequent_items),
            map_phase=rec))
        f_count = len(frequent_items)
        if f_count:
            # seeded between phases, so the (tiny) upload is attributed to
            # the phase that consumes it — the k=2 candgen
            lattice.seed_items(frequent_items)

        # ---- rounds k>=2: device candgen + device-combined counting ---
        k = 2
        while f_count and (cfg.max_k == 0 or k <= cfg.max_k):
            gen, serial = rt.run_serial(
                f"mba-candgen-k{k}",
                cost=candgen_cost(f_count, k, cfg.serial_unit_cost),
                fn=lattice.join,
                min_speed=cfg.serial_min_speed)
            if gen is None:
                report.rounds.append(RoundReport.from_phases(
                    k=k, n_candidates=0, n_frequent=0, map_phase=None,
                    serial=serial, n_devices=self.profile.n))
                break
            C, valid_c, bitmap, m_cap = gen
            self.data_plane.prepare_device(bitmap)
            # the tiles' partial counts fold with the round's combiner
            # inside the one program
            job = MapReduceJob(
                name=f"mba-round{k}-support",
                round_fn=functools.partial(self.data_plane.round_counts,
                                           combine_fn=donated_add))

            def finalize(counts, C=C, valid_c=valid_c):
                packed, Fn, vn = lattice.finalize(counts, C, valid_c, min_sup)
                return rt.meter.d2h(packed), Fn, vn   # the round's one sync

            (packed, Fn, vn), rec = self._map_round(
                job, tiles, failures,
                tile_flops=support_flops(tile_rows, n_items, m_cap),
                finalize=finalize)
            m_true, f_count = lattice.advance(packed, Fn, vn, min_sup)
            report.rounds.append(RoundReport.from_phases(
                k=k, n_candidates=m_true, n_frequent=f_count,
                map_phase=rec, serial=serial, m_padded=m_cap))
            k += 1

        # ---- step 3: rules — tuples decode here, once -----------------
        n_supports = len(supports) + lattice.n_frequent_total

        def rules_fn():
            supports.update(lattice.decode_supports())
            return generate_rules(
                AprioriResult(supports=supports, n_tx=n_tx_raw,
                              levels=k - 1),
                cfg.min_confidence, min_lift=cfg.min_lift)

        rules, rules_rec = rt.run_serial(
            "mba-rules",
            cost=max(1.0, n_supports * cfg.serial_unit_cost),
            fn=rules_fn,
            min_speed=cfg.serial_min_speed)
        report.rules_phase = rules_rec

        report.n_itemsets = len(supports)
        report.n_rules = len(rules)
        report.wall_time_s = time.perf_counter() - t_start
        report.ledger = rt.ledger.take_since(mark)
        return PipelineResult(supports=supports, rules=rules, report=report,
                              n_tx=n_tx_raw)
