"""Per-cell performance configuration (the §Perf levers) and the
roofline-seeded kernel tile spaces the autotuner sweeps.

Model-cell profiles (``cell_config``):

* ``baseline`` — the paper-faithful starting point: stock XLA attention
  (naive scores where they physically fit, chunked where an S² tensor could
  never be resident), dense vocab loss, full remat, minimal grad-accum.
* ``tuned``    — the beyond-paper hillclimbed settings recorded in
  EXPERIMENTS.md §Perf (chunked/online-softmax attention, chunked vocab
  loss for ≥100k vocabs, remat policy, grad-accum, MoE capacity).

Kernel tuning seeds (``kernel_candidates`` / ``estimate_cost_us`` /
``default_config``): the config spaces for the Apriori hot-loop kernels
(``support_count``, ``rule_match``) — each candidate names an
implementation *variant* (``mxu`` int8-matmul vs ``packed``
AND-popcount on uint32 words) plus its tile shape — and a roofline cost
model over :mod:`repro.launch.roofline` constants that orders the sweep
and supplies the cold-cache default: when
:mod:`repro.kernels.autotune` has no measurement for a (kernel,
shape-bucket, device), the argmin of the *estimated* costs is used, so a
missing or corrupt cache degrades to roofline-seeded defaults instead of
erroring.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from repro.configs.base import ModelConfig
from repro.launch.roofline import HBM_BW, PEAK_FLOPS

_BIG_VOCAB = 100_000


def pick_vocab_chunk(vocab: int, target: int = 8192, max_chunk: int = 16384) -> int:
    """Largest divisor of `vocab` ≤ max_chunk (0 if only trivial divisors):
    the chunked-logsumexp loss needs V % chunk == 0.  When the vocab is
    16-divisible we also keep the chunk aligned to the per-device vocab
    shard (V/16) so the reshape keeps its "model" sharding."""
    base = vocab // 16 if vocab % 16 == 0 else vocab
    for c in range(min(max_chunk, base), 0, -1):
        if base % c == 0 and vocab % c == 0:
            return c if c > 64 else 0
    return 0


def cell_config(cfg: ModelConfig, shape_name: str, profile: str
                ) -> Tuple[ModelConfig, Dict[str, Any]]:
    """Returns (model config with profile overrides, extra step options)."""
    opts: Dict[str, Any] = {"grad_accum": 1}
    over: Dict[str, Any] = {}

    if profile == "baseline":
        over["remat_policy"] = "full"
        if shape_name == "train_4k":
            # naive attention fits at 4k with grad-accum; S² is sharded
            over["attention_impl"] = "naive"
            opts["grad_accum"] = 8
        elif shape_name == "prefill_32k":
            # a 32k² f32 score tensor can never be resident -> chunked even
            # in the baseline (documented in EXPERIMENTS.md §Dry-run)
            over["attention_impl"] = "chunked"
            over["attention_chunk"] = 2048
        else:
            over["attention_impl"] = "naive"
        return cfg.replace(**over), opts

    # ---- tuned profile (final choices from the §Perf iteration log) ----
    over["remat_policy"] = "full"
    if shape_name == "train_4k":
        # measured: at 4k with head-sharded scores, naive attention beats the
        # chunked scan on HBM traffic; SP doubles AR volume on these
        # collective-bound cells (§Perf C iterations 1-2) -> both off.
        over["attention_impl"] = "naive"
        over["sequence_parallel"] = False
        opts["grad_accum"] = 8
        if cfg.moe is not None and cfg.moe.n_experts:
            opts["grad_accum"] = 16      # MoE dispatch working-set fit
    else:
        # 32k+ sequences: S² scores can never be resident -> online-softmax
        # chunks; these cells are memory-dominant, where SP's sharded
        # residual saves win (§Perf A/dry-run table).
        over["attention_impl"] = "chunked"
        over["attention_chunk"] = 2048
        if shape_name == "prefill_32k":
            over["sequence_parallel"] = True
    if shape_name in ("train_4k", "prefill_32k"):
        # full-sequence recurrences: chunked WKV / log-depth SSM scan
        # (baseline keeps the paper-naive sequential scans: 44-250x — §Perf A)
        over["time_mix_impl"] = "chunked"
        over["ssm_impl"] = "associative"
    # Chunked logsumexp loss: measured NET-NEGATIVE at these shapes even for
    # non-16-divisible vocabs (replicated [T,V] logits fit comfortably at
    # 4k and the chunk scan adds weight re-reads) — granite train frac
    # 0.0490 dense vs 0.0467 chunked.  The lever stays available
    # (`vocab_loss_chunk`) for configs where logits don't fit; see §Perf.
    return cfg.replace(**over), opts


# ---------------------------------------------------------------------------
# Kernel autotuning seeds (support_count / rule_match tile spaces)
# ---------------------------------------------------------------------------

# VPU-flavored throughput for the packed popcount path: the AND + popcount
# + add word ops run on the vector unit, not the systolic array, at roughly
# an eighth of the MXU's MAC rate per the v5e datapath width.
VPU_OPS = PEAK_FLOPS / 8.0
# Ops per packed word-pair: AND, popcount, accumulate.
_PACKED_OPS_PER_WORD = 3.0
# Fixed cost per grid step (launch + block DMA setup): what makes small
# tiles expensive in the estimate, so the seed order prefers few launches
# until the working set forces tiling.
KERNEL_STEP_OVERHEAD_US = 15.0

TUNABLE_KERNELS = ("support_count", "intersect_count", "rule_match")

# Scoped VMEM one kernel may use on TPU v5e is 16 MiB by default; configs
# whose estimated working set passes this budget leave the swept space,
# because the compiler refuses them ("Ran out of memory in memory space
# vmem").  The margin covers what the estimate leaves out.
VMEM_BUDGET_BYTES = 12 * 2**20


def _lane_pad(x: int) -> int:
    return -(-int(x) // 128) * 128


def _sublane_pad(x: int) -> int:
    return -(-int(x) // 8) * 8


def vmem_bytes(kernel: str, shape: Tuple[int, ...],
               config: Dict[str, Any]) -> int:
    """Estimated VMEM of one grid step: every block double-buffered by the
    grid pipeline (minor dim padded to 128 lanes, a 1-row block to 8
    sublanes), plus what the body keeps in VMEM — the MXU variants' int32
    scratch accumulator and dot result, the intersect popcount temporary.
    The packed variants walk their block 8 rows at a time in vregs."""
    if kernel == "intersect_count":
        tm, tw = config["bm"], config["bw"]
        block = tm * _lane_pad(tw) * 4
        return 2 * (2 * block + 8 * _lane_pad(tm) * 4) + block
    n, m, i = shape
    a, b = ("bn", "bm") if kernel == "support_count" else ("bb", "br")
    tn, tm = config[a], config[b]
    row = 8 * _lane_pad(tm) * 4                 # one [1, tm] i32/f32 block
    if kernel == "support_count":
        vecs, out = row, row                    # sizes; [1, bm] counts
    else:
        vecs, out = 2 * row, tn * _lane_pad(tm) * 4   # sizes, conf; scores
    if config["variant"] == "mxu":
        ti = config.get("bi", i)
        ins = tn * _lane_pad(ti) + tm * _lane_pad(ti)          # int8
        body = 2 * tn * _lane_pad(tm) * 4
    else:
        w = i // 32
        ins = (tn * _lane_pad(w) + _sublane_pad(w) * _lane_pad(tm)) * 4
        body = 0
    return 2 * (ins + vecs + out) + body


def _fit_tile(want: int, dim: int, floor: int = 1) -> int:
    """Largest power-of-two-shrunk tile <= want that divides dim."""
    t = max(floor, min(want, dim))
    while dim % t:
        t //= 2
    return max(t, 1)


# support_count's block rules on a TPU: T [bn, bi], C [bm, bi] (packed:
# [bn, W] and [W, bm]) and the [1, bm] sizes and counts.  A block's last
# dim is a multiple of 128 lanes and the one before it of 8 sublanes,
# unless the block spans the whole dim.  Key -> (shape axis, alignment).
_SUPPORT_COUNT_TILES = {"bn": (0, 8), "bm": (1, 128), "bi": (2, 128)}
# rule_match's: Q [bb, bi], A [br, bi] (packed: [bb, W] and [W, br]), the
# [1, br] sizes and confidences and the [bb, br] scores.
_RULE_MATCH_TILES = {"bb": (0, 8), "br": (1, 128), "bi": (2, 128)}
_TILES = {"support_count": _SUPPORT_COUNT_TILES,
          "rule_match": _RULE_MATCH_TILES}


def _fit_aligned(want: int, dim: int, align: int) -> int:
    """The whole dim when it is no larger than ``want``, else the largest
    multiple of ``align`` <= want that divides it (the ops wrappers pad
    every dim to its alignment, so ``align`` itself always does)."""
    if dim <= want:
        return dim
    t = max(align, want - want % align)
    while dim % t:
        t -= align
    return t


def fit_config(kernel: str, shape: Tuple[int, ...],
               config: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """A cached config fitted to the padded call ``shape`` it is used at:
    the cache hands a bucket's (or the nearest bucket's) winner to every
    shape near it, whose tiles need not divide this one's dims.  Each
    support_count and rule_match tile shrinks to the largest aligned
    divisor of its dim; None when the fitted tiles overrun
    ``VMEM_BUDGET_BYTES``, so the caller falls back to
    :func:`default_config`.  intersect_count configs pass as they are (its
    ops wrapper fits them)."""
    cfg = dict(config)
    tiles = _TILES.get(kernel)
    if tiles is None:
        return cfg
    for key, (axis, align) in tiles.items():
        if key in cfg:
            cfg[key] = _fit_aligned(int(cfg[key]), shape[axis], align)
    return cfg if vmem_bytes(kernel, shape, cfg) <= VMEM_BUDGET_BYTES else None


def kernel_candidates(kernel: str, shape: Tuple[int, ...]
                      ) -> List[Dict[str, Any]]:
    """The swept config space for one kernel at one (padded) shape.

    support_count:   shape = (N, M, I) — transactions, candidates, items.
    intersect_count: shape = (M, W)    — candidate rows, packed tid words.
    rule_match:      shape = (B, R, I) — queries, rule rows, items.
    Every candidate is a dict with a ``variant`` plus that variant's tile
    shape; all candidates compute bit-identical results (the fuzz harness
    holds the tuner to that), so picking any of them is safe.  Candidates
    over ``VMEM_BUDGET_BYTES`` (see :func:`vmem_bytes`) are left out; the
    smallest tiles (8 rows, 128 lanes) always fit.
    """
    if kernel not in TUNABLE_KERNELS:
        raise ValueError(f"unknown tunable kernel {kernel!r} "
                         f"(known: {', '.join(TUNABLE_KERNELS)})")
    cands: List[Dict[str, Any]] = []
    seen = set()

    def add(cfg: Dict[str, Any]) -> None:
        key = tuple(sorted(cfg.items()))
        if key not in seen:
            seen.add(key)
            cands.append(cfg)

    if kernel == "intersect_count":
        # row-aligned AND-popcount: one variant (there is no matmul
        # formulation of a per-row intersection), tiles over (M, W) only
        m, w = shape
        for wm in (512, 256, 128, m):
            for ww in (512, 128, w):
                add({"variant": "packed", "bm": _fit_tile(wm, m),
                     "bw": _fit_tile(ww, w)})
    elif kernel == "support_count":
        # the item tile also spans the whole item axis (bi = I: one item
        # step, and with bn = N the row tile is fetched once per launch)
        n, m, i = shape
        for wn in (512, 256, n):
            for wm in (512, 256, 128, m):
                tn, tm = _fit_tile(wn, n), _fit_tile(wm, m)
                for ti in (_fit_tile(512, i), i):
                    add({"variant": "mxu", "bn": tn, "bm": tm, "bi": ti})
                add({"variant": "packed", "bn": tn, "bm": tm})
    else:
        b, r, i = shape
        for wb in (512, 256, b):
            for wr in (256, 128, r):
                add({"variant": "mxu", "bb": _fit_tile(wb, b),
                     "br": _fit_tile(wr, r), "bi": _fit_tile(512, i)})
                add({"variant": "packed", "bb": _fit_tile(wb, b),
                     "br": _fit_tile(wr, r)})
    return [c for c in cands
            if vmem_bytes(kernel, shape, c) <= VMEM_BUDGET_BYTES]


def estimate_cost_us(kernel: str, shape: Tuple[int, ...],
                     config: Dict[str, Any]) -> float:
    """Roofline-seeded cost estimate (µs) for one candidate config.

    max(compute, HBM traffic) over the v5e constants plus a per-grid-step
    launch overhead; traffic counts the block re-reads tiling implies
    (T/Q re-read once per candidate tile, C/A once per row tile).
    """
    if kernel == "intersect_count":
        # both slabs read exactly once (row-aligned, no re-reads); the
        # [1, bm] out block is revisited once per word tile
        m, w = shape
        tm, tw = config["bm"], config["bw"]
        steps = (m // tm) * (w // tw)
        compute_s = _PACKED_OPS_PER_WORD * m * w / VPU_OPS
        traffic = 4.0 * (2.0 * m * w + m * (w // tw))
        return (max(compute_s, traffic / HBM_BW) * 1e6
                + steps * KERNEL_STEP_OVERHEAD_US)
    n, m, i = shape
    a, b = ("bn", "bm") if kernel == "support_count" else ("bb", "br")
    tn, tm = config[a], config[b]
    steps_n, steps_m = n // tn, m // tm
    if config["variant"] == "mxu":
        ti = config.get("bi", i)
        steps = steps_n * steps_m * (i // ti)
        compute_s = 2.0 * n * m * i / PEAK_FLOPS
        traffic = n * i * steps_m + m * i * steps_n + 4.0 * m * steps_n
    else:
        w = i / 32.0
        steps = steps_n * steps_m
        compute_s = _PACKED_OPS_PER_WORD * n * m * w / VPU_OPS
        traffic = 4.0 * (n * w * steps_m + m * w * steps_n + m * steps_n)
    return (max(compute_s, traffic / HBM_BW) * 1e6
            + steps * KERNEL_STEP_OVERHEAD_US)


def default_config(kernel: str, shape: Tuple[int, ...]) -> Dict[str, Any]:
    """Cold-cache fallback: argmin of the roofline estimates (no
    measurement, deterministic — ties broken by the candidate order)."""
    cands = kernel_candidates(kernel, shape)
    return min(cands, key=lambda c: (estimate_cost_us(kernel, shape, c),
                                     sorted(c.items()).__repr__()))


def seed_order(kernel: str, shape: Tuple[int, ...],
               cands: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Sweep order: cheapest estimate first, so a truncated (smoke) sweep
    still measures the configs the roofline model believes in."""
    return sorted(cands, key=lambda c: estimate_cost_us(kernel, shape, c))


def shape_flops_bytes(kernel: str, shape: Tuple[int, ...]
                      ) -> Tuple[float, float]:
    """Task-intrinsic (flops, bytes) for one kernel shape — the variant-
    independent work the containment test costs, used to turn a measured
    wall into effective peak/bandwidth for CostModelPolicy seeding."""
    if kernel == "intersect_count":
        # one AND+popcount+add per word-pair ≙ the 2·32 bit-ops the dense
        # formulation would spend on those 32 items (64 flops per word)
        m, w = shape
        return 64.0 * m * w, float(8 * m * w + 4 * m)
    n, m, i = shape
    flops = 2.0 * n * m * i
    bytes_ = float(n * i + m * i + 4 * m + (4 * n * m
                                            if kernel == "rule_match" else 0))
    return flops, bytes_
