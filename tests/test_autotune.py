"""The autotune subsystem's contracts:

* cache round-trip is deterministic (same entries, byte-identical re-save);
* the sweep verifies every candidate bit-identical to the oracle and picks
  the argmin of the *measured* costs;
* a cold/corrupt cache degrades to the roofline-seeded defaults without
  ever raising — autotuning may only make things faster, never break them;
* ``CostModelPolicy.from_autotune`` turns measured walls into effective
  peak/bandwidth, the planes' plans actually change versus the datasheet
  constants on a heterogeneous profile, and every ``PhaseRecord`` says
  where its planning costs came from (``cost_source``).
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.hetero import HeterogeneityProfile
from repro.core.scheduler import TaskSpec
from repro.kernels.autotune.cache import (LAST_DISPATCH, AutotuneCache,
                                          default_cache, resolve_config,
                                          shape_bucket)
from repro.kernels.autotune.tuner import standard_shapes, tune, tune_into
from repro.kernels.support_count.ops import support_count
from repro.kernels.support_count.ref import support_count_ref
from repro.launch.tuning import (TUNABLE_KERNELS, VMEM_BUDGET_BYTES,
                                 default_config, fit_config,
                                 kernel_candidates, shape_flops_bytes,
                                 vmem_bytes)
from repro.pipeline import MarketBasketPipeline, PipelineConfig
from repro.runtime import (CostModelPolicy, MeasuredPhase, Runtime,
                           autotuned_costmodel)

SC_SMOKE = (64, 128, 128)       # 2 candidates at this shape: one per variant
V5E = "TPU_v5_lite"             # the chip the benchmark runs on, cache-keyed
SC_K2 = (3128, 113152, 1024)    # its k=2 round's row tile (1% of T10I4D100K)


# ---------------------------------------------------------------------------
# cache round-trip + lookup
# ---------------------------------------------------------------------------

def test_cache_roundtrip_deterministic(tmp_path):
    cache = AutotuneCache()
    cfg = {"variant": "packed", "bn": 64, "bm": 128}
    cache.put("support_count", SC_SMOKE, cfg, 123.456,
              swept=[{"config": cfg, "cost_us": 123.456, "matched": True}],
              device="cpu")
    cache.put("rule_match", (8, 128, 128),
              {"variant": "mxu", "bb": 8, "br": 128, "bi": 128}, 55.5,
              device="cpu")
    path = str(tmp_path / "cache.json")
    cache.save(path)
    loaded = AutotuneCache.load(path)
    assert loaded.load_error is None
    assert loaded.entries == cache.entries
    loaded.save(str(tmp_path / "resave.json"))
    with open(path) as a, open(tmp_path / "resave.json") as b:
        assert a.read() == b.read()         # byte-identical re-save


def test_lookup_exact_then_nearest_bucket():
    cache = AutotuneCache()
    cfg = {"variant": "packed", "bn": 64, "bm": 128}
    cache.put("support_count", SC_SMOKE, cfg, 10.0, device="cpu")
    # exact bucket, and a different shape rounding into the same bucket
    assert cache.lookup("support_count", SC_SMOKE, "cpu")["config"] == cfg
    assert shape_bucket("support_count", (50, 100, 100)) \
        == shape_bucket("support_count", SC_SMOKE)
    assert cache.lookup("support_count", (50, 100, 100), "cpu")["config"] \
        == cfg
    # far-away shape: nearest-bucket fallback still serves the one entry
    assert cache.lookup("support_count", (4096, 8192, 256), "cpu")["config"] \
        == cfg
    # but never across device kinds or kernels
    assert cache.lookup("support_count", SC_SMOKE, "tpu_v99") is None
    assert cache.lookup("rule_match", (8, 128, 128), "cpu") is None


def test_checked_in_cache_covers_both_kernels():
    cache = default_cache(reload=True)
    assert cache.load_error is None
    for kernel in TUNABLE_KERNELS:
        entries = cache.entries_for(kernel, "cpu")
        assert entries, f"checked-in cache has no cpu entries for {kernel}"
        for ent in entries:
            assert ent["cost_us"] > 0 and ent["source"] == "measured"
            assert "variant" in ent["config"]


def test_checked_in_v5e_entries_are_measured_support_count_winners():
    cache = default_cache(reload=True)
    entries = {k: e for k, e in cache.entries.items()
               if k.endswith(f"|{V5E}")}
    assert entries
    for key, ent in entries.items():
        # Eclat's intersect_count keeps its roofline dispatch on the chip
        assert key.startswith(("support_count|", "rule_match|")), key
        assert ent["source"] == "measured" and ent["cost_us"] > 0
        winner = [s for s in ent["swept"] if s["config"] == ent["config"]]
        assert winner and winner[0]["matched"], key
        assert winner[0]["cost_us"] == min(
            s["cost_us"] for s in ent["swept"] if s["matched"])
    assert not cache.entries_for("intersect_count", V5E)
    # the tuner's own lattice plus the benchmark's shapes, one entry each
    for kernel in ("support_count", "rule_match"):
        assert {tuple(e["shape"]) for e in cache.entries_for(kernel, V5E)} \
            == set(standard_shapes(kernel))


def test_k2_bucket_resolves_to_its_measured_winner():
    cache = default_cache(reload=True)
    key = AutotuneCache.key("support_count", SC_K2, V5E)
    assert cache.lookup("support_count", SC_K2, V5E) is cache.entries[key]
    # a nearby row tile shares the bucket; a cpu lookup never crosses over
    assert cache.lookup("support_count", (3125, 113050, 1000), V5E) \
        is cache.entries[key]
    cpu = cache.lookup("support_count", SC_K2, "cpu")
    assert cpu is not None and cpu in cache.entries_for("support_count", "cpu")
    for ent in cache.entries_for("support_count", "cpu"):
        assert cache.lookup("support_count", tuple(ent["shape"]), "cpu") \
            is ent


# ---------------------------------------------------------------------------
# degradation: cold / corrupt caches fall back to roofline defaults
# ---------------------------------------------------------------------------

def test_cold_and_corrupt_cache_degrade(tmp_path):
    missing = AutotuneCache.load(str(tmp_path / "absent.json"))
    assert missing.load_error is not None and len(missing) == 0

    bad = tmp_path / "bad.json"
    bad.write_text("{this is not json")
    corrupt = AutotuneCache.load(str(bad))
    assert corrupt.load_error is not None and "corrupt" in corrupt.load_error
    assert len(corrupt) == 0

    schema = tmp_path / "schema.json"
    schema.write_text(json.dumps({"entries": {"k": {"shape": [1, 2, 3]}}}))
    assert AutotuneCache.load(str(schema)).load_error is not None

    # the resolver degrades to the roofline-seeded default, never raises
    want = default_config("support_count", SC_SMOKE)
    assert resolve_config("support_count", SC_SMOKE, corrupt) == want
    assert resolve_config("support_count", SC_SMOKE, False) == want
    pin = {"variant": "mxu", "bn": 8, "bm": 128, "bi": 128}
    got = resolve_config("support_count", SC_SMOKE, pin)
    assert got == pin and got is not pin     # pinned dicts pass through, copied

    # and the kernel itself still runs (correctly) off a cold cache
    rng = np.random.default_rng(3)
    T = (rng.random((32, 64)) < 0.3).astype(np.uint8)
    C = (rng.random((8, 64)) < 0.1).astype(np.uint8)
    np.testing.assert_array_equal(
        np.asarray(support_count(jnp.asarray(T), jnp.asarray(C),
                                 tuning=corrupt)),
        np.asarray(support_count_ref(jnp.asarray(T), jnp.asarray(C))))


def test_dispatch_records_config_source_and_interpret():
    rng = np.random.default_rng(4)
    T = jnp.asarray((rng.random((32, 64)) < 0.3).astype(np.uint8))
    C = jnp.asarray((rng.random((8, 64)) < 0.1).astype(np.uint8))
    pin = {"variant": "mxu", "bn": 8, "bm": 128, "bi": 128}
    for tuning, source in ((pin, "pinned"), (False, "roofline"),
                           (AutotuneCache(), "roofline"), (None, "cache")):
        support_count(T, C, tuning=tuning)
        rec = LAST_DISPATCH["support_count"]
        assert rec["source"] == source and rec["shape"] == (32, 128, 128)
        assert rec["interpret"] is True          # off-TPU default
    assert LAST_DISPATCH["support_count"]["config"] == resolve_config(
        "support_count", (32, 128, 128))


# padded support_count shapes on the chip that no sweep measured, each
# resolved through a nearby bucket's v5e winner: a 120,000-row corpus's
# 32-tile k=2 round, a four-chip shard's 25,000 rows, and a smoke-sized
# corpus against 512 candidates
OFF_LATTICE = [(3752, 113152, 1024), (25000, 113152, 1024),
               (99840, 512, 1024)]


def _valid_tiles(shape, cfg):
    """Every tile divides its dim and keeps the TPU block rules."""
    n, m, i = shape
    tiles = [(cfg["bn"], n, 8), (cfg["bm"], m, 128)]
    if cfg["variant"] == "mxu":
        tiles.append((cfg["bi"], i, 128))
    return all(dim % t == 0 and (t == dim or t % align == 0)
               for t, dim, align in tiles)


@pytest.mark.parametrize("shape", OFF_LATTICE)
def test_v5e_entries_fit_shapes_off_the_lattice(shape):
    cfg = resolve_config("support_count", shape, device=V5E)
    assert _valid_tiles(shape, cfg), cfg
    assert vmem_bytes("support_count", shape, cfg) <= VMEM_BUDGET_BYTES


@pytest.mark.parametrize("want, shape, got", [
    # a row tile that does not divide N shrinks to an aligned divisor
    ({"variant": "mxu", "bn": 3128, "bm": 512, "bi": 1024},
     (3752, 113152, 1024), {"bn": 536, "bm": 512, "bi": 1024}),
    ({"variant": "mxu", "bn": 3128, "bm": 256, "bi": 512},
     (25000, 113152, 1024), {"bn": 1000, "bm": 256, "bi": 512}),
    # a candidate tile of 384 lanes against 512 candidates
    ({"variant": "mxu", "bn": 512, "bm": 384, "bi": 1024},
     (99840, 512, 1024), {"bn": 512, "bm": 256, "bi": 1024}),
    # tiles wider than a small shape span the whole dim
    ({"variant": "packed", "bn": 3128, "bm": 512}, (40, 128, 256),
     {"bn": 40, "bm": 128}),
])
def test_fit_config_shrinks_tiles_to_aligned_divisors(want, shape, got):
    assert fit_config("support_count", shape, want) \
        == {"variant": want["variant"], **got}
    # a config that already fits its own shape is left as it is
    assert fit_config("support_count", shape, {**want, **got}) \
        == {**want, **got}


def test_cached_config_over_the_vmem_budget_falls_back_to_default():
    # whole-axis MXU tiles at 2,048 candidates x 2,048 items: ~21 MiB of
    # double-buffered blocks alone
    shape = (3128, 2048, 2048)
    big = {"variant": "mxu", "bn": 3128, "bm": 2048, "bi": 2048}
    assert fit_config("support_count", shape, big) is None
    cache = AutotuneCache()
    cache.put("support_count", shape, big, 1.0, device=V5E)
    assert resolve_config("support_count", shape, cache, device=V5E) \
        == default_config("support_count", shape)
    # intersect_count's configs pass through (its wrapper fits them)
    cfg = {"variant": "packed", "bm": 512, "bw": 384}
    assert fit_config("intersect_count", (128, 3200), cfg) == cfg


# rule_match at the serving buckets (8 and 64 baskets, 1,024 lanes) over
# the 0.5%-support index (6,272 padded rows), the same index after a rule
# refresh (6,400) and the 1% index (128)
RULE_SHAPES = [(b, r, 1024) for b in (8, 64) for r in (6272, 6400, 128)]


def _valid_rule_tiles(shape, cfg):
    """Every rule_match tile divides its dim and keeps the block rules:
    the basket tile spans B or is a multiple of 8 sublanes, the row and
    item tiles span their dim or are multiples of 128 lanes."""
    b, r, i = shape
    tiles = [(cfg["bb"], b, 8), (cfg["br"], r, 128)]
    if cfg["variant"] == "mxu":
        tiles.append((cfg["bi"], i, 128))
    return all(dim % t == 0 and (t == dim or t % align == 0)
               for t, dim, align in tiles)


@pytest.mark.parametrize("shape", RULE_SHAPES)
@pytest.mark.parametrize("want", [
    {"variant": "mxu", "bb": 8, "br": 6272, "bi": 512},
    {"variant": "mxu", "bb": 64, "br": 640, "bi": 1024},
    {"variant": "packed", "bb": 64, "br": 6272},
    {"variant": "packed", "bb": 24, "br": 384},
])
def test_fit_config_fits_rule_match_tiles_to_aligned_divisors(shape, want):
    got = fit_config("rule_match", shape, want)
    assert got is not None and _valid_rule_tiles(shape, got), got
    assert got["variant"] == want["variant"] and got.keys() == want.keys()
    for key, axis in (("bb", 0), ("br", 1), ("bi", 2)):
        if key in want:        # a tile never grows, and only to fit
            assert got[key] <= max(want[key], 1)
            assert got[key] == want[key] or shape[axis] % want[key] \
                or want[key] > shape[axis]
    assert vmem_bytes("rule_match", shape, got) <= VMEM_BUDGET_BYTES


@pytest.mark.parametrize("want, shape, got", [
    # the whole 6,272-row index tile after a refresh to 6,400 rows
    ({"variant": "mxu", "bb": 64, "br": 6272, "bi": 1024},
     (64, 6400, 1024), {"bb": 64, "br": 3200, "bi": 1024}),
    ({"variant": "packed", "bb": 8, "br": 6272}, (8, 6400, 1024),
     {"bb": 8, "br": 3200}),
    # a 640-row tile against 6,272 rows (49 x 128): the largest divisor
    ({"variant": "mxu", "bb": 64, "br": 640, "bi": 512},
     (64, 6272, 1024), {"bb": 64, "br": 128, "bi": 512}),
    # tiles wider than the 1% index and the 8-basket bucket span them
    ({"variant": "mxu", "bb": 64, "br": 6272, "bi": 1024},
     (8, 128, 1024), {"bb": 8, "br": 128, "bi": 1024}),
    # a basket tile of 24 against the 64-basket bucket
    ({"variant": "packed", "bb": 24, "br": 128}, (64, 128, 1024),
     {"bb": 16, "br": 128}),
])
def test_fit_config_rule_match_cases(want, shape, got):
    assert fit_config("rule_match", shape, want) \
        == {"variant": want["variant"], **got}
    assert fit_config("rule_match", shape, {**want, **got}) \
        == {**want, **got}


def test_cached_rule_tile_refits_after_a_rule_refresh():
    # a v5e entry measured at the 0.5% index's 6,272 rows, looked up at
    # 6,400 rows: the row tile is fitted to a multiple of 128, never 1
    cache = AutotuneCache()
    cache.put("rule_match", (64, 6272, 1024),
              {"variant": "packed", "bb": 64, "br": 6272}, 1.0, device=V5E)
    for rows in (6400, 6528, 6272 + 128 * 3):
        shape = (64, rows, 1024)
        cfg = resolve_config("rule_match", shape, cache, device=V5E)
        assert cfg["br"] % 128 == 0 and rows % cfg["br"] == 0, cfg
        assert _valid_rule_tiles(shape, cfg)


def test_rule_match_fit_over_the_vmem_budget_falls_back_to_default():
    # a whole 2,048-basket x 8,192-row score tile alone is 64 MiB
    shape = (2048, 8192, 1024)
    big = {"variant": "mxu", "bb": 2048, "br": 8192, "bi": 1024}
    assert fit_config("rule_match", shape, big) is None
    cache = AutotuneCache()
    cache.put("rule_match", shape, big, 1.0, device=V5E)
    assert resolve_config("rule_match", shape, cache, device=V5E) \
        == default_config("rule_match", shape)


def test_rule_match_ops_have_one_fitting_path():
    # the ops wrapper runs the dispatched config as it is: the one fit is
    # launch/tuning.fit_config
    import repro.kernels.rule_match.ops as rule_ops
    assert not hasattr(rule_ops, "_fit")


@pytest.mark.parametrize("kernel,shape", [
    ("support_count", (3128, 2176, 1024)),   # one 100k-tx row tile, k=2
    ("rule_match", (512, 1920, 1024)),
    ("intersect_count", (128, 3200)),
])
def test_candidates_fit_the_vmem_budget(kernel, shape):
    cands = kernel_candidates(kernel, shape)
    assert cands
    assert all(vmem_bytes(kernel, shape, c) <= VMEM_BUDGET_BYTES
               for c in cands)
    # whole-array MXU tiles need ~26 MiB of scratch alone: never proposed
    if kernel == "support_count":
        whole = {"variant": "mxu", "bn": 3128, "bm": 2176, "bi": 512}
        assert vmem_bytes(kernel, shape, whole) > VMEM_BUDGET_BYTES
        assert whole not in cands
        # a row tile with the whole item axis (bi = I) is swept, but not
        # with every candidate at once
        assert {"variant": "mxu", "bn": 3128, "bm": 128, "bi": 1024} in cands
        assert {**whole, "bi": 1024} not in cands


def test_autotuned_costmodel_degrades_to_roofline():
    pol = autotuned_costmodel("support_count", cache=AutotuneCache())
    assert isinstance(pol, CostModelPolicy)
    assert pol.cost_source == "roofline"     # constants, not measurements
    with pytest.raises(ValueError):
        CostModelPolicy.from_autotune(AutotuneCache(), "support_count",
                                      device="cpu")


# ---------------------------------------------------------------------------
# the sweep: bit-identical configs only, argmin of measured cost
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel,shape", [("support_count", SC_SMOKE),
                                          ("rule_match", (8, 128, 128))])
def test_sweep_configs_all_match_oracle(kernel, shape):
    res = tune(kernel, shape, reps=3)
    assert res.swept
    assert all(s.matched for s in res.swept), \
        [s.config for s in res.swept if not s.matched]
    best = min((s for s in res.swept if s.matched), key=lambda s: s.cost_us)
    assert res.best == best.config and res.cost_us == best.cost_us
    variants = {s.config["variant"] for s in res.swept}
    assert variants == {"mxu", "packed"}     # both implementations swept


def test_tune_picks_argmin_of_measured_cost():
    """Scripted timer: the sweep must pick whichever config *measures*
    cheapest, not the roofline favourite (candidate order)."""
    cands = kernel_candidates("support_count", SC_SMOKE)
    assert len(cands) == 2
    walls = [10.0, 1.0]                      # seconds per rep, per config
    ticks = []
    for ci, wall in enumerate(walls):        # 3 reps x 2 timer calls each
        t = 1e6 * ci
        for _ in range(3):
            ticks.extend([t, t + wall])
            t += wall
    it = iter(ticks)
    res = tune("support_count", SC_SMOKE, configs=cands, reps=3,
               timer=lambda: next(it))
    assert res.best == cands[1]
    assert res.cost_us == pytest.approx(1.0e6)       # 1 s in us
    assert [s.cost_us for s in res.swept] \
        == [pytest.approx(10.0e6), pytest.approx(1.0e6)]


def test_tune_into_writes_audited_entries():
    cache = AutotuneCache()
    results = tune_into(cache, "support_count", shapes=[SC_SMOKE], reps=3)
    assert len(results) == 1 and len(cache) == 1
    ent = cache.lookup("support_count", SC_SMOKE)
    assert ent["config"] == results[0].best
    assert ent["source"] == "measured" and ent["shape"] == list(SC_SMOKE)
    assert all(s["matched"] for s in ent["swept"])   # full sweep audited
    # the ops resolver serves this cache's winner when handed the cache
    assert resolve_config("support_count", SC_SMOKE, cache) == ent["config"]


def test_standard_shapes_smoke_is_tiny():
    for kernel in TUNABLE_KERNELS:
        full = standard_shapes(kernel)
        assert len(standard_shapes(kernel, smoke=True)) == 1
        assert len(full) > 1
        assert len({shape_bucket(kernel, s) for s in full}) == len(full)


# ---------------------------------------------------------------------------
# the feedback loop: measured costs reach the scheduler + the ledger
# ---------------------------------------------------------------------------

def _measured_cache(wall_us=1e6):
    cache = AutotuneCache()
    cache.put("support_count", (1024, 2048, 128),
              {"variant": "packed", "bn": 512, "bm": 256}, wall_us,
              device="cpu")
    return cache


def test_from_autotune_seeds_effective_rates():
    wall_us = 4000.0
    pol = CostModelPolicy.from_autotune(_measured_cache(wall_us),
                                        "support_count", device="cpu")
    flops, bytes_ = shape_flops_bytes("support_count", (1024, 2048, 128))
    assert pol.cost_source == "autotune"
    assert pol.peak_flops == pytest.approx(flops / (wall_us * 1e-6))
    assert pol.hbm_bw == pytest.approx(bytes_ / (wall_us * 1e-6))
    assert pol.flops_per_byte == pytest.approx(flops / bytes_)


def test_autotune_fed_costs_change_the_plan():
    """Same tiles, same byte estimates: the autotune-seeded policy must
    produce a different cost distribution — and a different LPT plan on
    the paper's heterogeneous profile — than the datasheet constants."""
    profile = HeterogeneityProfile.paper()
    const = CostModelPolicy()
    tuned = CostModelPolicy.from_autotune(_measured_cache(), "support_count",
                                          device="cpu")
    # effective (measured) ridge point differs from the datasheet's, so an
    # intensity between the two is flop-bound under exactly one model
    ridge_c = const.peak_flops / const.hbm_bw
    ridge_t = tuned.peak_flops / tuned.hbm_bw
    assert ridge_c != pytest.approx(ridge_t)
    mid = float(np.sqrt(ridge_c * ridge_t))
    tile_bytes = np.array([1e6, 0.9e6, 0.8e6, 0.7e6])
    tile_flops = np.array([mid * 1e6, 0.0, 0.0, 0.0])
    task = TaskSpec("count_tiles", cost=float(tile_bytes.sum()), n_tiles=4)

    plans = {}
    for name, pol in (("const", const), ("tuned", tuned)):
        rt = Runtime(profile, policy=pol)
        costs = pol.tile_costs(rt, task, tile_bytes, tile_flops)
        assert costs.sum() == pytest.approx(tile_bytes.sum())  # renormalized
        asg, _, _ = pol.plan(rt, task, costs)
        plans[name] = (costs, asg.tiles_of)
    rel_c = plans["const"][0] / plans["const"][0].sum()
    rel_t = plans["tuned"][0] / plans["tuned"][0].sum()
    assert not np.allclose(rel_c, rel_t)
    assert plans["const"][1] != plans["tuned"][1]


def test_phase_records_note_cost_source():
    profile = HeterogeneityProfile.paper()
    task = TaskSpec("count_tiles", cost=4.0, n_tiles=4)
    execute = lambda asg, costs: MeasuredPhase(result="ok")  # noqa: E731
    for policy, want in (("static", "bytes"), ("dynamic", "bytes"),
                         ("costmodel", "roofline")):
        rt = Runtime(profile, policy=policy)
        _, rec = rt.run_phase(task, execute)
        assert rec.cost_source == want, policy
    rt = Runtime(profile, policy=CostModelPolicy.from_autotune(
        _measured_cache(), "support_count", device="cpu"))
    _, rec = rt.run_phase(task, execute)
    assert rec.cost_source == "autotune"
    _, ser = rt.run_serial("load", 1.0)      # serial phases stamped too
    assert ser.cost_source == "autotune"


def test_pipeline_costmodel_policy_is_autotune_fed():
    """policy="costmodel" + autotune on (the default) seeds planning from
    the checked-in cache; --no-autotune pins the datasheet constants."""
    profile = HeterogeneityProfile.paper()
    on = MarketBasketPipeline(profile, PipelineConfig(policy="costmodel"))
    assert on.runtime.policy.cost_source == "autotune"
    off = MarketBasketPipeline(
        profile, PipelineConfig(policy="costmodel", autotune=False))
    assert off.runtime.policy.cost_source == "roofline"
