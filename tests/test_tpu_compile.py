"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
Mosaic lowering errors (e.g. float accumulation of int8 operands, unsigned
reductions) and kernels that overrun scoped VMEM.  Shapes are those of
``chip_smoke.py`` and the benchmark's corpus at 100,000 transactions x
1,000 items, three support_count shapes beside them, and the rule_match
launches of the benchmark's rule-rich serving cell; configs are the ones
the ops wrappers resolve on a TPU (the checked-in cache's measured v5e
entries for support_count and rule_match, fitted to the shape, a cache
miss and so the roofline default for intersect_count) or the ones the
smoke pins.
Nothing runs and nothing is timed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.autotune.cache import default_cache, resolve_config
from repro.kernels.rule_match.ops import rule_topk
from repro.kernels.support_count.ops import (intersect_count, support_count,
                                            support_count_tiles)
from repro.launch.tuning import VMEM_BUDGET_BYTES, vmem_bytes

V5E_KIND = "TPU_v5_lite"        # jax device_kind "TPU v5 lite", cache-keyed

# (kernel, padded shape the ops wrapper resolves at, pinned config or None
# for the TPU cache-miss default)
CASES = [
    # Apriori k=2 round: one of 32 row tiles (3,125 rows -> 3,128) x the
    # 2,145 candidates bucketed to 2,176
    ("support_count", (3128, 2176, 1024), None),
    # the benchmark's k=2 round at 1% support: 113,050 candidates -> 113,152
    ("support_count", (3128, 113152, 1024), None),
    # shapes no sweep measured, resolved through a nearby bucket's entry
    # fitted to them: a 120,000-row corpus's 32-tile k=2 round, a four-chip
    # shard's 25,000 rows, and the smoke's rows against 512 candidates
    ("support_count", (3752, 113152, 1024), None),
    ("support_count", (25000, 113152, 1024), None),
    ("support_count", (99840, 512, 1024), None),
    # the smoke's kernels phase: 99,840 rows, 195 row blocks of 512
    ("support_count", (99840, 384, 1024),
     {"variant": "mxu", "bn": 512, "bm": 128, "bi": 512}),
    ("support_count", (99840, 384, 1024),
     {"variant": "packed", "bn": 512, "bm": 128}),
    # Eclat round tile: 128 candidate tid-lists x 100,000 tx in 3,200 words
    ("intersect_count", (128, 3200), None),
    # serving: the 64-basket bucket against the mined index (816 rows)
    ("rule_match", (64, 896, 1024), None),
    ("rule_match", (512, 896, 1024),
     {"variant": "mxu", "bb": 64, "br": 128, "bi": 512}),
    ("rule_match", (512, 896, 1024),
     {"variant": "packed", "bb": 64, "br": 128}),
    # the rule-rich serving cell: the 64- and 8-basket buckets against the
    # 0.5%-support index (6,186 rows -> 6,272)
    ("rule_match", (64, 6272, 1024), None),
    ("rule_match", (8, 6272, 1024), None),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _config(kernel, shape, pinned):
    if pinned is not None:
        return pinned
    # what resolve_config does on the chip: support_count and rule_match
    # resolve through the checked-in cache's measured v5e entries (nearest
    # bucket, fitted to the shape); intersect_count has none, so the
    # roofline default applies
    entry = default_cache().lookup(kernel, shape, device=V5E_KIND)
    if kernel == "intersect_count":
        assert entry is None
    else:
        assert entry is not None and entry["source"] == "measured"
    return resolve_config(kernel, shape, device=V5E_KIND)


def _lowered(kernel, shape, cfg, one_chip):
    S = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    if kernel == "support_count":
        n, m, i = shape
        fn = lambda T, C: support_count(T, C, interpret=False, tuning=cfg)
        return jax.jit(fn).lower(S((n, i), jnp.int8), S((m, i), jnp.int8))
    if kernel == "support_count_tiles":
        # a whole counting round: the resident int8 tiles and the uint8
        # candidate bitmap the device join builds
        t, n, m, i = shape
        fn = lambda tiles, C: support_count_tiles(tiles, C, interpret=False,
                                                  tuning=cfg)
        return jax.jit(fn).lower(S((t, n, i), jnp.int8),
                                 S((m, i), jnp.uint8))
    if kernel == "intersect_count":
        fn = lambda A, B: intersect_count(A, B, interpret=False, tuning=cfg)
        return jax.jit(fn).lower(S(shape, jnp.uint32), S(shape, jnp.uint32))
    b, r, i = shape
    fn = lambda Q, A, sizes, conf, cons: rule_topk(
        Q, A, sizes, conf, cons, k=5, n_items=1000, backend="pallas",
        interpret=False, tuning=cfg)
    return jax.jit(fn).lower(S((b, i), jnp.int8), S((r, i), jnp.int8),
                             S((r,), jnp.float32), S((r,), jnp.float32),
                             S((r,), jnp.int32))


@pytest.mark.parametrize("kernel,shape,pinned", CASES,
                         ids=[f"{k}-{'x'.join(map(str, s))}-"
                              f"{(p or {}).get('variant', 'default')}"
                              for k, s, p in CASES])
def test_kernel_compiles_for_v5e(kernel, shape, pinned, one_chip):
    cfg = _config(kernel, shape, pinned)
    assert vmem_bytes(kernel, shape, cfg) <= VMEM_BUDGET_BYTES
    hlo = _lowered(kernel, shape, cfg, one_chip).compile().as_text()
    assert "tpu_custom_call" in hlo        # the Pallas kernel, not a fallback


# the benchmark's readers and its breakdown key on these device-op names
# (`support_count_roofline` sums the ops named `support_count*`,
# `rule_match_roofline` those named `rule_scores*`)
NAMED = [
    ("support_count", (99840, 384, 1024),
     {"variant": "packed", "bn": 512, "bm": 128}, "support_count_fused_pallas"),
    ("support_count", (3128, 128, 1024),
     {"variant": "mxu", "bn": 3128, "bm": 128, "bi": 1024},
     "support_count_pallas"),
    ("rule_match", (512, 896, 1024),
     {"variant": "packed", "bb": 64, "br": 128}, "rule_scores_fused_pallas"),
    ("rule_match", (512, 896, 1024),
     {"variant": "mxu", "bb": 64, "br": 128, "bi": 512}, "rule_scores_pallas"),
    # the benchmark's k=2 round as one program: 32 resident tiles of
    # 3,128 x 1,024 against 113,152 candidates, at the config the chip
    # resolves for one tile
    ("support_count_tiles", (32, 3128, 113152, 1024), None,
     "support_count_pallas"),
]


@pytest.mark.parametrize("kernel,shape,pinned,name", NAMED,
                         ids=[n if k != "support_count_tiles" else f"{k}-{n}"
                              for k, _, _, n in NAMED])
def test_packed_kernel_keeps_its_name_in_v5e_hlo(kernel, shape, pinned, name,
                                                 one_chip):
    if pinned is None:               # a round resolves at its tile's shape
        pinned = _config("support_count", shape[1:], None)
    hlo = _lowered(kernel, shape, pinned, one_chip).compile().as_text()
    assert name in hlo
