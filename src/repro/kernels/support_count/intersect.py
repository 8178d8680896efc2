"""Fused AND-popcount tid-slab intersection kernel (the Eclat primitive).

The Apriori fused kernel (:mod:`.fused`) intersects *candidate rows
against transaction rows*; the vertical (Eclat) formulation instead
intersects *two candidate tid-slabs against each other*: row m of A
holds the packed uint32 tid-list of one (k-1)-subset, row m of B the
tid-list of the sibling subset from the F_{k-1} ⋈ F_{k-1} join, and

  support(candidate m) = Σ_w popcount(A[m, w] & B[m, w])

— a pure row-aligned VPU op with no cross-row contraction at all, which
is why Eclat wins on dense data: the transaction axis was paid for once
at columnization and every later round touches only |candidates| × W
words instead of n_tx × n_items lanes.

Tiling (HBM→VMEM):
  grid = (M/bm, W/bw) — candidate tiles outermost, word tiles innermost,
  so each [1, bm] output block is revisited only across the
  sequential-innermost word axis (the same revisit pattern the Apriori
  fused kernel uses over its transaction axis) and the A/B block DMAs
  double-buffer across steps.

Padding contract: padded candidate rows and padded word lanes are
all-zero, so they contribute popcount 0 — inert, the caller just slices
rows.  (No ``sizes`` input is needed: there is no containment filter,
the popcount IS the support.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, out_ref):
    """Grid: (j, i) over (M-tiles, W-tiles); W innermost (out revisits)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    # int32 bit patterns in (signed reductions only; popcount ignores sign)
    inter = jax.lax.population_count(a_ref[...] & b_ref[...])   # [bm, bw]
    out_ref[...] += jnp.sum(inter, axis=1)[None, :]              # [1, bm]


@functools.partial(jax.jit, static_argnames=("bm", "bw", "interpret"))
def intersect_count_pallas(A: jnp.ndarray, B: jnp.ndarray, *,
                           bm: int = 256, bw: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """A, B: [M, W] packed uint32 tid-slabs -> [1, M] int32 popcounts."""
    M, W = A.shape
    assert B.shape == (M, W), (A.shape, B.shape)
    bm, bw = min(bm, M), min(bw, W)
    assert M % bm == 0 and W % bw == 0, (A.shape, (bm, bw))
    grid = (M // bm, W // bw)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bw), lambda j, i: (j, i)),
            pl.BlockSpec((bm, bw), lambda j, i: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, bm), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(A, jnp.int32),
      jax.lax.bitcast_convert_type(B, jnp.int32))
