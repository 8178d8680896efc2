"""Pallas TPU kernel: candidate-itemset support counting on the MXU.

The paper's compute hot-spot (Apriori step 2) adapted to TPU: transactions
are a 0/1 bitmap ``T[N, I]`` and candidates a bitmask ``C[M, I]``; support
is ``Σ_t 1[dot(T_t, C_m) == |C_m|]``.  The containment test becomes one
int-matmul on the systolic array plus a VPU compare — arithmetic intensity
is that of a matmul, so the kernel is compute-roofline-bound instead of the
byte-bound scalar hash-tree walk the paper's CPU cores would run.

Tiling (HBM→VMEM):
  grid = (M/bm, N/bn, I/bi) — candidate tiles outermost ("parallel"), the
  two reduction axes innermost ("arbitrary"): the item (contraction) axis
  accumulates the [bn, bm] int32 dot in VMEM scratch, and on its last tile
  the per-row hits fold into the [1, bm] output block.  That block's index
  depends on the outer axis only, so it stays resident across every
  (N, I) step that revisits it — the one revisit pattern TPU Pallas
  supports (an output block is written back when its index changes and
  never read again).  With ``bi == I`` the item grid has one step, and
  with ``bn == N`` too (a round's row tile) the T block index never
  changes, so the tile is fetched once per launch.

Block defaults (bn=512, bm=256, bi=512, int8 inputs):
  VMEM ≈ 2·512·512 (T) + 2·256·512 (C) + 3·512·256·4 (acc + temps)
       ≈ 3.1 MiB ✓; MXU: 512×512×256 int8 dots, int32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(t_ref, c_ref, sizes_ref, out_ref, acc_ref):
    """Grid: (j, i, l) over (M-tiles, N-tiles, I-tiles)."""
    i, l = pl.program_id(1), pl.program_id(2)
    nl = pl.num_programs(2)

    @pl.when((i == 0) & (l == 0))
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(l == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 accumulate on the MXU
    acc_ref[...] += jax.lax.dot_general(
        t_ref[...], c_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(l == nl - 1)
    def _fold():
        hits = (acc_ref[...] == sizes_ref[...]).astype(jnp.int32)  # [bn, bm]
        out_ref[...] += jnp.sum(hits, axis=0, keepdims=True)       # [1, bm]


@functools.partial(jax.jit, static_argnames=("bn", "bm", "bi", "interpret"))
def support_count_pallas(T: jnp.ndarray, C: jnp.ndarray, sizes: jnp.ndarray,
                         *, bn: int = 512, bm: int = 256, bi: int = 512,
                         interpret: bool = False) -> jnp.ndarray:
    """T: [N, I] int8; C: [M, I] int8; sizes: [1, M] i32 (=|C_m|) -> [1, M] i32."""
    N, I = T.shape
    M = C.shape[0]
    bn, bm, bi = min(bn, N), min(bm, M), min(bi, I)
    assert N % bn == 0 and M % bm == 0 and I % bi == 0, (T.shape, C.shape, (bn, bm, bi))
    grid = (M // bm, N // bn, I // bi)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bi), lambda j, i, l: (i, l)),
            pl.BlockSpec((bm, bi), lambda j, i, l: (j, l)),
            pl.BlockSpec((1, bm), lambda j, i, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm), lambda j, i, l: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(T, C, sizes.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bn", "bm", "bi", "interpret"))
def support_count_mxu(T: jnp.ndarray, C: jnp.ndarray, *, bn: int = 512,
                      bm: int = 256, bi: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """0/1 int8 bitmaps in, counts out: derives |C_m| (fused into this jit,
    one read of C) and runs the kernel.  Returns [1, M] int32."""
    sizes = jnp.sum(C, axis=1, dtype=jnp.int32)[None, :]       # [1, M]
    return support_count_pallas(T, C, sizes, bn=bn, bm=bm, bi=bi,
                                interpret=interpret)
