"""Pallas TPU kernel: batched basket × rule-antecedent subset matching.

The serving hot-spot, shaped like :mod:`repro.kernels.support_count` but
with the opposite output: the mining kernel reduces over transactions to a
per-candidate count, while serving keeps the full score matrix — one
confidence-weighted row per query basket, later max-segmented into item
scores and top-k'd by the ops wrapper.

Queries are a 0/1 bitmap ``Q[B, I]`` and rule antecedents a bitmask
``A[R, I]``; row r matches basket q iff ``dot(Q_q, A_r) == |A_r|``.  The
containment test is one int-matmul on the systolic array plus a VPU
compare/select, so batched serving inherits matmul arithmetic intensity.

Tiling (HBM→VMEM):
  grid = (B/bb, R/br, I/bi) — item (contraction) axis innermost so the
  [bb, br] int32 accumulator lives in VMEM scratch across the k-loop; on the
  last item-tile we compare against |A_r| and write the confidence-weighted
  match block straight to the [bb, br] output tile (each output block is
  owned by exactly one (i, j), so no cross-grid revisits).

Block defaults (bb=256, br=256, bi=512, int8 inputs):
  VMEM ≈ 2·256·512 (Q) + 2·256·512 (A) + 256·256·4 (acc i32)
       + 2·256·256·4 (out) + small ≈ 1.3 MiB ✓; MXU 256×512×256 int8
       dots with int32 accumulation, lane-aligned.

Padding contract (enforced by ops.py / the rule index): padded rule rows
carry ``sizes = -1`` so they can never match (an all-zero antecedent would
otherwise match every basket with dot == |A| == 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, a_ref, sizes_ref, conf_ref, out_ref, acc_ref):
    """Grid: (i, j, l) over (B-tiles, R-tiles, I-tiles)."""
    l = pl.program_id(2)
    nl = pl.num_programs(2)

    @pl.when(l == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 accumulate on the MXU
    acc_ref[...] += jax.lax.dot_general(
        q_ref[...], a_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(l == nl - 1)
    def _finalize():
        match = (acc_ref[...] == sizes_ref[...]).astype(jnp.float32)  # [bb, br]
        out_ref[...] = match * conf_ref[...]


@functools.partial(jax.jit, static_argnames=("bb", "br", "bi", "interpret"))
def rule_scores_pallas(Q: jnp.ndarray, A: jnp.ndarray, sizes: jnp.ndarray,
                       conf: jnp.ndarray, *, bb: int = 256, br: int = 256,
                       bi: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Q: [B, I] int8; A: [R, I] int8; sizes: [1, R] (=|A_r|, -1 on padding;
    compared as int32); conf: [1, R] f32 -> [B, R] f32."""
    B, I = Q.shape
    R = A.shape[0]
    bb, br, bi = min(bb, B), min(br, R), min(bi, I)
    assert B % bb == 0 and R % br == 0 and I % bi == 0, (Q.shape, A.shape,
                                                        (bb, br, bi))
    grid = (B // bb, R // br, I // bi)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bi), lambda i, j, l: (i, l)),
            pl.BlockSpec((br, bi), lambda i, j, l: (j, l)),
            pl.BlockSpec((1, br), lambda i, j, l: (0, j)),
            pl.BlockSpec((1, br), lambda i, j, l: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, br), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, br), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(Q, A, sizes.astype(jnp.int32), conf)
