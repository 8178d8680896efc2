"""Fused packed-popcount rule-match kernel (the serving twin of
:mod:`repro.kernels.support_count.fused`).

Same trade as on the mining plane: the MXU variant prices the antecedent
containment test as one int8 matmul; this variant packs the item axis
into uint32 words and computes

  dot(Q_q, A_r) == Σ_w popcount(Qw[q, w] & Aw[r, w])

with the subset filter (``== |A_r|``) and the confidence weighting fused
into the same kernel body — one launch per batch, a 32× smaller item
contraction, no unweighted match matrix materialized.  The autotuner
(:mod:`repro.kernels.autotune`) decides per device which variant serves.

Tiling (HBM→VMEM):
  grid = (B/bb, R/br): each [bb, br] output block is owned by exactly one
  grid point (no revisits), so both axes are parallel and Pallas' grid
  pipeline double-buffers the block DMAs.  The word axis rides whole per
  block (W = I/32 is lanes-small); antecedent words arrive transposed,
  ``[W, br]``, and the body walks ROW_CHUNK query rows at a time, one
  word at a time (see :func:`repro.kernels.support_count.fused.
  popcount_dots`), so VMEM holds little beyond the blocks.

Padding contract (identical to the MXU variant): padded rule rows carry
``sizes = -1`` so they can never match — popcounts are >= 0 — and
``conf = 0``; padded query rows are all-zero words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.support_count.fused import (ROW_CHUNK, as_word_lanes,
                                               chunk_rows, pack_words,
                                               popcount_dots)

__all__ = ["pack_words", "rule_scores_fused_pallas", "rule_scores_fused"]


def _kernel(q_ref, at_ref, sizes_ref, conf_ref, out_ref):
    """Grid: (i, j) over (B-tiles, R-tiles); every block owned once."""
    sizes, conf = sizes_ref[...], conf_ref[...]             # [1, br]

    def chunk(r, carry):
        rows = chunk_rows(r)
        dots = popcount_dots(q_ref, at_ref, rows)           # [8, br] i32
        out_ref[rows, :] = (dots == sizes).astype(jnp.float32) * conf
        return carry                                        # -1 never hits

    jax.lax.fori_loop(0, q_ref.shape[0] // ROW_CHUNK, chunk, 0)


@functools.partial(jax.jit, static_argnames=("bb", "br", "interpret"))
def rule_scores_fused_pallas(Qw: jnp.ndarray, Aw: jnp.ndarray,
                             sizes: jnp.ndarray, conf: jnp.ndarray, *,
                             bb: int = 256, br: int = 256,
                             interpret: bool = False) -> jnp.ndarray:
    """Qw: [B, W] uint32; Aw: [R, W] uint32; sizes: [1, R] i32;
    conf: [1, R] f32 -> [B, R] f32 confidence-weighted match scores.
    B must be a multiple of ROW_CHUNK."""
    B, W = Qw.shape
    R = Aw.shape[0]
    bb, br = min(bb, B), min(br, R)
    assert B % bb == 0 and R % br == 0 and bb % ROW_CHUNK == 0, \
        (Qw.shape, Aw.shape, (bb, br))
    grid = (B // bb, R // br)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, W), lambda i, j: (i, 0)),
            pl.BlockSpec((W, br), lambda i, j: (0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, br), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, R), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(Qw, jnp.int32), as_word_lanes(Aw),
      sizes.astype(jnp.int32), conf)


@functools.partial(jax.jit, static_argnames=("bb", "br", "interpret"))
def rule_scores_fused(Q: jnp.ndarray, A: jnp.ndarray, sizes: jnp.ndarray,
                      conf: jnp.ndarray, *, bb: int = 256, br: int = 256,
                      interpret: bool = False) -> jnp.ndarray:
    """Unpacked 0/1 bitmaps in, scores out: packs on device (fuses into
    this jit).  Q: [B, I] int8; A: [R, I] int8 (item axes 32-aligned);
    sizes/conf: [1, R] f32 per the index padding contract."""
    sizes_i = sizes.astype(jnp.int32)        # -1 padding survives the cast
    return rule_scores_fused_pallas(pack_words(Q), pack_words(A), sizes_i,
                                    conf.astype(jnp.float32),
                                    bb=bb, br=br, interpret=interpret)
