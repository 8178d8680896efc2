"""Fused packed-popcount support-count kernel (the autotuner's second
variant for the Apriori hot loop).

The MXU kernel in :mod:`repro.kernels.support_count.kernel` spends one
int8 MAC per (transaction, candidate, item) triple.  This variant packs
the item axis into uint32 *words* (32 items per lane element) and fuses
the whole round into a single launch:

  dot(T_t, C_m) == Σ_w popcount(Tw[t, w] & Cw[m, w])

so the containment test, the candidate filter (``== |C_m|``) and the
per-tile count reduce all happen in one kernel body — no [N, M] score
matrix ever leaves the core, and the item contraction shrinks 32× in
both bytes moved and lane ops.  On VPU-heavy devices (and in interpret
mode, where the body lowers to straight XLA ops) this beats the matmul
formulation; on MXU-rich devices the matmul usually wins.  Which variant
runs where is exactly what :mod:`repro.kernels.autotune` measures.

Tiling (HBM→VMEM):
  grid = (M/bm, N/bn) — candidate tiles outermost ("parallel"),
  transaction tiles innermost ("arbitrary"), so each [1, bm] output
  block is revisited only across the sequential-innermost N axis (the
  revisit pattern TPU Pallas supports) and Pallas' grid pipeline
  double-buffers the block DMAs across steps.  The word axis is carried
  whole per block (W = I/32 words; a 4096-item universe is 128 lanes).
  Candidate words arrive transposed, ``[W, bm]``, so each word is one
  lane-dense row.  The body walks the block ``ROW_CHUNK`` transaction
  rows at a time and, per chunk, the words one at a time: word column
  ``[ROW_CHUNK, 1]`` AND word row ``[1, bm]``, popcount, add.  The live
  set is a ``[ROW_CHUNK, bm]`` accumulator, never a ``[bn, bm, W]``
  intermediate, so VMEM holds little beyond the double-buffered blocks
  and the unrolled body does not grow with bn.

Padding contract (shared with the MXU variant's ops wrapper): padded
transaction rows are all-zero words (support only the empty itemset,
which Apriori never emits) and padded candidate rows are sliced away by
the caller — an all-zero candidate would match every transaction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD_BITS = 32
# Transaction (query) rows per step of the body's row loop: one int32
# sublane tile, so the accumulator of a chunk is bm/128 vregs.
ROW_CHUNK = 8


def pack_words(x: jnp.ndarray) -> jnp.ndarray:
    """0/1 bitmap [R, I] (I % 32 == 0) -> packed uint32 words [R, I/32].

    Bit b of word w holds item ``w * 32 + b``.  jit-friendly: a reshape
    plus a shift-weighted sum, so the packing fuses into the caller's
    program instead of round-tripping through the host.
    """
    r, i = x.shape
    assert i % WORD_BITS == 0, f"item axis must be 32-aligned, got {i}"
    bits = x.astype(jnp.uint32).reshape(r, i // WORD_BITS, WORD_BITS)
    shifts = jnp.left_shift(jnp.uint32(1),
                            jnp.arange(WORD_BITS, dtype=jnp.uint32))
    return jnp.sum(bits * shifts, axis=2, dtype=jnp.uint32)


def as_word_lanes(words: jnp.ndarray) -> jnp.ndarray:
    """Packed uint32 words [R, W] -> the kernels' operand layout: int32 bit
    patterns (Mosaic reduces signed integers only; popcount ignores the
    sign) with the word axis leading, ``[W, R]``."""
    return jax.lax.bitcast_convert_type(words, jnp.int32).T


def popcount_dots(t_ref, ct_ref, rows) -> jnp.ndarray:
    """AND-popcounts of block rows ``rows`` (a ``pl.ds`` of ROW_CHUNK) of
    the ``[bn, W]`` int32 word block against every column of the ``[W, bm]``
    word block -> ``[ROW_CHUNK, bm]`` int32.  The word loop unrolls (W =
    I/32 is static and small)."""
    acc = jnp.zeros((ROW_CHUNK, ct_ref.shape[1]), jnp.int32)
    for w in range(t_ref.shape[1]):
        acc += jax.lax.population_count(
            t_ref[rows, w:w + 1] & ct_ref[w:w + 1, :])
    return acc


def chunk_rows(r) -> pl.Slice:
    return pl.ds(pl.multiple_of(r * ROW_CHUNK, ROW_CHUNK), ROW_CHUNK)


def _kernel(t_ref, ct_ref, sizes_ref, out_ref):
    """Grid: (j, i) over (M-tiles, N-tiles); N innermost (out revisits)."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    sizes = sizes_ref[...]                                   # [1, bm]

    def chunk(r, hits):                                      # filter fused in
        dots = popcount_dots(t_ref, ct_ref, chunk_rows(r))
        return hits + (dots == sizes).astype(jnp.int32)

    hits = jax.lax.fori_loop(0, t_ref.shape[0] // ROW_CHUNK, chunk,
                             jnp.zeros((ROW_CHUNK, sizes.shape[1]),
                                       jnp.int32))
    out_ref[...] += jnp.sum(hits, axis=0, keepdims=True)     # [1, bm]


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def support_count_fused_pallas(Tw: jnp.ndarray, Cw: jnp.ndarray,
                               sizes: jnp.ndarray, *, bn: int = 512,
                               bm: int = 256,
                               interpret: bool = False) -> jnp.ndarray:
    """Tw: [N, W] uint32; Cw: [M, W] uint32; sizes: [1, M] i32 -> [1, M] i32.
    N must be a multiple of ROW_CHUNK."""
    N, W = Tw.shape
    M = Cw.shape[0]
    bn, bm = min(bn, N), min(bm, M)
    assert N % bn == 0 and M % bm == 0 and bn % ROW_CHUNK == 0, \
        (Tw.shape, Cw.shape, (bn, bm))
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, W), lambda j, i: (i, 0)),
            pl.BlockSpec((W, bm), lambda j, i: (0, j)),
            pl.BlockSpec((1, bm), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(Tw, jnp.int32), as_word_lanes(Cw),
      sizes.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def support_count_fused(T: jnp.ndarray, C: jnp.ndarray, *, bn: int = 512,
                        bm: int = 256,
                        interpret: bool = False) -> jnp.ndarray:
    """Unpacked 0/1 bitmaps in, fused counts out: packs on device (fuses
    into this jit), derives |C_m|, runs the kernel.  T: [N, I] int8/uint8,
    C: [M, I] — both item-axes 32-aligned; returns [1, M] int32."""
    sizes = jnp.sum(C.astype(jnp.int32), axis=1)[None, :]      # [1, M]
    return support_count_fused_pallas(pack_words(T), pack_words(C), sizes,
                                      bn=bn, bm=bm, interpret=interpret)
