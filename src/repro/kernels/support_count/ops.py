"""Jit'd public wrapper for the support-count kernel family (handles
padding, backend selection and autotuned variant/tile dispatch).

Two implementations compute the same counts bit-identically:

* ``mxu``    — the int8-matmul kernel (:mod:`.kernel`): containment as a
  systolic-array dot plus a VPU compare.
* ``packed`` — the fused packed-popcount kernel (:mod:`.fused`): items
  packed 32-per-uint32-word, containment + filter + count in one launch.

Which one runs — and at what tile shape — comes from the autotune cache
(:mod:`repro.kernels.autotune`) keyed by (kernel, shape-bucket, device
kind); with no cache entry the roofline-seeded default applies.  Off-TPU
both run in interpret mode (lowered to plain XLA ops), which is where
the CI baselines hold the packed variant to *beating* the jitted ref.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp

from repro.kernels.autotune.cache import dispatch
from repro.kernels.support_count.fused import (pack_words,
                                               support_count_fused,
                                               support_count_fused_pallas)
from repro.kernels.support_count.intersect import intersect_count_pallas
from repro.kernels.support_count.kernel import (support_count_mxu,
                                                support_count_pallas)
from repro.kernels.support_count.ref import intersect_count_ref, support_count_ref


def _pad_to(x: jnp.ndarray, axis: int, multiple: int):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit(want: int, dim: int) -> int:
    """Shrink a cached/heuristic tile until it divides the padded dim."""
    t = max(1, min(int(want), dim))
    while dim % t:
        t //= 2
    return max(t, 1)


def support_count(T: jnp.ndarray, C: jnp.ndarray, *,
                  interpret: bool | None = None,
                  tuning=None) -> jnp.ndarray:
    """Support counts [M] int32.  Pads N→8·, M→128·, I→128· as the kernels
    require; padded candidate rows have |c|=0 and are sliced away (a padded
    all-zero candidate would match every row, so we must slice, not rely on
    zero counts).

    ``tuning``: ``None`` = the checked-in autotune cache; ``False`` =
    roofline-seeded default config; a config ``dict`` or an
    ``AutotuneCache`` pins the choice (tests, the tuner, CI sweeps).
    """
    M0 = C.shape[0]
    if M0 == 0:          # empty candidate level: nothing to count
        return jnp.zeros((0,), jnp.int32)
    T = _pad_to(_pad_to(T.astype(jnp.int8), 1, 128), 0, 8)
    C = _pad_to(_pad_to(C.astype(jnp.int8), 1, 128), 0, 128)
    N, I = T.shape
    variant, blocks, interpret = _launch(N, C.shape[0], I, tuning, interpret)
    if variant == "packed":
        out = support_count_fused(T, C, **blocks, interpret=interpret)
    else:
        out = support_count_mxu(T, C, **blocks, interpret=interpret)
    counts = out[0, :M0]
    # padded transaction rows are all-zero: they can only match |c|=0 sets,
    # which do not occur among real candidates (Apriori starts at k=1).
    return counts


def _launch(N: int, M: int, I: int, tuning, interpret):
    """``(variant, block sizes, interpret)`` of one launch at the padded
    shape ``(N, M, I)``: the dispatched config, each block shrunk to
    divide its dim."""
    cfg, interpret = dispatch("support_count", (N, M, I), tuning, interpret)
    variant = cfg.get("variant", "mxu")
    blocks = {"bn": _fit(cfg.get("bn", 512), N),
              "bm": _fit(cfg.get("bm", 256), M)}
    if variant != "packed":
        blocks["bi"] = _fit(cfg.get("bi", 512), I)
    return variant, blocks, interpret


def support_count_tiles(tiles: jnp.ndarray, C: jnp.ndarray, *,
                        combine_fn=operator.add,
                        interpret: bool | None = None,
                        tuning=None) -> jnp.ndarray:
    """Support counts [M] int32 of C over every row tile of ``tiles``
    (``[n_tiles, N, I]`` 0/1 int8, ``N % 8 == 0``, ``I % 128 == 0``), as
    one device program: C is cast and |C| taken once, the config resolved
    once at the tile shape ``(N, M, I)`` — the shape :func:`support_count`
    resolves a tile at — and the kernel launched once per tile, each
    launch the same as :func:`support_count`'s, the partial counts folded
    in the program by ``combine_fn`` (a round's combiner, traced into the
    program).  ``tuning`` as for :func:`support_count`."""
    M0 = C.shape[0]
    if M0 == 0:
        return jnp.zeros((0,), jnp.int32)
    _, N, I = tiles.shape
    if N % 8 or I % 128 or C.shape[1] != I:
        raise ValueError(f"tiles {tiles.shape} and candidates {C.shape} "
                         f"are not aligned to the kernel")
    C = _pad_to(C, 0, 128)
    variant, blocks, interpret = _launch(N, C.shape[0], I, tuning,
                                         interpret)
    out = _count_tiles(tiles, C, combine_fn=combine_fn, variant=variant,
                       interpret=interpret, **blocks)
    return out if out.shape[0] == M0 else out[:M0]


@functools.partial(jax.jit, static_argnames=("combine_fn", "variant", "bn",
                                             "bm", "bi", "interpret"))
def _count_tiles(tiles, C, *, combine_fn, variant: str, bn: int, bm: int,
                 bi: int | None = None, interpret: bool = False):
    """The round program of :func:`support_count_tiles`: a scan over the
    tiles, one kernel launch per step, keeping the kernels' own names
    (``support_count_pallas`` / ``support_count_fused_pallas``)."""
    C = C.astype(jnp.int8)
    sizes = jnp.sum(C, axis=1, dtype=jnp.int32)[None, :]       # [1, M]
    if variant == "packed":
        Cw = pack_words(C)
        count = lambda T: support_count_fused_pallas(
            pack_words(T), Cw, sizes, bn=bn, bm=bm, interpret=interpret)
    else:
        count = lambda T: support_count_pallas(
            T, C, sizes, bn=bn, bm=bm, bi=bi, interpret=interpret)

    def step(acc, T):
        return combine_fn(acc, count(T)[0]), None

    acc, _ = jax.lax.scan(step, jnp.zeros((C.shape[0],), jnp.int32), tiles)
    return acc


def intersect_count(A: jnp.ndarray, B: jnp.ndarray, *,
                    interpret: bool | None = None,
                    tuning=None) -> jnp.ndarray:
    """Row-aligned tid-slab intersection counts [M] int32 (Eclat primitive).

    A, B: [M, W] packed uint32 tid-lists — row m of the output is
    |tidset(A[m]) ∩ tidset(B[m])|.  Pads M→128·, W→128· with zero words
    (inert: popcount(0) == 0) and slices padded rows away.

    ``tuning`` follows the family contract: ``None`` = the checked-in
    autotune cache; ``False`` = roofline-seeded default config; a config
    ``dict`` or an ``AutotuneCache`` pins the choice.
    """
    if A.shape != B.shape:
        raise ValueError(f"slab shapes differ: {A.shape} vs {B.shape}")
    M0 = A.shape[0]
    if M0 == 0:          # empty candidate level: nothing to intersect
        return jnp.zeros((0,), jnp.int32)
    A = _pad_to(_pad_to(A.astype(jnp.uint32), 1, 128), 0, 128)
    B = _pad_to(_pad_to(B.astype(jnp.uint32), 1, 128), 0, 128)
    M, W = A.shape
    cfg, interpret = dispatch("intersect_count", (M, W), tuning, interpret)
    bm = _fit(cfg.get("bm", 256), M)
    bw = _fit(cfg.get("bw", 128), W)
    out = intersect_count_pallas(A, B, bm=bm, bw=bw, interpret=interpret)
    return out[0, :M0]


support_count_oracle = support_count_ref
intersect_count_oracle = intersect_count_ref
