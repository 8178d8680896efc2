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

import jax.numpy as jnp

from repro.kernels.autotune.cache import dispatch
from repro.kernels.support_count.fused import support_count_fused
from repro.kernels.support_count.intersect import intersect_count_pallas
from repro.kernels.support_count.kernel import support_count_mxu
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
    M = C.shape[0]
    cfg, interpret = dispatch("support_count", (N, M, I), tuning, interpret)
    bn = _fit(cfg.get("bn", 512), N)
    bm = _fit(cfg.get("bm", 256), M)
    if cfg.get("variant", "mxu") == "packed":
        out = support_count_fused(T, C, bn=bn, bm=bm, interpret=interpret)
    else:
        bi = _fit(cfg.get("bi", 512), I)
        out = support_count_mxu(T, C, bn=bn, bm=bm, bi=bi,
                                interpret=interpret)
    counts = out[0, :M0]
    # padded transaction rows are all-zero: they can only match |c|=0 sets,
    # which do not occur among real candidates (Apriori starts at k=1).
    return counts


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
