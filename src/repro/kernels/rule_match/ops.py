"""Jit'd public wrapper for the rule-match kernel family: batched top-k
recommendation (handles padding, backend selection and autotuned
variant/tile dispatch — the same idiom as the mining data plane in
``repro.pipeline.dataplane``).

Two score implementations compute bit-identical [B, R] matrices:

* ``mxu``    — the int8-matmul kernel (:mod:`.kernel`).
* ``packed`` — the fused packed-popcount kernel (:mod:`.fused`): subset
  test + confidence weighting in one launch over uint32 item words.

The variant + tile shape come from the autotune cache
(:mod:`repro.kernels.autotune`); cache misses use the roofline-seeded
default.  Either way the scores fold through the shared
``topk_from_scores``, so the backends cannot drift on serving semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.autotune.cache import dispatch
from repro.kernels.rule_match.fused import rule_scores_fused
from repro.kernels.rule_match.kernel import rule_scores_pallas
from repro.kernels.rule_match.ref import (recommend_ref, rule_scores_ref,
                                          topk_from_scores)


def _pad_axis_to(x: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("k", "backend", "variant", "interpret",
                                    "bb", "br", "bi"))
def _rule_topk(Q, A, sizes, conf, cons, n_items, *, k, backend, variant,
               interpret, bb, br, bi):
    if backend == "pallas" and variant == "packed":
        scores = rule_scores_fused(Q, A, sizes[None, :], conf[None, :],
                                   bb=bb, br=br, interpret=interpret)
    elif backend == "pallas":
        scores = rule_scores_pallas(Q, A, sizes[None, :], conf[None, :],
                                    bb=bb, br=br, bi=bi, interpret=interpret)
    else:
        scores = rule_scores_ref(Q, A, sizes, conf)
    return topk_from_scores(scores, Q, cons, n_items, k)


def rule_topk(Q: jnp.ndarray, A: jnp.ndarray, sizes: jnp.ndarray,
              conf: jnp.ndarray, cons: jnp.ndarray, *, k: int, n_items: int,
              backend: str | None = None,
              interpret: bool | None = None,
              tuning=None):
    """Top-k item recommendations for a batch of query baskets.

    Q: [B, I] 0/1 baskets; A: [R, I] 0/1 antecedent masks; sizes: [R]
    (=|A_r|); conf: [R] rule confidences; cons: [R] consequent item ids.
    Pads B→8·, R→128·, I→128· as the kernels require — padded rule rows
    get ``sizes=-1`` (never match; an all-zero row would match everything),
    ``conf=0`` and ``cons=I_padded`` (a dummy max-segment sliced away).
    An all-padding index (R=0) still scores: every query simply matches
    nothing.  Returns (items [B, k] int32, scores [B, k] f32) ordered by
    (score desc, item id asc); entries with score <= 0 are non-matches the
    caller should drop.

    ``tuning``: ``None`` = the checked-in autotune cache; ``False`` =
    roofline-seeded default config; a config ``dict`` or an
    ``AutotuneCache`` pins the choice.  A pinned dict runs as it is (each
    tile no larger than its padded dim must divide it); a cached config is
    fitted to the padded shape first.
    """
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    B0, I0 = Q.shape
    R0 = A.shape[0]
    if not 0 < k <= I0:
        raise ValueError(f"k={k} must be in [1, n_query_items={I0}]")
    if n_items > I0 or A.shape[1] != I0:
        raise ValueError(f"item-axis mismatch: Q {Q.shape}, A {A.shape}, "
                         f"n_items={n_items}")
    Ip = I0 + (-I0) % 128
    Q = _pad_axis_to(jnp.asarray(Q, jnp.int8), 1, Ip)
    Q = _pad_axis_to(Q, 0, B0 + (-B0) % 8)
    A = _pad_axis_to(jnp.asarray(A, jnp.int8), 1, Ip)
    # an empty rule set still pads to one full lane block of never-match
    # rows so the kernel grid stays non-degenerate
    Rp = max(R0 + (-R0) % 128, 128)
    A = _pad_axis_to(A, 0, Rp)
    pad_r = Rp - R0
    sizes = jnp.pad(jnp.asarray(sizes, jnp.float32), (0, pad_r),
                    constant_values=-1.0)
    conf = jnp.pad(jnp.asarray(conf, jnp.float32), (0, pad_r))
    cons = jnp.pad(jnp.asarray(cons, jnp.int32), (0, pad_r),
                   constant_values=Ip)
    B, _ = Q.shape
    # a cached config arrives fitted to (B, Rp, Ip) (launch/tuning.fit_config)
    cfg, interpret = dispatch("rule_match", (B, Rp, Ip), tuning, interpret)
    items, scores = _rule_topk(Q, A, sizes, conf, cons, n_items, k=k,
                               backend=backend, variant=cfg["variant"],
                               interpret=interpret, bb=cfg["bb"],
                               br=cfg["br"], bi=cfg.get("bi"))
    return items[:B0], scores[:B0]


rule_topk_oracle = recommend_ref
