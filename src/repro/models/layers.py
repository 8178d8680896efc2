"""Shared neural-net building blocks (pure-pytree, no framework deps).

Parameters are nested dicts of jnp arrays.  Initializers take an explicit
PRNG key and return pytrees; apply functions are pure.  All blocks respect
``cfg.param_dtype`` / ``cfg.activ_dtype`` (params bf16, math where it matters
in f32).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


def sequence_shard(x: jnp.ndarray) -> jnp.ndarray:
    """Sequence-parallel sharding constraint (Korthikanti et al.): between
    blocks, activations [B, S, d] are sharded on ("pod","data") × batch and
    "model" × sequence, so the per-layer residual saves (and norms /
    elementwise work) are TP-sharded instead of replicated.  GSPMD inserts
    the all-gather before attention and the reduce-scatter after the row
    matmuls.  No-op outside a mesh context or when dims don't divide."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or x.ndim < 3:
        return x
    names = mesh.axis_names
    batch_ax = tuple(a for a in ("pod", "data") if a in names)
    if "model" not in names or not batch_ax:
        return x
    import numpy as _np
    bsz = int(_np.prod([mesh.shape[a] for a in batch_ax]))
    if x.shape[0] % bsz != 0 or x.shape[1] % mesh.shape["model"] != 0:
        return x
    from jax.sharding import PartitionSpec as _P
    return jax.lax.with_sharding_constraint(
        x, _P(batch_ax, "model", *([None] * (x.ndim - 2))))


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(orig)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs   # [..., seq, hd/2]
    angles = angles[..., None, :]                       # [..., seq, 1, hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jax.nn.silu(x @ p["w_gate"])
    return (g * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy; logits [..., V] in any float dtype (f32 math)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_softmax_xent(x: jnp.ndarray, embed: jnp.ndarray, labels: jnp.ndarray,
                         chunk: int, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Cross-entropy without materializing [tokens, V] logits.

    Scans over vocab chunks accumulating a running logsumexp and picking the
    label logit on the fly.  x: [T, d] final hidden states, embed: [V, d]
    (the unembedding), labels: [T].  This is the §Perf "chunked vocab loss"
    lever: HBM traffic drops from O(T·V) to O(T·V/..) streamed weights with a
    [T, chunk] working set.
    """
    T, d = x.shape
    V = embed.shape[0]
    assert V % chunk == 0, (V, chunk)
    n = V // chunk
    w = embed.reshape(n, chunk, d)

    # checkpointed: otherwise scan-autodiff saves every [T, chunk] logits
    # tile for backward — re-materializing the full [T, V] matrix the chunked
    # loss exists to avoid (same pattern as chunked attention).
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, wc_i):
        m, s, ll = carry
        wc, i = wc_i
        logits = (x @ wc.T).astype(jnp.float32)            # [T, chunk]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(-1)
        local = labels - i * chunk
        in_chunk = (local >= 0) & (local < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=-1)[:, 0]
        ll = jnp.where(in_chunk, picked, ll)
        return (m_new, s, ll), None

    init = (jnp.full((T,), -jnp.inf, jnp.float32),
            jnp.zeros((T,), jnp.float32),
            jnp.zeros((T,), jnp.float32))
    (m, s, ll), _ = jax.lax.scan(body, init, (w, jnp.arange(n)))
    nll = (m + jnp.log(s)) - ll
    if mask is not None:
        maskf = mask.astype(jnp.float32)
        return jnp.sum(nll * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)
    return jnp.mean(nll)
