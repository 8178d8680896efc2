"""Token-choice top-k Mixture-of-Experts with capacity-factor dispatch.

Design (TPU-native, GSPMD-shardable):

* Tokens stay grouped by batch row (group = sequence): router, ranking and
  dispatch indices are computed per group, so capacity is per-group
  ``C = ceil(S * top_k / E * capacity_factor)`` and all shapes are static.
* Dispatch uses *compact* [E, C] index buffers (gather/scatter-add), not the
  GShard [S, E, C] one-hot einsum — memory falls from O(S·E·C) to O(E·C·d),
  which is what makes 160-expert DeepSeek-V2 lowerable at 32k sequer length.
* Experts are sharded on the ``model`` ("expert") mesh axis; the gather in /
  scatter-out become all-to-alls under GSPMD — the MoE collective term in
  §Roofline.

The MB-scheduler connection (DESIGN.md §2): expert load imbalance is in-chip
heterogeneity; the router aux loss plus capacity factor plays the same role as
proportional shard sizing at the cluster level.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init


def moe_capacity(seq_len: int, n_experts: int, top_k: int, capacity_factor: float) -> int:
    c = math.ceil(seq_len * top_k / n_experts * capacity_factor)
    return max(8, int(math.ceil(c / 8) * 8))  # pad for TPU lane alignment


def _expert_shard(x_t: jnp.ndarray) -> jnp.ndarray:
    """Sharding constraint for [E, B, C, d] (expert-major) dispatch tensors:
    E on the expert-parallel axis ("data"), matching the expert-weight
    sharding.  No-op outside a mesh context or when E doesn't divide."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or "data" not in mesh.axis_names:
        return x_t
    if x_t.shape[0] % mesh.shape["data"] != 0:
        return x_t
    from jax.sharding import PartitionSpec as _P
    return jax.lax.with_sharding_constraint(
        x_t, _P("data", None, None, None))


def moe_init(key, cfg, dtype) -> Params:
    mc = cfg.moe
    d = cfg.d_model
    ff = mc.expert_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    E = mc.n_experts
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, ff), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, ff), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, ff, d), jnp.float32) / math.sqrt(ff)).astype(dtype),
    }
    if mc.n_shared:
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(ks[4], d, ff * mc.n_shared, dtype)
    return p


def moe_forward(p: Params, cfg, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y, aux_loss).  Group axis = B."""
    mc = cfg.moe
    B, S, d = x.shape
    E, K = mc.n_experts, mc.top_k
    C = moe_capacity(S, E, K, mc.capacity_factor)

    logits = (x.astype(jnp.float32) @ p["router"])            # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)           # [B,S,K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- aux load-balance loss (switch-style) ---
    me = probs.mean(axis=(0, 1))                              # [E] mean prob
    one_hot_top1 = jax.nn.one_hot(expert_ids[..., 0], E)
    ce = one_hot_top1.mean(axis=(0, 1))                       # [E] fraction
    aux = E * jnp.sum(me * ce) * mc.router_aux_coef

    # --- rank within expert, per group (vectorized over B) ---
    flat_ids = expert_ids.reshape(B, S * K)                   # slot-major
    flat_gate = gate_vals.reshape(B, S * K)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)     # [B, S*K, E]
    pos_in_expert = jnp.cumsum(onehot, axis=1) - onehot       # rank before self
    position = jnp.take_along_axis(pos_in_expert, flat_ids[..., None], axis=-1)[..., 0]
    keep = position < C
    token_of_slot = jnp.arange(S * K) // K                    # [S*K]

    # --- compact dispatch buffers ---
    safe_e = jnp.where(keep, flat_ids, 0)
    safe_c = jnp.where(keep, position, C)                     # C = drop bucket

    def build(eids, cpos, weights):
        idx = jnp.zeros((E, C + 1), jnp.int32).at[eids, cpos].set(token_of_slot, mode="drop")
        wbuf = jnp.zeros((E, C + 1), jnp.float32).at[eids, cpos].set(weights, mode="drop")
        return idx[:, :C], wbuf[:, :C]

    idx_buf, w_buf = jax.vmap(build)(safe_e, safe_c, jnp.where(keep, flat_gate, 0.0))

    # --- gather -> expert MLP -> scatter-add ---
    x_e = jax.vmap(lambda xg, ig: xg[ig])(x, idx_buf.reshape(B, E * C))
    x_e = x_e.reshape(B, E, C, d)
    # Token→expert routing as an explicit TRANSPOSE of the two sharded dims,
    # (B@data, E, C, d) -> (E@data, B, C, d): the SPMD partitioner
    # pattern-matches transposed-sharding as one all-to-all, where a bare
    # sharding constraint on the un-transposed layout lowered to
    # all-gather + slice (buffer dump; §Perf hillclimb B).
    x_t = _expert_shard(x_e.swapaxes(0, 1))          # [E@data, B, C, d]

    g = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", x_t, p["w_gate"]))
    u = jnp.einsum("ebcd,edf->ebcf", x_t, p["w_up"])
    y_t = jnp.einsum("ebcf,efd->ebcd", g * u, p["w_down"])
    y_t = _expert_shard(y_t)
    y_e = y_t.swapaxes(0, 1)                         # back to [B@data, E, C, d]
    y_e = y_e * w_buf[..., None].astype(y_e.dtype)

    def combine(ye, ig):
        return jnp.zeros((S, d), ye.dtype).at[ig].add(ye.reshape(E * C, d))

    y = jax.vmap(combine)(y_e, idx_buf.reshape(B, E * C))

    if mc.n_shared:
        from repro.models.layers import mlp
        y = y + mlp(p["shared"], x)
    return y.astype(x.dtype), aux
