"""Overlap-friendly collective schedules (shard_map building blocks).

GSPMD inserts collectives automatically in the jit path; these explicit
versions exist for (a) the compressed-DP train step, (b) tests that pin the
exact schedule, and (c) the §Perf experiments that compare an XLA-chosen
all-gather against a ring schedule that overlaps with compute.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp



def ring_all_gather(x: jnp.ndarray, axis_name: str,
                    compute: Optional[Callable[[jnp.ndarray, int], None]] = None
                    ) -> jnp.ndarray:
    """All-gather along `axis_name` via N-1 ppermute hops (bi-section-friendly
    ring).  If `compute` is given it is called with each arriving shard —
    the overlap hook: on hardware each hop's DMA runs concurrently with
    consuming the previous shard."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    shards = [x]
    cur = x
    for hop in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        shards.append(cur)
    # device i received shards in order i, i-1, i-2, ... — rotate to global order
    stacked = jnp.stack(shards)                        # [n, ...] local order
    offsets = (idx - jnp.arange(n)) % n                # global slot of each entry
    out = jnp.zeros_like(stacked)
    out = out.at[offsets].set(stacked)
    return out.reshape((-1,) + x.shape[1:]) if x.ndim else out


def reduce_scatter_sum(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """psum_scatter along leading dim."""
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)


def hierarchical_psum(x: jnp.ndarray, inner: str, outer: Optional[str]) -> jnp.ndarray:
    """Two-level gradient sum: reduce inside a pod first (fast ICI), then
    across pods (slower DCN) — the multi-pod schedule verified in the
    dry-run HLO."""
    x = jax.lax.psum(x, inner)
    if outer is not None:
        x = jax.lax.psum(x, outer)
    return x
