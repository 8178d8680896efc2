"""Data plane for the pipeline: candidate support counting with stable shapes.

The paper's hot spot (Apriori step 2) runs on one of two backends:

* ``pallas`` — the MXU kernel in :mod:`repro.kernels.support_count` (the
  default on TPU; forced elsewhere it runs in interpret mode, which is only
  useful for tests).
* ``ref`` — the jitted pure-jnp oracle (the automatic off-TPU fallback).

Shape discipline is what makes either backend cheap across Apriori levels:
XLA recompiles per distinct input shape, so the pipeline (a) splits the
transaction bitmap into *uniform* row tiles and (b) pads every level's
candidate matrix up to a multiple of ``m_bucket`` rows.  Levels whose
candidate counts land in the same bucket then hit the same jit-cache entry
— one compiled kernel serves the whole mining run.

Padded candidate rows are all-zero; an all-zero mask would match every
transaction (``dot == |c| == 0``), so counts are always sliced back to the
true candidate count rather than trusting zeros.
"""
from __future__ import annotations

import functools
import operator
from collections.abc import Sequence
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.data.baskets import ITEM_LANES
from repro.kernels.support_count.ops import support_count as _pallas_count
from repro.kernels.support_count.ops import support_count_tiles
from repro.kernels.support_count.ref import support_count_ref as _ref_count
from repro.runtime.transfers import METER, TransferMeter

_jitted_ref = jax.jit(_ref_count)


@functools.partial(jax.jit, static_argnames=("combine_fn",))
def _ref_round(tiles: jax.Array, C: jax.Array, combine_fn) -> jax.Array:
    """The jnp oracle's round program: :func:`support_count_tiles`'s scan
    over the tiles with the reference as its count."""
    def step(acc, T):
        return combine_fn(acc, _ref_count(T, C)), None
    return jax.lax.scan(step, jnp.zeros((C.shape[0],), jnp.int32), tiles)[0]


@jax.jit
def item_counts(tiles: jax.Array) -> jax.Array:
    """Per-item transaction counts [I] int32 over every resident tile —
    round 1 as one program."""
    return jnp.sum(tiles, axis=(0, 1), dtype=jnp.int32)


def resolve_backend(kind: str = "auto") -> str:
    """'auto' → pallas on TPU, ref elsewhere; 'pallas'/'ref' force."""
    if kind == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if kind not in ("pallas", "ref"):
        raise ValueError(f"unknown data plane {kind!r}")
    return kind


def pad_candidates(C: np.ndarray, m_bucket: int) -> np.ndarray:
    """Pad the candidate axis up to a multiple of m_bucket with zero rows."""
    m = C.shape[0]
    pad = (-m) % m_bucket
    if pad == 0:
        return C
    return np.pad(C, ((0, pad), (0, 0)))


def tile_geometry(n_tx: int, n_tiles: int,
                  row_multiple: int = 8) -> Tuple[int, int]:
    """``(tile count, rows per tile)`` of the uniform row tiling: the count
    clamped to ``[1, n_tx]``, the rows ``ceil(n_tx / count)`` rounded up to
    the kernel's sublane multiple."""
    n_tiles = max(1, min(n_tiles, n_tx))
    rows = -(-n_tx // n_tiles)                    # ceil
    rows += (-rows) % row_multiple                # kernel sublane alignment
    return n_tiles, rows


def uniform_tiles(T: np.ndarray, n_tiles: int,
                  row_multiple: int = 8) -> List[np.ndarray]:
    """Split T into n_tiles row tiles of identical shape (zero-row padded).

    Identical tile shapes are a jit-cache requirement, and all-zero padding
    rows are inert: they contain no items, so they can only support the
    empty itemset, which Apriori never emits (k >= 1).
    """
    n_tx = T.shape[0]
    n_tiles, rows = tile_geometry(n_tx, n_tiles, row_multiple)
    padded = np.pad(T, ((0, rows * n_tiles - n_tx), (0, 0)))
    return [np.ascontiguousarray(padded[i * rows:(i + 1) * rows])
            for i in range(n_tiles)]


@functools.partial(jax.jit, static_argnames=("shape", "n_tiles"))
def device_tiles(flat: jax.Array, shape: Tuple[int, int],
                 n_tiles: int) -> jax.Array:
    """``uniform_tiles(pad_items(T), n_tiles)`` stacked into one
    ``[n_tiles, rows, I]`` int8 array (the dtype the kernels read), built
    on the device from the raw bitmap ``T`` of ``shape``, uploaded as its
    flat bytes: one program reshapes it, zero-pads the item axis to the
    lane multiple and the rows to the tiling, casts, and views the rows as
    tiles.  The host uploads the bitmap once and copies it never; a 1-D
    upload also spares the host the 2-D array's relayout to the device's
    tiled layout, which 1,000 unaligned lanes need (TPU v5e: 0.018 s to a
    ready tile set, against 0.034-0.039 s for the 2-D upload)."""
    n_tx, n_items = shape
    n_tiles, rows = tile_geometry(n_tx, n_tiles)
    padded = jnp.pad(flat.reshape(shape), ((0, rows * n_tiles - n_tx),
                                           (0, (-n_items) % ITEM_LANES)))
    return padded.astype(jnp.int8).reshape(n_tiles, rows, -1)


class ResidentTiles(Sequence):
    """The bitmap's row tiles, resident on the device as one
    ``[n_tiles, rows, I]`` int8 array (``stack``) that a round program
    reads whole.  As a sequence it holds each tile's shape and dtype — what
    the scheduler plans and prices a tile by — so planning slices nothing
    on the device."""

    def __init__(self, stack: jax.Array):
        self.stack = stack
        self._tile = jax.ShapeDtypeStruct(stack.shape[1:], stack.dtype)

    def __len__(self) -> int:
        return int(self.stack.shape[0])

    def __getitem__(self, i: int) -> jax.ShapeDtypeStruct:
        if not -len(self) <= i < len(self):
            raise IndexError(i)
        return self._tile


class DataPlane:
    """Per-level candidate batch + support counting.

    Usage: ``prepare(C)`` (host bitmap) or ``prepare_device(C)`` once per
    Apriori level, then ``round_counts(tiles)`` once over the resident
    tiles (a MapReduceJob's round_fn), or ``tile_counts_device(tile)`` for
    every transaction tile (a MapReduceJob's map_fn).
    """

    def __init__(self, kind: str = "auto", m_bucket: int = 128,
                 interpret: Optional[bool] = None, tuning=None,
                 meter: Optional[TransferMeter] = None):
        if m_bucket <= 0 or m_bucket % 128:
            raise ValueError(
                "m_bucket must be a positive multiple of 128 (kernel lanes)")
        self.backend = resolve_backend(kind)
        self.m_bucket = m_bucket
        self.interpret = interpret
        # None = the checked-in autotune cache picks variant + tiles;
        # False = roofline defaults; dict/AutotuneCache pin the choice
        self.tuning = tuning
        # all boundary crossings this plane makes are metered, so the
        # owning Runtime's ledger can attribute them per phase
        self.meter = meter if meter is not None else METER
        self._C: Optional[jnp.ndarray] = None

    @property
    def m_padded(self) -> int:
        return int(self._C.shape[0]) if self._C is not None else 0

    # ------------------------------------------------------------------
    def prepare(self, C: np.ndarray) -> None:
        """Stage a level's candidate bitmap (padded to the bucket shape)."""
        self._C = self.meter.h2d(pad_candidates(C, self.m_bucket))

    def prepare_device(self, C: jnp.ndarray) -> None:
        """Stage an already-device-resident candidate bitmap (from the
        device candidate generator: padding rows are zeroed, so no re-pad
        and no transfer — the generator built it in place)."""
        if C.shape[0] % self.m_bucket:
            raise ValueError(
                f"device candidate bitmap rows {C.shape[0]} not a multiple "
                f"of m_bucket={self.m_bucket}")
        self._C = C

    def _counts(self, tile) -> jnp.ndarray:
        Tj = self.meter.h2d(tile)
        if self.backend == "pallas":
            return _pallas_count(Tj, self._C, interpret=self.interpret,
                                 tuning=self.tuning)
        return _jitted_ref(Tj, self._C)

    def round_counts(self, tiles: ResidentTiles,
                     combine_fn=operator.add) -> jnp.ndarray:
        """Device-resident counts [m_padded] int32 over every resident
        tile, the tiles' partial counts folded by ``combine_fn`` inside one
        program (one host call a round) — no readback, no sync."""
        assert self._C is not None, "prepare() before round_counts()"
        if self.backend == "pallas":
            return support_count_tiles(tiles.stack, self._C,
                                       combine_fn=combine_fn,
                                       interpret=self.interpret,
                                       tuning=self.tuning)
        return _ref_round(tiles.stack, self._C, combine_fn)

    def tile_counts_device(self, tile) -> jnp.ndarray:
        """Device-resident counts [m_padded] int32 for one tile — no slice,
        no readback, no sync: the round combines these on device
        and reads one packed vector back at round close."""
        assert self._C is not None, "prepare() before tile_counts_device()"
        return self._counts(tile)
