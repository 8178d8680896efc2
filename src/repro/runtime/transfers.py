"""Device-transfer accounting — the observability half of device-held rounds.

JAX dispatch is asynchronous: a round of tile kernels costs almost nothing
to *launch*; what serializes a mining round is every host/device boundary
crossing — an ``np.asarray`` on a device value blocks until the whole
dependency chain flushes (one sync), and every ``jnp.asarray`` of host data
is an H2D copy.  The planes therefore route **all** boundary crossings
through a :class:`TransferMeter`, which makes three quantities exact and
ledger-attributable per phase:

* ``h2d_bytes`` — bytes staged host → device (tile uploads, host-built
  candidate bitmaps, fallback candidate matrices)
* ``d2h_bytes`` — bytes read back device → host (one packed count vector
  per counting round)
* ``syncs``     — device→host synchronization points (each ``d2h`` is one;
  the round contract is **exactly one per counting round**)

:class:`repro.runtime.Runtime` snapshots its meter after every phase, so
each :class:`~repro.runtime.ledger.PhaseRecord` carries the transfers that
happened since the previous phase ended — staging between phases (e.g. the
one-time tile upload) lands on the phase that consumes it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class TransferStats:
    """A point-in-time (or delta) view of a meter's counters."""

    h2d_bytes: int = 0
    d2h_bytes: int = 0
    syncs: int = 0

    def __sub__(self, other: "TransferStats") -> "TransferStats":
        return TransferStats(self.h2d_bytes - other.h2d_bytes,
                             self.d2h_bytes - other.d2h_bytes,
                             self.syncs - other.syncs)

    def __add__(self, other: "TransferStats") -> "TransferStats":
        return TransferStats(self.h2d_bytes + other.h2d_bytes,
                             self.d2h_bytes + other.d2h_bytes,
                             self.syncs + other.syncs)


class TransferMeter:
    """Counts every host/device boundary crossing routed through it.

    ``h2d``/``d2h`` are drop-in replacements for ``jnp.asarray`` /
    ``np.asarray`` that account bytes (and, for ``d2h``, the sync point).
    Both run under ``jax.transfer_guard("allow")`` so a test can wrap a
    whole mine in ``jax.transfer_guard("disallow")`` and catch any
    *unaccounted* transfer the planes still make.
    """

    def __init__(self) -> None:
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.syncs = 0

    # ------------------------------------------------------------------
    def h2d(self, x: Any, dtype=None, sharding=None) -> jnp.ndarray:
        """Stage host data on device, counting the bytes moved.  A value
        that is already device-resident passes through uncounted — call
        sites can route every input here without double-billing.  With a
        ``sharding``, each device receives only its own shard."""
        if isinstance(x, jax.Array):
            return x if dtype is None else x.astype(dtype)
        with jax.transfer_guard("allow"):
            out = (jnp.asarray(x, dtype=dtype) if sharding is None
                   else jax.device_put(np.asarray(x, dtype=dtype), sharding))
        self.h2d_bytes += int(out.nbytes)
        return out

    def d2h(self, x: Any, dtype=None) -> np.ndarray:
        """Read a device value back to host: one sync + its bytes.  Host
        values pass through uncounted (no boundary crossed)."""
        if isinstance(x, np.ndarray) and not isinstance(x, jnp.ndarray):
            return x if dtype is None else np.asarray(x, dtype=dtype)
        with jax.transfer_guard("allow"):
            out = np.asarray(x, dtype=dtype)
        self.d2h_bytes += int(out.nbytes)
        self.syncs += 1
        return out

    def sync(self, n: int = 1) -> None:
        """Record a synchronization that moved no bytes through the meter
        (e.g. an explicit ``block_until_ready``)."""
        self.syncs += n

    # ------------------------------------------------------------------
    def stats(self) -> TransferStats:
        return TransferStats(self.h2d_bytes, self.d2h_bytes, self.syncs)

    def since(self, mark: TransferStats) -> TransferStats:
        return self.stats() - mark


# A process-wide default for callers without a Runtime (reference drivers,
# one-off scripts).  Planes use their Runtime's own meter so concurrent
# planes cannot cross-attribute each other's transfers.
METER = TransferMeter()
