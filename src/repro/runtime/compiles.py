"""Compile accounting — how many executables a phase lowered, and the
seconds JAX spent tracing, lowering and compiling them.

A warm, repeated phase should lower nothing: JAX caches an executable per
function and input shape, so a lowering inside a steady loop means a new
shape, a first eager op or a cache miss — each a host stall the chip
waits through.  JAX reports every compile step through
``jax.monitoring``; one listener, registered once per process, counts
them:

* ``lowerings`` — ``/jax/core/compile/jaxpr_to_mlir_module_duration``
  events, one per lowering of a new executable (a repeated call fires
  none; a hit in the persistent compile cache still lowers, so it counts);
* ``compile_s`` — the summed seconds of the trace, lower and
  backend-compile events.

:class:`repro.runtime.Runtime` snapshots :data:`COMPILES` the way it
snapshots its ``TransferMeter``, so each
:class:`~repro.runtime.ledger.PhaseRecord` carries the compiles since the
previous phase ended.  The counter is **process-wide**: a compile on a
concurrent thread lands on whichever phase is running at that moment.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

import jax

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    LOWERING_EVENT,
    "/jax/core/compile/backend_compile_duration",
})


@dataclass(frozen=True)
class CompileStats:
    """A point-in-time (or delta) view of the compile counter."""

    lowerings: int = 0
    compile_s: float = 0.0

    def __sub__(self, other: "CompileStats") -> "CompileStats":
        return CompileStats(self.lowerings - other.lowerings,
                            self.compile_s - other.compile_s)


class CompileCounter:
    """Counts the compile events ``jax.monitoring`` reports."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._lowerings = 0
        self._compile_s = 0.0

    def on_event(self, event: str, duration_secs: float, **_) -> None:
        if event not in COMPILE_EVENTS:
            return
        with self._lock:
            self._compile_s += duration_secs
            if event == LOWERING_EVENT:
                self._lowerings += 1

    def stats(self) -> CompileStats:
        with self._lock:
            return CompileStats(self._lowerings, self._compile_s)


COMPILES = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(COMPILES.on_event)
