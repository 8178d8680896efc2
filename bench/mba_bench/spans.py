"""Shared by the readers of the program's own phase spans and counters.

Every phase of a mine is a ``PhaseRecord`` in the mine's ledger slice,
timed by the program's ``Runtime`` (``host_time_s``) with the JAX compiles
it made (``lowerings``).  A program that does not time every phase of a
mine has no ``mba-ingest`` record; its readers then find nothing to read.
"""
from __future__ import annotations

from typing import Callable, List, Optional


def timed_mines(run) -> Optional[List]:
    """The window's mine results, where every phase of each is timed."""
    mines = getattr(run.loop, "mines", None)
    if not mines or not all(any(p.name == "mba-ingest"
                                for p in res.report.ledger.phases)
                            for res in mines):
        return None
    return mines


def per_mine(run, keep: Callable[[str], bool],
             value: Callable = lambda p: p.host_time_s) -> Optional[float]:
    """Mean over the window's mines of ``value`` summed over the records
    whose name ``keep`` accepts."""
    mines = timed_mines(run)
    if mines is None:
        return None
    return sum(value(p) for res in mines for p in res.report.ledger.phases
               if keep(p.name)) / len(mines)
