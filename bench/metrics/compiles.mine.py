"""JAX lowerings per mine (``PhaseRecord.lowerings`` summed over each
window mine's phases); a warm mine should lower nothing."""
from mba_bench import spans


def read(run):
    return spans.per_mine(run, lambda name: True,
                          value=lambda p: p.lowerings)
