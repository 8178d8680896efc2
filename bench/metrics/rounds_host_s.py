"""Host seconds per mine of the counting rounds (``mba-round*``): each
round's kernel dispatches, device combine and its one readback."""
from mba_bench import spans


def read(run):
    return spans.per_mine(run, lambda name: name.startswith("mba-round"))
