"""Host seconds per mine of ``mba-upload``: staging the bitmap's row tiles
on the device (the bytes ``h2d_mb_per_mine`` counts)."""
from mba_bench import spans


def read(run):
    return spans.per_mine(run, lambda name: name == "mba-upload")
