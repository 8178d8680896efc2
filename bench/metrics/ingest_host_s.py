"""Host seconds per mine of ``mba-ingest``: validating and packing the
corpus into the lane-padded bitmap and cutting its row tiles."""
from mba_bench import spans


def read(run):
    return spans.per_mine(run, lambda name: name == "mba-ingest")
