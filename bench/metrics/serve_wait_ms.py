"""Median milliseconds an answered request of the window waited in the
queue: from its arrival to the drain step that took it (``Handle.taken_s``
on the server clock)."""
import numpy as np


def read(run):
    handles = getattr(run.loop, "handles", None)
    waits = [h.taken_s - h.arrival_s for h in handles or ()
             if h.status == "done" and hasattr(h, "taken_s")]
    if not waits:
        return None
    return float(np.median(waits)) * 1e3
