"""99th percentile of serving latency, each request timed from its
scheduled send time to its answer, over the requests due after the traced
span has closed (the profiler's stop stalls the host; the span's own
requests would read that stall)."""
import numpy as np

AFTER_TRACE_S = 1.0


def read(run):
    loop = run.loop
    handles = getattr(loop, "handles", None)
    if not handles:
        return None
    start = loop.trace_span[1] + AFTER_TRACE_S
    lat = [h.latency_s for h in handles
           if h.status == "done" and h.arrival_s >= start]
    if len(lat) < 1000:
        return None
    return float(np.percentile(np.array(lat) * 1e3, 99))
