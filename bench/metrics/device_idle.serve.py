"""Percent of the traced serving span in which no operation ran on the
device: 1 - (union of device-op intervals / traced seconds)."""
from mba_bench import trace


def read(run):
    if (run.trace is None or not run.trace.device_ops
            or not getattr(run.loop, "steps", None)):
        return None
    return trace.idle_share(trace.busy_seconds(run.trace),
                            run.trace_window_s)
