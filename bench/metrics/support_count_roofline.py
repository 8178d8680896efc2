"""Share of the roofline that the support_count kernel reaches in the
traced mines: the least time of each round's problem (``work.py``,
counted from the reference's candidates) over the kernel's summed device
time in the trace."""
from mba_bench import trace, work

KERNEL_NAMES = ("support_count",)


def read(run):
    loop = run.loop
    traced = getattr(loop, "traced", 0)
    if run.trace is None or run.peak is None or not traced:
        return None
    n_tx = loop.T.shape[0]
    calls = [work.support_count_work(n_tx, m, i_eff)
             for m, i_eff in loop.ref.levels]
    least = work.least_seconds(calls, run.peak) * traced
    return work.roofline_share(least,
                               trace.kernel_seconds(run.trace, KERNEL_NAMES))
