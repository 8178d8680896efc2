"""Share of the roofline that the rule_match kernel reaches in the traced
serving span: the least time of every scoring step that starts inside
the span (``rule_work.py``: the step's cache-missing baskets against the
index's true rows and their antecedents' items) over the summed device time of the ops
named ``rule_scores*`` (``rule_scores_pallas``, the int8-matmul kernel,
and ``rule_scores_fused_pallas``, the packed one).  A trace that holds
no such op, as on a plane that scores with the jitted reference, leaves
nothing to read."""
from mba_bench import rule_work, trace, work

KERNEL_NAMES = ("rule_scores",)


def read(run):
    loop = run.loop
    index = getattr(loop, "index", None)
    steps = getattr(loop, "steps", None)
    if run.trace is None or run.peak is None or index is None or not steps:
        return None
    start, stop = loop.trace_span
    i_eff = rule_work.antecedent_items(index)
    calls = [rule_work.rule_match_work(s.n_misses, index.n_rows, i_eff)
             for s in steps if start <= s.t_start < stop and s.n_misses]
    least = work.least_seconds(calls, run.peak)
    return work.roofline_share(least,
                               trace.kernel_seconds(run.trace, KERNEL_NAMES))
