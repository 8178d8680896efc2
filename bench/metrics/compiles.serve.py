"""JAX lowerings over the window's serving steps (``PhaseRecord.lowerings``
summed over the server's ledger slice); warmed buckets should lower
nothing."""


def read(run):
    report = getattr(run.loop, "report", None)
    phases = report.ledger.phases if report and report.ledger else ()
    if not phases or not hasattr(phases[0], "lowerings"):
        return None
    return sum(p.lowerings for p in phases)
