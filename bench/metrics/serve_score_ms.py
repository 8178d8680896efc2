"""Mean host milliseconds of a scoring step (``serve-score-*`` phases of
the server's ledger slice over the window)."""


def read(run):
    report = getattr(run.loop, "report", None)
    if report is None or report.ledger is None:
        return None
    walls = [p.host_time_s for p in report.ledger.phases
             if p.name.startswith("serve-score-")]
    if not walls:
        return None
    return 1e3 * sum(walls) / len(walls)
