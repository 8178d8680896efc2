"""Host seconds of the rule-generation phase (``mba-rules``) per mine,
from each window mine's ledger slice (``PhaseRecord.host_time_s``)."""


def read(run):
    mines = getattr(run.loop, "mines", None)
    if not mines:
        return None
    return sum(p.host_time_s for res in mines
               for p in res.report.ledger.phases
               if p.name == "mba-rules") / len(mines)
