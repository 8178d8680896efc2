"""Host seconds of candidate generation (``mba-candgen-k*``, the device
join or its host fallback) per mine, from each window mine's ledger."""


def read(run):
    mines = getattr(run.loop, "mines", None)
    if not mines:
        return None
    return sum(p.host_time_s for res in mines
               for p in res.report.ledger.phases
               if p.name.startswith("mba-candgen-k")) / len(mines)
