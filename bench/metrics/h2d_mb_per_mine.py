"""Megabytes staged host to device per mine (``TransferMeter`` counts in
each window mine's ledger slice)."""


def read(run):
    mines = getattr(run.loop, "mines", None)
    if not mines:
        return None
    return sum(res.report.ledger.total_h2d_bytes
               for res in mines) / len(mines) / 1e6
