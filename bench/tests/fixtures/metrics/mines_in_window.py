"""Test fixture: a per-layer metric added as a file of its own."""


def read(run):
    mines = getattr(run.loop, "mines", None)
    return float(len(mines)) if mines else None
