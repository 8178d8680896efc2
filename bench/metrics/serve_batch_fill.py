"""Mean admitted requests over the bucket they ran in, per scoring step
of the window (``AsyncServingReport.batch_fill``); padded slots are
wasted work."""


def read(run):
    report = getattr(run.loop, "report", None)
    if report is None or not report.bucket_counts:
        return None
    return report.batch_fill
