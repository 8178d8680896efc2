"""Seconds per mine that no phase covers: the mine's host wall
(``PipelineReport.wall_time_s``) less the summed ``host_time_s`` of its
phases, i.e. the pipeline's own work between its phases."""
from mba_bench import spans


def read(run):
    mines = spans.timed_mines(run)
    if mines is None:
        return None
    return sum(res.report.wall_time_s
               - sum(p.host_time_s for p in res.report.ledger.phases)
               for res in mines) / len(mines)
