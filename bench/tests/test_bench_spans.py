"""The readers of the program's own phase spans and counters: each reads a
number on the fixture cells, and each finds nothing (rather than raising)
in a program whose phases carry no such span or counter."""
import math
from types import SimpleNamespace as NS

import pytest

import bench_fixtures as fx
from bench_fixtures import harness

MINE_READERS = ("ingest_host_s", "upload_host_s", "rounds_host_s",
                "mine_unspanned_s", "compiles.mine")
SERVE_READERS = ("serve_wait_ms", "compiles.serve")


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        fx.use_fixture_files(mp, tmp_path_factory.mktemp("bench"))
        bench = fx.fixture_benchmark()
        return {cell: fx.run(bench, cell, trace=True)
                for cell in (fx.MINE, fx.SERVE)}


@pytest.mark.parametrize("name", MINE_READERS + SERVE_READERS)
def test_reader_reads_a_number_on_the_fixture_cells(lines, name):
    cell = fx.MINE if name in MINE_READERS else fx.SERVE
    line = lines[cell]
    assert line["correct"] is True
    value = line["metrics"][name]["value"]
    assert isinstance(value, (int, float)) and math.isfinite(value)
    assert value >= 0


@pytest.mark.parametrize("cell, host_side", [
    (fx.MINE, {"rules_host_s", "candgen_host_s", "h2d_mb_per_mine",
               "mines_in_window"} | set(MINE_READERS)),
    (fx.SERVE, {"serve_batch_fill", "serve_score_ms"} | set(SERVE_READERS)),
])
def test_traced_line_holds_every_host_side_metric(lines, cell, host_side):
    # with no chip the device readers stay silent, and 200 requests are too
    # few for the p99 reader: the line holds the host-side metrics, all of them
    assert set(lines[cell]["metrics"]) == host_side


def test_every_mine_phase_is_timed(lines):
    m = {k: v["value"] for k, v in lines[fx.MINE]["metrics"].items()}
    assert m["ingest_host_s"] > 0 and m["upload_host_s"] > 0
    assert m["rounds_host_s"] > 0


def _parent_like_run():
    """A window as a program without the spans and counters leaves it:
    mines without an ``mba-ingest`` record, handles without ``taken_s``,
    records without ``lowerings``."""
    rec = NS(name="mba-round1-item-counts", host_time_s=0.0)
    mine = NS(report=NS(ledger=NS(phases=[rec]), wall_time_s=1.0))

    class Handle:
        __slots__ = ("status", "arrival_s", "done_s")

    h = Handle()
    h.status, h.arrival_s, h.done_s = "done", 0.0, 0.01
    report = NS(ledger=NS(phases=[NS(name="serve-score-0",
                                     host_time_s=0.005)]))
    loop = NS(mines=[mine], handles=[h], report=report)
    return harness.RunRecord(loop=loop, trace=None, trace_window_s=0.0,
                             peak=None)


@pytest.mark.parametrize("name", MINE_READERS + SERVE_READERS)
def test_reader_finds_nothing_without_the_program_spans(name):
    assert harness.load_reader(name)(_parent_like_run()) is None
