"""The reader of ``support_count_mxu_share``: the MXU kernel's share of
the support_count launches in a traced window, counted from the trace's
device-op names.  It finds nothing (rather than raising) where the trace
holds no support_count kernel or where no mine was traced."""
from types import SimpleNamespace as NS

import pytest

import bench_fixtures as fx
from bench_fixtures import harness
from mba_bench.trace import Event, Trace

NAME = "support_count_mxu_share"
MS = 1e6  # ns


def _run(ops, traced=1):
    """A traced run whose one device plane ran ``ops`` (names) in turn."""
    dev = [Event(name, k * MS, MS) for k, name in enumerate(ops)]
    return harness.RunRecord(
        loop=NS(traced=traced),
        trace=Trace(device_ops={"/device:TPU:0": dev}, host_events=[]),
        trace_window_s=len(ops) * 1e-3, peak=None)


@pytest.mark.parametrize("ops, share", [
    (["support_count_pallas"] * 4 + ["convert"], 1.0),
    (["support_count_fused_pallas"] * 3 + ["copy"], 0.0),
    (["support_count_pallas"] * 3 + ["support_count_fused_pallas"], 0.75),
])
def test_reader_reads_the_mxu_share_of_the_launches(ops, share):
    assert harness.load_reader(NAME)(_run(ops)) == pytest.approx(share)


def test_reader_counts_launches_on_every_device_plane():
    run = _run(["support_count_pallas"])
    run.trace.device_ops["/device:TPU:1"] = [
        Event("support_count_fused_pallas", 0, MS)]
    assert harness.load_reader(NAME)(run) == pytest.approx(0.5)


def test_fixture_mine_on_the_ref_plane_reads_nothing(monkeypatch, tmp_path):
    # with no chip the fixture cell counts on the jitted reference, which
    # launches no support_count kernel: the line leaves the metric out
    fx.use_fixture_files(monkeypatch, tmp_path)
    line = fx.run(fx.fixture_benchmark(), fx.MINE, trace=True)
    assert line["correct"] is True
    assert NAME not in line["metrics"]


def test_reader_finds_nothing_without_a_kernel_or_a_trace():
    read = harness.load_reader(NAME)
    assert read(_run(["intersect_count_pallas", "convert"])) is None
    assert read(_run(["support_count_pallas"], traced=0)) is None
    untraced = harness.RunRecord(loop=NS(traced=1), trace=None,
                                 trace_window_s=0.0, peak=None)
    assert read(untraced) is None
