"""The control: the plain reference in the system's place, in bfloat16,
reads as wrong through the whole harness (at a size a test can hold; the
chip readings at the cells' own sizes are in PERF.md)."""
import bench_fixtures as fx
from mba_bench import control


def test_mine_control_reads_wrong(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    with control.mine_control():
        line = fx.run(fx.fixture_benchmark(), fx.MINE)
    assert line["correct"] is False
    assert line["checks"]["support_mismatch"]["value"] > 0
    assert line["failed"] == line["attempted"]


def test_serve_control_reads_wrong(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    with control.serve_control():
        line = fx.run(fx.fixture_benchmark(), fx.SERVE)
    assert line["correct"] is False
    assert line["checks"]["topk_mismatch"]["value"] > 0
    assert line["checks"]["support_mismatch"]["value"] == 0
