"""A later change adds a configuration, a traffic mix and a per-layer
metric by adding files and entries only: the fixture does exactly that
and the harness runs the new cells and reports the new metric."""
import os

import bench_fixtures as fx
from bench_fixtures import harness


def test_new_files_are_found_by_name(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    bench = fx.fixture_benchmark()
    cell = harness.find_cell(bench, fx.SERVE)
    assert cell.cfg["name"] == "quest-tiny"
    assert cell.spec["rate_qps"] == 200
    assert [m["name"] for m in harness.per_layer_metrics(bench, fx.MINE)
            ][-1] == "mines_in_window"
    assert harness.load_reader("mines_in_window") is not None
    # the benchmark's own directories are untouched
    for sub in ("configs", "traffic", "metrics"):
        assert not os.path.exists(os.path.join(fx.BENCH, sub,
                                               "quest-tiny.json"))
    assert not os.path.exists(os.path.join(fx.BENCH, "metrics",
                                           "mines_in_window.py"))


def test_added_serving_cell_runs_and_is_correct(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    line = fx.run(fx.fixture_benchmark(), fx.SERVE)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 200
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_qps", "setup_s"}
    assert set(line["checks"]) == {"support_mismatch", "rule_mismatch",
                                   "topk_mismatch", "unanswered"}
