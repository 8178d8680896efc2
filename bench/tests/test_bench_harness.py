"""The harness on the CPU: BENCHMARK.json keeps the benchmark contract,
every name resolves to its file, a non-TPU platform is refused, and a
run's last line has the keys the benchmark contract names."""
import json
import os
import re

import pytest

import bench_fixtures as fx
from bench_fixtures import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def _line_ok(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_the_contract(bench):
    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line_ok(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51

    configs = {c["name"]: c for c in bench["configs"]}
    assert len(configs) == len(bench["configs"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["source"])
        assert _line_ok(c["why"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]

    cells = {}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line_ok(w["why"])
        cells[w["name"]] = w
    assert len(cells) == len(bench["workloads"])
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)
    assert {w["config"] for w in cells.values()} == set(configs)
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)

    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and _line_ok(m["layer"])
        assert m["moves"] in e2e
        for cell in m.get("workloads", ()):
            assert cell in cells
            assert m["moves"] in {x["name"] for x in
                                  harness.end_to_end_metrics(bench, cell)}
    for cell in cells:
        reported = {m["name"] for m in harness.end_to_end_metrics(bench,
                                                                   cell)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.per_layer_metrics(bench, cell)


def test_every_name_resolves_to_its_file(bench):
    from mba_bench import traffic
    for w in bench["workloads"]:
        cell = harness.find_cell(bench, w["name"])
        assert cell.cfg["name"] == w["config"]
        assert cell.spec["kind"] in traffic.KINDS
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    with pytest.raises(KeyError):
        harness.load_config("no-such-config")
    with pytest.raises(KeyError):
        harness.load_reader("no_such_metric")
    with pytest.raises(KeyError):
        harness.find_cell(bench, "no-such-cell")


def test_non_tpu_platform_is_refused(capsys):
    with pytest.raises(harness.NoChip):
        harness.require_devices(1)
    import run as entry
    assert entry.main(["--workload", "t10i4-mine", "--seed", "1",
                       "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_last_line_keys_and_end_to_end_metrics(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    line = fx.run(fx.fixture_benchmark(), fx.MINE)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"mine_s", "setup_s"}
    assert line["metrics"]["mine_s"]["unit"] == "s"
    assert line["metrics"]["mine_s"]["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    assert line["checks"] == {"support_mismatch": {"value": 0, "limit": 0},
                              "rule_mismatch": {"value": 0, "limit": 0}}
    json.dumps(line)


def test_traced_line_reports_per_layer_metrics(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    line = fx.run(fx.fixture_benchmark(), fx.MINE, trace=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["correct"] is True
    # host-side readers read; device readers find no chip and stay silent
    assert {"rules_host_s", "candgen_host_s", "h2d_mb_per_mine",
            "mines_in_window"} == set(line["metrics"])
    assert line["metrics"]["h2d_mb_per_mine"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_serving_line(monkeypatch, tmp_path):
    fx.use_fixture_files(monkeypatch, tmp_path)
    line = fx.run(fx.fixture_benchmark(), fx.SERVE, trace=True)
    assert line["correct"] is True
    # 200 requests are too few for the p99 reader; no chip, no device ones
    assert set(line["metrics"]) == {"serve_batch_fill", "serve_score_ms"}
    assert 0 < line["metrics"]["serve_batch_fill"]["value"] <= 1
