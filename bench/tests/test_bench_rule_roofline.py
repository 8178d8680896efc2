"""The rule-rich serving cell and the reader of ``rule_match_roofline``:
the least time of the traced span's scoring steps (``rule_work.py``) over
the device time of the ops named ``rule_scores*``.  The reader finds
nothing (rather than raising) where the trace holds no such op, as on a
plane that scores with the jitted reference."""
from types import SimpleNamespace as NS

import pytest

import bench_fixtures as fx  # noqa: F401  (puts bench/ on the path)
from bench_fixtures import harness
from mba_bench import rule_work, work
from mba_bench.peaks import peaks
from mba_bench.trace import Event, Trace

NAME = "rule_match_roofline"
CELL = "t10i4-serve-rich"
V5E = peaks("TPU v5 lite")
US = 1e3  # ns


def test_work_count_at_a_hand_computed_step():
    # 64 baskets against the 0.5% index's 6,186 rows over 197 items
    ops, nbytes = rule_work.rule_match_work(64, 6186, 197)
    assert ops == 2 * 64 * 197 * 6186 == 155_986_176
    assert nbytes == 1_576 + 152_330.25 + 24_744
    # compute-bound at the int8 peak: 0.397 us against 0.218 us of bytes
    assert work.least_seconds([(ops, nbytes)], V5E) \
        == pytest.approx(155_986_176 / 393e12)


def _index():
    """Three true rows over items {1, 2, 3} -> {5, 6}, padded to 128."""
    from repro.core.rules import Rule
    from repro.serving import RuleIndex
    rules = [Rule((1, 2), (5,), 0.01, 0.9, 1.5),
             Rule((3,), (5, 6), 0.02, 0.7, 1.2)]
    return RuleIndex.build(rules, 1000)


def test_antecedent_items_leave_consequent_only_items_out():
    index = _index()
    assert (index.n_rows, index.n_rows_padded) == (3, 128)
    assert rule_work.antecedent_items(index) == 3      # {1, 2, 3}, not 5, 6


def _step(t_start, misses):
    return NS(t_start=t_start, n_misses=misses)


def _run(ops, steps, span=(10.0, 13.0), peak=V5E):
    """A traced serving run whose one device plane ran ``ops`` (name,
    microseconds) in turn."""
    dev, t = [], 0.0
    for name, us in ops:
        dev.append(Event(name, t, us * US))
        t += us * US
    loop = NS(index=_index(), steps=steps, trace_span=span)
    return harness.RunRecord(
        loop=loop, trace=Trace(device_ops={"/device:TPU:0": dev},
                               host_events=[]),
        trace_window_s=span[1] - span[0], peak=peak)


def test_reader_divides_the_span_steps_least_time_by_kernel_time():
    # steps before and after the span, and a step answered from the
    # result cache alone, are left out
    steps = [_step(9.9, 40), _step(10.0, 20), _step(11.0, 0),
             _step(12.5, 7), _step(13.0, 30)]
    run = _run([("rule_scores_pallas", 30.0), ("convert", 5.0),
                ("rule_scores_fused_pallas", 20.0)], steps)
    i_eff = 3
    least = work.least_seconds([rule_work.rule_match_work(b, 3, i_eff)
                                for b in (20, 7)], V5E)
    assert harness.load_reader(NAME)(run) \
        == pytest.approx(100.0 * least / 50e-6)


def test_reader_finds_nothing_without_a_rule_scores_op():
    read = harness.load_reader(NAME)
    steps = [_step(11.0, 20)]
    assert read(_run([("fusion", 30.0), ("copy", 1.0)], steps)) is None
    assert read(_run([("rule_scores_pallas", 30.0)], [])) is None
    assert read(_run([("rule_scores_pallas", 30.0)], steps,
                     peak=None)) is None
    untraced = harness.RunRecord(loop=NS(), trace=None, trace_window_s=0.0,
                                 peak=V5E)
    assert read(untraced) is None


def test_rich_cell_resolves_and_reports_the_serving_metrics():
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, CELL)
    assert cell.chips == 1 and cell.spec["kind"] == "open_loop"
    assert cell.cfg["mining"]["min_support"] == 0.005
    base = harness.load_config("quest-t10i4d100k")
    # the 1% configuration with its own support, name, source and reason
    for key in ("quest", "generator_seed", "serving", "guarantees"):
        assert cell.cfg[key] == base[key], key
    assert {**cell.cfg["mining"], "min_support": 0.01} == base["mining"]
    assert {m["name"] for m in harness.end_to_end_metrics(bench, CELL)} \
        == {"serve_p50_ms", "serve_qps", "setup_s"}
    per_layer = {m["name"] for m in harness.per_layer_metrics(bench, CELL)}
    assert per_layer == {m["name"] for m in harness.per_layer_metrics(
        bench, "t10i4-serve")} | {NAME}
    for name in per_layer:
        assert callable(harness.load_reader(name))
