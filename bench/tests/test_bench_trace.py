"""Trace reduction, peaks table and roofline work counts, on synthetic
traces and hand-computed values."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mba_bench import peaks, trace, work  # noqa: E402
from mba_bench.trace import Event, Trace  # noqa: E402

MS = 1e6  # ns


def synthetic() -> Trace:
    dev = [Event("support_count_fused_pallas", 0, 10 * MS),
           Event("convert", 5 * MS, 10 * MS),            # overlaps: 0-15 ms
           Event("support_count_pallas", 40 * MS, 20 * MS),   # 40-60 ms
           Event("rule_scores_pallas", 90 * MS, 5 * MS)]      # 90-95 ms
    host = [Event("PjitFunction(_join_prune)", 14 * MS, 30 * MS),
            Event("ExecuteHelper", 16 * MS, 2 * MS),
            Event("$run", 0, 100 * MS)]
    return Trace(device_ops={"/device:TPU:0": dev}, host_events=host)


def test_busy_union_and_idle_share():
    tr = synthetic()
    assert trace.busy_seconds(tr) == pytest.approx(0.040)
    assert trace.idle_share(trace.busy_seconds(tr), 0.100) == \
        pytest.approx(60.0)
    assert trace.idle_share(0.0, 0.0) is None


def test_busy_is_averaged_over_devices():
    tr = synthetic()
    tr.device_ops["/device:TPU:1"] = [Event("x", 0, 20 * MS)]
    assert trace.busy_seconds(tr) == pytest.approx(0.030)


def test_kernel_time_matched_by_name():
    tr = synthetic()
    assert trace.kernel_seconds(tr, ("support_count",)) == \
        pytest.approx(0.030)
    assert trace.kernel_seconds(tr, ("rule_scores",)) == pytest.approx(0.005)
    assert trace.kernel_seconds(tr, ("intersect",)) == 0.0
    assert trace.kernel_seconds(Trace({}, []), ("support_count",)) == 0.0


def test_op_name_drops_operands_and_instance():
    text = ("%support_count_fused_pallas.1 = s32[1,128]{1,0} custom-call("
            "s32[3128,32]{1,0} %convert.2), custom_call_target=\"tpu\"")
    assert trace.op_name(text) == "support_count_fused_pallas"
    assert trace.op_name("%fusion.12 = f32[8] fusion(%rule_scores.1)") == \
        "fusion"
    assert trace.op_name("copy-start") == "copy-start"


def test_top_ops_and_idle_gaps():
    tr = synthetic()
    top = trace.top_device_ops(tr)
    assert top[0][0] == "support_count_pallas"
    gaps = trace.idle_gaps(tr)
    # 15-40 ms (host in _join_prune, the shortest event over 27.5 ms) and
    # 60-90 ms (only the long "$run" covers 75 ms)
    assert [round(g[1], 6) for g in gaps] == [0.030, 0.025]
    assert gaps[0][0] == "host: $run"
    assert gaps[1][0] == "host: PjitFunction(_join_prune)"
    tr.host_events = []
    assert trace.idle_gaps(tr)[0][0] == "after support_count_pallas"


def test_op_counts_over_planes():
    tr = synthetic()
    tr.device_ops["/device:TPU:1"] = [Event("convert", 0, MS)]
    assert trace.op_counts(tr) == {"support_count_fused_pallas": 1,
                                   "convert": 2, "support_count_pallas": 1,
                                   "rule_scores_pallas": 1}


def test_only_planes_with_an_ops_line_are_chips():
    from types import SimpleNamespace as NS

    def ev(name, start, dur):
        return NS(name=name, start_ns=start, duration_ns=dur)
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_f(1)", 0, 100)]),
            NS(name="XLA Ops", events=[
                ev("%support_count_fused_pallas.1 = s32[1] custom-call()",
                   0, 60),
                ev("%copy.3 = s8[2] copy(%support_count_fused_pallas.1)",
                   70, 10)])]),
        NS(name="/device:CUSTOM:Megascale Trace", lines=[]),
        NS(name="/host:CPU", lines=[
            NS(name="main", events=[ev("Execute", 0, 50), ev("mark", 5, 0)])]),
    ]
    tr = trace.reduce_planes(planes)
    assert list(tr.device_ops) == ["/device:TPU:0"]
    assert [e.name for e in tr.device_ops["/device:TPU:0"]] == \
        ["support_count_fused_pallas", "copy"]
    assert trace.busy_seconds(tr) == pytest.approx(70e-9)
    assert trace.kernel_seconds(tr, ("support_count",)) == \
        pytest.approx(60e-9)
    assert [e.name for e in tr.host_events] == ["Execute"]


def test_recorded_cpu_trace_has_host_events_and_no_device(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench-span"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    assert any(e.name == "bench-span" for e in tr.host_events)
    assert tr.device_ops == {}
    assert trace.busy_seconds(tr) == 0.0


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("cpu")


def test_roofline_work_counts():
    # 100 rows, 10 candidates over 8 distinct items
    ops, nbytes = work.support_count_work(100, 10, 8)
    assert ops == 2 * 100 * 8 * 10
    assert nbytes == 100 * 8 / 8 + 10 * 8 / 8 + 4 * 10
    peak = peaks.peaks("TPU v5 lite")
    # compute-bound call and a bandwidth-bound call
    least = work.least_seconds([(393e12, 1.0), (1.0, 819e9)], peak)
    assert least == pytest.approx(2.0)
    assert work.roofline_share(0.5, 2.0) == pytest.approx(25.0)
    assert work.roofline_share(0.5, 0.0) is None
