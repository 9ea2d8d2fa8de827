"""bench/trace.py: device busy/idle time, per-op self time and idle gaps
named by the benchmark's host spans, on hand-made events and on a trace
recorded on a TPU v5e (Caps-MN1 serving waves, trimmed)."""
import pathlib

import pytest

from bench import trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "mn1-serve-tail.trace.json.gz"


def test_op_names_drop_the_instruction_suffix():
    assert trace.op_name("%routing_procedure_fused.5 = f32[8,160] "
                         "custom-call(...)") == "routing_procedure_fused"
    assert trace.op_name("%copy.36 = f32[8,1152,160] copy(...)") == "copy"
    assert trace.op_name("%multiply_reduce_fusion.3 = f32[8,1152]") == \
        "multiply_reduce_fusion"
    assert trace.op_name("%while = (s32[]) while(...)") == "while"


def _events():
    # device: a while loop [0, 100) holding two ops, then idle [100, 160),
    # then one op [160, 200); host spans cover the idle stretch
    dev = [("while", 0, 100), ("conv", 10, 30), ("routing_procedure_fused",
                                                   50, 40),
           ("copy", 160, 40)]
    host = [("bench.step", 0, 210), ("bench.unpack", 95, 70)]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_busy_union_gaps_and_self_time():
    s = trace.reduce(_events(), window=(0, 200))
    d = s["devices"]["/device:TPU:0"]
    assert d["busy_ns"] == 140
    assert d["gaps"] == [(100, 160)]
    assert d["op_ns"] == {"while": 30, "conv": 30,
                          "routing_procedure_fused": 40, "copy": 40}
    assert trace.busy_fraction(s) == pytest.approx(0.7)
    assert trace.idle_gaps(s) == [["bench.unpack", 60e-9]]
    assert trace.op_seconds(s, ["routing_procedure_fused"]) == {
        "routing_procedure_fused": 40e-9}


def test_window_defaults_to_the_benchmark_spans_and_clips():
    s = trace.reduce(_events())
    assert s["window_ns"] == 210
    assert s["devices"]["/device:TPU:0"]["gaps"][-1] == (200, 210)
    clipped = trace.reduce(_events(), window=(20, 60))
    assert clipped["devices"]["/device:TPU:0"]["busy_ns"] == 40


def test_devices_are_averaged():
    ev = _events()
    ev["devices"]["/device:TPU:1"] = [("copy", 0, 200)]
    s = trace.reduce(ev, window=(0, 200))
    assert trace.busy_fraction(s) == pytest.approx((0.7 + 1.0) / 2)
    assert trace.op_seconds(s)["copy"] == pytest.approx(120e-9)


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(trace.read_saved(str(FIXTURE)))


def test_recorded_trace_busy_union_and_window(recorded):
    # 12 Caps-MN1 32-lane waves on one v5e, from the first step span to
    # the last; the device is busy about a fifth of the window
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    assert recorded["window_ns"] == pytest.approx(35.75206e6)
    assert trace.busy_fraction(recorded) == pytest.approx(0.180475, rel=1e-4)
    dev = recorded["devices"]["/device:TPU:0"]
    assert dev["busy_ns"] + sum(e - s for s, e in dev["gaps"]) == \
        pytest.approx(recorded["window_ns"])


def test_recorded_trace_routing_kernel_time_by_name(recorded):
    ops = trace.op_seconds(recorded)
    # one procedure megakernel per microbatch, 4 per wave, ~31.6 us each
    assert ops["routing_procedure_fused"] == pytest.approx(1.514927e-3)
    assert {"fusion", "copy", "routing_procedure_fused"} <= set(ops)
    assert sum(ops.values()) == pytest.approx(
        recorded["devices"]["/device:TPU:0"]["busy_ns"] / 1e9)
    assert [name for name, _ in trace.top_ops(recorded, 3)] == [
        "fusion", "copy", "routing_procedure_fused"]


def test_recorded_trace_idle_gaps_name_the_host_span(recorded):
    gaps = trace.idle_gaps(recorded)
    assert len(gaps) == 10
    # the device waits while the host is inside WaveServer.step
    assert {name for name, _ in gaps} == {"bench.step"}
    assert gaps[0][1] == pytest.approx(2.28865e-3)
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))


def test_recorded_trace_shows_the_routing_kernel_ran(recorded):
    from bench.common import path_readings
    readings = dict((n, v) for n, v, _ in path_readings(
        "procedure", "procedure", recorded))
    assert readings == {"path_form": 0.0, "kernels_absent": 0.0}
    without = trace.reduce(_events(), window=(0, 200))
    without["devices"]["/device:TPU:0"]["op_ns"].pop(
        "routing_procedure_fused")
    readings = dict((n, v) for n, v, _ in path_readings(
        "procedure", None, without))
    assert readings == {"path_form": 1.0, "kernels_absent": 1.0}
