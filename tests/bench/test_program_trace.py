"""bench/program_trace.py: the program's own spans, read from a CPU
profiler trace of a small ``WaveServer``; device time by program scope
and idle time by program span, on hand-made events and on a trace
recorded on a TPU v5e (Caps-EN3 serving waves, trimmed)."""
import gc
import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import program_trace as pt
from bench import trace

DATA = pathlib.Path(__file__).parent / "data"
FIXTURE = DATA / "mn1-serve-tail.trace.json.gz"
# four Caps-EN3 128-lane waves of a traced en3-serve-rate window on one
# v5e, around its longest idle gap, with program spans and op scopes
SCOPED = DATA / "en3-serve-rate.trace.json.gz"


def test_base_reduction_is_left_as_it_is():
    # the reduction of busy time, op self time and gaps of the first chip
    # benchmark, digested: reading program spans and scopes moves none
    events = trace.read_saved(str(FIXTURE))
    s = pt.reduce(events)
    d = s["devices"]["/device:TPU:0"]
    blob = json.dumps([d["busy_ns"], sorted(d["op_ns"].items()), d["gaps"]])
    assert hashlib.sha256(blob.encode()).hexdigest() == (
        "5f2d88ed8a6a3457a80fc8ec84897e9edca0e792cd2363a0e7b63874245ba3be")
    assert d["busy_ns"] == 6452346.0 and len(d["gaps"]) == 301
    assert {k: v for k, v in s.items() if k != "program"} == \
        trace.reduce(events)
    assert "scope_ns" not in d and s["program"] == []
    assert pt.device_scopes(s) == []


def test_scope_is_the_innermost_program_scope_in_the_name_stack():
    assert pt.scope_of("jit(wave)/capsnet.routing/capsnet.encode/"
                       "capsnet.encode/mul") == "capsnet.encode"
    assert pt.scope_of("jit(wave)/capsnet.routing/jit(routing_procedure"
                       "_fused)/routing.relayout/reshape") == \
        "routing.relayout"
    assert pt.scope_of("jit(train_step)/transpose(jvp(capsnet.encode))/"
                       "conv_general_dilated") == "capsnet.encode"
    assert pt.scope_of("jit(train_step)/opt_state.mu['digit']") is None


def _scoped_events():
    # device: a while loop [0, 100) holding two ops, then idle [100, 160),
    # then one op [160, 200)
    dev = [("while", 0, 100), ("conv", 10, 30), ("routing_procedure_fused",
                                                   50, 40),
           ("copy", 160, 40)]
    return {
        "devices": {"/device:TPU:0": dev},
        "host": [("bench.step", 0, 210), ("bench.unpack", 95, 70)],
        "scopes": {"/device:TPU:0": ["capsnet.routing", "capsnet.encode",
                                     "capsnet.routing", "unscoped"]},
        # the wave thread: a step with pack, guard; a collection in the gap
        "program": [
            ("serve.step", 0, 200, "w", {"wave": 0, "lanes": 8}),
            ("serve.launch", 5, 10, "w", {}),
            ("serve.guard", 20, 100, "w", {}),
            ("serve.unpack", 120, 50, "w", {}),
            ("host.gc", 130, 10, "main", {"generation": 2}),
            ("serve.pack", 195, 5, "w", {}),
        ]}


def test_scope_self_time_and_idle_time_by_span():
    s = pt.reduce(_scoped_events(), window=(0, 200))
    d = s["devices"]["/device:TPU:0"]
    # the while loop's self time stays with its scope, its body's with theirs
    assert d["scope_ns"] == {"capsnet.routing": 70, "capsnet.encode": 30,
                             "unscoped": 40}
    assert pt.device_scopes(s)[0] == ["capsnet.routing", 70e-9]
    # idle [100, 160): guard to 120, unpack to 130, gc to 140, unpack to 160
    idle = dict(pt.idle_by_span(s))
    assert idle == pytest.approx({"serve.guard": 20e-9,
                                  "serve.unpack": 30e-9,
                                  "host.gc": 10e-9, "no span": 0.0})
    steps = pt.window_spans(s, "serve.step")
    assert [sp[0] for sp in steps] == ["serve.step"]
    assert pt.child_ns(s, steps, "serve.guard") == [100]
    out = pt.breakdown(s, units=1)
    assert out["serve_host_ms"] == pytest.approx(100e-6)
    assert out["serve_pack_ms"] == pytest.approx(5e-6)
    assert out["host_gc_ms_per_s"] == pytest.approx(1e3 * 10 / 200)
    assert out["scope_ms_per_unit"]["capsnet.encode"] == pytest.approx(30e-6)


class _Doubler:
    """A numpy workload for ``WaveServer``: payloads doubled."""

    def validate(self, items):
        return [np.float32(x) for x in items]

    def make_wave_fn(self, cfg):
        return lambda x: 2.0 * x

    def make_reference_wave_fn(self, cfg):
        return None

    def pack(self, payloads, cfg):
        out = np.zeros((cfg.wave_lanes,), np.float32)
        out[:len(payloads)] = payloads
        return out

    def unpack(self, out, n):
        return [float(v) for v in out[:n]]

    def finite(self, out):
        return bool(np.isfinite(out).all())


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """A CPU profiler trace of three waves of a small ``WaveServer``, one
    explicit garbage collection and one compile, read by
    ``program_trace.load``."""
    import jax
    from repro.runtime import tracing
    from repro.runtime.wave_serve import ServeConfig, WaveServer
    log_dir = str(tmp_path_factory.mktemp("served") / "trace")
    server = WaveServer(_Doubler(), cfg=ServeConfig(microbatch=2,
                                                    n_micro=2))
    tracing.watch_gc()
    tracing.watch_gc()
    gc.disable()              # no collection but the one made below
    try:
        with trace.capture(log_dir):
            server.submit([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0])
            done = server.drain()
            gc.collect()
            jax.jit(lambda x: x * 3.0 + 1.0)(np.float32(2.0))
    finally:
        gc.enable()
    assert [c.pred for c in done] == [2.0 * x for x in range(1, 10)]
    return pt.load(log_dir)


def test_serve_step_nests_its_phases_in_order(served_trace):
    spans = [sp for sp in served_trace["program"]
             if sp[0].startswith("serve.")]
    steps = [sp for sp in spans if sp[0] == "serve.step"]
    assert [sp[4] for sp in steps] == [{"wave": 0, "lanes": 4},
                                       {"wave": 1, "lanes": 4},
                                       {"wave": 2, "lanes": 1}]
    for name, start, dur, thread, _ in steps:
        inner = [sp for sp in spans if sp[0] != "serve.step"
                 and start <= sp[1] and sp[1] + sp[2] <= start + dur]
        assert [sp[0] for sp in inner] == [
            "serve.pack", "serve.launch", "serve.guard", "serve.unpack",
            "serve.complete"]
        assert {sp[3] for sp in inner} == {thread}
        assert all(a[1] + a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    assert len(spans) == 3 * 6


def test_one_collection_is_one_gc_span(served_trace):
    collections = [sp for sp in served_trace["program"]
                   if sp[0] == "host.gc"]
    assert len(collections) == 1
    assert collections[0][4] == {"generation": 2}


def test_a_compile_is_a_compile_span(served_trace):
    compiles = [sp for sp in served_trace["program"] if sp[0] == "compile"]
    assert len(compiles) >= 1
    assert not any(sp[0].startswith("backend_compile")
                   for sp in served_trace["program"])


@pytest.fixture(scope="module")
def scoped():
    events = trace.read_saved(str(SCOPED))
    steps = [sp for sp in events["program"] if sp[0] == "serve.step"]
    window = (steps[0][1], steps[-1][1] + steps[-1][2])
    return pt.reduce(events, window=window)


def test_recorded_scopes_cover_the_device_time(scoped):
    d = scoped["devices"]["/device:TPU:0"]
    assert d["busy_ns"] == 18082412.0
    assert sum(d["scope_ns"].values()) == pytest.approx(d["busy_ns"])
    assert d["scope_ns"] == {"capsnet.routing": 10069057.0,
                             "capsnet.encode": 6379558.0,
                             "routing.relayout": 1599976.0,
                             "unscoped": 33821.0}
    # the on-chip Fig. 4 split: every op but 0.2% under a program scope
    scopes = pt.device_scopes(scoped)
    assert [name for name, _ in scopes] == [
        "capsnet.routing", "capsnet.encode", "routing.relayout", "unscoped"]
    assert scopes[-1][1] / sum(v for _, v in scopes) < 0.01


def test_recorded_idle_time_falls_under_program_spans(scoped):
    idle = dict(pt.idle_by_span(scoped))
    gaps = scoped["devices"]["/device:TPU:0"]["gaps"]
    assert sum(idle.values()) == pytest.approx(
        sum(e - s for s, e in gaps) / 1e9)
    # the longest gap is a full (generation 2) collection inside the pack
    assert idle["host.gc"] == pytest.approx(0.045719657)
    assert [sp[4] for sp in scoped["program"] if sp[0] == "host.gc"] == [
        {"generation": 2}, {"generation": 0}]
    assert max(idle, key=idle.get) == "host.gc"
    assert idle["no span"] / sum(idle.values()) < 0.01
    assert trace.idle_gaps(scoped, 1)[0][0] == "bench.pack"


def test_recorded_wave_spans_give_host_and_pack_time(scoped):
    steps = pt.window_spans(scoped, "serve.step")
    assert [sp[4]["wave"] for sp in steps] == [220, 221, 222, 223]
    assert pt.child_ns(scoped, steps, "serve.guard") == [
        6001660.0, 5863610.0, 5604620.0, 5734951.0]
    assert pt.child_ns(scoped, steps, "serve.pack") == [
        2038780.0, 47779390.0, 2276000.0, 1781140.0]


def test_op_scopes_come_from_the_event_metadata(tmp_path):
    # a device plane as the TPU writes it: the name stack is the ``tf_op``
    # stat of the op's metadata; an op XLA deduplicated into a loop body
    # carries only the name of the op it repeats, in the same program
    space = pt._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "program_id"),
                      (3, "deduplicated_name")):
        plane.stat_metadata.add(key=key).value.name = name
    ops = {10: ("%copy.25 = f32[8,1152,62,16] copy(...)",
                "jit(wave)/capsnet.routing/while:", None),
           11: ("%copy.56 = f32[8,1152,62,16] copy(...)", None, "copy.25"),
           12: ("%fusion.9 = f32[8,1152,62,16] fusion(...)",
                "jit(wave)/capsnet.routing/while/body/closed_call/"
                "capsnet.encode/capsnet.encode/dot_general:", None),
           13: ("%while = (s32[]) while(...)", None, None),
           14: ("%copy.57 = f32[8,1152,992] copy(...)", None, "copy.26")}
    for key, (name, stack, dedup) in ops.items():
        md = plane.event_metadata.add(key=key).value
        md.name = name
        md.stats.add(metadata_id=2, int64_value=7)
        if stack:
            md.stats.add(metadata_id=1, str_value=stack)
        if dedup:
            md.stats.add(metadata_id=3, str_value=dedup)
    line = plane.lines.add(name="XLA Ops")
    for key in (10, 11, 12, 13, 14, 12):
        line.events.add(metadata_id=key)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert pt._op_scopes(str(path)) == {"/device:TPU:0": [
        "capsnet.routing", "capsnet.routing", "capsnet.encode", "unscoped",
        "unscoped", "capsnet.encode"]}


def test_script_reads_a_served_window_on_the_cpu(monkeypatch, capsys):
    # the script's own path through a cell's driver, at the small serving
    # size of the harness's CPU tests: no device plane on the CPU, but the
    # wave thread's spans are read
    import jax
    from bench import registry
    from bench import run as harness
    import _cells
    monkeypatch.setattr(harness, "device_check",
                        lambda cell, peaks: (jax.devices(), _cells.PEAK))
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(registry, "config", _cells._small_config)
    monkeypatch.setattr(registry, "mix", _cells._small_mix)
    assert pt.main(["--workload", "en3-serve-rate", "--seed",
                    str(2 ** 31 + 7), "--seconds", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["workload"] == "en3-serve-rate" and out["correct"]
    assert out["units"] > 0
    assert 0 < out["serve_pack_ms"] < out["serve_host_ms"]
    assert out["host_gc_ms_per_s"] >= 0
    assert out["device_scopes"] == [] and out["idle_by_span"] == []
