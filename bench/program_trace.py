"""The program's own spans and named scopes in a traced window.

``bench/trace.py`` reads a trace's device ops and the benchmark's
``bench.*`` spans.  This module reads, from the same trace, what the
program records itself (``src/repro/runtime/tracing.py``):

* its host spans (``serve.*``, ``host.gc``, and JAX's
  ``backend_compile*``, kept as ``compile``), with their thread and args;
* each device op's program scope: the innermost of ``SCOPES`` in the op's
  name stack (``jax.named_scope``), read from the ``tf_op`` stat of the
  op's event metadata.

``reduce`` is ``trace.reduce`` with, per device, ``scope_ns`` (op self
time by program scope, else ``unscoped``) and the program spans that
overlap the window.  ``device_scopes`` breaks the device's busy time down
by scope, ``idle_by_span`` its idle time by what the host was doing.

``bench/run.py`` does not read it yet (PERF.md, Open questions).  Run as a
script, it runs one cell with its window traced, through the cell's
driver, and prints that breakdown as one JSON line:

    python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s>
"""
from __future__ import annotations

import glob
import json
import os
import pathlib
import re
import shutil
import sys
import time
from typing import Dict, List, Sequence, Tuple

if __name__ == "__main__":
    _REPO = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_REPO), str(_REPO / "src")]

from bench import trace  # noqa: E402

# (name, start_ns, duration_ns, thread, args)
Span = Tuple[str, float, float, str, dict]
PROGRAM_SPANS = ("serve.", "host.")
COMPILE_SPAN = "backend_compile"      # JAX's own; kept as "compile"
# The program's named scopes (src/repro/runtime/tracing.py).
SCOPES = ("capsnet.encode", "capsnet.routing", "capsnet.decoder",
          "routing.relayout", "train.clip", "train.adamw")
UNSCOPED = "unscoped"
NO_SPAN = "no span"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+")


def scope_of(name_stack: str) -> str | None:
    """The innermost of ``SCOPES`` in an op's name stack: the one that
    occurs last (``jit(wave)/capsnet.routing/capsnet.encode/mul`` ->
    ``capsnet.encode``; a backward op keeps its forward scope,
    ``transpose(jvp(capsnet.encode))/...``)."""
    found = [(name_stack.rfind(s), s) for s in SCOPES if s in name_stack]
    return max(found)[1] if found else None


def _xspace_class():
    """A protobuf class for the fields of the trace file (``XSpace``,
    tsl/profiler/protobuf/xplane.proto) that hold each device op's name
    stack: ``jax.profiler.ProfileData`` gives an event's own stats but not
    those of its metadata, where the ``tf_op`` stat sits."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    messages = {
        "XStat": [(1, "metadata_id", F.TYPE_INT64, one),
                  (3, "uint64_value", F.TYPE_UINT64, one),
                  (4, "int64_value", F.TYPE_INT64, one),
                  (5, "str_value", F.TYPE_STRING, one),
                  (7, "ref_value", F.TYPE_UINT64, one)],
        "XStatMetadata": [(2, "name", F.TYPE_STRING, one)],
        "XEventMetadata": [(2, "name", F.TYPE_STRING, one),
                           (5, "stats", "XStat", many)],
        "XEvent": [(1, "metadata_id", F.TYPE_INT64, one)],
        "XLine": [(2, "name", F.TYPE_STRING, one),
                  (4, "events", "XEvent", many)],
        # map<int64, ...> fields, read as their wire form: key 1, value 2
        "EventMetadataEntry": [(1, "key", F.TYPE_INT64, one),
                               (2, "value", "XEventMetadata", one)],
        "StatMetadataEntry": [(1, "key", F.TYPE_INT64, one),
                              (2, "value", "XStatMetadata", one)],
        "XPlane": [(2, "name", F.TYPE_STRING, one),
                   (3, "lines", "XLine", many),
                   (4, "event_metadata", "EventMetadataEntry", many),
                   (5, "stat_metadata", "StatMetadataEntry", many)],
        "XSpace": [(1, "planes", "XPlane", many)],
    }
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for name, fields in messages.items():
        m = fd.message_type.add(name=name)
        for number, field, kind, label in fields:
            f = m.field.add(name=field, number=number, label=label)
            if isinstance(kind, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _instruction(text: str) -> str:
    """``%copy.56 = f32[...] copy(...)`` -> ``copy.56``."""
    return text.split(" ", 1)[0].lstrip("%")


def _plane_scopes(plane) -> List[str]:
    """The program scope of each event of a device plane's ``XLA Ops``
    line.  An op without a name stack of its own that XLA deduplicated
    (an op in a loop body repeating one outside it; stat
    ``deduplicated_name``) takes that of the op it repeats, in the same
    program."""
    stat_names = {e.key: e.value.name for e in plane.stat_metadata}
    info = {}                       # metadata id -> (program, name, stats)
    for entry in plane.event_metadata:
        md = entry.value
        stats = {}
        for st in md.stats:
            key = stat_names.get(st.metadata_id)
            stats[key] = (st.str_value or stat_names.get(st.ref_value)
                          or st.int64_value or st.uint64_value)
        info[entry.key] = (stats.get("program_id"), _instruction(md.name),
                           stats)
    stack = {(prog, name): stats.get("tf_op")
             for prog, name, stats in info.values() if stats.get("tf_op")}

    scopes = {}
    for mid, (prog, _, stats) in info.items():
        ops = stats.get("tf_op") or stack.get(
            (prog, stats.get("deduplicated_name")))
        scopes[mid] = (scope_of(ops) if ops else None) or UNSCOPED
    return [scopes.get(e.metadata_id, UNSCOPED)
            for line in plane.lines if line.name == "XLA Ops"
            for e in line.events]


def _op_scopes(path: str) -> Dict[str, List[str]]:
    """Each device plane's op scopes, in the order of its ``XLA Ops``
    events."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    return {plane.name: _plane_scopes(plane) for plane in space.planes
            if DEVICE_PLANE.fullmatch(plane.name)}


def _program_span(event, thread: str) -> Span | None:
    name = event.name
    if name.startswith(COMPILE_SPAN):
        name = "compile"
    elif not name.startswith(PROGRAM_SPANS):
        return None
    args = {k: v for k, v in event.stats if not k.startswith("_")}
    return (name, event.start_ns, event.duration_ns, thread, args)


def load(log_dir: str) -> dict:
    """``trace.load`` of the trace a ``trace.capture`` wrote, with
    "scopes": {plane: [scope, ...]} (one per device event) and
    "program": [span, ...]."""
    import jax
    events = trace.load(log_dir)
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    scopes = _op_scopes(path)
    for plane, evs in events["devices"].items():
        if len(scopes.get(plane, [])) != len(evs):
            raise ValueError(f"{plane}: the trace file's ops do not match "
                             "the profiler's")
    program: List[Span] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(sp for sp in (_program_span(e, line.name)
                                             for e in line.events)
                               if sp is not None)
    return {**events,
            "scopes": {p: scopes[p] for p in events["devices"]},
            "program": sorted(program, key=lambda sp: sp[1])}


def reduce(events: dict, window: Tuple[float, float] | None = None) -> dict:
    """``trace.reduce``, whose numbers it leaves as they are, with each
    device's ``scope_ns`` where the trace holds scopes, and "program": the
    program spans that overlap the window."""
    summary = trace.reduce(events, window)
    t0, t1 = summary["window"]
    for plane, scopes in events.get("scopes", {}).items():
        scoped = [(sc, s, d) for (_, s, d), sc in
                  zip(events["devices"][plane], scopes)]
        summary["devices"][plane]["scope_ns"] = trace._self_times(
            trace._clip(scoped, t0, t1))
    summary["program"] = [sp for sp in events.get("program", [])
                          if sp[1] < t1 and sp[1] + sp[2] > t0]
    return summary


def scope_seconds(summary: dict) -> Dict[str, float]:
    """Device self time by program scope in seconds, averaged over the
    devices; empty where the trace holds no scopes."""
    total: Dict[str, float] = {}
    devs = list(summary["devices"].values())
    for d in devs:
        for scope, ns in d.get("scope_ns", {}).items():
            total[scope] = total.get(scope, 0.0) + ns / 1e9 / len(devs)
    return total


def device_scopes(summary: dict) -> List[List]:
    return [[k, v] for k, v in sorted(scope_seconds(summary).items(),
                                      key=lambda kv: -kv[1])]


def window_spans(summary: dict, name: str) -> List[Span]:
    """The program's ``name`` spans that start inside the window."""
    t0, t1 = summary["window"]
    return [sp for sp in summary["program"]
            if sp[0] == name and t0 <= sp[1] < t1]


def child_ns(summary: dict, parents: Sequence[Span],
             child: str) -> List[float]:
    """For each parent span, the summed duration of the program's
    ``child`` spans that start inside it on its thread."""
    kids = [sp for sp in summary["program"] if sp[0] == child]
    return [sum(k[2] for k in kids if k[3] == thread and s <= k[1] < s + d)
            for _, s, d, thread, _ in parents]


def _span_label(open_: Sequence[Span]) -> str:
    """What the host was doing under a set of open program spans:
    ``host.gc``, else ``compile``, else the innermost (latest started)
    ``serve.*`` span, else ``no span``."""
    names = {sp[0] for sp in open_}
    for name in ("host.gc", "compile"):
        if name in names:
            return name
    serve = [sp for sp in open_ if sp[0].startswith("serve.")]
    return max(serve, key=lambda sp: sp[1])[0] if serve else NO_SPAN


def _span_segments(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The timeline of ``_span_label`` over the spans, as (start, end,
    label) pieces between consecutive span boundaries."""
    bounds = sorted({t for sp in spans for t in (sp[1], sp[1] + sp[2])})
    starts = sorted(spans, key=lambda sp: sp[1])
    out, open_, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][1] <= a:
            open_.append(starts[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] + sp[2] > a]
        out.append((a, b, _span_label(open_)))
    return out


def idle_by_span(summary: dict) -> List[List]:
    """Device idle seconds by what the host was doing (``_span_label``),
    averaged over the devices; each stretch of a gap takes the label of
    the program spans open during it."""
    segments = _span_segments(summary["program"])
    total: Dict[str, float] = {}
    devs = list(summary["devices"].values())
    for d in devs:
        j = 0
        for s, e in d["gaps"]:
            covered = 0.0
            while j < len(segments) and segments[j][1] <= s:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < e:
                a, b = max(segments[k][0], s), min(segments[k][1], e)
                if b > a:
                    label = segments[k][2]
                    total[label] = total.get(label, 0.0) + (b - a)
                    covered += b - a
                k += 1
            # what no span reaches: before the first, after the last
            total[NO_SPAN] = total.get(NO_SPAN, 0.0) + (e - s - covered)
    return [[k, v / 1e9 / len(devs)]
            for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


def breakdown(summary: dict, units: int) -> dict:
    """What the program's spans and scopes say of a window of ``units``
    waves or train steps: device time by scope (s, and ms per unit), idle
    time by span, collection time, and of a serving window the mean host
    and pack milliseconds of a wave (``serve.step`` less its
    ``serve.guard``, which holds the wait for the device)."""
    t0, t1 = summary["window"]
    gc_ns = sum(min(s + d, t1) - max(s, t0)
                for name, s, d, *_ in summary["program"] if name == "host.gc")
    out = {"units": units,
           "device_scopes": device_scopes(summary),
           "scope_ms_per_unit": {k: 1e3 * v / units for k, v in
                                 device_scopes(summary)},
           "idle_by_span": idle_by_span(summary),
           "host_gc_ms_per_s": 1e3 * gc_ns / (t1 - t0)}
    steps = window_spans(summary, "serve.step")
    if steps:
        guard = child_ns(summary, steps, "serve.guard")
        pack = child_ns(summary, steps, "serve.pack")
        out["serve_host_ms"] = 1e-6 * (sum(sp[2] for sp in steps)
                                       - sum(guard)) / len(steps)
        out["serve_pack_ms"] = 1e-6 * sum(pack) / len(steps)
    return out


def main(argv=None) -> int:
    from bench import check, common, registry
    from bench import run as harness
    from repro.runtime import tracing
    t_start = time.perf_counter()
    args = harness.parse(argv)
    cell = registry.cell(registry.benchmark(harness.REPO), args.workload)
    mix = registry.mix(cell["traffic"])
    peaks = json.loads((registry.ROOT / "peaks.json").read_text())
    _, peak = harness.device_check(cell, peaks)
    harness.enable_cache()
    scratch = harness.SCRATCH / f"{args.workload}.program"
    scratch.mkdir(parents=True, exist_ok=True)
    ctx = common.Context(
        workload=args.workload, config=registry.config(cell["config"]),
        mix=mix, seed=args.seed, seconds=args.seconds, trace=True,
        t_start=t_start, chips=cell["chips"], peak=peak,
        scratch_dir=str(scratch), log=harness.log)
    tracing.watch_gc()
    outcome = registry.driver(mix["driver"]).run(ctx)
    summary = reduce(load(str(scratch / "trace")))
    shutil.rmtree(scratch, ignore_errors=True)
    units = outcome.counters.get("waves") or outcome.counters.get("steps")
    print(json.dumps({"workload": args.workload,
                      "correct": check.verdict(outcome.readings),
                      **breakdown(summary, units)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
