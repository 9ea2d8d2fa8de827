"""Profiler trace -> device busy/idle time, per-op time and named idle gaps.

A run with ``--trace 1`` records a JAX profiler trace of its window
(``capture``).  ``load`` keeps only what the reduction needs, as plain
lists: for each device plane (``/device:TPU:<n>``) the events of its
``XLA Ops`` line, and the benchmark's own host spans (``bench.*``
TraceAnnotations).  Host and device events share the trace's clock (ns).
``reduce`` then computes, per device:

* busy_ns: the length of the union of the op intervals in the window;
* op self time by op name (an op's duration less the ops nested in it,
  so a ``while`` loop does not count its body twice);
* idle gaps: the stretches of the window with no op running, each named
  by the shortest host span open at its middle (``bench.window`` alone
  means no benchmark call was in flight).

The op name is the HLO instruction name without its ``.<n>`` suffix
(``%routing_procedure_fused.5 = ...`` -> ``routing_procedure_fused``).
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import shutil
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"     # the measured window, when a run marks it
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s|=|$)")


def op_name(text: str) -> str:
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the block with the Python tracer off (it would trace every
    Python call) and host annotations on."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> dict:
    """Read the trace a ``capture`` wrote into the plain form ``reduce``
    takes: {"devices": {plane: [event, ...]}, "host": [event, ...]}."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {log_dir}, "
                                f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def save(events: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_saved(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Duration of each op less that of the ops nested inside it."""
    out: Dict[str, float] = {}
    stack: List[List] = []            # [name, end, child_ns]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            n, _, child = stack.pop()
            out[n] = out.get(n, 0.0) + child
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for n, _, child in stack:
        out[n] = out.get(n, 0.0) + child
    return out


def _clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce(events: dict, window: Tuple[float, float] | None = None) -> dict:
    """Reduce a loaded trace.  ``window`` (ns) defaults to the benchmark's
    ``bench.window`` span, else the stretch from the first to the last
    benchmark span."""
    host = events["host"]
    marked = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if window is None and marked:
        window = marked[0]
    if window is None:
        if not host:
            raise ValueError("trace holds no benchmark span to set its "
                             "window")
        window = (min(s for _, s, _ in host),
                  max(s + d for _, s, d in host))
    t0, t1 = window
    per_device = {}
    for plane, evs in sorted(events["devices"].items()):
        evs = _clip(evs, t0, t1)
        busy = _union([(s, s + d) for _, s, d in evs])
        gaps, cursor = [], t0
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < t1:
            gaps.append((cursor, t1))
        per_device[plane] = {
            "busy_ns": sum(e - s for s, e in busy),
            "op_ns": _self_times(evs),
            "gaps": gaps,
        }
    return {"window_ns": t1 - t0, "window": window, "devices": per_device,
            "host": _clip(host, t0, t1)}


def busy_fraction(summary: dict) -> float:
    """Busy time over the window, averaged over the devices."""
    devs = summary["devices"].values()
    return (sum(d["busy_ns"] for d in devs) / len(devs)
            / summary["window_ns"])


def op_seconds(summary: dict, names: Sequence[str] | None = None
               ) -> Dict[str, float]:
    """Self time by op name in seconds, averaged over the devices;
    ``names`` keeps only those ops."""
    total: Dict[str, float] = {}
    devs = list(summary["devices"].values())
    for d in devs:
        for name, ns in d["op_ns"].items():
            if names is None or name in names:
                total[name] = total.get(name, 0.0) + ns / 1e9 / len(devs)
    return total


def top_ops(summary: dict, n: int = 10) -> List[List]:
    ops = op_seconds(summary)
    return [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])
            [:n]]


def _innermost_span(host: Sequence[Event], s: float, e: float) -> str:
    """The shortest benchmark span open at the middle of [s, e)."""
    mid = (s + e) / 2
    open_ = [(hd, name) for name, hs, hd in host if hs <= mid < hs + hd]
    return min(open_)[1] if open_ else "no span"


def idle_gaps(summary: dict, n: int = 10) -> List[List]:
    """The longest idle gaps over all devices, named by host span."""
    gaps = [(e - s, s, e) for d in summary["devices"].values()
            for s, e in d["gaps"]]
    gaps.sort(reverse=True)
    return [[_innermost_span(summary["host"], s, e), dur / 1e9]
            for dur, s, e in gaps[:n]]
