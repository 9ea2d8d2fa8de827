"""Chip benchmark of CapsNet serving and training: one run of one cell.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json, its configuration from
bench/configs/<config>.json and its mix from bench/mixes/<traffic>.json,
and hands them to the mix's driver (bench/drivers/<driver>.py).  Exits
with 2, printing no result, when JAX finds no TPU, fewer chips than the
cell asks for, a device kind that bench/peaks.json does not list, or
Pallas kernels that would run interpreted.

Standard output ends with one JSON line: correct, attempted, failed,
metrics (the cell's end-to-end metrics with --trace 0, its per-layer
metrics with --trace 1), device, with --trace 1 a breakdown, and last the
numbers compared with their limits under "checks".  Those numbers are
also the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

# Writes of the benchmark stay in the checkout.
SCRATCH = REPO / ".bench_run"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def device_check(cell: dict, peaks: dict):
    """The devices and their peaks, or exit 2 before any set-up."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    problem = None
    if dev.platform != "tpu":
        problem = f"JAX platform is {dev.platform!r}, not tpu"
    elif len(devices) < cell["chips"]:
        problem = (f"{len(devices)} chip(s) found, the cell asks for "
                   f"{cell['chips']}")
    elif dev.device_kind not in peaks["devices"]:
        problem = (f"device kind {dev.device_kind!r} is not in "
                   "bench/peaks.json")
    else:
        from repro import kernels
        if kernels.pallas_interpret_mode():
            problem = "Pallas kernels would run interpreted"
    if problem:
        log(f"no run: {problem}")
        sys.exit(2)
    return devices, peaks["devices"][dev.device_kind]


def enable_cache() -> None:
    """The program's fixed compile cache, keeping every program."""
    import jax
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import check, common, registry, trace
    bench = registry.benchmark(REPO)
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(cell["config"])
    mix = registry.mix(cell["traffic"])
    peaks = json.loads((registry.ROOT / "peaks.json").read_text())
    devices, peak = device_check(cell, peaks)

    import jax
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == COMPILE_EVENT else None)
    enable_cache()
    scratch = SCRATCH / args.workload
    scratch.mkdir(parents=True, exist_ok=True)
    ctx = common.Context(
        workload=args.workload, config=cfg, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=T_START,
        chips=cell["chips"], peak=peak, scratch_dir=str(scratch), log=log)
    outcome = registry.driver(mix["driver"]).run(ctx)
    shutil.rmtree(scratch, ignore_errors=True)
    w0, w1 = outcome.counters.get("window", (0.0, 0.0))
    log(f"setup_s {outcome.setup_s!r}; backend compiles {len(compiles)} "
        f"in all, {sum(w0 <= t <= w1 for t in compiles)} in the window")

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if applies(m, args.workload):
                value = registry.metric_reader(m["name"])(outcome, ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, args.workload):
                value = (outcome.setup_s if m["name"] == "setup_s"
                         else outcome.end_to_end[m["name"]])
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": check.verdict(outcome.readings),
              "attempted": outcome.attempted, "failed": outcome.failed,
              "metrics": metrics, "device": device}
    if args.trace:
        window_s = outcome.trace["window_ns"] / 1e9
        device["busy_s"] = trace.busy_fraction(outcome.trace) * window_s
        device["window_s"] = window_s
        result["breakdown"] = {"device_ops": trace.top_ops(outcome.trace),
                               "idle_gaps": trace.idle_gaps(outcome.trace)}
    result["checks"] = check.as_json(outcome.readings)
    for name, value, limit in outcome.readings:
        log(f"check {name} {value!r} limit {limit!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
