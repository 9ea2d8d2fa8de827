"""Runs that set the benchmark's numbers; not part of a benchmark run.

    python bench/explore.py readings --workload W --seeds 1,2,3 [--control C]
    python bench/explore.py sweep --workload W --rates 4000,6000 --seconds 3

A cell that BENCHMARK.json does not hold yet is named by ``--config`` and
``--traffic`` beside its ``--workload`` name (one chip).

``readings`` runs the cell once per seed in one process and prints the
numbers compared with their limits (with ``--control bf16``, of the
reference in bfloat16 put in the program's place; ``fewer_iters`` and
``half_batch`` plant a fault in the program): the readings the
limits in bench/configs/*.json were set from.  ``sweep`` runs an
open-loop serving cell at each offered rate and prints the latency and
completion rate at each: the sweep that fixed the serve-tail mix's rate.
Both exit with 2 where bench/run.py would.
"""
import argparse
import json
import sys
import time

import run as harness  # bench/run.py, which puts the checkout on sys.path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config", default=None,
                    help="with --traffic: a cell not in BENCHMARK.json")
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--control", default=None,
                    choices=("bf16", "fewer_iters", "half_batch"))
    ap.add_argument("--ref-precisions", default="",
                    help="readings: also print the gaps against the "
                         "reference at these matmul precisions")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--save-trace", default=None,
                    help="readings: trace each run, save the loaded trace "
                         "of the last one here (.json.gz)")
    args = ap.parse_args(argv)

    from bench import check, common, registry, trace
    bench = registry.benchmark(harness.REPO)
    cell = ({"name": args.workload, "config": args.config,
             "traffic": args.traffic, "chips": 1} if args.config
            else registry.cell(bench, args.workload))
    cfg = registry.config(cell["config"])
    mix = registry.mix(cell["traffic"])
    peaks = json.loads((registry.ROOT / "peaks.json").read_text())
    _, peak = harness.device_check(cell, peaks)
    harness.enable_cache()
    drv = registry.driver(mix["driver"])

    def one(seed, run_mix, control, traced=False):
        ctx = common.Context(
            workload=args.workload, config=cfg, mix=run_mix, seed=seed,
            seconds=args.seconds, trace=traced, t_start=time.perf_counter(),
            chips=cell["chips"], peak=peak,
            scratch_dir=str(harness.SCRATCH / args.workload),
            control=control, log=harness.log,
            ref_precisions=tuple(p for p in args.ref_precisions.split(",")
                                 if p))
        return ctx, drv.run(ctx)

    if args.what == "readings":
        for seed in (int(s) for s in args.seeds.split(",")):
            ctx, out = one(seed, mix, args.control, bool(args.save_trace))
            layers = {}
            if args.save_trace:
                trace.save(trace.load(str(harness.SCRATCH / args.workload
                                          / "trace")), args.save_trace)
                layers = {m["name"]: registry.metric_reader(m["name"])(
                    out, ctx) for m in bench["per_layer"]
                    if harness.applies(m, args.workload)}
                layers["breakdown"] = {"device_ops": trace.top_ops(out.trace),
                                       "idle_gaps": trace.idle_gaps(out.trace)}
            print(json.dumps({
                "seed": seed, "control": args.control,
                "correct": check.verdict(out.readings),
                "readings": {n: v for n, v, _ in out.readings},
                "gaps_at": out.counters.get("gaps_at", {}),
                "end_to_end": out.end_to_end, "setup_s": out.setup_s,
                "memory_peak_bytes": out.memory_peak_bytes, **layers}),
                flush=True)
    else:
        seed = int(args.seeds.split(",")[0])
        for rate in (float(r) for r in args.rates.split(",")):
            _, out = one(seed, dict(mix, rate_per_s=rate), None)
            print(json.dumps({"rate_per_s": rate, **out.end_to_end,
                              "readings": {n: v for n, v, _ in out.readings},
                              "attempted": out.attempted,
                              "failed": out.failed,
                              "waves": out.counters["waves"],
                              "host_ms_per_wave": 1e3
                              * out.counters["host_s"]
                              / max(out.counters["waves"], 1)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
