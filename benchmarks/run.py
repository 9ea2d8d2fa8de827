"""Benchmark orchestrator — one module per paper table/figure.

    python -m benchmarks.run                    # all benches
    python -m benchmarks.run --only rp_speedup accuracy
    python -m benchmarks.run --smoke --only rp_speedup   # CI-sized shapes

Each bench prints its human-readable table to stdout AND returns a dict
that this orchestrator persists as ``BENCH_<name>.json`` (bench name,
config, median/p90 times, speedups — schema per benchmarks/README.md), so
the perf trajectory survives the run.

| bench            | paper artifact                                     |
|------------------|----------------------------------------------------|
| layer_breakdown  | Fig.4  — per-layer time, RP fraction               |
| rp_speedup       | Fig.15/16 — naive vs fused vs sharded-fused vs PIM |
| distribution     | Fig.18 — dimension choice vs PE frequency          |
| accuracy         | Table 5 — approximation ± recovery accuracy        |
| scaling          | §6.2.1 — speedup vs network size                   |
| pipeline         | Fig.8/§6.3 — host||PIM pipelined execution         |
| serving          | Fig.8 served end-to-end — load sweep, 2 arms       |
| roofline         | (this repro) §Roofline terms from the dry-run      |
| train            | §5.2 for backprop — fused-VJP vs jnp train step    |
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import traceback

BENCHES = ("layer_breakdown", "rp_speedup", "distribution", "accuracy",
           "scaling", "pipeline", "serving", "roofline", "train")


def _provenance() -> dict:
    """Execution-environment block stamped into every artifact: which jax
    backend timed the numbers and whether pallas arms ran in interpret mode
    (off-TPU they always do — those arms are modeled_only, never hardware
    measurements)."""
    import jax

    from repro import kernels
    return {"jax_backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "pallas_interpret": kernels.pallas_interpret_mode()}


def write_artifact(name: str, payload: dict, smoke: bool) -> str:
    """Persist one bench's machine-readable results as BENCH_<name>.json."""
    path = f"BENCH_{name}.json"
    doc = {"bench": name, "smoke": smoke,
           "schema": "benchmarks/README.md",
           "provenance": _provenance(), **payload}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=float)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of {BENCHES}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few timing reps (CI artifact check)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        from benchmarks import common
        common.SMOKE = True
    names = args.only or BENCHES
    failed = []
    for name in names:
        mod_name = ("benchmarks.roofline" if name == "roofline"
                    else f"benchmarks.bench_{name}")
        print(f"\n===== {name} ({mod_name}) =====", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            payload = mod.main()
            if isinstance(payload, dict):
                path = write_artifact(name, payload, args.smoke)
                print(f"# [{name}] wrote {path}", flush=True)
            print(f"# [{name}] done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
