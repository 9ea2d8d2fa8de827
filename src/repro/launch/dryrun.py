import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init) — task spec, MULTI-POD DRY-RUN step 0.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh ((16,16) or (2,16,16)),
  2. builds sharded abstract inputs (ShapeDtypeStruct — no allocation),
  3. ``jax.jit(step).lower(...).compile()`` — a failure here (sharding
     mismatch, OOM at compile, unsupported collective) is a bug,
  4. records ``compiled.memory_analysis()`` (proves fit),
     ``compiled.cost_analysis()`` and the trip-count-aware HLO analysis
     (launch/hlo_analysis.py) for §Roofline,
  5. writes one JSON per cell to --out.

Usage:
  python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as C
from repro.compile_cache import enable_compile_cache
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import lm
from repro.optim import adamw_init
from repro.runtime import sharding as sh
from repro.runtime import train_loop
from repro.runtime.mesh_utils import dp_axes, dp_size

# per-arch microbatch counts for train_4k (bounds activation memory; must
# keep (256/n) % dp_size == 0 for both meshes -> n in {1,2,4,8}).
# n=8 holds peak activation memory < 5 GiB/device on every arch (measured;
# EXPERIMENTS.md §Dry-run) and the extra per-microbatch gradient psums are
# noise next to the TP activation collectives.
TRAIN_MICROBATCHES: Dict[str, int] = {
    "mistral-large-123b": 8,
    "phi3-medium-14b": 8,
    "stablelm-12b": 8,
    "qwen3-moe-30b-a3b": 8,
    "mixtral-8x7b": 8,
    "zamba2-7b": 8,
    "falcon-mamba-7b": 8,
    "llava-next-mistral-7b": 8,
    "granite-3-2b": 8,
    "seamless-m4t-large-v2": 8,
}


def _abstract_params(cfg, rules):
    pabs = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    shard = lm.param_shardings(cfg, rules)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        pabs, shard)


def _with_sharding(tree, mesh, spec_fn):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, spec_fn(a))), tree)


def _batch_specs(cfg, shape, mesh, rules, num_micro):
    specs = C.input_specs(cfg, shape, num_micro)
    bspec = rules.spec("batch")
    b_axes = bspec[0] if len(bspec) else None

    def spec_for(a):
        lead = (None,) if num_micro > 1 and shape.kind == "train" else ()
        rest = (None,) * (len(a.shape) - len(lead) - 1)
        return P(*lead, b_axes, *rest)

    return _with_sharding(specs, mesh, spec_for)


def _decode_state_abs(cfg, shape, mesh, rules):
    state = jax.eval_shape(
        lambda: lm.init_decode_state(cfg, shape.global_batch, shape.seq_len))
    b_axes = rules.rules.get("batch")
    cache_ax = rules.rules.get("cache_seq")
    inner_ax = rules.rules.get("ssm_inner")

    def spec_for_path(path, a):
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        if "pos" in keys[-1:]:
            return P()
        if any(k in ("conv",) for k in keys):
            return P(None, b_axes, None, inner_ax)
        if any(k in ("ssm",) for k in keys):
            return P(None, b_axes, inner_ax, None)
        # kv / cross caches: (L, B, S, K, dh)
        return P(None, b_axes, cache_ax, None, None)

    return jax.tree_util.tree_map_with_path(
        lambda p, a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, spec_for_path(p, a))), state)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               smoke: bool = False,
               overrides: Optional[dict] = None) -> dict:
    """Lower + compile one cell; returns the result record."""
    shape = C.SHAPES[shape_name]
    runnable, why = C.cell_is_runnable(arch, shape_name)
    if not runnable:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skip", "reason": why}

    cfg = C.get_smoke_config(arch) if smoke else C.get_config(arch)
    mesh = (make_smoke_mesh(multi_pod=multi_pod) if smoke
            else make_production_mesh(multi_pod=multi_pod))
    n_dev = mesh.size
    mode = {"train": "train", "prefill": "prefill",
            "decode": "decode"}[shape.kind]
    rule_overrides = dict(overrides or {})
    if shape.global_batch < dp_size(mesh):
        rule_overrides.setdefault("batch", None)   # e.g. long_500k B=1
    rules = sh.make_rules(cfg, mesh, mode, rule_overrides)

    params_abs = _abstract_params(cfg, rules)
    record = {"arch": arch, "shape": shape_name,
              "mesh": "multi" if multi_pod else "single",
              "kind": shape.kind, "n_devices": n_dev,
              "seq_len": shape.seq_len, "global_batch": shape.global_batch}

    t0 = time.time()
    if shape.kind == "train":
        n_micro = 1 if smoke else TRAIN_MICROBATCHES.get(arch, 2)
        if arch == "mistral-large-123b" and not multi_pod and not smoke:
            # 123B needs microbatch=1/device on the single-pod mesh to fit
            # (multi-pod halves the per-device batch already): dp=16 allows
            # n=16, dp=32 caps n at 8.
            n_micro = 16
        while (shape.global_batch // n_micro) % dp_size(mesh):
            n_micro //= 2
        n_micro = max(n_micro, 1)
        record["num_microbatches"] = n_micro
        step = train_loop.make_train_step(cfg, rules,
                                          num_microbatches=n_micro)
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        batch_abs = _batch_specs(cfg, shape, mesh, rules, n_micro)
        lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
            params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        batch_abs = _batch_specs(cfg, shape, mesh, rules, 1)

        def prefill_fn(params, batch):
            return lm.prefill(params, cfg, batch, max_len=shape.seq_len,
                              rules=rules)

        lowered = jax.jit(prefill_fn).lower(params_abs, batch_abs)
    else:  # decode
        state_abs = _decode_state_abs(cfg, shape, mesh, rules)
        tok_abs = jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32,
            sharding=NamedSharding(mesh, P(rules.rules.get("batch"), None)))

        def decode_fn(params, state, tokens):
            return lm.decode_step(params, cfg, state, tokens, rules)

        lowered = jax.jit(decode_fn, donate_argnums=(1,)).lower(
            params_abs, state_abs, tok_abs)
    record["lower_s"] = round(time.time() - t0, 1)

    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    compiled_text = compiled.as_text()
    peak = int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # fp32 shadows of bf16 buffers created by CPU float-normalization
    # (bf16 dot/DUS are native on TPU) — see hlo_analysis + EXPERIMENTS.md.
    artifact = hlo_analysis.cpu_bf16_artifact_bytes(compiled_text)
    record["memory"] = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_bytes_per_device": peak,
        "cpu_bf16_artifact_bytes": int(artifact),
        "peak_tpu_corrected": peak - int(artifact),
    }
    ca = compiled.cost_analysis() or {}
    record["xla_cost"] = {k: float(ca[k]) for k in
                          ("flops", "bytes accessed") if k in ca}

    t2 = time.time()
    stats = hlo_analysis.analyze_hlo(compiled_text, n_dev)
    record["analyze_s"] = round(time.time() - t2, 1)
    record["hlo"] = stats.as_dict()
    record["status"] = "ok"
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=("off", "on", "both"),
                    default="off")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs + (2,4)/(2,2,4) mesh (CI check)")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    enable_compile_cache()

    cells = []
    archs = C.list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(C.SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                cells.append((arch, shape, mp))

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape, mp in cells:
        tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
        out_path = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_path):
            with open(out_path) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skip"):
                print(f"[cached] {tag}: {prev['status']}")
                n_ok += prev["status"] == "ok"
                n_skip += prev["status"] == "skip"
                continue
        print(f"[lower]  {tag} ...", flush=True)
        try:
            rec = lower_cell(arch, shape, mp, smoke=args.smoke)
        except Exception as e:  # a failing cell is a bug — record it loudly
            rec = {"arch": arch, "shape": shape,
                   "mesh": "multi" if mp else "single",
                   "status": "fail", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        status = rec["status"]
        n_ok += status == "ok"
        n_skip += status == "skip"
        n_fail += status == "fail"
        extra = ""
        if status == "ok":
            extra = (f" compile={rec['compile_s']}s "
                     f"mem/dev={rec['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
                     f"flops/dev={rec['hlo']['flops']:.3e}")
        elif status == "fail":
            extra = " " + rec["error"][:160]
        print(f"[{status}]  {tag}{extra}", flush=True)
    print(f"\ndone: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
