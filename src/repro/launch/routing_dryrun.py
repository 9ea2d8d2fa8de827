import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# Must run before any jax import (device count locks on first init).

"""Paper-technique dry-run: the routing procedure distributed across the
production pod (the §Perf "paper-representative" hillclimb cell).

Three experiments per CapsNet config (DESIGN.md §2 mapping vault->chip):

  vault32   — the paper's own scale: 32 "vaults" (chips) on one axis,
              every feasible distribution dimension (B and L; H=10..62 is
              not divisible by 32 — the paper allows imbalanced snippets,
              GSPMD requires divisibility; recorded as skip).
  pod_B1d   — 256 chips, B distributed (the only single dim that divides).
  pod_BL2d  — beyond-paper: B over the 16-chip "data" axis x L over the
              16-chip "model" axis — each aggregation localizes to one
              ring of 16 instead of a group of 256.
  pod_full_train — the COMPLETE CapsNet training step (conv + votes + RP
              + decoder + margin loss + SGD) as one B-distributed
              shard_map program on all 256 chips.

For every cell: lower + compile, roofline terms from the partitioned HLO,
and the planner models (the paper's Eq.6-12 forms + the TPU ring model)
for comparison.

    python -m repro.launch.routing_dryrun --out results/routing_dryrun
"""
import argparse
import json
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.compile_cache import enable_compile_cache
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS
from repro.core import distribution as D
from repro.core import routing
from repro.core.router import ExecutionPlan, RouterSpec, build_router
from repro.launch import hlo_analysis

PEAK_FLOPS, HBM_BW, ICI_BW = 197e12, 819e9, 50e9
N_CHIPS = 256
POD_BATCH = 2048   # production batch: 256 chips x 8 inputs (paper BS=100
                   # per 32 vaults ~ 3/vault; we keep 8/chip)


def _mesh_1d(n):
    return compat.make_mesh((n,), ("vault",))


def _mesh_2d():
    return compat.make_mesh((16, 16), ("data", "model"))


def lower_routing(mesh, axes, caps, batch, iters, use_approx=False):
    routed = build_router(
        RouterSpec(algorithm="dynamic", iterations=iters,
                   use_approx=use_approx),
        ExecutionPlan(mesh=mesh, axes=tuple(axes)))
    ax = dict(axes)
    B, L, H, C = batch, caps.num_l_caps, caps.num_h_caps, caps.h_caps_dim
    spec = P(ax.get("B"), ax.get("L"), ax.get("H"), None)
    u_hat = jax.ShapeDtypeStruct((B, L, H, C), jnp.float32,
                                 sharding=NamedSharding(mesh, spec))
    t0 = time.time()
    compiled = jax.jit(routed).lower(u_hat).compile()
    stats = hlo_analysis.analyze_hlo(compiled.as_text(), mesh.size)
    mem = compiled.memory_analysis()
    return {
        "compile_s": round(time.time() - t0, 1),
        "flops": stats.flops,
        "hbm_bytes": stats.hbm_bytes,
        "hbm_bytes_lower": stats.hbm_bytes_lower,
        "collective_bytes": stats.collective_bytes,
        "collective_by_kind": dict(stats.collective_by_kind),
        "peak_bytes": int(mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes
                          + mem.output_size_in_bytes
                          - mem.alias_size_in_bytes),
        "terms": {
            "compute_s": stats.flops / PEAK_FLOPS,
            "memory_s": math.sqrt(max(stats.hbm_bytes_lower, 1.0)
                                  * max(stats.hbm_bytes, 1.0)) / HBM_BW,
            "collective_s": stats.collective_bytes / ICI_BW,
        },
        "status": "ok",
    }


def run_config(name: str, batch: int) -> dict:
    caps = CAPS_BENCHMARKS[name]
    s = D.RPShape(n_b=batch, n_l=caps.num_l_caps, n_h=caps.num_h_caps,
                  c_l=caps.l_caps_dim, c_h=caps.h_caps_dim,
                  iters=caps.routing_iters)
    out = {"config": name, "batch": batch, "cells": {}}

    # --- paper scale: 32 vaults, single-dimension choices -----------------
    mesh32 = _mesh_1d(32)
    planner32 = D.DeviceModel.tpu_v5e(32)
    out["paper_scale"] = {
        "planner_pick": D.plan(s, planner32),
        "paper_E": {d: D.workload_E(d, s, 32) for d in D.DIMS},
        "paper_M": {d: D.comm_M(d, s, 32) for d in D.DIMS},
    }
    for dim in D.DIMS:
        extent = {"B": s.n_b, "L": s.n_l, "H": s.n_h}[dim]
        tag = f"vault32_{dim}"
        if extent % 32:
            out["cells"][tag] = {
                "status": "skip",
                "reason": f"{dim}-extent {extent} % 32 != 0 (paper allows "
                          f"imbalanced snippets; GSPMD needs divisibility)"}
            continue
        rec = lower_routing(mesh32, ((dim, "vault"),), caps, batch, s.iters)
        rec["ring_M_model"] = D.comm_M_ring({dim: 32}, s)
        out["cells"][tag] = rec
        print(f"  [{tag}] coll={rec['collective_bytes']:.3e}B "
              f"ringM={rec['ring_M_model']:.3e}B "
              f"mem={rec['terms']['memory_s'] * 1e3:.3f}ms", flush=True)

    # --- pod scale: 1D B over 256 vs 2D B x L over (16,16) ----------------
    candidates = {"B1d": {"B": 256}, "BL2d": {"B": 16, "L": 16}}
    out["pod_scale"] = {
        "planner_pick": D.plan_multi(s, D.DeviceModel.tpu_v5e(256),
                                     candidates),
        "ring_M_model": {k: D.comm_M_ring(v, s)
                         for k, v in candidates.items()},
        "E_model": {k: D.workload_E_multi(v, s)
                    for k, v in candidates.items()},
    }
    rec = lower_routing(_mesh_1d(256), (("B", "vault"),), caps, batch,
                        s.iters)
    out["cells"]["pod_B1d"] = rec
    print(f"  [pod_B1d] coll={rec['collective_bytes']:.3e}B "
          f"mem={rec['terms']['memory_s'] * 1e3:.3f}ms", flush=True)
    if s.n_l % 16 == 0:
        rec = lower_routing(_mesh_2d(), (("B", "data"), ("L", "model")),
                            caps, batch, s.iters)
        out["cells"]["pod_BL2d"] = rec
        print(f"  [pod_BL2d] coll={rec['collective_bytes']:.3e}B "
              f"mem={rec['terms']['memory_s'] * 1e3:.3f}ms", flush=True)
    else:
        out["cells"]["pod_BL2d"] = {"status": "skip",
                                    "reason": f"N_L={s.n_l} % 16 != 0"}
    ok = {k: c for k, c in out["cells"].items()
          if c.get("status") == "ok" and k.startswith("pod")}
    if ok:
        out["pod_scale"]["best_measured"] = min(
            ok, key=lambda k: max(ok[k]["terms"].values()))
    return out


def full_capsnet_cell(cfg_name: str, batch: int) -> dict:
    """Lower + compile a FULL CapsNet training step (conv + votes + RP +
    decoder + margin loss + SGD update) on the production single-pod mesh
    — the paper's model as a first-class citizen of the same dry-run the
    LM architectures pass.  Data-parallel over all 256 chips on the
    B-dimension (the planner's pick for TPU constants; the routing
    aggregations stay vault-local exactly as the paper's B-distribution
    keeps them, with only the (L,H) logit psum crossing chips)."""
    import functools
    from repro.core import capsule_layers as CL
    from repro.models import capsnet

    caps = CAPS_BENCHMARKS[cfg_name]
    mesh = _mesh_1d(N_CHIPS)
    rc = routing.RoutingConfig(iterations=caps.routing_iters,
                               sharded_dim="B", axis_name="vault")
    spec_img = P("vault", None, None, None)
    spec_lbl = P("vault")

    params = jax.eval_shape(
        lambda k: capsnet.init_capsnet(k, caps),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())), params)
    images = jax.ShapeDtypeStruct(
        (batch, caps.image_hw, caps.image_hw, caps.image_channels),
        jnp.float32, sharding=NamedSharding(mesh, spec_img))
    labels = jax.ShapeDtypeStruct((batch,), jnp.int32,
                                  sharding=NamedSharding(mesh, spec_lbl))

    def local_loss(params, images, labels):
        # per-shard batch slice; RP runs B-sharded (paper distribution)
        out_loss, metrics = capsnet.loss_fn(params, images, labels, caps,
                                            rc)
        return jax.lax.pmean(out_loss, "vault"), metrics

    @functools.partial(
        compat.shard_map, mesh=mesh,
        in_specs=(P(), spec_img, spec_lbl), out_specs=P())
    def train_step(params, images, labels):
        def scalar_loss(p):
            return local_loss(p, images, labels)[0]
        loss, grads = jax.value_and_grad(scalar_loss)(params)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "vault"), grads)
        new_params = jax.tree.map(lambda p, g: p - 0.01 * g, params, grads)
        return loss, new_params

    t0 = time.time()
    compiled = jax.jit(train_step).lower(params, images, labels).compile()
    stats = hlo_analysis.analyze_hlo(compiled.as_text(), N_CHIPS)
    mem = compiled.memory_analysis()
    return {
        "config": cfg_name, "batch": batch, "kind": "full_train_step",
        "compile_s": round(time.time() - t0, 1),
        "flops": stats.flops, "hbm_bytes": stats.hbm_bytes,
        "hbm_bytes_lower": stats.hbm_bytes_lower,
        "collective_bytes": stats.collective_bytes,
        "collective_by_kind": dict(stats.collective_by_kind),
        "peak_bytes": int(mem.argument_size_in_bytes
                          + mem.temp_size_in_bytes
                          + mem.output_size_in_bytes
                          - mem.alias_size_in_bytes),
        "terms": {
            "compute_s": stats.flops / PEAK_FLOPS,
            "memory_s": math.sqrt(max(stats.hbm_bytes_lower, 1.0)
                                  * max(stats.hbm_bytes, 1.0)) / HBM_BW,
            "collective_s": stats.collective_bytes / ICI_BW,
        },
        "status": "ok",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/routing_dryrun")
    ap.add_argument("--configs", nargs="*",
                    default=["Caps-MN1", "Caps-EN3", "Caps-SV3"])
    ap.add_argument("--batch", type=int, default=POD_BATCH)
    ap.add_argument("--skip-full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    for name in args.configs:
        print(f"[{name}]", flush=True)
        out = run_config(name, args.batch)
        if not args.skip_full:
            rec = full_capsnet_cell(name, args.batch)
            out["cells"]["pod_full_train"] = rec
            print(f"  [pod_full_train] peak={rec['peak_bytes'] / 2 ** 30:.2f}"
                  f"GiB coll={rec['collective_bytes']:.3e}B "
                  f"compute={rec['terms']['compute_s'] * 1e3:.2f}ms "
                  f"mem={rec['terms']['memory_s'] * 1e3:.2f}ms", flush=True)
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(out, f, indent=1)
        pod = out["pod_scale"]
        print(f"[{name}] paper32 planner={out['paper_scale']['planner_pick']}"
              f"  pod planner={pod['planner_pick']} "
              f"best_measured={pod.get('best_measured')}", flush=True)


if __name__ == "__main__":
    main()
