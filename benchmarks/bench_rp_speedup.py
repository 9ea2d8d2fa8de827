"""Paper Fig.15/16 — RP acceleration: naive baseline vs fused-kernel vs
distribution-planned execution.

Four complementary measurements:

(1) MEASURED (this container, CPU): the naive RP (materialise every
    intermediate — the paper's GPU-pathology baseline) vs the optimised
    single-pass schedule through the unified Router API (jnp backend) —
    the memory-traffic ratio the kernel eliminates.

(2) MEASURED, sharded-fused arm: the same networks through
    ``RouterSpec(backend="pallas")`` composed with an L-sharded
    ExecutionPlan (DESIGN.md §Sharded-fused) — the in-vault PE chain split
    at the Table-2 aggregation points.

(3) MEASURED, procedure-fused arms (fp32 + bf16 û streaming):
    ``RouterSpec(backend="pallas")`` — the whole-procedure megakernel
    (DESIGN.md §Procedure-fused).  Every row cross-checks the measured
    output against the jnp backend (<=1e-5 for fp32 arms) and attaches
    the modeled DMA bytes of both kernel forms; the model itself is
    self-checked (procedure keeps only the final v write beside the û
    stream, bf16 halves the û stream bytes).

    Off-TPU every pallas arm runs in interpret mode and carries
    ``"modeled_only": true`` — its wall-clock documents plumbing, not
    performance (an interpret-mode "0.2x speedup" is not a hardware
    regression); the perf claim is the DMA model
    (kernels/routing/ops.py::dma_bytes_per_call).

(4) MODELED (paper Table-4 operating points): the analytical execution-time
    model S⁻¹ = αE + βM (core.distribution) evaluated with the paper's HMC
    coefficients vs a GPU-baseline model (same FLOP count over P100
    FLOP/s + HBM traffic over 732GB/s), per Table-1 benchmark — the
    reproduction of the paper's 2.17x-average RP claim shape.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import common
from benchmarks.common import kernel_arm_stats, time_stats
from repro import compat, kernels
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS
from repro.core import distribution as D
from repro.core.router import ExecutionPlan, RouterSpec, build_router
from repro.kernels.routing import ops as rt_ops

# P100 operating point for the modeled GPU baseline (paper Table 4)
P100_FLOPS = 9.5e12          # FP32
P100_HBM = 732e9             # bytes/s
# RP traffic factor: the characterisation (paper §3.2) finds the RP
# re-reads/writes its intermediates from off-chip memory each equation;
# naive traffic ~ 4 tensors x u_hat bytes per iteration (u_hat, c·u_hat
# products, agreement, b updates).
NAIVE_TRAFFIC_FACTOR = 4.0
FUSED_TRAFFIC_FACTOR = 1.0   # stream u_hat once (kernel design)

# (name, B, L, H, C, iters) — smoke sizes for the CI artifact check
# (iters=3 so the early-exit ladder can show eff < iters * n_l_tiles: the
# first possible ‖Δb‖ freeze lands after iteration 1, saving work from
# iteration 2 on)
SMOKE_SHAPES = [("smoke", 2, 64, 6, 8, 3)]

# ‖Δb‖∞ thresholds for the measured early-exit ladder (ascending; the
# last rung is effectively ∞ — every tile freezes at its first check)
EARLY_EXIT_EPS_LADDER = (1.0, 8.0, 64.0, 1e6)


def _measure_shapes(batch: int):
    if common.smoke():
        return SMOKE_SHAPES
    return [(name, batch, cfg.num_l_caps, cfg.num_h_caps, cfg.h_caps_dim,
             cfg.routing_iters)
            for name, cfg in CAPS_BENCHMARKS.items()
            if name in ("Caps-MN1", "Caps-EN3", "Caps-SV1")]


def dma_model_row(B: int, L: int, H: int, C: int, iters: int) -> dict:
    """Modeled DMA bytes of every kernel form for one network, with the
    acceptance cross-checks applied (raise = the model or the kernel
    regressed, fail the bench):

    * the procedure megakernel keeps b/v/s on chip — only the final v
      write remains beside the û stream;
    * bf16 û streaming halves the stream bytes of the only large operand;
    * int8 û streaming quarters them (per-tile scales are O(L/l_tile),
      not modeled — DESIGN.md §Quantized-routing);
    * the early-exit floor row models the analytic best case: every tile
      frozen after iteration 1 -> û work fraction min(iters, 2)/iters
      (each tile must stream twice before its first ‖Δb‖ check can fire);
    * the measured sharded arm is the L-only plan, whose STAGE 2 is the
      softmax-folded kernel (B and H unsharded) — its row uses
      ``fold=True`` (the plain stage_split model overstates that path by
      iters·2·L·H·4 bytes).
    """
    ee_floor = min(iters, 2) / iters
    model = {
        "procedure_fused_fp32": rt_ops.dma_bytes_per_call(
            B, L, H, C, iters, form="procedure"),
        "procedure_fused_bf16": rt_ops.dma_bytes_per_call(
            B, L, H, C, iters, form="procedure", stream_dtype="bf16"),
        "procedure_fused_int8": rt_ops.dma_bytes_per_call(
            B, L, H, C, iters, form="procedure", stream_dtype="int8"),
        "procedure_fused_early_exit_bound": rt_ops.dma_bytes_per_call(
            B, L, H, C, iters, form="procedure",
            early_exit_work_fraction=ee_floor),
        # the measured arm shards L only -> the fold kernel runs
        "sharded_stage_split": rt_ops.dma_bytes_per_call(
            B, L, H, C, iters, form="stage_split", fold=True),
        # reference: the unfolded stage-split form (B- or H-sharded plans)
        "sharded_stage_split_unfolded": rt_ops.dma_bytes_per_call(
            B, L, H, C, iters, form="stage_split"),
    }
    pf = model["procedure_fused_fp32"]
    assert pf["roundtrip_bytes"] == B * H * C * 4, (
        "procedure-fused roundtrip traffic not eliminated", model)
    assert (2 * model["procedure_fused_bf16"]["u_hat_stream_bytes"]
            == pf["u_hat_stream_bytes"]), (
        "bf16 streaming does not halve û bytes", model)
    i8 = model["procedure_fused_int8"]
    assert 4 * i8["u_hat_stream_bytes"] == pf["u_hat_stream_bytes"], (
        "int8 streaming does not quarter û bytes", model)
    assert i8["roundtrip_bytes"] == pf["roundtrip_bytes"], (
        "int8 must not change the fp32 b/v/s roundtrip", model)
    ee = model["procedure_fused_early_exit_bound"]
    assert (ee["u_hat_stream_bytes"]
            == int(round(pf["u_hat_stream_bytes"] * ee_floor))), (
        "early-exit floor row must scale exactly the û stream", model)
    assert ee["early_exit_work_fraction"] == ee_floor, model
    assert (model["sharded_stage_split_unfolded"]["total_bytes"]
            - model["sharded_stage_split"]["total_bytes"]
            == iters * 2 * L * H * 4), (
        "fold model must save exactly the per-iteration db round-trip",
        model)
    return model


def early_exit_ladder(u_hat, iters: int, v_jnp) -> dict:
    """Measured early-exit arm: sweep EARLY_EXIT_EPS_LADDER, record the
    effective-tile-iterations counter the megakernel emits and the DMA
    model re-evaluated at the MEASURED work fraction.  Cross-checks:
    monotone non-increasing work along the ladder, the analytic
    freeze-everything floor at the ∞ rung, and strictly-less-than-full
    work at every ε > 0 rung that converged anything."""
    B, L, H, C = u_hat.shape
    l_tile = rt_ops.procedure_l_tile(B, L, H, C, "fp32", early_exit=True)
    n_tiles = L // l_tile
    full = iters * n_tiles
    rows = []
    for eps in EARLY_EXIT_EPS_LADDER:
        v, eff = rt_ops.dynamic_routing_procedure_stats(
            u_hat, iterations=iters, l_tile=l_tile, early_exit_eps=eps)
        eff = int(eff)
        frac = eff / full
        rows.append({
            "eps": eps,
            "effective_tile_iterations": eff,
            "full_tile_iterations": full,
            "work_fraction": frac,
            "max_abs_delta_vs_jnp":
                float(np.abs(np.asarray(v) - v_jnp).max()),
            "dma_model": rt_ops.dma_bytes_per_call(
                B, L, H, C, iters, form="procedure",
                early_exit_work_fraction=frac)})
    effs = [r["effective_tile_iterations"] for r in rows]
    assert all(a >= b for a, b in zip(effs, effs[1:])), (
        "early-exit work not monotone in eps", effs)
    assert all(e <= full for e in effs), (effs, full)
    # ∞ rung: every tile works exactly twice (iteration 0 + the iteration
    # that trips its first ‖Δb‖ check) — the analytic floor of the bound
    # row in dma_model_row
    assert effs[-1] == min(iters, 2) * n_tiles, (effs, iters, n_tiles)
    return {"l_tile": l_tile, "n_l_tiles": n_tiles,
            "full_tile_iterations": full, "ladder": rows}


def measured_speedups(batch: int = 2):
    """CPU-measured naive vs routed RP step times, incl. the sharded-fused
    (pallas x L-sharded plan) and procedure-fused (fp32 + bf16) arms."""
    reps = 2 if common.smoke() else 5
    mesh = compat.make_mesh((len(jax.devices()),), ("vault",))
    rows = []
    for name, B, L, H, C, iters in _measure_shapes(batch):
        key = jax.random.PRNGKey(0)
        u_hat = jax.random.normal(key, (B, L, H, C))

        def naive(uh):
            # eager Algorithm-1: two u_hat sweeps/iter + explicit products
            b = jnp.zeros((L, H))
            v = None
            for _ in range(iters):
                c = jax.nn.softmax(b, -1)
                weighted = uh * c[None, :, :, None]       # materialised
                s = weighted.sum(1)
                n2 = (s ** 2).sum(-1, keepdims=True)
                v = s * (n2 / (1 + n2)) / jnp.sqrt(n2 + 1e-9)
                agree = (uh * v[:, None]).sum(-1)         # materialised
                b = b + agree.sum(0)
            return v

        # the optimised schedule through the unified Router API (jnp
        # backend: scan-based single-pass routing, no materialised
        # intermediates)
        router = build_router(RouterSpec(algorithm="dynamic",
                                         iterations=iters))
        # sharded-fused arm: pallas backend x L-sharded ExecutionPlan
        # (stage-split kernels + cross-shard psum; interpret mode on CPU)
        sharded_fused = build_router(
            RouterSpec(algorithm="dynamic", backend="pallas",
                       iterations=iters),
            ExecutionPlan(mesh=mesh, axes=(("L", "vault"),)))
        # procedure-fused arms: the whole-procedure megakernel, fp32 and
        # bf16 û streaming (DESIGN.md §Procedure-fused)
        proc = build_router(RouterSpec(
            algorithm="dynamic", backend="pallas", iterations=iters))
        proc_bf16 = build_router(RouterSpec(
            algorithm="dynamic", backend="pallas", iterations=iters,
            stream_dtype="bf16"))
        # deep-edge arm: int8 û streaming (DESIGN.md §Quantized-routing)
        proc_int8 = build_router(RouterSpec(
            algorithm="dynamic", backend="pallas", iterations=iters,
            stream_dtype="int8"))

        # measured-output cross-check vs the jnp backend (acceptance:
        # <=1e-5 for fp32 arms; bf16/int8 deltas recorded with loose
        # sanity rails — the real int8 gate is top-1 accuracy in
        # bench_accuracy, per ROADMAP item 1)
        v_jnp = np.asarray(router(u_hat))
        delta = {
            arm: float(np.abs(np.asarray(r(u_hat)) - v_jnp).max())
            for arm, r in (("sharded_fused", sharded_fused),
                           ("procedure_fused", proc),
                           ("procedure_fused_bf16", proc_bf16),
                           ("procedure_fused_int8", proc_int8))}
        for arm in ("sharded_fused", "procedure_fused"):
            assert delta[arm] <= 1e-5, (name, arm, delta)
        assert delta["procedure_fused_int8"] <= 0.1, (name, delta)

        t_n = time_stats(jax.jit(naive), u_hat, iters=reps)
        t_f = time_stats(jax.jit(lambda uh: router(uh)), u_hat, iters=reps)
        t_sf = kernel_arm_stats(jax.jit(lambda uh: sharded_fused(uh)),
                                u_hat, iters=reps)
        t_p = kernel_arm_stats(jax.jit(lambda uh: proc(uh)), u_hat,
                               iters=reps)
        t_pb = kernel_arm_stats(jax.jit(lambda uh: proc_bf16(uh)), u_hat,
                                iters=reps)
        t_pi = kernel_arm_stats(jax.jit(lambda uh: proc_int8(uh)), u_hat,
                                iters=reps)
        resolved = proc.resolve(u_hat)
        rows.append({"network": name,
                     "shape": {"B": B, "L": L, "H": H, "C": C,
                               "iters": iters},
                     "naive": t_n, "router_jnp": t_f,
                     "sharded_fused": t_sf,
                     "procedure_fused": t_p,
                     "procedure_fused_bf16": t_pb,
                     "procedure_fused_int8": t_pi,
                     "resolved_fusion": resolved.fusion,
                     "max_abs_delta_vs_jnp": delta,
                     "dma_model": dma_model_row(B, L, H, C, iters),
                     "early_exit": early_exit_ladder(u_hat, iters, v_jnp),
                     "speedup": t_n["median_s"] / t_f["median_s"],
                     "sharded_fused_speedup":
                         t_n["median_s"] / t_sf["median_s"],
                     "procedure_fused_speedup":
                         t_n["median_s"] / t_p["median_s"]})
    return rows


HMC_INTERNAL_BW = 512e9      # paper Table 4: aggregate vault bandwidth
HMC_XBAR_BW = 512e9          # crossbar for inter-vault traffic


def modeled_speedups():
    """Analytical PIM-vs-GPU RP model per Table-1 config (paper Fig.15a).

    Both sides are bandwidth-roofline models — the paper's own mechanism:
    the GPU re-streams the unshareable intermediates from off-chip memory
    ~4x per iteration (§3.2 characterisation; LDST 85.9% vs ALU 38.6%
    utilised), while the in-memory PEs stream û once per iteration at the
    vaults' aggregate internal bandwidth, plus the planner-chosen
    dimension's inter-vault traffic M over the crossbar.  (A pure
    op-throughput model with Table-4's literal 512 PEs x 312.5 MHz makes
    HMC compute-bound and *slower* — the PEs must stream, not ALU-bind;
    noted in EXPERIMENTS.md §Paper-claims.)
    """
    rows = []
    hmc = D.DeviceModel.hmc()
    for name, cfg in CAPS_BENCHMARKS.items():
        s = D.RPShape.from_caps_config(cfg)
        dim = D.plan(s, hmc)
        u_hat_bytes = 4.0 * s.n_b * s.n_l * s.n_h * s.c_h
        total_ops = D.workload_E("B", s, 1)  # n_vault=1 -> total RP ops
        t_gpu = max(total_ops / P100_FLOPS,
                    NAIVE_TRAFFIC_FACTOR * s.iters * u_hat_bytes / P100_HBM)
        t_pim = max(FUSED_TRAFFIC_FACTOR * s.iters * u_hat_bytes
                    / HMC_INTERNAL_BW,
                    D.comm_M(dim, s, hmc.n_vault) / HMC_XBAR_BW)
        rows.append({"network": name, "chosen_dim": dim,
                     "gpu_model_s": t_gpu, "pim_model_s": t_pim,
                     "speedup": t_gpu / t_pim})
    return rows


def _kernel_config(measured) -> dict:
    """Provenance block: the l_tile each pallas arm's auto-picker chose per
    network and stream dtype (the knobs that shape the BlockSpecs) — read
    from the same ops helpers the wrappers call, so it cannot drift."""
    out = {}
    for r in measured:
        s = r["shape"]
        dims = (s["B"], s["L"], s["H"], s["C"])
        out[r["network"]] = {
            "l_tile_fp32": rt_ops.auto_l_tile(*dims, "fp32"),
            "l_tile_bf16": rt_ops.auto_l_tile(*dims, "bf16"),
            "procedure_l_tile_fp32": rt_ops.procedure_l_tile(*dims, "fp32"),
            "procedure_l_tile_bf16": rt_ops.procedure_l_tile(*dims, "bf16"),
            "procedure_l_tile_int8": rt_ops.procedure_l_tile(*dims, "int8"),
            "procedure_l_tile_early_exit": rt_ops.procedure_l_tile(
                *dims, "fp32", early_exit=True),
        }
    return {"l_tile": out, "stream_dtypes": ["fp32", "bf16", "int8"],
            "early_exit_eps_ladder": list(EARLY_EXIT_EPS_LADDER)}


def main():
    measured = measured_speedups()
    print("== measured (CPU): naive vs routed RP schedule ==")
    print("network,naive_s,router_jnp_s,sharded_fused_s,procedure_fused_s,"
          "procedure_bf16_s,procedure_int8_s,speedup,"
          "sharded_fused_speedup,procedure_fused_speedup")
    for r in measured:
        print(f"{r['network']},{r['naive']['median_s']:.4f},"
              f"{r['router_jnp']['median_s']:.4f},"
              f"{r['sharded_fused']['median_s']:.4f},"
              f"{r['procedure_fused']['median_s']:.4f},"
              f"{r['procedure_fused_bf16']['median_s']:.4f},"
              f"{r['procedure_fused_int8']['median_s']:.4f},"
              f"{r['speedup']:.2f},{r['sharded_fused_speedup']:.2f},"
              f"{r['procedure_fused_speedup']:.2f}")
    print("# (CPU wall-time is a weak proxy — XLA CPU fuses the naive "
          "form too, and every pallas arm runs in interpret mode "
          "[modeled_only]; the traffic claim is the kernel DMA model, "
          "kernels/routing/ops.py::dma_bytes_per_call)")
    d0 = measured[0]["dma_model"]
    print(f"# DMA model ({measured[0]['network']}): procedure-fused "
          f"{d0['procedure_fused_fp32']['total_bytes']:,}B (roundtrip "
          f"{d0['procedure_fused_fp32']['roundtrip_bytes']:,}B), bf16 û "
          f"stream {d0['procedure_fused_bf16']['u_hat_stream_bytes']:,}B, "
          f"int8 {d0['procedure_fused_int8']['u_hat_stream_bytes']:,}B")
    for r in measured:
        ee = r["early_exit"]
        effs = ",".join(str(x["effective_tile_iterations"])
                        for x in ee["ladder"])
        print(f"# early-exit ({r['network']}): eps ladder "
              f"{list(EARLY_EXIT_EPS_LADDER)} -> effective tile-iterations "
              f"[{effs}] of {ee['full_tile_iterations']} "
              f"(l_tile={ee['l_tile']})")
    print()
    modeled = modeled_speedups()
    print("== modeled (paper Table-4 coefficients): GPU vs PIM RP ==")
    print("network,chosen_dim,gpu_model_s,pim_model_s,speedup")
    sps = []
    for r in modeled:
        print(f"{r['network']},{r['chosen_dim']},{r['gpu_model_s']:.5f},"
              f"{r['pim_model_s']:.5f},{r['speedup']:.2f}")
        sps.append(r["speedup"])
    geomean = float(jnp.prod(jnp.array(sps)) ** (1 / len(sps)))
    print(f"# geomean modeled RP speedup: {geomean:.2f} "
          f"(paper Fig.15: 2.17x avg)")
    return {"paper_artifact": "Fig.15/16",
            "config": {"device": jax.default_backend(),
                       "n_devices": len(jax.devices()),
                       "sharded_fused_plan": [["L", "vault"]],
                       "pallas_interpret": kernels.pallas_interpret_mode(),
                       "kernel": _kernel_config(measured)},
            "measured": measured,
            "modeled": modeled,
            "geomean_modeled_speedup": geomean}


if __name__ == "__main__":
    main()
