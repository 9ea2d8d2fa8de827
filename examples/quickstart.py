"""Quickstart: the paper's technique in five minutes on CPU.

1. Build a CapsNet (paper Fig.2) and run inference with dynamic routing.
2. Swap in the paper's §5.2.2 approximated special functions through the
   unified Router API — same classification, one extra multiply per op.
3. Ask the §5.1.2 planner which dimension to distribute the routing
   procedure on — and let ``plan="auto"`` make the same choice inside
   ``build_router`` (the planner -> execution loop, closed).
4. Run the routing procedure through the fused Pallas kernel backend
   (interpret mode on CPU) and check it agrees.
5. Serve the deep-edge tier — int8 û streaming + per-capsule early exit
   in the procedure megakernel (DESIGN.md §Quantized-routing) — and read
   the megakernel's own work counter showing the routing work saved.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.caps_benchmarks import CAPS_BENCHMARKS, smoke_caps
from repro.core import distribution as D
from repro.core.router import ExecutionPlan, RouterSpec, build_router
from repro.data.synthetic import SyntheticCapsDataset
from repro.models import capsnet


def main():
    cfg = smoke_caps()
    key = jax.random.PRNGKey(0)
    params = capsnet.init_capsnet(key, cfg)
    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)
    batch = ds.batch(0, 8)
    images = jnp.asarray(batch["images"])

    # 1 — exact dynamic routing (paper Algorithm 1; default RouterSpec)
    out = capsnet.forward(params, images, cfg)
    print("capsule norms (input 0):",
          [f"{p:.3f}" for p in out["class_probs"][0]])

    # 2 — approximated special functions (paper §5.2.2), via the Router API:
    #     one spec field, same call site.
    router_apx = build_router(RouterSpec(iterations=cfg.routing_iters,
                                         use_approx=True))
    out_apx = capsnet.forward(params, images, cfg, router=router_apx)
    drift = float(jnp.abs(out["class_probs"] - out_apx["class_probs"]).max())
    same = bool(jnp.all(jnp.argmax(out["class_probs"], -1)
                        == jnp.argmax(out_apx["class_probs"], -1)))
    print(f"approx routing: max prob drift {drift:.4f}, "
          f"same classification: {same}")

    # 3 — the execution-score planner (paper §5.1.2, S = 1/(aE + bM)), and
    #     plan="auto": build_router runs the same planner internally and
    #     picks the sharded dimension itself.
    caps_mn1 = CAPS_BENCHMARKS["Caps-MN1"]
    s = D.RPShape.from_caps_config(caps_mn1)
    for dev_name, dev in [("HMC 32 vaults (paper Table 4)", D.DeviceModel.hmc()),
                          ("TPU v5e 256 chips", D.DeviceModel.tpu_v5e(256))]:
        table = D.score_table(s, dev)
        pick = D.plan(s, dev)
        auto_router = build_router(
            RouterSpec(iterations=s.iters),
            ExecutionPlan(auto=True, device=dev, rp_shape=s))
        auto_axes = auto_router.resolve(
            jnp.zeros((s.n_b, s.n_l, s.n_h, s.c_h)))
        print(f"planner[{dev_name}]: scores "
              + ", ".join(f"{d}={v:.3g}" for d, v in table.items())
              + f" -> distribute on {pick}; plan='auto' resolves "
              + f"{auto_axes or 'unsharded'}")

    # 4 — fused-kernel backend (Pallas; the capability check selects
    #     interpret mode off-TPU): unsharded, the whole-procedure
    #     megakernel.
    router_fused = build_router(RouterSpec(iterations=cfg.routing_iters,
                                           backend="pallas"))
    out_fused = capsnet.forward(params, images, cfg, router=router_fused)
    err = float(jnp.abs(out["v"] - out_fused["v"]).max())
    print(f"pallas backend vs jnp backend routing: max |dv| = {err:.2e}")

    # 5 — the deep-edge tier (DESIGN.md §Quantized-routing): int8 û codes
    #     quarter the megakernel's dominant DMA term, early exit freezes
    #     converged capsule tiles. Inference-only, accuracy-gated
    #     (bench_accuracy: top-1 within 0.5pt of fp32).
    router_edge = build_router(RouterSpec(iterations=cfg.routing_iters,
                                          backend="pallas",
                                          stream_dtype="int8",
                                          early_exit_eps=0.05))
    out_edge = capsnet.forward(params, images, cfg, router=router_edge)
    drift = float(jnp.abs(out["class_probs"]
                          - out_edge["class_probs"]).max())
    agree = float(jnp.mean((jnp.argmax(out["class_probs"], -1)
                            == jnp.argmax(out_edge["class_probs"], -1))
                           .astype(jnp.float32)))
    print(f"deep edge {router_edge.resolve()}: max prob drift {drift:.4f}, "
          f"top-1 agreement {agree:.0%} (untrained smoke weights — the "
          f"trained gate lives in bench_accuracy)")
    # the megakernel's own work counter: effective tile-iterations done vs
    # the fixed iterations x L_tiles grid, as eps loosens (eps=0 is
    # bit-identical full work; huge eps freezes every tile after its
    # mandatory first two passes)
    from repro.kernels.routing import ops as rt_ops
    u_hat = capsnet.encode_votes(params, images, cfg)
    B, L, H, C = u_hat.shape
    lt = rt_ops.procedure_l_tile(B, L, H, C, "fp32", early_exit=True)
    full = cfg.routing_iters * (L // lt)
    effs = {}
    for eps in (0.0, 8.0, 1e6):
        _, eff = rt_ops.dynamic_routing_procedure_stats(
            u_hat, iterations=cfg.routing_iters, l_tile=lt,
            early_exit_eps=eps)
        effs[eps] = int(eff)
    print(f"early-exit work (l_tile={lt}): "
          + ", ".join(f"eps={eps:g}: {e}/{full}"
                      for eps, e in effs.items())
          + " tile-iterations")


if __name__ == "__main__":
    main()
