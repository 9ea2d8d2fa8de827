"""Training-step bench (DESIGN.md §Training) — differentiable fused routing.

Measures one full CapsNet train step (forward + backward + AdamW update)
through ``runtime.train_loop.make_capsnet_train_step`` in four arms:

* ``jnp``       — the autodiff reference: exact jnp routing, plain
                  ``jax.grad`` (per-iteration residuals spill as usual).
* ``jnp_dp``    — the same reference under a data-parallel ExecutionPlan
                  (B sharded over all local devices; on this container the
                  mesh has one device, so the arm documents the plumbing).
* ``fused``     — ``plan="auto"``: the procedure megakernel with its
                  custom VJP — the forward keeps û and its small c_t/s_t
                  state, the backward walks the iterations in reverse.
* ``fused_bf16``— the same with bf16 û streaming in both directions.

Gates (written into the artifact AND asserted here):

* grad parity — ``jax.grad`` of the full model loss through the fused
  router vs the jnp router, max |Δ| over the parameter tree, ≤1e-4 (fp32)
  and ≤2e-2 (bf16) — the same per-dtype tolerances as the tier-1 grad
  suite (tests/_gradcheck.py);
* one train step strictly decreases the loss in every arm;
* the modeled backward DMA bill of the fused path beats unfused autodiff.

Off-TPU every pallas arm runs in interpret mode and carries
``modeled_only``: its wall-clock documents plumbing, never hardware
performance — the perf claim is the DMA/residual model
(kernels/routing/ops.py::dma_bytes_per_call(backward=True)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from benchmarks.common import kernel_arm_stats, time_stats
from repro import compat, kernels
from repro.configs.caps_benchmarks import smoke_caps
from repro.core.router import ExecutionPlan, RouterSpec
from repro.kernels.routing import ops as rt_ops
from repro.models import capsnet
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import train_loop

GRAD_TOL = {"fp32": 1e-4, "bf16": 2e-2}    # = tests/_gradcheck.GRAD_ATOL


def _arm_specs():
    """(name, spec, plan, pallas_arm) per bench arm."""
    mesh = compat.make_mesh((len(jax.devices()),), ("data",))
    return [
        ("jnp", None, None, False),
        ("jnp_dp", RouterSpec(),
         ExecutionPlan(mesh=mesh, axes=(("B", "data"),)), False),
        ("fused", None, "auto", True),
        ("fused_bf16",
         RouterSpec(backend="pallas", stream_dtype="bf16"), None, True),
    ]


def _tree_max_abs_delta(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _grad_parity(cfg, params, images, labels, routers) -> dict:
    """max |Δ| over the full parameter-gradient tree, fused vs the jnp
    autodiff reference, per stream dtype — the bench-level gate mirroring
    the tier-1 grad suite."""
    def grads_via(router):
        return jax.grad(
            lambda p: capsnet.loss_fn(p, images, labels, cfg,
                                      router=router)[0])(params)

    g_ref = grads_via(routers["jnp"])
    fused = _tree_max_abs_delta(grads_via(routers["fused"]), g_ref)
    bf16 = _tree_max_abs_delta(grads_via(routers["fused_bf16"]), g_ref)
    out = {"fused_max_abs_param_grad_delta": fused,
           "fused_tol": GRAD_TOL["fp32"],
           "fused_pass": bool(fused <= GRAD_TOL["fp32"]),
           "bf16_max_abs_param_grad_delta": bf16,
           "bf16_tol": GRAD_TOL["bf16"],
           "bf16_pass": bool(bf16 <= GRAD_TOL["bf16"])}
    assert out["fused_pass"] and out["bf16_pass"], (
        "fused/jnp grad parity gate failed", out)
    return out


def _residual_model(B: int, L: int, H: int, C: int, iters: int) -> dict:
    """Residual-byte accounting (DESIGN.md §Training): what the forward
    must keep alive for the backward.  The custom VJP saves û plus each
    iteration's c (L,H) and s (B,H,C); jnp autodiff of the same procedure
    drags û plus the per-iteration b, c, s, v_prev and softmax/squash
    locals to HBM."""
    u = B * L * H * C * 4
    per_iter = 2 * L * H * 4 + 2 * B * H * C * 4      # b/c + s/v per iter
    return {"fused_residual_bytes": u + iters * (L * H + B * H * C) * 4,
            "unfused_residual_bytes": u + iters * per_iter,
            "per_iteration_residual_bytes": per_iter}


def main():
    cfg = smoke_caps()
    batch = 4 if common.smoke() else 16
    reps = 2 if common.smoke() else 5
    iters = cfg.routing_iters
    key = jax.random.PRNGKey(0)
    params = capsnet.init_capsnet(key, cfg)
    images = jax.random.uniform(
        jax.random.fold_in(key, 1),
        (batch, cfg.image_hw, cfg.image_hw, cfg.image_channels))
    labels = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0,
                                cfg.num_h_caps)
    votes_shape = (batch, cfg.num_l_caps, cfg.num_h_caps, cfg.h_caps_dim)
    opt_cfg = AdamWConfig(weight_decay=0.0)

    arms, routers, resolved = {}, {}, {}
    for name, spec, plan, pallas_arm in _arm_specs():
        step = train_loop.make_capsnet_train_step(
            cfg, spec=spec, plan=plan, opt_cfg=opt_cfg, warmup=1,
            total_steps=100)
        routers[name] = step.router
        rp = step.router.resolve(jnp.zeros(votes_shape))
        resolved[name] = {"fusion": rp.fusion,
                          "stream_dtype": rp.stream_dtype,
                          "differentiable": rp.differentiable,
                          "axes": list(map(list, rp))}
        step_jit = jax.jit(step)
        opt = adamw_init(params)
        p1, _, metrics = jax.block_until_ready(
            step_jit(params, opt, images, labels))
        loss_before = float(metrics["loss"])
        loss_after = float(capsnet.loss_fn(p1, images, labels, cfg,
                                           router=step.router)[0])
        stats_fn = kernel_arm_stats if pallas_arm else time_stats
        stats = stats_fn(step_jit, params, opt, images, labels, iters=reps)
        stats.update(loss_before=loss_before, loss_after=loss_after,
                     loss_decreased=bool(loss_after < loss_before))
        assert stats["loss_decreased"], (name, loss_before, loss_after)
        arms[name] = stats

    parity = _grad_parity(cfg, params, images, labels, routers)

    B, L, H, C = votes_shape
    dma = {"forward_fp32": rt_ops.dma_bytes_per_call(
               B, L, H, C, iters, form="procedure"),
           "backward_fp32": rt_ops.dma_bytes_per_call(
               B, L, H, C, iters, form="procedure", backward=True),
           "backward_bf16": rt_ops.dma_bytes_per_call(
               B, L, H, C, iters, form="procedure", stream_dtype="bf16",
               backward=True)}
    assert dma["backward_fp32"]["total_bytes"] \
        < dma["backward_fp32"]["naive_bytes"], dma
    residuals = _residual_model(B, L, H, C, iters)

    print("== CapsNet train step: fused(custom VJP) vs jnp arms ==")
    print("arm,median_s,p90_s,loss_before,loss_after,decreased,modeled_only")
    for name, s in arms.items():
        print(f"{name},{s['median_s']:.4f},{s['p90_s']:.4f},"
              f"{s['loss_before']:.4f},{s['loss_after']:.4f},"
              f"{s['loss_decreased']},{s.get('modeled_only', '-')}")
    print(f"# grad parity: fused "
          f"{parity['fused_max_abs_param_grad_delta']:.2e} (tol 1e-4), "
          f"bf16 {parity['bf16_max_abs_param_grad_delta']:.2e} (tol 2e-2)")
    print(f"# backward DMA model: fused "
          f"{dma['backward_fp32']['total_bytes']:,}B vs unfused-autodiff "
          f"{dma['backward_fp32']['naive_bytes']:,}B; residuals "
          f"{residuals['fused_residual_bytes']:,}B (û, c_t, s_t) vs "
          f"{residuals['unfused_residual_bytes']:,}B")
    print("# (interpret-mode pallas arms are modeled_only — wall-clock "
          "documents plumbing; the perf claim is the DMA/residual model)")

    return {"paper_artifact": "§5.2 applied to backprop "
                              "(DESIGN.md §Training)",
            "config": {"network": cfg.name, "batch": batch,
                       "routing_iters": iters,
                       "votes_shape": {"B": B, "L": L, "H": H, "C": C},
                       "opt": {"lr": opt_cfg.lr,
                               "weight_decay": opt_cfg.weight_decay},
                       "train_l_tile_fp32": rt_ops.procedure_train_l_tile(
                           B, L, H, C, iters, "fp32"),
                       "train_l_tile_bf16": rt_ops.procedure_train_l_tile(
                           B, L, H, C, iters, "bf16"),
                       "n_devices": len(jax.devices()),
                       "pallas_interpret": kernels.pallas_interpret_mode()},
            "arms": arms,
            "resolved": resolved,
            "grad_parity": parity,
            "dma_model": dma,
            "residual_model": residuals}


if __name__ == "__main__":
    main()
