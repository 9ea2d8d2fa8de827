"""Distributed training step: grad accumulation + AdamW + clip (+compression).

``make_train_step`` returns a jit-able pure function
    (params, opt_state, batch) -> (params, opt_state, metrics)
with microbatch gradient accumulation via ``lax.scan`` — the per-microbatch
reduced (sharded) gradients let XLA overlap the reduction of microbatch i
with the backward of i+1 (latency-hiding scheduler; flags in launch/train.py).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import lm
from repro.models.layers import AxisRules, NO_RULES
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, linear_warmup_cosine)
from repro.runtime import compression


def make_train_step(cfg: lm.ArchConfig, rules: AxisRules = NO_RULES,
                    opt_cfg: Optional[AdamWConfig] = None,
                    num_microbatches: int = 1,
                    max_grad_norm: float = 1.0,
                    total_steps: int = 10_000, warmup: int = 100,
                    compress_grads: bool = False) -> Callable:
    """Build the train step.  Batch layout:
       num_microbatches == 1: {tokens (B,S), labels (B,S), ...}
       num_microbatches  > 1: {tokens (n,mb,S), ...} — scanned.

    opt_cfg: None -> a fresh ``AdamWConfig()`` per call.  (Never a shared
    default instance — the PR-5 shared-``ServeConfig`` bug class: a single
    module-level default object leaking across independently built steps.)
    """
    if opt_cfg is None:
        opt_cfg = AdamWConfig()
    # Gradients (and the accumulation buffer) must carry the parameters'
    # sharding: without the constraint XLA is free to replicate the fp32
    # accumulator, which costs param_count*4 bytes *per device* (observed
    # +10GiB/dev on granite-3-2b before this constraint existed).
    param_sharding = (lm.param_shardings(cfg, rules)
                      if rules.enabled and rules.mesh is not None else None)

    def _constrain_like_params(tree):
        if param_sharding is None:
            return tree
        return jax.tree.map(lax.with_sharding_constraint, tree,
                            param_sharding)

    def loss_for(params, microbatch):
        loss, metrics = lm.loss_fn(params, cfg, microbatch, rules)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def train_step(params, opt_state, batch, error_fb=None):
        if num_microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = _constrain_like_params(grads)
        else:
            def micro(carry, mb):
                acc = carry
                (l, m), g = grad_fn(params, mb)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), acc, g)
                return _constrain_like_params(acc), (l, m)

            zeros = _constrain_like_params(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            grads, (losses, metrics) = lax.scan(micro, zeros, batch)
            grads = jax.tree.map(lambda g: g / num_microbatches, grads)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, metrics)

        if compress_grads and error_fb is not None:
            grads, error_fb = compression.compress_grads_with_feedback(
                grads, error_fb)

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        # schedule indexed by the step being taken (1-based): step 0 of a
        # 0-based index has lr == 0 and silently wastes the first batch.
        lr_scale = linear_warmup_cosine(opt_state.step + 1, warmup,
                                        total_steps)
        params, opt_state = adamw_update(grads, opt_state, params, opt_cfg,
                                         lr_scale)
        out_metrics = {"loss": loss, "grad_norm": gnorm,
                       "lr_scale": lr_scale, **metrics}
        if compress_grads:
            return params, opt_state, out_metrics, error_fb
        return params, opt_state, out_metrics

    train_step.opt_cfg = opt_cfg     # introspection: which config this
    return train_step                # step was built with (tests/benches)


def init_train_state(cfg: lm.ArchConfig, key):
    params = lm.init_params(cfg, key)
    return params, adamw_init(params)


# ---------------------------------------------------------------------------
# CapsNet training step (paper workload, Router API)
# ---------------------------------------------------------------------------

def make_capsnet_train_step(caps_cfg, spec=None, plan=None,
                            opt_cfg: Optional[AdamWConfig] = None,
                            max_grad_norm: float = 1.0,
                            total_steps: int = 10_000, warmup: int = 100
                            ) -> Callable:
    """Build a jit-able CapsNet train step over the unified Router API.

    spec/plan go to ``core.router.build_router`` with
    ``differentiable=True`` stamped on the spec (DESIGN.md §Training) —
    grads are about to flow through the router, so the pallas backend must
    resolve to the fused form that HAS a backward (the procedure
    megakernel's custom VJP) rather than a forward-only
    kernel:

      spec=None, plan=None      exact jnp routing (the autodiff reference)
      spec=None, plan="auto"    pallas procedure megakernel + custom VJP
                                (auto plans resolve shard-local when
                                differentiable)
      RouterSpec(...)           as given, ``_replace(differentiable=True)``
      prebuilt Router           used as-is (plan must be None); the caller
                                owns its differentiability

    opt_cfg: None -> a fresh ``AdamWConfig()`` per call (never a shared
    default instance).  The same AdamW + clip + warmup-cosine machinery as
    the LM step.  Returned signature:
        (params, opt_state, images, labels) -> (params, opt_state, metrics)
    The built step exposes ``train_step.router`` / ``train_step.opt_cfg``
    so callers can inspect the resolved execution (e.g.
    ``train_step.router.resolve(votes).differentiable``).
    """
    from repro.core import router as router_lib
    from repro.models import capsnet

    if opt_cfg is None:
        opt_cfg = AdamWConfig()
    if spec is None:
        # plan=None keeps the historical jnp default; any actual plan
        # (auto or explicit) asks for the pallas backend and therefore the
        # differentiable fused resolution
        spec = router_lib.RouterSpec(
            backend="jnp" if plan is None else "pallas",
            iterations=caps_cfg.routing_iters, differentiable=True)
    elif isinstance(spec, router_lib.RouterSpec):
        spec = spec._replace(differentiable=True)
    router = router_lib.as_router(
        spec, plan, default_iterations=caps_cfg.routing_iters)

    def loss_for(params, images, labels):
        return capsnet.loss_fn(params, images, labels, caps_cfg,
                               router=router)

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def train_step(params, opt_state, images, labels):
        (loss, metrics), grads = grad_fn(params, images, labels)
        with jax.named_scope("train.clip"):
            grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        with jax.named_scope("train.adamw"):
            lr_scale = linear_warmup_cosine(opt_state.step + 1, warmup,
                                            total_steps)
            params, opt_state = adamw_update(grads, opt_state, params,
                                             opt_cfg, lr_scale)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "lr_scale": lr_scale, **metrics}

    train_step.router = router       # resolved execution is inspectable
    train_step.opt_cfg = opt_cfg     # (and regression-testable: no shared
    return train_step                # default config across built steps)
