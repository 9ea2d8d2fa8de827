"""Training driver: the program's jitted CapsNet train step, back to back.

Set-up makes the weights and a pool of ``pool_batches`` batches on the
device from the seed, builds ONE compiled step (``make_capsnet_train_step``
with the mix's plan, its parameters and optimizer state donated as the
program's own launcher jits it) and drives it through the first
``check_steps`` steps on batches 0, 1, 2, ...: those steps compile (or
load) the program and give the readings the reference is compared with.
The same step and state then run the window: steps are dispatched back to
back, at most ``in_flight`` ahead of the one waited for (a few seconds of
steps, so that a stall of the host leaves the device fed), on the pool's
batches in turn.  When the window's time is up nothing more is sent; the
rate counts every step sent over the time until the last of them is done.

Compared with the reference after the window (see bench/check.py): each
first step's loss, the first clipped gradient as AdamW received it
(recovered from its first moment: mu_1 / (1 - b1)) and the parameters'
change over the first steps, both per leaf by norm.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, reference, trace as trace_lib, work
from bench.common import (Context, Outcome, annotate, caps_config,
                          memory_peak_bytes, path_readings)
from repro.optim import AdamWConfig, adamw_init
from repro.runtime.train_loop import make_capsnet_train_step


def make_pool(cfg, batches: int, batch: int, seed: int):
    """Images uniform in [0, 1) and labels, made on the device."""
    hw, ch, n_h = cfg["image_hw"], cfg["image_channels"], cfg["num_h_caps"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.uniform(k1, (batches, batch, hw, hw, ch)),
                jax.random.randint(k2, (batches, batch), 0, n_h))
    return make(jax.random.fold_in(jax.random.key(seed % (2 ** 32)), 3))


def program_step(cfg, mix):
    """The program's train step for the configuration, not yet jitted."""
    tr = cfg["train"]
    opt_cfg = AdamWConfig(lr=tr["lr"], b1=tr["b1"], b2=tr["b2"],
                          eps=tr["eps"], weight_decay=tr["weight_decay"])
    return make_capsnet_train_step(
        caps_config(cfg), plan=mix["plan"], opt_cfg=opt_cfg,
        max_grad_norm=tr["max_grad_norm"], total_steps=tr["total_steps"],
        warmup=tr["warmup"])


def resolved_form(cfg, mix):
    """The routing kernel form the program's step resolves at the batch:
    "procedure" only where gradients run through the fused kernel's
    custom VJP, None where the router fell back to jnp autodiff."""
    votes = jax.ShapeDtypeStruct((cfg["table1_batch"], cfg["num_l_caps"],
                                  cfg["num_h_caps"], cfg["h_caps_dim"]),
                                 jnp.float32)
    return program_step(cfg, mix).router.resolve(votes).fusion


def build_step(cfg, mix, ctx: Context):
    """The compiled step the window drives; for the control, the
    reference step in bfloat16 with the program's AdamW state layout; for
    the ``half_batch`` fault, the program's step on the first half of
    each batch."""
    if ctx.control == "bf16":
        def control(params, opt, images, labels):
            p, (t, mu, nu), loss, _ = reference.train_step(
                params, (opt.step, opt.mu, opt.nu), images, labels, cfg,
                jnp.bfloat16)
            return p, opt._replace(step=t, mu=mu, nu=nu), {"loss": loss}
        return jax.jit(control)
    step = jax.jit(program_step(cfg, mix), donate_argnums=(0, 1))
    if ctx.control == "half_batch":
        half = cfg["table1_batch"] // 2
        return lambda p, o, x, y: step(p, o, x[:half], y[:half])
    return step


def run(ctx: Context) -> Outcome:
    cfg, mix = ctx.config, ctx.mix
    batch = cfg["table1_batch"]
    params0 = reference.init_params(cfg, ctx.seed)
    images, labels = make_pool(cfg, mix["pool_batches"], batch, ctx.seed)
    step = build_step(cfg, mix, ctx)

    # set-up: the first steps, through the window's own call and feed; the
    # step donates its inputs, so what is compared is read back first
    params, opt = jax.tree.map(jnp.copy, params0), adamw_init(params0)
    losses, mu1 = [], None
    for i in range(mix["check_steps"]):
        params, opt, metrics = step(params, opt, images[i], labels[i])
        losses.append(metrics["loss"])
        if i == 0:
            mu1 = jax.device_get(opt.mu)
    params_after = jax.device_get(params)
    setup_s = time.perf_counter() - ctx.t_start

    window = (min(ctx.seconds, mix["trace_seconds"]) if ctx.trace
              else ctx.seconds)
    trace_dir = os.path.join(ctx.scratch_dir, "trace")
    tracer = (trace_lib.capture(trace_dir) if ctx.trace
              else contextlib.nullcontext())
    n_pool, steps = mix["pool_batches"], 0
    in_flight = collections.deque()
    with tracer, annotate(ctx, "bench.window"):
        w0 = time.perf_counter()
        w_end = w0 + window
        i = mix["check_steps"]
        while time.perf_counter() < w_end:
            with annotate(ctx, "bench.train_dispatch"):
                params, opt, metrics = step(params, opt, images[i % n_pool],
                                            labels[i % n_pool])
            in_flight.append(metrics["loss"])
            if len(in_flight) > mix["in_flight"]:
                with annotate(ctx, "bench.train_wait"):
                    in_flight.popleft().block_until_ready()
            i += 1
            steps += 1
        with annotate(ctx, "bench.train_wait"):
            jax.block_until_ready((params, opt))
        t_end = time.perf_counter()
    elapsed = t_end - w0
    final_loss = float(in_flight[-1]) if in_flight else float("nan")
    ctx.log(f"train steps {steps} in {elapsed!r} s, last loss "
            f"{final_loss!r}")

    summary = None
    if ctx.trace:
        summary = trace_lib.reduce(trace_lib.load(trace_dir))
    peak = memory_peak_bytes(jax.devices()[:ctx.chips])
    call = work.routing_call(cfg, batch)
    counters = {
        "window": (w0, t_end),
        "steps": steps,
        "images": steps * batch,
        "wave_wall_s": elapsed,
        "model_flops": steps * batch * work.train_flops_per_image(cfg,
                                                                 batch),
        "routing_fwd": (call, steps),
        "routing_bwd": (call, steps),
    }

    got = {"losses": [float(x) for x in losses],
           "grads": [np.asarray(m) / (1 - cfg["train"]["b1"])
                     for m in jax.tree.leaves(mu1)],
           "update": [np.asarray(a) - np.asarray(b) for a, b in
                      zip(jax.tree.leaves(params_after),
                          jax.tree.leaves(params0))]}
    del params, opt, metrics, in_flight, step, mu1, params_after
    gc.collect()
    readings = compare(got, params0, images, labels, cfg,
                       mix["check_steps"], cfg["reference_precision"])
    counters["gaps_at"] = {
        p: {n: v for n, v, _ in compare(got, params0, images, labels, cfg,
                                        mix["check_steps"], p)}
        for p in ctx.ref_precisions}
    readings += path_readings(cfg["train"]["router_form"],
                              resolved_form(cfg, mix), summary)
    return Outcome(attempted=steps, failed=0, setup_s=setup_s,
                   end_to_end={"train_images_per_s": steps * batch
                               / elapsed},
                   readings=readings, counters=counters,
                   memory_peak_bytes=peak, trace=summary)


def reference_readings(params0, images, labels, cfg, n_steps: int,
                       precision: str) -> dict:
    """The reference's losses, first clipped gradient and change, at
    matmul ``precision``."""
    ref_step = jax.jit(lambda p, s, x, y: reference.train_step(
        p, s, x, y, cfg, precision=precision))
    p, s = params0, reference.init_state(params0)
    losses, grads = [], None
    for i in range(n_steps):
        p, s, loss, g = ref_step(p, s, images[i], labels[i])
        losses.append(float(loss))
        if i == 0:
            grads = [np.asarray(x) for x in jax.tree.leaves(g)]
    update = [np.asarray(a) - np.asarray(b) for a, b in
              zip(jax.tree.leaves(p), jax.tree.leaves(params0))]
    return {"losses": losses, "grads": grads, "update": update}


def compare(got: dict, params0, images, labels, cfg, n_steps: int,
            precision: str):
    ref = reference_readings(params0, images, labels, cfg, n_steps,
                             precision)
    keep = check.moving_leaves(ref["grads"])
    lim = cfg["limits"]
    return [
        ("loss_gap", check.loss_gap(got["losses"], ref["losses"]),
         lim["loss_gap"]),
        ("grad_gap", check.norm_gap(got["grads"], ref["grads"]),
         lim["grad_gap"]),
        ("update_gap", check.norm_gap(got["update"], ref["update"], keep),
         lim["update_gap"]),
    ]
