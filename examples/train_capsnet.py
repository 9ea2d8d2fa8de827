"""End-to-end driver: train a CapsNet for a few hundred steps on the
synthetic class-conditional dataset, with the full substrate — AdamW +
schedule, routing-mode selection, async checkpointing, straggler watchdog,
step-indexed resume.

    PYTHONPATH=src python examples/train_capsnet.py --steps 200
    PYTHONPATH=src python examples/train_capsnet.py --steps 300  # resumes
    PYTHONPATH=src python examples/train_capsnet.py --smoke --routing fused

``--routing fused`` trains through the procedure megakernel's custom VJP
(DESIGN.md §Training) — the forward keeps only û and its small
per-iteration c/s state, and the backward walks the routing loop in
reverse from them.
"""
import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ck
from repro.configs.caps_benchmarks import smoke_caps
from repro.core.router import RouterSpec, build_router
from repro.data.synthetic import SyntheticCapsDataset, caps_batch_iterator
from repro.models import capsnet
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         linear_warmup_cosine)
from repro.runtime.straggler import Prefetcher, StepWatchdog


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_capsnet_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--routing", choices=("exact", "approx", "fused"),
                    default="exact")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: a dozen steps, one tiny eval batch")
    args = ap.parse_args()
    if args.smoke:
        args.steps = min(args.steps, 12)
        args.ckpt_every = min(args.ckpt_every, 6)

    cfg = smoke_caps()
    router = build_router(RouterSpec(
        iterations=cfg.routing_iters,
        use_approx=args.routing == "approx",
        backend="pallas" if args.routing == "fused" else "jnp",
        differentiable=args.routing == "fused"))
    ocfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
    key = jax.random.PRNGKey(0)

    params = capsnet.init_capsnet(key, cfg)
    opt = adamw_init(params)
    start = ck.latest_step(args.ckpt_dir)
    if start is not None:
        params = ck.load_checkpoint(args.ckpt_dir, start, params)
        print(f"resumed from step {start}")
    start = start or 0

    ds = SyntheticCapsDataset(cfg.image_hw, cfg.image_channels,
                              cfg.num_h_caps)
    data = Prefetcher(caps_batch_iterator(ds, cfg.batch_size,
                                          start_step=start), depth=2)
    ckpt = ck.AsyncCheckpointer(args.ckpt_dir, keep=2)
    watchdog = StepWatchdog(
        on_slow=lambda s, dt, med: print(
            f"  [watchdog] step {s} took {dt:.2f}s (median {med:.2f}s)"))

    @jax.jit
    def step_fn(params, opt, images, labels, lr_scale):
        (loss, m), grads = jax.value_and_grad(
            capsnet.loss_fn, has_aux=True)(params, images, labels, cfg,
                                           router=router)
        params, opt = adamw_update(grads, opt, params, ocfg, lr_scale)
        return params, opt, loss, m

    for i in range(start, args.steps):
        b = next(data)
        watchdog.start(i)
        lr_scale = linear_warmup_cosine(jnp.asarray(i + 1), 20, args.steps)
        params, opt, loss, m = step_fn(params, opt,
                                       jnp.asarray(b["images"]),
                                       jnp.asarray(b["labels"]), lr_scale)
        watchdog.stop()
        if (i + 1) % (4 if args.smoke else 20) == 0:
            print(f"step {i + 1:4d}  loss {float(loss):.4f}  "
                  f"acc {float(m['accuracy']):.3f}")
        if (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, params)
    ckpt.wait()

    # final eval
    hits = n = 0
    eval_batches, eval_bs = (1, 32) if args.smoke else (4, 64)
    for j in range(1000, 1000 + eval_batches):
        b = ds.batch(j, eval_bs)
        out = capsnet.forward(params, jnp.asarray(b["images"]), cfg,
                              router=router)
        hits += int((jnp.argmax(out["class_probs"], -1)
                     == jnp.asarray(b["labels"])).sum())
        n += eval_bs
    print(f"eval accuracy ({args.routing} routing): {hits / n:.4f}")


if __name__ == "__main__":
    main()
