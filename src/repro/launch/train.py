"""Production training launcher.

Wires the full runtime: mesh construction, logical-axis sharding rules,
elastic checkpoint resume (possibly on a different device count),
step-indexed sharded data loading with prefetch, gradient accumulation,
async checkpointing, straggler watchdog, and the XLA flags that enable
compute/communication overlap on TPU.

On a real pod:
    python -m repro.launch.train --arch granite-3-2b --steps 10000 \
        --global-batch 256 --seq 4096 --ckpt-dir gs://...

In this container (single CPU device) it runs the same code path with the
smoke config and a 1-device mesh:
    PYTHONPATH=src python -m repro.launch.train --smoke --steps 20
"""
import os

# Latency-hiding scheduler flags (TPU): overlap collective issue with
# compute; harmless no-ops on CPU.  Set before jax import.
os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true")

import argparse      # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import compat                           # noqa: E402

import repro.configs as C                          # noqa: E402
from repro import checkpoint as ck                 # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.data.synthetic import (SyntheticLMDataset,      # noqa: E402
                                  lm_batch_iterator)
from repro.models import lm                        # noqa: E402
from repro.optim import AdamWConfig, adamw_init    # noqa: E402
from repro.runtime import elastic, sharding as sh, train_loop  # noqa: E402
from repro.runtime.mesh_utils import dp_size       # noqa: E402
from repro.runtime.straggler import Prefetcher, StepWatchdog  # noqa: E402


def build_mesh(args) -> jax.sharding.Mesh:
    n = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        names = ("pod", "data", "model")[-len(shape):]
        return compat.make_mesh(shape, names)
    # default: all devices on "data", no TP (single-host dev loop)
    return compat.make_mesh((n, 1), ("data", "model"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=C.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU dev loop)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="",
                    help="comma mesh shape, e.g. 16,16 or 2,16,16")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = C.get_smoke_config(args.arch) if args.smoke \
        else C.get_config(args.arch)
    mesh = build_mesh(args)
    rules = sh.make_rules(cfg, mesh, "train")
    sh.batch_shape_check(cfg, mesh, args.global_batch, "train")
    print(f"mesh {dict(mesh.shape)} | {cfg.name} | dp={dp_size(mesh)} "
          f"| microbatches={args.microbatches}")

    key = jax.random.PRNGKey(0)
    if args.ckpt_dir:
        params, opt, start, rules = elastic.resume_or_init(
            cfg, mesh, args.ckpt_dir, key)
    else:
        params = lm.init_params(cfg, key)
        shardings = lm.param_shardings(cfg, rules)
        params = jax.tree.map(jax.device_put, params, shardings)
        opt, start = adamw_init(params), 0
    if start:
        print(f"resumed at step {start}")

    step_fn = jax.jit(train_loop.make_train_step(
        cfg, rules, opt_cfg=AdamWConfig(lr=args.lr),
        num_microbatches=args.microbatches, total_steps=args.steps,
        compress_grads=args.compress_grads), donate_argnums=(0, 1))

    ds = SyntheticLMDataset(vocab=cfg.vocab, seq_len=args.seq)
    data = Prefetcher(lm_batch_iterator(ds, args.global_batch,
                                        start_step=start), depth=2)
    ckpt = ck.AsyncCheckpointer(args.ckpt_dir, keep=3) if args.ckpt_dir \
        else None
    wd = StepWatchdog(on_slow=lambda s, dt, med: print(
        f"[watchdog] step {s}: {dt:.2f}s (median {med:.2f}s)"))
    batch_sharding = NamedSharding(mesh, rules.spec("batch"))

    def shard_batch(b):
        out = {}
        n, gb = args.microbatches, args.global_batch
        for k, v in b.items():
            v = jnp.asarray(v)
            if n > 1:
                v = v.reshape(n, gb // n, *v.shape[1:])
            out[k] = jax.device_put(v, NamedSharding(
                mesh, P(*((None,) if n > 1 else ()),
                        *batch_sharding.spec)))
        return out

    t0 = time.time()
    for i in range(start, args.steps):
        wd.start(i)
        params, opt, metrics = step_fn(params, opt, shard_batch(next(data)))
        wd.stop()
        if (i + 1) % 10 == 0:
            print(f"step {i + 1:5d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.2f}  "
                  f"{(i + 1 - start) / (time.time() - t0):.2f} it/s")
        if ckpt and (i + 1) % args.ckpt_every == 0:
            ckpt.save(i + 1, {"params": params, "opt": opt})
    if ckpt:
        ckpt.wait()
    print("done")


if __name__ == "__main__":
    main()
