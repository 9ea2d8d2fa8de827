"""Production serving launcher: continuous-batching greedy decoding.

Builds the serve-mode sharding rules (flash-decoding cache layout:
sequence-sharded KV over "model", batch over DP), prefills incoming
requests, and steps the decode loop with slot-level request swap-in —
the runtime shape of the decode_32k / long_500k dry-run cells.

    PYTHONPATH=src python -m repro.launch.serve --smoke --requests 8
"""
import argparse
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro import compat
from repro.compile_cache import enable_compile_cache
from repro.models import lm
from repro.runtime import serve_loop, sharding as sh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=C.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = C.get_smoke_config(args.arch) if args.smoke \
        else C.get_config(args.arch)
    n = len(jax.devices())
    mesh = compat.make_mesh((n, 1), ("data", "model"))
    rules = sh.make_rules(cfg, mesh, "decode") if n > 1 else None

    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    max_len = args.prompt_len + args.gen \
        + (cfg.n_img_tokens if cfg.family == "vlm" else 0)

    def make_request(i):
        req = {"tokens": jax.random.randint(
            jax.random.fold_in(key, i), (args.prompt_len,), 0, cfg.vocab)}
        if cfg.family == "vlm":
            req["image_embeds"] = jnp.zeros(
                (cfg.n_img_tokens, cfg.d_model), jnp.float32)
        if cfg.enc_dec:
            req["frames"] = jax.random.normal(
                jax.random.fold_in(key, 1000 + i),
                (cfg.source_len, cfg.d_model), jnp.float32)
        return req

    requests = [make_request(i) for i in range(args.requests)]
    t0 = time.time()
    results = []
    for lo in range(0, len(requests), args.batch):
        group = requests[lo:lo + args.batch]
        batch = {k: jnp.stack([r[k] for r in group])
                 for k in group[0]}
        out, stats = serve_loop.generate(params, cfg, batch,
                                         max_new_tokens=args.gen)
        results.extend(list(out))
    dt = time.time() - t0
    total_toks = sum(len(r) for r in results)
    print(f"{cfg.name}: served {args.requests} requests "
          f"({total_toks} tokens) in {dt:.1f}s "
          f"({total_toks / dt:.0f} tok/s on this host)")
    for i, r in enumerate(results[:3]):
        print(f"  request {i}: {r[:12].tolist()}")


if __name__ == "__main__":
    main()
