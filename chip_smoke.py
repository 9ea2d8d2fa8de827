"""Chip smoke test: drive the CapsNet main path once on a TPU.

    python chip_smoke.py                # serve, serve-EM and train on one chip
    python chip_smoke.py --four-chips   # sharded serving over four chips only

One process runs every phase (a chip belongs to one process).  Each phase
prints its resolved routing plan, compile seconds, wave latency or step
time, and max |Δ| against the jnp reference:

* serve     Caps-MN1 (Table 1) at full width with random weights from
            ``--seed``: ``CapsServer`` over ``RouterSpec(backend="pallas")``
            — the procedure megakernel — and the launcher's
            ``ServeConfig(microbatch=8, n_micro=4, pipeline="software")``;
            64 synthetic requests through ``repro.launch.serve_caps``'s
            ``arrival_schedule`` / ``run_sync``.  All must complete with no
            wave error and no output-guard trip (a trip would hide a broken
            kernel behind the jnp re-run); one wave's class scores must
            match the jnp reference wave within 1e-4 with identical argmax,
            and the routing stage's own output on one microbatch of votes
            must match the jnp reference router within 1e-4.
* serve-em  one wave of the same server with ``algorithm="em"`` (the EM
            stage kernels), same checks — the router check covers the EM
            pose as well as the activations the scores read.
* train     3 steps of ``make_capsnet_train_step(Caps-MN1, plan="auto")``
            at batch 100: the router must resolve to the differentiable
            megakernel, losses must be finite, and the first step's
            gradients must match the jnp step's within 1e-4.
* --four-chips  the serve phase with ``routing_plan="auto"`` over all four
            chips: the planner must shard a dimension and the stage-split
            kernels must run; scores are compared with the unsharded jnp
            reference on one device.  No other phase runs.

Both sides of every comparison run under
``jax.default_matmul_precision("highest")`` — TPU's default fp32 matmul
precision would otherwise make the reference the inexact side; the
routing kernels fix their own in-kernel precision.

The script exits non-zero before any phase when JAX finds no TPU or the
Pallas kernels would run in interpret mode, and whenever a phase fails.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import kernels  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS  # noqa: E402
from repro.core import router as router_lib  # noqa: E402
from repro.data.synthetic import SyntheticCapsDataset  # noqa: E402
from repro.launch.serve_caps import arrival_schedule, run_sync  # noqa: E402
from repro.models import capsnet  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.runtime.caps_serve import (CapsAdapter, CapsServer,  # noqa: E402
                                      ServeConfig)
from repro.runtime.train_loop import make_capsnet_train_step  # noqa: E402

NETWORK = "Caps-MN1"
REQUESTS = 64
LOAD = 0.75            # the serve launcher's default offered load
TOL = 1e-4             # the repo's fp32 parity gate (tests/_gradcheck.py)


class PhaseFailed(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _votes_struct(caps_cfg, batch: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(
        (batch, caps_cfg.num_l_caps, caps_cfg.num_h_caps,
         caps_cfg.h_caps_dim), jnp.float32)


def _compile(phase: str, fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    log(phase, f"compile_s={time.perf_counter() - t0:.3f}")
    return compiled


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _compare_wave(phase, adapter, cfg, ref_cfg, packed):
    """Scores of one wave vs the jnp reference wave, both at highest
    matmul precision: max |Δ| and argmax identity over every lane."""
    with jax.default_matmul_precision("highest"):
        got = np.asarray(adapter.make_wave_fn(cfg)(packed))
        ref = np.asarray(adapter.make_reference_wave_fn(ref_cfg)(packed))
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          "non-finite wave scores")
    delta = float(np.max(np.abs(got - ref)))
    same = int((got.argmax(-1) == ref.argmax(-1)).sum())
    lanes = got.shape[0] * got.shape[1]
    log(phase, f"max|d_score|={delta!r} (tol {TOL}), argmax identical "
               f"{same}/{lanes}")
    check(delta <= TOL, f"max |Δ score| {delta} > {TOL}")
    check(same == lanes, f"argmax differs on {lanes - same} lanes")


def _compare_router(phase, adapter, plan, micro):
    """The routing stage alone on one microbatch of encoded votes — every
    output leaf (dynamic: v; EM: pose and activations) vs the jnp
    reference router, both at highest matmul precision."""
    spec = adapter.spec
    with jax.default_matmul_precision("highest"):
        votes = capsnet.encode_votes(adapter.params, micro["images"],
                                     adapter.caps_cfg)
        votes = votes * micro["mask"][:, None, None, None]
        args = ((votes, jnp.broadcast_to(micro["mask"][:, None],
                                         votes.shape[:2]))
                if spec.algorithm == "em" else (votes,))
        got = jax.jit(router_lib.build_router(spec, plan))(*args)
        ref = jax.jit(router_lib.build_router(
            router_lib.reference_spec(spec)))(*args)
    delta = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)))
    log(phase, f"router outputs max|d|={delta!r} (tol {TOL})")
    check(delta <= TOL, f"router output max |Δ| {delta} > {TOL}")


def _check_metrics(phase, server, expect: int):
    s = server.metrics.summary()
    log(phase, f"completed {s['completed']}/{expect}, failed {s['failed']}, "
               f"wave_errors {s['wave_errors']}, guard_trips "
               f"{s['guard_trips']}, waves {s['waves']}, p50_latency_s "
               f"{s['p50_latency_s']!r}")
    check(s["completed"] == expect and server.pending() == 0,
          f"{s['completed']}/{expect} requests completed")
    check(s["failed"] == 0, f"{s['failed']} requests failed")
    check(s["wave_errors"] == 0, f"wave errors: {s['last_error']}")
    check(s["guard_trips"] == 0,
          "output guard tripped: a non-finite wave was re-run through "
          "the jnp reference")


def _serve(phase: str, spec, cfg: ServeConfig, ref_cfg: ServeConfig,
           seed: int, requests: int, expect_fusion: str, sharded: bool):
    caps_cfg = CAPS_BENCHMARKS[NETWORK]
    params = capsnet.init_capsnet(jax.random.PRNGKey(seed), caps_cfg)
    adapter = CapsAdapter(params, caps_cfg, spec)
    ds = SyntheticCapsDataset(caps_cfg.image_hw, caps_cfg.image_channels,
                              caps_cfg.num_h_caps, seed=seed)

    votes = _votes_struct(caps_cfg, cfg.microbatch)
    args = ((votes, jax.ShapeDtypeStruct(votes.shape[:2], jnp.float32))
            if spec.algorithm == "em" else (votes,))
    plan = (router_lib.ExecutionPlan(auto=True)
            if cfg.routing_plan == "auto" else None)
    resolved = router_lib.build_router(spec, plan).resolve(*args)
    log(phase, f"plan {resolved!r} on {len(jax.devices())} device(s)")
    check(resolved.fusion == expect_fusion,
          f"fusion {resolved.fusion!r}, expected {expect_fusion!r}")
    check(bool(tuple(resolved)) == sharded,
          f"axes {tuple(resolved)} (sharded expected: {sharded})")

    wave_fn = _compile(phase, adapter.make_wave_fn(cfg),
                       adapter.pack([], cfg))
    probe = adapter.pack(list(ds.batch(10 ** 6, cfg.wave_lanes)["images"]),
                         cfg)
    _timed(wave_fn, probe)
    _, wave_s = _timed(wave_fn, probe)
    log(phase, f"wave_latency_s={wave_s!r} ({cfg.n_micro} x "
               f"{cfg.microbatch} lanes)")

    server = CapsServer(params, caps_cfg, spec=spec, cfg=cfg,
                        wave_fn=wave_fn)
    if requests == cfg.wave_lanes:
        server.submit(ds.batch(0, requests)["images"])
        done = server.step()
    else:
        schedule = arrival_schedule(requests,
                                    max(1.0, LOAD * cfg.wave_lanes), seed)
        done = run_sync(server, ds, schedule)
    check(len(done) == requests, f"{len(done)}/{requests} completions")
    _check_metrics(phase, server, requests)
    _compare_wave(phase, adapter, cfg, ref_cfg, probe)
    _compare_router(phase, adapter, plan,
                    jax.tree.map(lambda a: a[0], probe))


def phase_serve(seed: int) -> None:
    cfg = ServeConfig(microbatch=8, n_micro=4, pipeline="software")
    spec = router_lib.RouterSpec(backend="pallas", iterations=3)
    _serve("serve", spec, cfg, cfg, seed, REQUESTS, "procedure",
           sharded=False)


def phase_serve_em(seed: int) -> None:
    cfg = ServeConfig(microbatch=8, n_micro=4, pipeline="software")
    spec = router_lib.RouterSpec(algorithm="em", backend="pallas",
                                 iterations=3)
    _serve("serve-em", spec, cfg, cfg, seed, cfg.wave_lanes, "stage_split",
           sharded=False)


def phase_serve_four_chips(seed: int) -> None:
    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = ServeConfig(microbatch=8, n_micro=4, pipeline="software",
                      routing_plan="auto")
    spec = router_lib.RouterSpec(backend="pallas", iterations=3)
    # the reference is the unsharded jnp wave on one device
    _serve("serve-4chip", spec, cfg,
           dataclasses.replace(cfg, routing_plan=None), seed, REQUESTS,
           "stage_split", sharded=True)


def phase_train(seed: int, steps: int = 3) -> None:
    phase = "train"
    caps_cfg = CAPS_BENCHMARKS[NETWORK]
    batch = caps_cfg.batch_size
    step = make_capsnet_train_step(caps_cfg, plan="auto")
    resolved = step.router.resolve(_votes_struct(caps_cfg, batch))
    log(phase, f"plan {resolved!r}")
    check(resolved.fusion == "procedure" and resolved.differentiable
          and not tuple(resolved),
          "train router did not resolve to the differentiable megakernel")

    params = capsnet.init_capsnet(jax.random.PRNGKey(seed), caps_cfg)
    opt_state = adamw_init(params)
    data = SyntheticCapsDataset(caps_cfg.image_hw, caps_cfg.image_channels,
                                caps_cfg.num_h_caps,
                                seed=seed).batch(0, batch)
    images = jnp.asarray(data["images"])
    labels = jnp.asarray(data["labels"])

    compiled = _compile(phase, jax.jit(step), params, opt_state, images,
                        labels)
    p, o, losses, times = params, opt_state, [], []
    for _ in range(steps):
        (p, o, metrics), dt = _timed(compiled, p, o, images, labels)
        losses.append(float(metrics["loss"]))
        times.append(dt)
    log(phase, f"losses={losses!r} step_s={times!r}")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")

    jnp_router = router_lib.build_router(router_lib.RouterSpec(
        iterations=caps_cfg.routing_iters, differentiable=True))

    def grads(router):
        return jax.jit(jax.grad(lambda q: capsnet.loss_fn(
            q, images, labels, caps_cfg, router=router)[0]))(params)

    with jax.default_matmul_precision("highest"):
        got, ref = grads(step.router), grads(jnp_router)
    delta = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)))
    scale = max(float(jnp.max(jnp.abs(b))) for b in jax.tree.leaves(ref))
    log(phase, f"first-step max|d_grad|={delta!r} (tol {TOL}; max|grad| "
               f"{scale!r})")
    check(delta <= TOL, f"gradient max |Δ| {delta} > {TOL}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded serving phase over 4 chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and synthetic data")
    args = ap.parse_args()

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" or kernels.pallas_interpret_mode():
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); the "
              "Pallas kernels would run interpreted — no phase run",
              file=sys.stderr)
        return 2
    phases = ([phase_serve_four_chips] if args.four_chips
              else [phase_serve, phase_serve_em, phase_train])
    for phase in phases:
        phase(args.seed)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
