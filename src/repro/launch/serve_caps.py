"""Wave-serving launcher — continuous batching over the §4 pipeline.

Drives the paper's workload (Table-1 CapsNet benchmarks) through
``repro.runtime.caps_serve`` (DESIGN.md §Serving): synthetic requests
arrive in ragged bursts, the server pads them into fixed microbatch lanes,
and every wave streams through the host‖PIM pipeline with the routing
distribution chosen by ``--plan auto`` (§5.1.2 planner).  ``--async`` runs
the threaded driver instead of the tick loop: submitter threads feed the
bounded queue concurrently while ``serve_forever`` forms waves on its own
thread; ``--algorithm em`` serves EM routing (the multi-input pipeline
stage hand-off).

``--replicas N`` / ``--tenants T`` switch to the fleet front-end
(``repro.runtime.caps_fleet``, DESIGN.md §Fleet): T tenant threads submit
concurrently to a CapsFleet of N replica servers with deadline-ordered
waves (``--slo-ms`` sets the per-request SLO), per-tenant accounting, and
— when ``--max-replicas`` exceeds N — the elastic controller scaling the
fleet between the two bounds.

``--chaos`` arms deterministic fault injection (``repro.runtime.faults``,
DESIGN.md §Faults): a seeded ``FaultPlan`` of wave exceptions and NaN
corruption — plus a replica crash in fleet mode — runs against the
hardened wave path, and the exit assertions prove the extended invariant
(``submitted == completed + shed + failed``) held: no request is ever
silently lost, only completed, shed, or failed-with-accounting.

``--model lm`` / ``--model moe`` serve the non-CapsNet workload adapters
(DESIGN.md §WaveServe) through the *same* generic wave core
(``repro.runtime.wave_serve``): LM greedy decode waves over
``LMDecodeAdapter`` and fixed-shape MoE dispatch waves over ``MoEAdapter``
(the 'moe' Router algorithm via ``build_router``) — one serving stack,
three workloads.

    PYTHONPATH=src python -m repro.launch.serve_caps --smoke
    PYTHONPATH=src python -m repro.launch.serve_caps --smoke --async
    PYTHONPATH=src python -m repro.launch.serve_caps --smoke --chaos
    PYTHONPATH=src python -m repro.launch.serve_caps --smoke --model lm
    PYTHONPATH=src python -m repro.launch.serve_caps --smoke --model moe
    PYTHONPATH=src python -m repro.launch.serve_caps --smoke \
        --replicas 2 --tenants 2 --slo-ms 2000 --chaos
    PYTHONPATH=src python -m repro.launch.serve_caps \
        --network Caps-MN1 --requests 64 --pipeline software --plan auto \
        --algorithm em --async --submitters 4
"""
import argparse
import dataclasses
import math
import threading
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS, smoke_caps
from repro.core.router import RouterSpec
from repro.data.synthetic import SyntheticCapsDataset
from repro.models import capsnet
from repro.runtime.caps_fleet import CapsFleet, TenantPolicy
from repro.runtime.caps_serve import CapsServer, ServeConfig, make_wave_fn
from repro.runtime.elastic import ElasticPolicy


def chaos_plan(args, cfg: ServeConfig, faults, crash: bool):
    """Seeded fault schedule sized to the run: enough scheduled waves to
    cover the request count twice over (retries advance the call index),
    with wave-exception and NaN-corruption rates per DESIGN.md §Faults
    and — in fleet mode — one replica crash early in the run."""
    n_waves = max(8, 2 * math.ceil(args.requests / cfg.wave_lanes) + 4)
    return faults.FaultPlan.generate(
        args.chaos_seed, n_waves, p_error=0.15, p_corrupt=0.1,
        crash_wave=1 if crash else None)


def arrival_schedule(total: int, mean_per_tick: float, seed: int = 0):
    """Deterministic ragged arrival counts summing to ``total``."""
    rng = np.random.default_rng(seed)
    counts = []
    left = total
    while left > 0:
        c = min(left, int(rng.poisson(mean_per_tick)))
        counts.append(c)
        left -= c
    return counts


def _fmt_ms(v) -> str:
    return "n/a" if v is None else f"{v * 1e3:.1f} ms"


def run_sync(server: CapsServer, ds, schedule):
    """One wave per tick (the caller-cadence loop), then drain."""
    done = []
    for tick, count in enumerate(schedule):
        if count:
            batch = ds.batch(tick, count)
            server.submit(batch["images"])
        done.extend(server.step())
    done.extend(server.drain())
    return done


def run_async(server: CapsServer, ds, schedule, n_submitters: int):
    """Threaded driver: ``serve_forever`` forms waves on a background
    thread while submitter threads feed the queue concurrently (wave
    formation decoupled from arrival cadence)."""
    stop = threading.Event()
    done = []
    driver = threading.Thread(
        target=lambda: done.extend(server.serve_forever(stop, poll_s=0.002)))
    driver.start()

    def submitter(worker: int):
        for tick, count in enumerate(schedule[worker::n_submitters]):
            if count:
                batch = ds.batch(1000 * worker + tick, count)
                server.submit(batch["images"])
            time.sleep(0.001)

    threads = [threading.Thread(target=submitter, args=(w,))
               for w in range(n_submitters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    driver.join()
    return done


def run_fleet(args, caps_cfg, params, ds, cfg: ServeConfig, spec, schedule):
    """Fleet mode: ``--tenants`` submitter threads (one per tenant) feed a
    ``--replicas``-sized CapsFleet; waves are deadline-ordered and the
    per-tenant books must balance on stop (DESIGN.md §Fleet)."""
    slo_s = None if args.slo_ms is None else args.slo_ms / 1e3
    tenants = [TenantPolicy(f"t{i}", slo_s=slo_s, priority=i % 2)
               for i in range(args.tenants)]
    max_replicas = (args.replicas if args.max_replicas is None
                    else args.max_replicas)
    wave_wrap = None
    if args.chaos:
        from repro.runtime import faults   # chaos only: lazy, opt-in
        crash = args.replicas > 1          # need a survivor to adopt
        wave_wrap = faults.fleet_wrap(
            {"default/r0": chaos_plan(args, cfg, faults, crash)})
    fleet = CapsFleet(
        params, caps_cfg, tenants=tenants,
        models={"default": (spec,
                            dataclasses.replace(cfg,
                                                queue_order="deadline"))},
        policy=ElasticPolicy(min_replicas=args.replicas,
                             max_replicas=max_replicas),
        control_interval_s=0.05,
        wave_wrap=wave_wrap)
    print(f"fleet: {args.replicas}..{max_replicas} replicas x "
          f"{args.tenants} tenants, slo="
          f"{'none' if slo_s is None else f'{args.slo_ms:.0f} ms'}, "
          f"deadline-ordered waves")
    fleet.start()

    def submitter(i: int, tenant: TenantPolicy):
        for tick, count in enumerate(schedule[i::args.tenants]):
            if count:
                batch = ds.batch(1000 * i + tick, count)
                fleet.submit(batch["images"], tenant=tenant.name)
            time.sleep(0.002)

    threads = [threading.Thread(target=submitter, args=(i, t))
               for i, t in enumerate(tenants)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = fleet.stop()

    assert s["pending"] == 0, s
    assert s["submitted"] == s["completed"] + s["shed"] + s["failed"], s
    assert s["submitted"] == args.requests, (s, args.requests)
    for name, t in s["per_tenant"].items():
        assert t["submitted"] == (t["completed"] + t["shed"] + t["failed"]
                                  + t["pending"]), (name, t)
    print(f"served {s['completed']} requests in {s['waves']} waves across "
          f"{s['replicas']} replicas ({s['shed']} shed, {s['failed']} "
          f"failed, goodput {s['goodput']}, "
          f"{len(fleet.completions)} completions)")
    if args.chaos:
        print(f"chaos: {s['wave_errors']} wave errors, {s['retried']} "
              f"retried, {s['requeued']} requeued, {s['guard_trips']} guard "
              f"trips, {s['evacuated']} evacuated -> {s['adopted']} adopted, "
              f"{len(s['health_events'])} burials")
    for name, t in s["per_tenant"].items():
        print(f"  {name}: submitted {t['submitted']}, completed "
              f"{t['completed']}, shed {t['shed']}, goodput {t['goodput']}")
    events = [e for evs in s["scale_events"].values() for e in evs]
    print(f"latency p50 {_fmt_ms(s['p50_latency_s'])}, "
          f"p90 {_fmt_ms(s['p90_latency_s'])}; "
          f"{len(events)} scale events")


def run_model_workload(args):
    """``--model lm`` / ``--model moe``: serve a non-CapsNet workload
    adapter through the generic wave core (single server, sync tick loop)
    and prove the same accounting invariant the CapsNet paths assert."""
    import jax.numpy as jnp

    from repro.runtime import wave_serve

    cfg = ServeConfig(microbatch=args.microbatch, n_micro=args.n_micro,
                      pipeline=None, max_queue=args.max_queue)
    rng = np.random.default_rng(args.chaos_seed + 1)
    if args.model == "lm":
        from repro.configs.base import get_smoke_config
        from repro.models import lm
        from repro.runtime.serve_loop import LMDecodeAdapter
        arch = get_smoke_config("granite-3-2b")
        params = lm.init_params(arch, jax.random.PRNGKey(0))
        prompt_len, max_new = 8, 4
        adapter = LMDecodeAdapter(params, arch, prompt_len=prompt_len,
                                  max_new_tokens=max_new)
        desc = (f"{arch.name}: greedy decode waves, prompt {prompt_len} "
                f"-> +{max_new} tokens")

        def make_items(count):
            return rng.integers(0, arch.vocab, (count, prompt_len),
                                dtype=np.int32)
    else:
        from repro.models import moe as moe_lib
        from repro.runtime.serve_loop import MoEAdapter
        # capacity_factor >= n_experts/top_k: nothing dropped, so padded
        # lanes can never evict real tokens (see MoEAdapter docstring)
        moe_cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, n_experts=4,
                                    top_k=2, capacity_factor=4.0)
        params = moe_lib.init_moe(jax.random.PRNGKey(0), moe_cfg,
                                  dtype=jnp.float32)
        seq_len = 8
        adapter = MoEAdapter(params, moe_cfg, seq_len=seq_len)
        desc = (f"moe-tiny: E={moe_cfg.n_experts} top{moe_cfg.top_k} "
                f"dispatch waves via RouterSpec(algorithm='moe'), "
                f"blocks ({seq_len}, {moe_cfg.d_model})")

        def make_items(count):
            return rng.standard_normal(
                (count, seq_len, moe_cfg.d_model)).astype(np.float32)

    wave_fn = None
    if args.chaos:
        from repro.runtime import faults   # chaos only: lazy, opt-in
        wave_fn = faults.chaos_wave_fn(
            adapter.make_wave_fn(cfg),
            chaos_plan(args, cfg, faults, crash=False))
    server = wave_serve.WaveServer(adapter, cfg=cfg, wave_fn=wave_fn)
    schedule = arrival_schedule(args.requests,
                                max(1.0, args.load * cfg.wave_lanes))
    print(f"{desc}; {args.requests} requests over {len(schedule)} ticks, "
          f"wave = {cfg.n_micro} x {cfg.microbatch} lanes"
          + (f", chaos seed {args.chaos_seed}" if args.chaos else ""))

    done = []
    for tick, count in enumerate(schedule):
        if count:
            server.submit(make_items(count))
        done.extend(server.step())
    done.extend(server.drain())

    s = server.metrics.summary()
    assert s["submitted"] == s["completed"] + s["shed"] + s["failed"], s
    assert server.pending() == 0, server.pending()
    assert s["completed"] + s["shed"] + s["failed"] == args.requests, \
        (s, args.requests)
    print(f"served {s['completed']} requests in {s['waves']} waves "
          f"({s['padded_lanes']} padded lanes, {s['shed']} shed, "
          f"{s['failed']} failed)")
    if args.chaos:
        print(f"chaos: {s['wave_errors']} wave errors, {s['retried']} "
              f"retried, {s['requeued']} requeued, {s['guard_trips']} "
              f"guard trips")
    thr = s["throughput_rps"]
    print(f"latency p50 {_fmt_ms(s['p50_latency_s'])}, "
          f"p90 {_fmt_ms(s['p90_latency_s'])}; "
          f"throughput {'n/a' if thr is None else f'{thr:.1f} req/s'}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", default="Caps-MN1",
                    choices=sorted(CAPS_BENCHMARKS))
    ap.add_argument("--model", default="caps", choices=("caps", "lm", "moe"),
                    help="workload adapter to serve (DESIGN.md §WaveServe): "
                         "caps = the paper's CapsNet waves; lm / moe run "
                         "the single-server tick loop over the LM-decode / "
                         "MoE adapters (fleet/async flags are caps-only)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny request count (CI)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=8)
    ap.add_argument("--n-micro", type=int, default=4)
    ap.add_argument("--pipeline", default="software",
                    choices=("software", "two_stage", "none"),
                    help="§4 pipeline form; two_stage needs a 2-sized "
                         "'pipe' mesh axis (>=2 devices)")
    ap.add_argument("--plan", default="none", choices=("none", "auto"),
                    help="routing-stage distribution: §5.1.2 planner or "
                         "unsharded")
    ap.add_argument("--algorithm", default="dynamic",
                    choices=("dynamic", "em"),
                    help="routing algorithm the waves run (em = the "
                         "multi-input pipeline stage hand-off)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="threaded driver: serve_forever + concurrent "
                         "submitter threads instead of the tick loop")
    ap.add_argument("--submitters", type=int, default=2,
                    help="submitter threads for --async")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded-queue depth (back-pressure); default "
                         "unbounded")
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1 serves through the CapsFleet front-end with "
                         "this many replica servers (DESIGN.md §Fleet)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="elastic upper bound for the fleet controller; "
                         "default = --replicas (no elasticity)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="> 1 submits from this many tenant threads with "
                         "per-tenant fleet accounting")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request SLO for fleet mode; waves form "
                         "deadline-first and goodput counts met deadlines")
    ap.add_argument("--load", type=float, default=0.75,
                    help="offered load as a fraction of wave capacity "
                         "per tick")
    ap.add_argument("--chaos", action="store_true",
                    help="deterministic fault injection against the "
                         "hardened wave path (runtime.faults, DESIGN.md "
                         "§Faults): seeded wave exceptions + NaN "
                         "corruption, plus a replica crash in fleet mode")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="FaultPlan.generate seed (same seed = same "
                         "schedule, every run)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        caps_cfg = smoke_caps()
        args.requests = min(args.requests, 24)
        args.microbatch, args.n_micro = 4, 2
    else:
        caps_cfg = CAPS_BENCHMARKS[args.network]

    if args.model != "caps":
        run_model_workload(args)
        return

    pipeline = None if args.pipeline == "none" else args.pipeline
    mesh = None
    if pipeline == "two_stage":
        n = len(jax.devices())
        if n < 2:
            raise SystemExit("--pipeline two_stage needs >= 2 devices for "
                             "the 2-sized 'pipe' axis (this host has "
                             f"{n}); use --pipeline software")
        from repro import compat
        mesh = compat.make_mesh((2, n // 2), ("pipe", "vault"),
                                devices=jax.devices()[:2 * (n // 2)])
    cfg = ServeConfig(microbatch=args.microbatch, n_micro=args.n_micro,
                      pipeline=pipeline, mesh=mesh,
                      routing_plan="auto" if args.plan == "auto" else None,
                      max_queue=args.max_queue)
    spec = RouterSpec(algorithm=args.algorithm,
                      iterations=caps_cfg.routing_iters)

    params = capsnet.init_capsnet(jax.random.PRNGKey(0), caps_cfg)
    ds = SyntheticCapsDataset(caps_cfg.image_hw, caps_cfg.image_channels,
                              caps_cfg.num_h_caps)

    mean_per_tick = max(1.0, args.load * cfg.wave_lanes)
    schedule = arrival_schedule(args.requests, mean_per_tick)

    if args.replicas > 1 or args.tenants > 1 or args.slo_ms is not None:
        run_fleet(args, caps_cfg, params, ds, cfg, spec, schedule)
        return

    wave_fn = None
    if args.chaos:
        from repro.runtime import faults   # chaos only: lazy, opt-in
        wave_fn = faults.chaos_wave_fn(
            make_wave_fn(params, caps_cfg, spec, cfg),
            chaos_plan(args, cfg, faults, crash=False))
    server = CapsServer(params, caps_cfg, spec=spec, cfg=cfg,
                        wave_fn=wave_fn)
    mode = (f"async x {args.submitters} submitters" if args.async_mode
            else "sync tick loop")
    print(f"{caps_cfg.name}: {args.requests} requests over "
          f"{len(schedule)} ticks (ragged), wave = {cfg.n_micro} x "
          f"{cfg.microbatch} lanes, pipeline={pipeline}, "
          f"plan={args.plan}, algorithm={args.algorithm}, {mode}"
          + (f", chaos seed {args.chaos_seed}" if args.chaos else ""))

    if args.async_mode:
        done = run_async(server, ds, schedule, max(1, args.submitters))
    else:
        done = run_sync(server, ds, schedule)

    s = server.metrics.summary()
    assert s["submitted"] == s["completed"] + s["shed"] + s["failed"], s
    assert server.pending() == 0, server.pending()
    assert s["completed"] + s["shed"] + s["failed"] == args.requests, \
        (s, args.requests)
    print(f"served {s['completed']} requests in {s['waves']} waves "
          f"({s['padded_lanes']} padded lanes, {s['shed']} shed, "
          f"{s['failed']} failed)")
    if args.chaos:
        print(f"chaos: {s['wave_errors']} wave errors, {s['retried']} "
              f"retried, {s['requeued']} requeued, {s['guard_trips']} "
              f"guard trips")
    thr = s["throughput_rps"]
    print(f"latency p50 {_fmt_ms(s['p50_latency_s'])}, "
          f"p90 {_fmt_ms(s['p90_latency_s'])}; "
          f"throughput {'n/a' if thr is None else f'{thr:.1f} req/s'}")
    preds = {c.rid: c.pred for c in done}
    print("first predictions:", [preds[r] for r in sorted(preds)[:8]])


if __name__ == "__main__":
    main()
