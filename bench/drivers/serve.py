"""Serving driver: CapsNet requests through ``WaveServer`` on the clock.

Set-up makes the weights and an image pool on the device from the seed,
builds the program's wave executable for the cell's wave shape and warms
it through the server itself.  The window then offers load in one of two
ways, fixed by the mix:

* ``arrivals: "poisson"`` — an open loop.  ``rate_per_s`` x seconds
  arrivals (a Poisson process given its count: the same number for every
  seed) at sorted uniform times, one image each.  ``serve_forever`` forms
  waves on its own thread.  A request's latency runs from when it was
  *due*, so a late generator cannot hide queueing; lateness is printed.
* ``arrivals: "backlog"`` — a saturated queue: whenever fewer than
  ``backlog_waves`` waves are pending, a wave's worth of images is
  submitted.

The adapter is the program's ``CapsAdapter`` with the benchmark's spans
around pack, the wait for the wave's result and unpack.  It keeps a
sample of waves served in the window (reservoir, drawn from the seed);
after the window those waves' scores are compared with the reference's.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import threading
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, reference, trace as trace_lib, work
from bench.common import (Context, Outcome, annotate, caps_config,
                          memory_peak_bytes, path_readings, router_spec)
from repro.core import router as router_lib
from repro.runtime.caps_serve import CapsAdapter
from repro.runtime.wave_serve import ServeConfig, WaveServer

DRAIN_TIMEOUT_S = 60.0


class Reservoir:
    """A uniform sample of ``size`` items from a stream, drawn from a
    seeded generator."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen, self.items = size, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.items[j] = item


class TimedAdapter(CapsAdapter):
    """``CapsAdapter`` with the benchmark's spans; samples served waves."""

    def __init__(self, params, caps_cfg, spec, ctx: Context,
                 sample: Reservoir):
        super().__init__(params, caps_cfg, spec)
        self.ctx, self.sample = ctx, sample
        self.recording = False
        self.blocked_s = 0.0
        self._payloads: List[np.ndarray] = []

    def pack(self, payloads, cfg):
        self._payloads = list(payloads)
        with annotate(self.ctx, "bench.pack"):
            return super().pack(payloads, cfg)

    def finite(self, out):
        t = time.perf_counter()
        with annotate(self.ctx, "bench.wave_wait"):
            scores = np.asarray(jax.block_until_ready(out))
        self.blocked_s = time.perf_counter() - t
        if self.recording:
            self.sample.offer((self._payloads, scores))
        return bool(np.isfinite(scores).all())

    def unpack(self, out, n):
        with annotate(self.ctx, "bench.unpack"):
            return super().unpack(out, n)


def make_pool(cfg, n: int, seed: int) -> np.ndarray:
    """``n`` images, uniform in [0, 1), made on the device in one call."""
    hw, ch = cfg["image_hw"], cfg["image_channels"]
    pool = jax.jit(lambda k: jax.random.uniform(k, (n, hw, hw, ch)))(
        jax.random.fold_in(jax.random.key(seed % (2 ** 32)), 1))
    return np.asarray(pool)


def build_wave_fn(adapter: TimedAdapter, params, scfg: ServeConfig,
                  ctx: Context):
    """The executable the window drives: the program's wave; for the
    control, the reference in bfloat16 in its place; for the
    ``fewer_iters`` fault, the program's wave with one routing iteration
    fewer."""
    cfg = ctx.config
    if ctx.control == "bf16":
        control = jax.jit(lambda p, w: reference.wave_scores(
            p, w["images"], w["mask"], cfg, jnp.bfloat16))
        return lambda w: control(params, w)
    if ctx.control == "fewer_iters":
        spec = adapter.spec._replace(iterations=adapter.spec.iterations - 1)
        return CapsAdapter(params, adapter.caps_cfg, spec).make_wave_fn(scfg)
    return adapter.make_wave_fn(scfg)


def resolved_form(cfg, scfg: ServeConfig):
    """The routing kernel form the program resolves for one wave's
    routing call (None for the jnp backend)."""
    plan = (router_lib.ExecutionPlan(auto=True)
            if scfg.routing_plan == "auto" else None)
    votes = jax.ShapeDtypeStruct((scfg.microbatch, cfg["num_l_caps"],
                                  cfg["num_h_caps"], cfg["h_caps_dim"]),
                                 jnp.float32)
    return router_lib.build_router(router_spec(cfg), plan).resolve(
        votes).fusion


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values), q)) if len(values) else \
        math.nan


def run(ctx: Context) -> Outcome:
    cfg, mix = ctx.config, ctx.mix
    rng = np.random.default_rng(ctx.seed)
    params = reference.init_params(cfg, ctx.seed)
    scfg = ServeConfig(microbatch=cfg["wave"]["microbatch"],
                       n_micro=mix["n_micro"],
                       pipeline=cfg["wave"]["pipeline"],
                       routing_plan=cfg["routing_plan"])
    lanes = scfg.wave_lanes
    sample = Reservoir(mix["sample_waves"], np.random.default_rng(
        [ctx.seed, 2]))
    adapter = TimedAdapter(params, caps_config(cfg), router_spec(cfg), ctx,
                           sample)
    wave_fn = build_wave_fn(adapter, params, scfg, ctx)

    def wave_call(wave):
        with annotate(ctx, "bench.wave_call"):
            return wave_fn(wave)

    server = WaveServer(adapter, cfg=scfg, wave_fn=wave_call)
    waves: List[tuple] = []          # (start, end, blocked_s, real lanes)
    step = server.step

    def timed_step():
        t0 = time.perf_counter()
        with annotate(ctx, "bench.step"):
            done = step()
        if adapter.recording and done:
            waves.append((t0, time.perf_counter(), adapter.blocked_s,
                          len(done)))
        return done

    server.step = timed_step
    pool = make_pool(cfg, mix["pool_images"], ctx.seed)

    # warm-up: full and partial waves through the server itself
    for n in (lanes, lanes, max(1, lanes // 3)):
        server.submit(pool[:n])
        server.drain()

    window = (min(ctx.seconds, mix["trace_seconds"]) if ctx.trace
              else ctx.seconds)
    if mix["arrivals"] == "poisson":
        n_req = int(round(mix["rate_per_s"] * window))
        due = np.sort(rng.uniform(0.0, window, n_req))
    else:
        n_req, due = 0, None
    images = rng.integers(0, len(pool), size=max(n_req, 1))

    done_at = {}

    def on_completion(c):
        done_at[c.rid] = time.perf_counter()

    stop = threading.Event()
    served: List = []
    thread = threading.Thread(
        target=lambda: served.extend(server.serve_forever(
            stop, on_completion=on_completion)),
        name="bench-serve")
    setup_s = time.perf_counter() - ctx.t_start
    trace_dir = os.path.join(ctx.scratch_dir, "trace")
    tracer = (trace_lib.capture(trace_dir) if ctx.trace
              else contextlib.nullcontext())
    sent: List[tuple] = []           # (rid, due time)
    lateness: List[float] = []

    def open_loop(w0, w_end):
        i = 0
        while i < n_req:
            now = time.perf_counter()
            wait = w0 + due[i] - now
            if wait > 0:
                time.sleep(wait)
                continue
            while i < n_req and w0 + due[i] <= now:
                with annotate(ctx, "bench.submit"):
                    rid = server.submit(pool[images[i]:images[i] + 1])[0]
                sent.append((rid, w0 + due[i]))
                lateness.append(time.perf_counter() - w0 - due[i])
                i += 1
        time.sleep(max(0.0, w_end - time.perf_counter()))

    def backlog(w0, w_end):
        depth = mix["backlog_waves"] * lanes
        while time.perf_counter() < w_end:
            if server.pending() < depth:
                idx = rng.integers(0, len(pool), size=lanes)
                with annotate(ctx, "bench.submit"):
                    rids = server.submit(pool[idx])
                now = time.perf_counter()
                sent.extend((r, now) for r in rids)
            else:
                time.sleep(0.0002)

    with tracer:
        thread.start()
        with annotate(ctx, "bench.window"):
            adapter.recording = True
            w0 = time.perf_counter()
            w_end = w0 + window
            (open_loop if due is not None else backlog)(w0, w_end)
            adapter.recording = False
        stop.set()
        thread.join(DRAIN_TIMEOUT_S)
    if thread.is_alive():
        raise RuntimeError("the server did not drain within "
                           f"{DRAIN_TIMEOUT_S} s of the window's close")

    lat = [done_at[r] - t_due if r in done_at else math.inf
           for r, t_due in sent]
    missing = sum(1 for r, _ in sent if r not in done_at)
    completed_in_window = sum(1 for r, _ in sent
                              if done_at.get(r, math.inf) <= w_end)
    e2e = {"serve_images_per_s": completed_in_window / window}
    if due is not None:
        e2e["serve_p95_ms"] = _percentile(lat, 95) * 1e3
        ctx.log("latency from due p50/p95/p99/max ms " + " ".join(
            repr(_percentile(lat, q) * 1e3) for q in (50, 95, 99, 100)))
        ctx.log(f"generator lateness p50 {_percentile(lateness, 50)*1e3!r} "
                f"ms, p95 {_percentile(lateness, 95)*1e3!r} ms over "
                f"{len(lateness)} requests at {mix['rate_per_s']} /s")
    wave_errors = server.metrics.summary()["wave_errors"]
    guard_trips = server.metrics.guard_trips
    ctx.log(f"waves {len(waves)}, requests {len(sent)}, completed in "
            f"window {completed_in_window}, missing {missing}, "
            f"server {wave_errors} wave errors, {guard_trips} guard trips")

    summary = None
    if ctx.trace:
        summary = trace_lib.reduce(trace_lib.load(trace_dir))
    peak = memory_peak_bytes(jax.devices()[:ctx.chips])
    wall = sum(t1 - t0 for t0, t1, _, _ in waves)
    imgs = sum(n for *_, n in waves)
    counters = {
        "window": (w0, w_end),
        "waves": len(waves),
        "wave_wall_s": wall,
        "host_s": sum(t1 - t0 - b for t0, t1, b, _ in waves),
        "images": imgs,
        "model_flops": imgs * work.forward_flops_per_image(
            cfg, scfg.microbatch),
        "routing_fwd": (work.routing_call(cfg, scfg.microbatch),
                        len(waves) * scfg.n_micro),
    }

    sampled = list(sample.items)
    del server, adapter, wave_fn, served, step
    gc.collect()
    gap = _score_gap(params, sampled, scfg, cfg, cfg["reference_precision"])
    counters["gaps_at"] = {
        p: {"score_gap": _score_gap(params, sampled, scfg, cfg, p)}
        for p in ctx.ref_precisions}
    lim = cfg["limits"]
    readings = [("score_gap", gap, lim["score_gap"]),
                ("missing", float(missing), 0.0),
                ("wave_errors", float(wave_errors), 0.0),
                ("guard_trips", float(guard_trips), 0.0),
                *path_readings(cfg["router"]["form"],
                              resolved_form(cfg, scfg), summary)]
    return Outcome(attempted=len(sent), failed=missing, setup_s=setup_s,
                   end_to_end=e2e, readings=readings, counters=counters,
                   memory_peak_bytes=peak, trace=summary)


def _score_gap(params, sampled, scfg: ServeConfig, cfg,
               precision: str) -> float:
    """Widest score gap over the sampled waves' real lanes, against the
    reference at matmul ``precision``."""
    if not sampled:
        return math.nan
    hw, ch = cfg["image_hw"], cfg["image_channels"]
    ref = jax.jit(lambda p, x, m: reference.wave_scores(
        p, x, m, cfg, precision=precision))
    gap = 0.0
    for payloads, scores in sampled:
        x = np.zeros((scfg.wave_lanes, hw, hw, ch), np.float32)
        m = np.zeros((scfg.wave_lanes,), np.float32)
        x[:len(payloads)] = np.stack(payloads)
        m[:len(payloads)] = 1.0
        want = ref(params,
                   x.reshape(scfg.n_micro, scfg.microbatch, hw, hw, ch),
                   m.reshape(scfg.n_micro, scfg.microbatch))
        gap = max(gap, check.score_gap(scores, np.asarray(want), m))
    return gap

