"""Serving driver of matrix capsules with EM routing through ``WaveServer``.

Set-up makes the weights (``bench/reference_em.py``) and an image pool on
the device from the seed, builds the program's wave executable
(``MatrixCapsAdapter``) for the cell's wave shape and warms it through the
server itself.  The window offers a saturated backlog: whenever fewer than
``backlog_waves`` waves are pending, a wave's worth of images is
submitted.  ``serve_images_per_s`` counts the requests completed inside
the window.

The adapter carries the benchmark's spans around pack, the wait for the
wave's result and unpack (``serve.TimedAdapter``), and keeps a seeded
sample of the waves served in the window.  After the window the sampled
waves' class logits and poses are compared with the reference's:

* ``score_gap``: over the real lanes, the widest gap of the class
  activation logits λ(β_a − Σ cost), over the larger of 1 and the lane's
  largest reference logit (they run to the thousands with random weights,
  where the sigmoid saturates and would hide a wrong result);
* ``pose_gap``: over the real lanes, the widest gap of the class poses
  over the lane's largest reference pose entry;
* ``missing``, ``wave_errors``, ``guard_trips``, ``path_form`` (every
  routed layer resolves the form the configuration states) and, traced,
  ``kernels_absent`` (an EM kernel of ``metrics/routing_roofline.em.py``
  ran in the window), all held to 0.

``ctx.control``: "bf16" serves the reference in bfloat16; "fewer_iters"
the program with one EM iteration fewer; "window_local" the program with
each window's E-step normalised on its own, the per-window softmax in
place of the cross-window one.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import threading
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference_em, registry, trace as trace_lib, work_em
from bench.common import Context, Outcome, annotate, memory_peak_bytes
from repro.core import em_routing
from repro.core import router as router_lib
from repro.models import matrix_capsnet
from repro.runtime.caps_serve import MatrixCapsAdapter
from repro.runtime.wave_serve import ServeConfig, WaveServer

serve = registry.driver("serve")
DRAIN_TIMEOUT_S = 60.0
CONTROLS = (None, "bf16", "fewer_iters", "window_local")
# Where the program's cross-window fold runs (src/repro/core/em_routing.py).
FOLD_SCOPE = "routing.em_fold"


class TimedEMAdapter(serve.TimedAdapter, MatrixCapsAdapter):
    """``MatrixCapsAdapter`` with ``serve.TimedAdapter``'s spans around
    pack and unpack; the wait for the wave's (logits, poses) here."""

    def finite(self, out):
        t = time.perf_counter()
        with annotate(self.ctx, "bench.wave_wait"):
            out = tuple(np.asarray(x) for x in jax.block_until_ready(out))
        self.blocked_s = time.perf_counter() - t
        if self.recording:
            self.sample.offer((self._payloads, out))
        return all(bool(np.isfinite(x).all()) for x in out)


def model_config(cfg) -> matrix_capsnet.MatrixCapsConfig:
    return matrix_capsnet.MatrixCapsConfig(
        image_hw=cfg["image_hw"], image_channels=cfg["image_channels"],
        conv_kernel=cfg["conv_kernel"], conv_stride=cfg["conv_stride"],
        A=cfg["A"], B=cfg["B"], C=cfg["C"], D=cfg["D"], E=cfg["E"],
        caps_kernel=cfg["caps_kernel"],
        strides=tuple(cfg["caps_strides"]),
        iterations=cfg["routing_iters"], inv_temp=cfg["inv_temp"],
        eps=cfg["eps"])


def router_spec(cfg) -> router_lib.RouterSpec:
    r = cfg["router"]
    return router_lib.RouterSpec(algorithm=r["algorithm"],
                                 backend=r["backend"],
                                 iterations=r["iterations"])


def make_pool(cfg, n: int, seed: int) -> np.ndarray:
    """``n`` images, N(0, 1), made on the device in one call."""
    hw, ch = cfg["image_hw"], cfg["image_channels"]
    pool = jax.jit(lambda k: jax.random.normal(k, (n, hw, hw, ch)))(
        jax.random.fold_in(jax.random.key(seed % (2 ** 32)), 1))
    return np.asarray(pool)


def build_wave_fn(adapter: TimedEMAdapter, params, scfg: ServeConfig,
                  ctx: Context):
    """The executable the window drives: the program's wave, or the
    control's or fault's in its place."""
    cfg = ctx.config
    if ctx.control == "bf16":
        control = jax.jit(lambda p, w: reference_em.wave_outputs(
            p, w["images"], w["mask"], cfg, jnp.bfloat16))
        return lambda w: control(params, w)
    if ctx.control == "fewer_iters":
        mcfg = dataclasses.replace(adapter.caps_cfg,
                                   iterations=adapter.caps_cfg.iterations - 1)
        return MatrixCapsAdapter(params, mcfg, adapter.spec).make_wave_fn(
            scfg)
    return adapter.make_wave_fn(scfg)


@contextlib.contextmanager
def planted(control):
    """The "window_local" fault: each window's E-step softmax on its own."""
    if control != "window_local":
        yield
        return
    real = em_routing.cross_window_softmax
    em_routing.cross_window_softmax = \
        lambda logits, geom: jax.nn.softmax(logits, axis=-1)
    try:
        yield
    finally:
        em_routing.cross_window_softmax = real


def resolved_forms(cfg, mcfg, spec, microbatch: int):
    """The kernel form the program resolves for each routed layer's call
    (None for the jnp backend)."""
    forms = {}
    for layer in matrix_capsnet.ROUTED:
        windows, L, H = mcfg.layer_shape(layer)
        rows = microbatch * windows
        votes = jax.ShapeDtypeStruct((rows, L, H, 16), jnp.float32)
        a_in = jax.ShapeDtypeStruct((rows, L), jnp.float32)
        router = router_lib.build_router(
            matrix_capsnet.layer_spec(spec, mcfg, layer))
        forms[layer] = router.resolve(votes, a_in).fusion
    return forms


def fold_seconds(trace_dir: str, window) -> float:
    """Device self time of the ops under the program's ``routing.em_fold``
    scope in the window (``bench/program_trace.py``, its scope list
    widened by that scope while it reads)."""
    from bench import program_trace
    scopes = program_trace.SCOPES
    program_trace.SCOPES = scopes + (FOLD_SCOPE,)
    try:
        summary = program_trace.reduce(program_trace.load(trace_dir),
                                       window)
    finally:
        program_trace.SCOPES = scopes
    return program_trace.scope_seconds(summary).get(FOLD_SCOPE, 0.0)


def run(ctx: Context) -> Outcome:
    if ctx.control not in CONTROLS:
        raise ValueError(f"control {ctx.control!r}: this driver knows "
                         f"{CONTROLS}")
    with planted(ctx.control):
        return _run(ctx)


def _run(ctx: Context) -> Outcome:
    cfg, mix = ctx.config, ctx.mix
    rng = np.random.default_rng(ctx.seed)
    params = reference_em.init_params(cfg, ctx.seed)
    mcfg, spec = model_config(cfg), router_spec(cfg)
    scfg = ServeConfig(microbatch=cfg["wave"]["microbatch"],
                       n_micro=mix["n_micro"], pipeline=None)
    lanes = scfg.wave_lanes
    sample = serve.Reservoir(mix["sample_waves"], np.random.default_rng(
        [ctx.seed, 2]))
    adapter = TimedEMAdapter(params, mcfg, spec, ctx, sample)
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
    done_at = {}

    def on_completion(c):
        done_at[c.rid] = time.perf_counter()

    stop = threading.Event()
    thread = threading.Thread(
        target=lambda: server.serve_forever(stop,
                                            on_completion=on_completion),
        name="bench-serve")
    setup_s = time.perf_counter() - ctx.t_start
    trace_dir = os.path.join(ctx.scratch_dir, "trace")
    tracer = (trace_lib.capture(trace_dir) if ctx.trace
              else contextlib.nullcontext())
    sent: List[int] = []
    depth = mix["backlog_waves"] * lanes
    with tracer:
        thread.start()
        with annotate(ctx, "bench.window"):
            adapter.recording = True
            w0 = time.perf_counter()
            w_end = w0 + window
            while time.perf_counter() < w_end:
                if server.pending() < depth:
                    idx = rng.integers(0, len(pool), size=lanes)
                    with annotate(ctx, "bench.submit"):
                        sent.extend(server.submit(pool[idx]))
                else:
                    time.sleep(0.0002)
            adapter.recording = False
        stop.set()
        thread.join(DRAIN_TIMEOUT_S)
    if thread.is_alive():
        raise RuntimeError("the server did not drain within "
                           f"{DRAIN_TIMEOUT_S} s of the window's close")

    missing = sum(1 for r in sent if r not in done_at)
    completed = sum(1 for r in sent if done_at.get(r, math.inf) <= w_end)
    wave_errors = server.metrics.summary()["wave_errors"]
    guard_trips = server.metrics.guard_trips
    ctx.log(f"waves {len(waves)}, requests {len(sent)}, completed in "
            f"window {completed}, missing {missing}, server {wave_errors} "
            f"wave errors, {guard_trips} guard trips")

    summary = None
    counters = {}
    if ctx.trace:
        summary = trace_lib.reduce(trace_lib.load(trace_dir))
        counters["em_fold_s"] = fold_seconds(trace_dir, summary["window"])
    peak = memory_peak_bytes(jax.devices()[:ctx.chips])
    imgs = sum(n for *_, n in waves)
    calls = len(waves) * scfg.n_micro
    counters.update({
        "window": (w0, w_end),
        "waves": len(waves),
        "wave_wall_s": sum(t1 - t0 for t0, t1, _, _ in waves),
        "host_s": sum(t1 - t0 - b for t0, t1, b, _ in waves),
        "images": imgs,
        "model_flops": imgs * work_em.forward_flops_per_image(
            cfg, scfg.microbatch),
        "em_routing": [(call, calls) for call in work_em.em_calls(
            cfg, scfg.microbatch).values()],
    })

    sampled = list(sample.items)
    forms = resolved_forms(cfg, mcfg, spec, scfg.microbatch)
    del server, adapter, wave_fn, step
    gc.collect()
    gaps = _gaps(params, sampled, scfg, cfg, cfg["reference_precision"])
    counters["gaps_at"] = {p: _gaps(params, sampled, scfg, cfg, p)
                           for p in ctx.ref_precisions}
    stated = cfg["router"]["forms"]
    lim = cfg["limits"]
    path = [("path_form", float(forms != stated), 0.0)]
    if summary is not None and any(stated.values()):
        names = registry.metric_module("routing_roofline.em").KERNELS[
            "em_routing"]
        ran = sum(trace_lib.op_seconds(summary, names).values()) > 0
        path.append(("kernels_absent", 0.0 if ran else 1.0, 0.0))
    readings = [("score_gap", gaps["score_gap"], lim["score_gap"]),
                ("pose_gap", gaps["pose_gap"], lim["pose_gap"]),
                ("missing", float(missing), 0.0),
                ("wave_errors", float(wave_errors), 0.0),
                ("guard_trips", float(guard_trips), 0.0),
                *path]
    return Outcome(attempted=len(sent), failed=missing, setup_s=setup_s,
                   end_to_end={"serve_images_per_s": completed / window},
                   readings=readings, counters=counters,
                   memory_peak_bytes=peak, trace=summary)


def _gaps(params, sampled, scfg: ServeConfig, cfg, precision: str) -> dict:
    """Widest relative gaps of the class logits and poses over the
    sampled waves' real lanes, against the reference at matmul
    ``precision``."""
    if not sampled:
        return {"score_gap": math.nan, "pose_gap": math.nan}
    hw, ch = cfg["image_hw"], cfg["image_channels"]
    ref = jax.jit(lambda p, x, m: reference_em.wave_outputs(
        p, x, m, cfg, precision=precision))
    score = pose = 0.0
    for payloads, (logits, poses) in sampled:
        x = np.zeros((scfg.wave_lanes, hw, hw, ch), np.float32)
        m = np.zeros((scfg.wave_lanes,), np.float32)
        x[:len(payloads)] = np.stack(payloads)
        m[:len(payloads)] = 1.0
        want = ref(params,
                   x.reshape(scfg.n_micro, scfg.microbatch, hw, hw, ch),
                   m.reshape(scfg.n_micro, scfg.microbatch))
        real = m > 0
        score = max(score, _lane_gap(logits, want[0], real, floor=1.0))
        pose = max(pose, _lane_gap(poses, want[1], real,
                                   floor=np.finfo(np.float32).tiny))
    return {"score_gap": score, "pose_gap": pose}


def _lane_gap(got, ref, real: np.ndarray, floor: float) -> float:
    """max over real lanes of max|got − ref| / max(floor, max|ref|)."""
    got = np.asarray(got, np.float64).reshape(len(real), -1)[real]
    ref = np.asarray(ref, np.float64).reshape(len(real), -1)[real]
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return math.inf
    scale = np.maximum(np.abs(ref).max(axis=1), floor)
    return float((np.abs(got - ref).max(axis=1) / scale).max())
