"""Continuous-batching CapsNet serving over the §4 host‖PIM pipeline.

The ROADMAP north star as a subsystem (DESIGN.md §Serving): a request queue
admits variable-count arrivals, pads them into fixed microbatch lanes so the
routed forward compiles exactly once per (spec, plan), and streams waves of
microbatches through the paper's two-stage pipeline — encoder ("host") stage
overlapping the routing ("PIM") stage of the previous microbatch, with the
§5.1 vault distribution optionally running *inside* the routing stage
(``routing_plan="auto"`` lets the §5.1.2 planner pick the dimension; a
tuple of (dim, mesh_axis) pairs shards the stage over one *or several*
vault axes).

Since the WaveServe refactor (DESIGN.md §WaveServe) the queueing, admission,
tenancy, retry, guard and evacuation machinery lives in the model-agnostic
``runtime.wave_serve`` core; this module is the CapsNet *adapter* —
``CapsAdapter`` packs image payloads into masked microbatch lanes and builds
the §4 wave executable via ``make_wave_fn`` — plus ``CapsServer``, a
bit-identical subclass binding that adapter under the pre-refactor
constructor.  ``ServeConfig``/``Request``/``Completion``/``ServeMetrics``
and friends are re-exported from ``wave_serve`` so existing imports keep
working.

Admission is asynchronous and thread-safe: any number of client threads may
call ``submit()`` while ``serve_forever(stop_event)`` drives waves on its
own thread — wave formation is decoupled from caller cadence (a wave forms
whenever the queue is non-empty, batching whatever has arrived).  Back
pressure is a bounded queue (``ServeConfig.max_queue``) with a shed
(tail-drop, counted in ``metrics.shed``) or reject (``QueueFullError``,
nothing admitted) policy.  The accounting invariant under any interleaving
(``pending()`` counts queued requests AND the wave in flight, so it holds
even while ``step()`` is mid-wave on another thread — and through every
fault: a failed wave requeues or fails its requests with accounting,
DESIGN.md §Faults):

    metrics.submitted == metrics.completed + metrics.shed
                         + metrics.failed + metrics.evacuated + pending()

(``failed`` counts requests dropped after ``ServeConfig.max_wave_retries``
exhausted retries; ``evacuated`` counts requests handed off a dead replica
by the fleet rescue path — both zero on a fault-free standalone server.)

Both registered routing algorithms serve: ``RouterSpec(algorithm="dynamic")``
waves score classes as ‖v‖; ``algorithm="em"`` waves hand the pipeline the
(votes, a_in) pair — the multi-input stage hand-off of DESIGN.md §Serving —
and score classes as the EM output activations ``a_out``.

Padding note (DESIGN.md §Serving): the routing logits ``b`` are shared
across the batch (the paper's Table-2 B-dim aggregation), so batch lanes
couple through Eq.4 and naive zero-image padding would perturb real lanes
once biases are non-zero.  The encoder stage therefore multiplies the
primary capsules u by a per-lane 0/1 mask before the Eq.1 projection — a
masked lane's votes are exactly zero, so it contributes exactly zero to
every cross-lane aggregation, making padding bit-invariant for the real
lanes.  (Masking u rather than the votes lets the projection write the
votes straight in the kernels' lane-packed layout.)  (EM keeps no
cross-batch state, but the same mask zeroes a padded lane's input
activations so its votes never weight any Gaussian.)

    server = CapsServer(params, caps_cfg)
    server.submit(images)           # any count, any tick, any thread
    done = server.step()            # one wave: [Completion(rid, pred, ...)]

    stop = threading.Event()        # or: the async driver
    thread = threading.Thread(target=server.serve_forever, args=(stop,))

Multi-tenant / SLO serving (DESIGN.md §Fleet): every request carries a
``tenant`` tag, an optional absolute ``deadline`` and a ``priority``;
``ServeConfig(queue_order="deadline")`` forms waves from the requests
closest to violating their SLO — a priority queue ordered by
``(deadline, arrival)`` instead of FIFO — and the bounded-queue shed
policy evicts the most-doomed requests (expired first, then lowest
priority, then earliest deadline) rather than tail-dropping the arrival.
``ServeMetrics`` keeps a per-tenant breakdown plus goodput
(deadline-met completions); ``runtime.caps_fleet.CapsFleet`` multiplexes
N replica servers behind one quota/rate-limited admission front-end.

``repro.launch.serve_caps`` is the CLI (``--async`` for the threaded
driver, ``--replicas``/``--tenants`` for the fleet);
``benchmarks/bench_serving.py`` sweeps offered load over the pipelined /
unpipelined / async / EM / fleet arms.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import capsule_layers as CL
from repro.core import router as router_lib
from repro.models import capsnet, matrix_capsnet
from repro.runtime import wave_serve
from repro.runtime.wave_serve import (  # noqa: F401 — pre-WaveServe API
    OVERFLOW_POLICIES,
    QUEUE_ORDERS,
    Completion,
    QueueFullError,
    ReplicaCrash,
    Request,
    ServeConfig,
    ServeMetrics,
    TenantMetrics,
    WaveServer,
    WorkloadAdapter,
)


def validate_arrival(images: Sequence[np.ndarray],
                     image_shape: tuple) -> np.ndarray:
    """The validate half of validate-then-mutate admission: assemble an
    arrival into one ``(n,) + image_shape`` float32 array or raise without
    side effects.  Shared by ``CapsServer.submit`` and the fleet front-end
    (``runtime.caps_fleet``) so both admission layers reject bad arrivals
    before any counter moves."""
    try:
        arr = np.asarray(images, np.float32)
    except (ValueError, TypeError) as e:
        raise ValueError(
            "ragged arrival: could not assemble the images into one "
            f"(n,) + {image_shape} float array — every image "
            "must be a numeric array of that shape") from e
    if arr.ndim != 1 + len(image_shape) or arr.shape[1:] != image_shape:
        got = (arr.shape[1:] if arr.ndim == 1 + len(image_shape)
               else arr.shape)
        raise ValueError(f"image shape {got} != {image_shape}")
    return arr


def make_wave_fn(params, caps_cfg, spec: Optional[router_lib.RouterSpec],
                 cfg: ServeConfig) -> Callable:
    """Build the jitted wave executable.

    wave({"images": (n_micro, microbatch, H, W, C),
          "mask":   (n_micro, microbatch)}) -> class_scores
                                               (n_micro, microbatch, N_H)

    The encoder stage masks the primary capsules per lane before the Eq.1
    projection (padding invariance, see module docstring), projecting
    through W's lane-packed view built here once, and the routing stage
    runs through
    ``core.router.build_router`` — pipelined per ``cfg.pipeline``, with the
    routing distribution per ``cfg.routing_plan``.  ``spec.algorithm``
    selects the stage hand-off: "dynamic" hands the pipeline the votes and
    scores classes as ‖v‖; "em" hands it the (votes, a_in) pair (a_in = the
    lane mask broadcast over the L capsules) and scores classes as the EM
    output activations.  Constant wave shapes mean exactly one compilation
    per (spec, plan).
    """
    if spec is None:
        spec = router_lib.RouterSpec(iterations=caps_cfg.routing_iters)
    algo = router_lib.get_algorithm(spec.algorithm)
    # packed once per build, never per microbatch
    w_votes = CL.pack_vote_weights(params["digit"]["W"])

    def encode(micro):
        with jax.named_scope("capsnet.encode"):
            u = capsnet.primary_caps(params, micro["images"], caps_cfg)
            u = u * micro["mask"][:, None, None]
            return CL.project_votes(u, w_votes, caps_cfg.num_h_caps)

    if algo.num_inputs == 1:
        stage_a = encode
        score = lambda out: jnp.linalg.norm(out, axis=-1)      # noqa: E731
    elif spec.algorithm == "em":
        def stage_a(micro):
            votes = encode(micro)
            a_in = jnp.broadcast_to(micro["mask"][:, None], votes.shape[:2])
            return votes, a_in
        score = lambda out: out[1]                             # noqa: E731
    else:
        raise ValueError(
            f"no serving wave recipe for algorithm {spec.algorithm!r} "
            f"({algo.num_inputs} inputs); register one in make_wave_fn")

    auto = cfg.routing_plan == "auto"
    axes = (tuple(cfg.routing_plan)
            if isinstance(cfg.routing_plan, (tuple, list)) else ())

    if cfg.pipeline is not None:
        plan = router_lib.ExecutionPlan(
            mesh=cfg.mesh, axes=axes, auto=auto, pipeline=cfg.pipeline,
            pipeline_axis=cfg.pipeline_axis, stage_a=stage_a)
        router = router_lib.build_router(spec, plan)

        def wave(micro):
            with jax.named_scope("capsnet.routing"):
                out = router(micro)
            return score(out)
        return jax.jit(wave)

    # unpipelined reference arm: same stages, strictly sequential per
    # microbatch (lax.map = scan, so a sharded routing core traces fine).
    plan = (router_lib.ExecutionPlan(mesh=cfg.mesh, axes=axes, auto=auto)
            if (axes or auto or cfg.mesh is not None) else None)
    core = router_lib.build_router(spec, plan)

    def run_one(m):
        h = stage_a(m)
        with jax.named_scope("capsnet.routing"):
            return core(*h) if isinstance(h, tuple) else core(h)

    return jax.jit(lambda micro: score(jax.lax.map(run_one, micro)))


# ---------------------------------------------------------------------------
# CapsAdapter — the CapsNet workload behind the WaveServe core
# ---------------------------------------------------------------------------

class CapsAdapter(wave_serve.WorkloadAdapter):
    """CapsNet classification as a ``WorkloadAdapter`` (DESIGN.md
    §WaveServe): payloads are ``(H, W, C)`` float32 images, the wave
    executable is ``make_wave_fn``'s §4 pipeline, packing zero-pads the
    tail microbatch with a per-lane mask (bit-invariant, see module
    docstring), and completions are argmax class predictions over the wave
    scores.  The output-guard reference is the jnp reference spec
    (``core.router.reference_spec``) — the same fallback target as the
    VMEM non-fit path of the pallas router."""

    def __init__(self, params, caps_cfg,
                 spec: Optional[router_lib.RouterSpec] = None):
        self.params = params
        self.caps_cfg = caps_cfg
        self.spec = spec
        self.image_shape = (caps_cfg.image_hw, caps_cfg.image_hw,
                            caps_cfg.image_channels)

    def validate(self, items) -> np.ndarray:
        return validate_arrival(items, self.image_shape)

    def make_wave_fn(self, cfg: ServeConfig) -> Callable:
        return make_wave_fn(self.params, self.caps_cfg, self.spec, cfg)

    def make_reference_wave_fn(self, cfg: ServeConfig) -> Callable:
        ref = (router_lib.reference_spec(self.spec)
               if self.spec is not None else None)
        return make_wave_fn(self.params, self.caps_cfg, ref, cfg)

    def pack(self, payloads: Sequence[np.ndarray], cfg: ServeConfig):
        shape = self.image_shape
        images = np.zeros((cfg.wave_lanes,) + shape, np.float32)
        mask = np.zeros((cfg.wave_lanes,), np.float32)
        for i, payload in enumerate(payloads):
            images[i] = payload
            mask[i] = 1.0
        return {
            "images": jnp.asarray(images).reshape(
                (cfg.n_micro, cfg.microbatch) + shape),
            "mask": jnp.asarray(mask).reshape(cfg.n_micro, cfg.microbatch),
        }

    def unpack(self, out, n: int) -> List[int]:
        scores = np.asarray(out)
        preds = scores.reshape(-1, scores.shape[-1]).argmax(-1)
        return [int(p) for p in preds[:n]]

    def cache_key(self):
        # fleets cache compiled waves per (spec, cfg) — the pre-WaveServe
        # key, so test fixtures seeding {(None, cfg): wave_fn} still hit
        return self.spec


class MatrixCapsAdapter(CapsAdapter):
    """Matrix capsules with EM routing (``models.matrix_capsnet``) as a
    ``WorkloadAdapter``: ``CapsAdapter``'s payloads, packing and lane
    mask, and a wave of the matrix-capsule forward over the
    ``(n_micro, microbatch)`` lanes, one microbatch after another (at
    microbatch 8 one ConvCaps1 call holds 170 MB of votes; a 128-lane
    wave at once would hold 3.9 GB).  ``cfg.pipeline`` and
    ``cfg.routing_plan`` do not apply: EM's convolutional layers route
    shard-local.  The wave's output is (class activation logits
    (n_micro, mb, E), class poses (n_micro, mb, E, 16)); a request's
    result is its argmax class.  A padded lane's child activations are 0
    in every layer, and EM keeps no state across lanes, so padding cannot
    move a real lane."""

    def make_wave_fn(self, cfg: ServeConfig) -> Callable:
        return matrix_capsnet.make_wave_fn(self.params, self.caps_cfg,
                                           self._em_spec())

    def make_reference_wave_fn(self, cfg: ServeConfig) -> Callable:
        return matrix_capsnet.make_wave_fn(
            self.params, self.caps_cfg,
            router_lib.reference_spec(self._em_spec()))

    def _em_spec(self) -> router_lib.RouterSpec:
        return self.spec or router_lib.RouterSpec(algorithm="em")

    def unpack(self, out, n: int) -> List[int]:
        return super().unpack(out[0], n)

    def finite(self, out) -> bool:
        return all(bool(np.isfinite(np.asarray(x)).all()) for x in out)

    def cache_key(self):
        return ("matrix", self.spec)


# ---------------------------------------------------------------------------
# CapsServer — queue -> pad -> microbatch -> pipeline
# ---------------------------------------------------------------------------

class CapsServer(wave_serve.WaveServer):
    """Continuous-batching CapsNet classification server (DESIGN.md
    §Serving).

    ``submit()`` admits any number of requests at any time from any thread;
    ``step()`` drains up to one wave (``cfg.wave_lanes`` requests) from the
    queue, pads the tail microbatch to the fixed lane count, runs the wave
    through the pipelined router, and returns per-request completions with
    queue+compute latency.  ``drain()`` steps until the queue is empty;
    ``serve_forever(stop_event)`` is the async driver — run it on its own
    thread while clients submit concurrently.

    Pre-WaveServe constructor, preserved verbatim: this is now a thin
    binding of ``CapsAdapter`` under the generic ``WaveServer`` core, with
    behavior bit-identical to the standalone implementation it replaced.
    """

    def __init__(self, params, caps_cfg,
                 spec: Optional[router_lib.RouterSpec] = None,
                 cfg: Optional[ServeConfig] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 wave_fn: Optional[Callable] = None,
                 watchdog=None,
                 sleep: Callable[[float], None] = time.sleep):
        adapter = CapsAdapter(params, caps_cfg, spec)
        super().__init__(adapter, cfg=cfg, clock=clock, wave_fn=wave_fn,
                         watchdog=watchdog, sleep=sleep)
        self.caps_cfg = caps_cfg
        self._params = params
        self._spec = spec
        self._image_shape = adapter.image_shape
