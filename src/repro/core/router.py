"""Unified Router API — one entry point for algorithm x backend x plan.

The paper's central claim is compositional: the routing procedure's
dimension-level parallelism (§5.1, Table 2) can be planned offline
(§5.1.2, S = 1/(alpha*E + beta*M)) and "easily applied to other routing
algorithms" (§2.2).  This module is that claim as an API:

    spec = RouterSpec(algorithm="dynamic", backend="pallas", iterations=3)
    plan = ExecutionPlan(mesh=mesh, axes=(("B", "vault"),))
    router = build_router(spec, plan)
    v = router(u_hat)                       # jit-ready callable

Three orthogonal choices compose:

  * RouterSpec — WHAT to route: an algorithm from the registry ("dynamic"
    [Sabour et al. 2017] or "em" [Hinton et al. 2018], both over the common
    (B, L, H, C) vote layout) and a kernel backend ("jnp" | "pallas"; the
    Pallas backend replaces the old ``RoutingConfig.fused`` bool and runs
    the fused kernels, in interpret mode off-TPU).  The ``fusion`` knob
    picks between the whole-procedure megakernel and the per-iteration
    kernel (DESIGN.md §Procedure-fused), ``stream_dtype`` selects fp32 or
    bf16 û streaming.  With a sharded plan the Pallas backend switches to
    the stage-split sharded-fused form: per-shard Pallas stages with
    cross-shard psums at the paper's Table-2 aggregation points (DESIGN.md
    §Sharded-fused).
  * ExecutionPlan — WHERE/HOW to run it: unsharded, one dim sharded over a
    mesh axis (the paper's inter-vault distribution), several dims at once
    (2D torus), or the paper's §4 host||PIM two-stage pipeline.  With
    ``plan="auto"`` the §5.1.2 execution-score planner picks the sharded
    dimension from an RPShape + DeviceModel derived from the votes shape
    and the mesh — closing the planner -> execution loop that previously
    required hand-wiring ``plan()``'s "B"|"L"|"H" into a PartitionSpec.
  * build_router(spec, plan) — the façade that fuses the two into a single
    callable.

New algorithms/backends register via ``register_algorithm`` instead of
growing another parallel ``make_sharded_*`` code path (DESIGN.md §Router).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import distribution as dist_lib
from repro.core import em_routing as em_lib
from repro.core import pipeline as pipeline_lib
from repro.core import routing as routing_lib

P = jax.sharding.PartitionSpec

BACKENDS = ("jnp", "pallas")


# ---------------------------------------------------------------------------
# RouterSpec — algorithm x backend (+ static algorithm options)
# ---------------------------------------------------------------------------

class RouterSpec(NamedTuple):
    """Static routing specification (hashable; safe as a jit static arg).

    algorithm: registry name ("dynamic" | "em" | user-registered).
    backend:   "jnp" (pure-XLA path) or "pallas" (fused kernels; replaces
               the old ``RoutingConfig.fused`` bool; composes with sharded
               plans via the stage-split sharded-fused form).
    fusion:    pallas-backend fusion level (DESIGN.md §Procedure-fused):
               "auto" (default — whole-procedure megakernel when the plan
               is shard-local and the VMEM model fits, per-iteration kernel
               otherwise), "procedure" (force the megakernel; rejects
               sharded plans) or "iteration" (force the per-iteration
               kernel).  Under a sharded plan execution is always the
               stage-split form; ``resolve()`` reports the concrete level.
    stream_dtype: dtype û streams HBM→VMEM at on the pallas backend —
               "fp32", "bf16" or "int8" (fp32 in-kernel accumulation in
               every case; bf16 halves the DMA bytes of the only large
               operand, int8 quarters them via per-L-tile symmetric
               quantization — DESIGN.md §Quantized-routing).  int8 is
               procedure-megakernel-only: it forces the procedure form
               under fusion="auto", rejects fusion="iteration", sharded
               plans and ``differentiable=True`` (quantization rounding
               has no derivative — train fp32/bf16, serve int8), and is
               accuracy-gated by bench_accuracy, not the 1e-5 parity gate.
    early_exit_eps: per-capsule early exit inside the procedure megakernel
               (DESIGN.md §Quantized-routing): L-tiles whose deferred-Eq.4
               logit update satisfied ‖Δb‖∞ < ε (checked after iteration
               0) skip the Eq.4/Eq.5 work of every later iteration, their
               couplings frozen in VMEM scratch — effective work becomes
               proportional to unconverged capsules.  ε=0 is bit-identical
               to the fixed grid; None (default) disables the convergence
               scratch entirely.  Same composition rules as int8: forces
               the procedure form, rejects fusion="iteration", sharded
               plans, and ``differentiable=True`` (the recompute-b
               backward replays the fixed-grid schedule).
    differentiable: the router will be differentiated (``jax.grad`` /
               ``jax.vjp`` through it — DESIGN.md §Training).  The jnp
               backend is differentiable by construction (plain autodiff,
               the gradient reference).  On the pallas backend this routes
               the 'dynamic' algorithm through the recompute-b custom VJP
               of the procedure megakernel
               (``dynamic_routing_procedure_train``); plans must be
               shard-local (the stage-split form has no VJP — auto plans
               resolve unsharded) and ``use_approx`` is rejected (the
               §5.2.2 bit manipulations have no derivative).  When the
               procedure form does not fit VMEM the router falls back to
               jnp autodiff rather than a forward-only kernel.
    options:   algorithm-specific extras as a sorted (name, value) tuple,
               e.g. (("beta_a", 1.0),) for EM.  Use ``spec.option(name)``.
    """
    algorithm: str = "dynamic"
    backend: str = "jnp"
    iterations: int = 3
    use_approx: bool = False
    options: Tuple[Tuple[str, Any], ...] = ()
    fusion: str = "auto"
    stream_dtype: str = "fp32"
    differentiable: bool = False
    early_exit_eps: Optional[float] = None

    def option(self, name: str, default: Any = None) -> Any:
        for k, v in self.options:
            if k == name:
                return v
        return default

    def with_options(self, **kw) -> "RouterSpec":
        merged = dict(self.options)
        merged.update(kw)
        return self._replace(options=tuple(sorted(merged.items())))


def reference_spec(spec: RouterSpec) -> RouterSpec:
    """The jnp reference twin of ``spec``: same algorithm, iterations and
    options, but the pure-XLA backend with every pallas-only knob reset
    (fusion/stream_dtype/early_exit/approx).  This is the fallback target
    shared by the differentiable pallas path (VMEM non-fit, DESIGN.md
    §Training) and the serving output guard's NaN/Inf quarantine
    (runtime.caps_serve, DESIGN.md §Faults)."""
    return spec._replace(backend="jnp", fusion="auto", stream_dtype="fp32",
                         early_exit_eps=None, use_approx=False)


# ---------------------------------------------------------------------------
# Algorithm registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Algorithm:
    """A routing algorithm over the common (B, L, H, C) vote layout.

    run(args, spec, axes): the per-shard computation; ``axes`` maps each
        sharded logical dim to its mesh axis name and the implementation
        must insert the matching cross-shard aggregations (paper Table 2).
    in_specs/out_specs(axes): shard_map PartitionSpecs for the callable's
        inputs/outputs under that axes mapping.
    sharded_dims: logical dims this algorithm can shard ("B"/"L"/"H").
    backends: supported kernel backends.
    """
    name: str
    run: Callable[[tuple, RouterSpec, Mapping[str, str]], Any]
    in_specs: Callable[[Mapping[str, str]], tuple]
    out_specs: Callable[[Mapping[str, str]], Any]
    sharded_dims: Tuple[str, ...] = ("B", "L", "H")
    backends: Tuple[str, ...] = ("jnp",)
    num_inputs: int = 1
    describe: str = ""


_REGISTRY: Dict[str, Algorithm] = {}


def register_algorithm(algo: Algorithm) -> Algorithm:
    if algo.name in _REGISTRY:
        raise ValueError(f"algorithm {algo.name!r} already registered")
    _REGISTRY[algo.name] = algo
    return algo


def get_algorithm(name: str) -> Algorithm:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown routing algorithm {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def registered_algorithms() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# --- "dynamic" [Sabour et al. 2017] — paper Algorithm 1 --------------------

def _differentiable_kernel(spec: RouterSpec, shape, axes) -> bool:
    """Whether a differentiable pallas router runs the procedure
    megakernel's custom VJP (DESIGN.md §Training) for votes of ``shape``:
    shard-local, procedure form, and — under fusion="auto" — a backward
    working set that fits VMEM.  Otherwise it falls back to jnp autodiff
    (the gradient reference), never to a forward-only kernel that would
    fail under jax.grad."""
    from repro.kernels.routing import ops as routing_ops
    form = routing_ops.resolve_fusion(spec.fusion, shape, spec.stream_dtype,
                                      sharded=bool(axes))
    return (form == "procedure" and not axes
            and (spec.fusion == "procedure"
                 or routing_ops.procedure_train_fits(
                     *shape, spec.iterations, spec.stream_dtype)))


def _dynamic_run(args, spec: RouterSpec, axes: Mapping[str, str]):
    (u_hat,) = args
    if spec.backend == "pallas" and spec.differentiable:
        # _validate already rejected sharded/pipelined plans and use_approx
        from repro.kernels.routing import ops as routing_ops
        if _differentiable_kernel(spec, jnp.shape(u_hat), axes):
            return routing_ops.dynamic_routing_procedure_train(
                u_hat, iterations=spec.iterations,
                use_approx=spec.use_approx, stream_dtype=spec.stream_dtype)
        cfg = routing_lib.RoutingConfig(
            iterations=spec.iterations, use_approx=spec.use_approx)
        return routing_lib.dynamic_routing(u_hat, cfg)
    if spec.backend == "pallas":
        from repro.kernels.routing import ops as routing_ops
        form = routing_ops.resolve_fusion(
            spec.fusion, jnp.shape(u_hat), spec.stream_dtype,
            sharded=bool(axes),
            early_exit=spec.early_exit_eps is not None)
        if form == "stage_split":
            # sharded-fused: stage-split kernels + cross-shard psums at
            # the Table-2 aggregation points (DESIGN.md §Sharded-fused)
            return routing_ops.dynamic_routing_fused_sharded(
                u_hat, axes=axes, iterations=spec.iterations,
                use_approx=spec.use_approx, stream_dtype=spec.stream_dtype)
        if form == "procedure":
            # whole-procedure megakernel (DESIGN.md §Procedure-fused);
            # int8 û streaming and early exit live only here
            # (DESIGN.md §Quantized-routing)
            return routing_ops.dynamic_routing_procedure_fused(
                u_hat, iterations=spec.iterations,
                use_approx=spec.use_approx, stream_dtype=spec.stream_dtype,
                early_exit_eps=spec.early_exit_eps)
        return routing_ops.dynamic_routing_fused(
            u_hat, iterations=spec.iterations, use_approx=spec.use_approx,
            stream_dtype=spec.stream_dtype)
    cfg = routing_lib.RoutingConfig(
        iterations=spec.iterations, use_approx=spec.use_approx,
        axes=tuple(sorted(axes.items())) or None)
    return routing_lib.dynamic_routing(u_hat, cfg)


DYNAMIC = register_algorithm(Algorithm(
    name="dynamic",
    run=_dynamic_run,
    in_specs=lambda ax: (P(ax.get("B"), ax.get("L"), ax.get("H"), None),),
    out_specs=lambda ax: P(ax.get("B"), ax.get("H"), None),
    sharded_dims=("B", "L", "H"),
    backends=("jnp", "pallas"),
    describe="dynamic routing (paper Alg.1): u_hat (B,L,H,C) -> v (B,H,C)",
))


# --- "em" [Hinton, Sabour, Frosst 2018] ------------------------------------

def _em_run(args, spec: RouterSpec, axes: Mapping[str, str]):
    votes, a_in = args
    if spec.backend == "pallas":
        from repro.kernels.routing import ops as routing_ops
        return routing_ops.em_routing_fused(
            votes, a_in, axes=axes, iterations=spec.iterations,
            beta_a=spec.option("beta_a", 1.0),
            beta_u=spec.option("beta_u", 1.0),
            inv_temp=spec.option("inv_temp", 1.0),
            eps=spec.option("eps", 1e-9))
    cfg = em_lib.EMRoutingConfig(
        iterations=spec.iterations,
        beta_a=spec.option("beta_a", 1.0),
        beta_u=spec.option("beta_u", 1.0),
        inv_temp=spec.option("inv_temp", 1.0),
        eps=spec.option("eps", 1e-9),
        sharded_dim="L" if "L" in axes else None,
        axis_name=axes.get("L"))
    return em_lib.em_routing(votes, a_in, cfg)


EM = register_algorithm(Algorithm(
    name="em",
    run=_em_run,
    in_specs=lambda ax: (P(ax.get("B"), ax.get("L"), None, None),
                         P(ax.get("B"), ax.get("L"))),
    # pose (B,H,C) + activations (B,H); the L-psums leave outputs
    # replicated on L's axis, so only B stays sharded.
    out_specs=lambda ax: (P(ax.get("B"), None, None), P(ax.get("B"), None)),
    # H-sharding would split the per-H Gaussian statistics.
    sharded_dims=("B", "L"),
    backends=("jnp", "pallas"),
    num_inputs=2,
    describe="EM routing: votes (B,L,H,C) + a_in (B,L) -> (pose, a_out)",
))


# --- "moe" top-k expert dispatch -------------------------------------------
#
# MoE expert dispatch has the routing-procedure shape the paper's §2.2
# characterizes — per-token assignment logits, a cross-token aggregation
# (capacity-bounded gather/scatter), massive unshareable intermediates —
# so it registers here as a Router algorithm (DESIGN.md §WaveServe) and
# expert-parallel plans flow through the same build_router registry and
# Table-2 psum seams ("E" on a mesh axis == experts sharded, outputs
# psum'd) instead of a parallel code path.  jnp backend first; args are
# ``models.moe.router_args(params)`` order.

def _moe_run(args, spec: RouterSpec, axes: Mapping[str, str]):
    # lazy: CapsNet routing never pays the models-package import
    from repro.models import moe as moe_lib
    x2d, router_w, w_gate, w_up, w_down = args
    cfg = spec.option("moe_cfg")
    if cfg is None:
        raise ValueError(
            "algorithm 'moe' needs the static MoEConfig in the spec "
            "options: RouterSpec(algorithm='moe', "
            "options=(('moe_cfg', cfg),))")
    axis = axes.get("E")
    offset = (jax.lax.axis_index(axis) * w_gate.shape[0]
              if axis is not None else 0)
    return moe_lib._moe_local(x2d, router_w, w_gate, w_up, w_down, cfg,
                              offset, axis)


MOE = register_algorithm(Algorithm(
    name="moe",
    run=_moe_run,
    # tokens + router replicated; the three expert stacks sharded on E
    in_specs=lambda ax: (P(None, None), P(None, None),
                         P(ax.get("E"), None, None),
                         P(ax.get("E"), None, None),
                         P(ax.get("E"), None, None)),
    # y (T, D) is psum'd over the expert axis inside _moe_local, aux with
    # it — both leave the shard_map replicated
    out_specs=lambda ax: (P(None, None), P()),
    sharded_dims=("E",),
    backends=("jnp",),
    num_inputs=5,
    describe="MoE top-k dispatch: x (T,D) + router/expert weights -> "
             "(y (T,D), aux); shard 'E' for expert parallelism",
))


# ---------------------------------------------------------------------------
# ExecutionPlan — distribution + pipelining
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Where and how the routing procedure executes.

    One type unifies the previously separate execution paths:

      ExecutionPlan()                                   unsharded
      ExecutionPlan(mesh=m, axes=(("B", "x"),))         single-dim shard_map
      ExecutionPlan(mesh=m, axes=(("B","data"),
                                  ("L","model")))       multi-dim shard_map
      ExecutionPlan(mesh=m, auto=True)                  §5.1.2 planner picks
      ExecutionPlan(mesh=m, pipeline="two_stage", ...)  paper §4 host||PIM
      ExecutionPlan(pipeline="software")                skewed-scan overlap

    auto: derive RPShape from the votes shape (or use ``rp_shape``), derive
        a DeviceModel from the mesh (or use ``device``), evaluate the
        execution score S = 1/(alpha*E + beta*M) per shardable-and-divisible
        dimension, and shard the argmax — ``plan="auto"`` in build_router.
    pipeline: "software" (single-group skewed scan) or "two_stage"
        (disjoint device groups on ``pipeline_axis``, |axis| == 2); the
        router then consumes stacked microbatches — a pytree whose leaves
        are (n_micro, ...) (e.g. images + a padding mask, DESIGN.md
        §Serving).  ``stage_a`` is the producer stage (e.g. conv + votes);
        identity when omitted — for multi-input algorithms (EM) it must
        return the algorithm's input tuple in argument order (the
        (votes, a_in) hand-off), and the pipeline hands the whole tuple
        across stages.  Pipeline plans COMPOSE with axes/auto: the
        sharded/auto distribution applies to the routing stage *inside*
        the pipeline (the paper's §5.1 vault distribution running in the
        §4 PIM stage) over one or several non-pipe mesh axes
        (``axes=(("B","data"), ("L","model"))`` shards the stage over
        both), resolved against the stage_a output (votes) shape.
    """
    mesh: Optional[jax.sharding.Mesh] = None
    axes: Tuple[Tuple[str, str], ...] = ()
    auto: bool = False
    device: Optional[dist_lib.DeviceModel] = None
    rp_shape: Optional[dist_lib.RPShape] = None
    pipeline: Optional[str] = None
    pipeline_axis: str = "pipe"
    stage_a: Optional[Callable] = None

    def __post_init__(self):
        if self.pipeline not in (None, "software", "two_stage"):
            raise ValueError(f"unknown pipeline kind {self.pipeline!r}")
        if self.axes and self.auto:
            raise ValueError("ExecutionPlan: give explicit axes OR auto=True,"
                             " not both")
        dims = [d for d, _ in self.axes]
        if len(set(dims)) != len(dims):
            raise ValueError(f"duplicate logical dims in axes {self.axes}")
        names = [a for _, a in self.axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axes in axes {self.axes}; "
                             "each sharded dim needs its own mesh axis")
        for d, a in self.axes:
            if self.mesh is None:
                raise ValueError("ExecutionPlan with sharded axes needs a "
                                 "mesh")
            if a not in self.mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh axes "
                                 f"{self.mesh.axis_names}")


def _normalize_plan(plan) -> ExecutionPlan:
    if plan is None:
        return ExecutionPlan()
    if isinstance(plan, str):
        if plan == "auto":
            return ExecutionPlan(auto=True)
        raise ValueError(f"unknown plan {plan!r} (expected None, 'auto', or "
                         "an ExecutionPlan)")
    if isinstance(plan, ExecutionPlan):
        return plan
    raise TypeError(f"plan must be None, 'auto', or ExecutionPlan; got "
                    f"{type(plan).__name__}")


def _default_mesh() -> jax.sharding.Mesh:
    """All local devices on one axis — the TPU stand-in for the paper's
    vault array (DESIGN.md §2: vault == mesh shard)."""
    return compat.make_mesh((len(jax.devices()),), ("vault",))


def derive_rp_shape(algorithm: str, shapes: tuple, iterations: int,
                    ) -> dist_lib.RPShape:
    """RPShape (paper Table 3) from the router's input shapes.

    The votes tensor is (B, L, H, C_H) for both registered algorithms;
    C_L is not recoverable from the votes (Eq.1 already consumed it), so
    the C_H value is used for both — it only biases the E-terms' shared
    prefactor, never the B/L/H ordering for a fixed shape.
    """
    B, L, H, C = shapes[0]
    return dist_lib.RPShape(n_b=B, n_l=L, n_h=H, c_l=C, c_h=C,
                            iters=iterations)


def plan_axes(spec: RouterSpec, plan: ExecutionPlan,
              shapes: tuple) -> Tuple[Tuple[str, str], ...]:
    """Resolve an auto plan to concrete (dim, mesh_axis) pairs.

    Feasible dims = the algorithm's shardable dims whose extent divides the
    mesh axis size (GSPMD needs divisibility; the paper allows imbalanced
    snippets).  Among those, argmax of the §5.1.2 execution score.  The
    mesh's *first* axis hosts the distribution (the paper shards exactly
    one dimension; multi-axis auto plans are future work — explicit
    ``axes`` already supports them).  Pipelined plans reserve
    ``plan.pipeline_axis`` for the stage split, so the first *other* mesh
    axis hosts the distribution (the routing stage's vault axis).
    """
    mesh = plan.mesh if plan.mesh is not None else _default_mesh()
    candidates = [a for a in mesh.axis_names
                  if not (plan.pipeline is not None
                          and a == plan.pipeline_axis)]
    if not candidates:
        return ()
    axis = candidates[0]
    n = mesh.shape[axis]
    algo = get_algorithm(spec.algorithm)
    if not set(algo.sharded_dims) & {"B", "L", "H"}:
        # the §5.1.2 score table ranks capsule dims only; algorithms
        # sharded on other dims (e.g. moe's "E") take explicit axes
        return ()
    s = plan.rp_shape or derive_rp_shape(spec.algorithm, shapes,
                                         spec.iterations)
    # an explicit DeviceModel keeps its own operating point (e.g. the
    # paper's 32-vault HMC); the default model follows the mesh's device
    # kind and is sized to the mesh.
    dev = plan.device or dist_lib.DeviceModel.for_device_kind(
        mesh.devices.flat[0].device_kind, n)
    extents = {"B": s.n_b, "L": s.n_l, "H": s.n_h}
    feasible = [d for d in algo.sharded_dims if extents[d] % n == 0]
    if not feasible:
        return ()
    table = dist_lib.score_table(s, dev)
    best = max(feasible, key=table.__getitem__)
    return ((best, axis),)


# ---------------------------------------------------------------------------
# build_router
# ---------------------------------------------------------------------------

class ResolvedPlan(tuple):
    """``Router.resolve()`` result: behaves exactly like the historical
    tuple of concrete (dim, mesh_axis) pairs (len / indexing / iteration),
    plus the resolved kernel execution attributes:

    fusion:       "procedure" | "iteration" | "stage_split" — the concrete
                  kernel form a pallas-backend router will run (DESIGN.md
                  §Procedure-fused); None for the jnp backend.
    stream_dtype: "fp32" | "bf16" | "int8" û streaming dtype; None for jnp.
    differentiable: True iff execution runs the fused procedure kernel
                  through its recompute-b custom VJP (DESIGN.md §Training)
                  — i.e. ``jax.grad`` hits the backward megakernel.  False
                  for the jnp backend (plain autodiff, no fused backward)
                  and for forward-only pallas execution.
    early_exit_eps: the ‖Δb‖∞ convergence threshold the megakernel will
                  skip converged L-tiles at (DESIGN.md §Quantized-routing);
                  None when early exit is off or the backend is jnp.
    """

    def __new__(cls, axes=(), fusion=None, stream_dtype=None,
                differentiable=False, early_exit_eps=None):
        self = super().__new__(cls, tuple(axes))
        self.fusion = fusion
        self.stream_dtype = stream_dtype
        self.differentiable = differentiable
        self.early_exit_eps = early_exit_eps
        return self

    def __repr__(self):
        return (f"ResolvedPlan(axes={tuple(self)}, fusion={self.fusion!r}, "
                f"stream_dtype={self.stream_dtype!r}, "
                f"differentiable={self.differentiable!r}, "
                f"early_exit_eps={self.early_exit_eps!r})")


class Router:
    """The callable built by ``build_router`` — also carries its spec/plan
    and exposes ``resolve(*args)`` so callers can inspect the concrete
    distribution an auto plan picked for given inputs."""

    def __init__(self, spec: RouterSpec, plan: ExecutionPlan):
        self.spec = spec
        self.plan = plan
        self.algorithm = get_algorithm(spec.algorithm)
        self._cache: Dict[tuple, Callable] = {}
        _validate(self.algorithm, spec, plan)

    # -- plan resolution ----------------------------------------------------

    def resolve(self, *args) -> ResolvedPlan:
        """Concrete execution for these inputs: a ``ResolvedPlan`` — a tuple
        of (dim, mesh_axis) pairs (backward compatible) carrying the
        resolved ``fusion`` level and ``stream_dtype`` as attributes.

        With a pipeline plan the distribution lives inside the routing
        stage, so resolution runs against the stage_a output (votes — for
        multi-input algorithms the first hand-off leaf) shape of one
        microbatch, not the stacked pipeline inputs.
        """
        if self.plan.pipeline is not None:
            hidden = self._hidden_struct(args[0])
            shapes = tuple(l.shape for l in jax.tree.leaves(hidden))
        else:
            shapes = tuple(jnp.shape(a) for a in args)
        axes = self._resolve_shapes(shapes)
        return ResolvedPlan(axes, *self._resolve_fusion(axes, shapes))

    def _resolve_fusion(self, axes, shapes):
        """(fusion, stream_dtype, differentiable, early_exit_eps) the pallas
        backend will execute with — the same ``resolve_fusion`` the run
        path calls, so the report can never drift from execution.  jnp
        backend: (None, None, False, None); a no-arg ``resolve()``
        (historically legal for static plans) reports None for fusion when
        the "auto" fit check would need the votes shape — except for the
        deep-edge knobs (int8 / early exit), which resolve "procedure"
        without a shape."""
        if self.spec.backend != "pallas":
            return None, None, False, None
        if self.spec.algorithm != "dynamic":
            # EM: stage-split is the only form
            return "stage_split", "fp32", False, None
        early_exit = self.spec.early_exit_eps is not None
        deep_edge = self.spec.stream_dtype == "int8" or early_exit
        if (not shapes and not axes and self.spec.fusion == "auto"
                and not deep_edge):
            return None, self.spec.stream_dtype, False, None
        from repro.kernels.routing import ops as routing_ops
        if self.spec.differentiable:
            # _dynamic_run's differentiable dispatch: the custom VJP, or
            # jnp autodiff (reported as the jnp 4-tuple)
            if _differentiable_kernel(self.spec,
                                      shapes[0] if shapes else None, axes):
                return "procedure", self.spec.stream_dtype, True, None
            return None, None, False, None
        form = routing_ops.resolve_fusion(self.spec.fusion,
                                          shapes[0] if shapes else None,
                                          self.spec.stream_dtype,
                                          sharded=bool(axes),
                                          early_exit=early_exit)
        return (form, self.spec.stream_dtype, False,
                self.spec.early_exit_eps)

    def _resolve_shapes(self, shapes: tuple) -> Tuple[Tuple[str, str], ...]:
        if not self.plan.auto:
            return tuple(self.plan.axes)
        if self.spec.backend == "pallas" and (
                self.spec.differentiable
                or self.spec.stream_dtype == "int8"
                or self.spec.early_exit_eps is not None):
            # these auto plans resolve shard-local: the §5.1.2 planner's
            # sharded pick would force the stage-split form, which has no
            # custom VJP (DESIGN.md §Training), no int8 dequant path and
            # no convergence scratch (DESIGN.md §Quantized-routing)
            return ()
        return plan_axes(self.spec, self.plan, shapes)

    def _hidden_struct(self, micro) -> jax.ShapeDtypeStruct:
        """Abstract stage_a output for one microbatch of stacked pipeline
        inputs (a pytree with (n_micro, ...) leaves)."""
        stage_a = self.plan.stage_a or (lambda x: x)
        per_micro = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a)[1:],
                                           jnp.result_type(a)), micro)
        return jax.eval_shape(stage_a, per_micro)

    def _mesh(self) -> jax.sharding.Mesh:
        return self.plan.mesh if self.plan.mesh is not None \
            else _default_mesh()

    # -- executor construction ---------------------------------------------

    def _core_fn(self, axes: Tuple[Tuple[str, str], ...]) -> Callable:
        # invalid compositions (un-shardable dims with sharded axes) were
        # rejected in _validate; auto plans only resolve to dims that pass
        # the same filters (plan_axes).  Both backends are sharding-aware:
        # the jnp path and the pallas stage-split path insert the Table-2
        # psums themselves from the ``axes`` mapping.
        algo, spec = self.algorithm, self.spec
        ax = dict(axes)
        if not axes:
            return lambda *args: algo.run(args, spec, {})
        return compat.shard_map(
            lambda *args: algo.run(args, spec, ax),
            self._mesh(), tuple(algo.in_specs(ax)), algo.out_specs(ax))

    def _stage_b(self, axes: Tuple[Tuple[str, str], ...]) -> Callable:
        """Pipeline stage B: the algorithm body consuming the stage-A
        hand-off — a bare votes array for 1-input algorithms, a tuple for
        multi-input ones (EM's (votes, a_in) — DESIGN.md §Serving)."""
        core = self._core_fn(axes)
        if self.algorithm.num_inputs == 1:
            return core
        return lambda h: core(*h)

    def _pipelined_fn(self, micro) -> Callable:
        plan = self.plan
        stage_a = plan.stage_a or (lambda x: x)
        hidden = self._hidden_struct(micro)
        shapes = tuple(l.shape for l in jax.tree.leaves(hidden))
        axes = self._resolve_shapes(shapes)
        if plan.pipeline == "software":
            # the routing stage may itself be a shard_map program (§5.1
            # distribution inside the stage, over one or several vault
            # axes) — it traces under the scan.
            stage_b = self._stage_b(axes)
            return lambda m: pipeline_lib.software_pipeline_scan(
                stage_a, stage_b, m)
        if not axes:
            return pipeline_lib.two_stage_pipeline(
                stage_a, self._stage_b(()), self._mesh(),
                plan.pipeline_axis, hidden)
        return self._two_stage_sharded_fn(stage_a, hidden, axes)

    def _two_stage_sharded_fn(self, stage_a: Callable, hidden,
                              axes: Tuple[Tuple[str, str], ...]) -> Callable:
        """§4 pipeline with the §5.1 vault distribution inside the PIM
        stage (DESIGN.md §Serving): ONE shard_map spans the pipe axis AND
        every vault axis; stage B is the per-shard algorithm body with its
        Table-2 psums per vault axis.  Generalizes along two directions:

        * multi-dim plans — ``axes`` may hold several (dim, mesh_axis)
          pairs (e.g. B over "data" x L over "model"); each sharded dim's
          position in each stage-B input comes from the algorithm's own
          ``in_specs``, so the slicing never hard-codes a layout;
        * multi-input algorithms — ``hidden`` is the stage-A hand-off
          pytree (a tuple in algorithm-argument order for EM's
          (votes, a_in)); every leaf crosses the ppermute hand-off.

        B-sharded plans shard the pipeline *inputs* (each vault's host
        group encodes its own lanes — logical B is the stacked inputs'
        lane dim); other sharded dims replicate the encoder and have each
        host shard slice its vault's chunk before the hand-off — the
        paper's host-computes-votes, scatters-to-vaults traffic pattern.
        """
        plan, algo, spec = self.plan, self.algorithm, self.spec
        mesh = self._mesh()
        ax = dict(axes)
        in_specs = tuple(algo.in_specs(ax))
        structs = list(hidden) if algo.num_inputs > 1 else [hidden]
        if len(structs) != len(in_specs):
            raise ValueError(
                f"stage_a must hand off {algo.num_inputs} leaves in "
                f"{algo.name!r}'s argument order; got {len(structs)}")
        axis_dim = {a: d for d, a in axes}
        b_axis = ax.get("B")

        def shard_struct(struct, ispec):
            shape = list(struct.shape)
            for pos, name in enumerate(ispec):
                if name is None:
                    continue
                n = mesh.shape[name]
                if shape[pos] % n:
                    raise ValueError(
                        f"votes dim {axis_dim[name]}={shape[pos]} not "
                        f"divisible by |{name}|={n}")
                shape[pos] //= n
            return jax.ShapeDtypeStruct(tuple(shape), struct.dtype)

        per_shard = tuple(shard_struct(s, i)
                          for s, i in zip(structs, in_specs))
        a_out_shape = per_shard if algo.num_inputs > 1 else per_shard[0]

        def stage_a_shard(x):
            h = stage_a(x)
            leaves = list(h) if algo.num_inputs > 1 else [h]
            out = []
            for leaf, ispec in zip(leaves, in_specs):
                for pos, name in enumerate(ispec):
                    if name is None or name == b_axis:
                        continue    # B arrived pre-sharded via the inputs
                    chunk = leaf.shape[pos] // mesh.shape[name]
                    i = jax.lax.axis_index(name)
                    leaf = jax.lax.dynamic_slice_in_dim(
                        leaf, i * chunk, chunk, pos)
                out.append(leaf)
            return tuple(out) if algo.num_inputs > 1 else out[0]

        def stage_b_shard(h):
            args = tuple(h) if algo.num_inputs > 1 else (h,)
            return algo.run(args, spec, ax)

        in_spec = P(None, b_axis) if b_axis is not None else P(None)
        outs = algo.out_specs(ax)
        if isinstance(outs, P):
            out_spec = P(None, *outs)
        else:
            out_spec = tuple(P(None, *s) for s in outs)
        return pipeline_lib.two_stage_pipeline(
            stage_a_shard, stage_b_shard, mesh, plan.pipeline_axis,
            a_out_shape, in_spec=in_spec, out_spec=out_spec,
            stage_b_collectives=True)

    def _executor(self, args) -> Callable:
        leaves, treedef = jax.tree.flatten(args)
        key = (treedef, tuple((jnp.shape(l), jnp.result_type(l))
                              for l in leaves))
        fn = self._cache.get(key)
        if fn is None:
            if self.plan.pipeline is not None:
                fn = self._pipelined_fn(args[0])
            else:
                fn = self._core_fn(self._resolve_shapes(
                    tuple(jnp.shape(a) for a in args)))
            self._cache[key] = fn
        return fn

    def __call__(self, *args):
        if (self.plan.pipeline is None
                and len(args) != self.algorithm.num_inputs):
            raise TypeError(
                f"{self.spec.algorithm!r} router takes "
                f"{self.algorithm.num_inputs} input(s) "
                f"({self.algorithm.describe or 'see registry entry'}); "
                f"got {len(args)}")
        return self._executor(args)(*args)

    def __repr__(self):
        return (f"Router(algorithm={self.spec.algorithm!r}, "
                f"backend={self.spec.backend!r}, "
                f"fusion={self.spec.fusion!r}, "
                f"stream_dtype={self.spec.stream_dtype!r}, "
                f"differentiable={self.spec.differentiable!r}, "
                f"early_exit_eps={self.spec.early_exit_eps!r}, "
                f"plan={'auto' if self.plan.auto else self.plan.axes}, "
                f"pipeline={self.plan.pipeline!r})")


def _validate(algo: Algorithm, spec: RouterSpec, plan: ExecutionPlan):
    # fusion / stream-dtype vocabularies live in kernels/routing/vocab.py —
    # a light module with no pallas import, so every build_router no longer
    # drags the kernel package in just to spell-check two strings.
    from repro.kernels.routing import vocab as routing_vocab
    if spec.backend not in BACKENDS:
        raise ValueError(f"unknown backend {spec.backend!r}; expected one "
                         f"of {BACKENDS}")
    if spec.backend not in algo.backends:
        raise ValueError(
            f"algorithm {algo.name!r} has no {spec.backend!r} backend "
            f"(supported: {algo.backends}); register a kernel for it or "
            "use backend='jnp'")
    if spec.fusion not in routing_vocab.FUSION_LEVELS:
        raise ValueError(f"unknown fusion level {spec.fusion!r}; expected "
                         f"one of {routing_vocab.FUSION_LEVELS}")
    if spec.stream_dtype not in routing_vocab.STREAM_DTYPES:
        raise ValueError(f"unknown stream_dtype {spec.stream_dtype!r}; "
                         f"expected one of "
                         f"{tuple(sorted(routing_vocab.STREAM_DTYPES))}")
    _pallas_dynamic = spec.backend == "pallas" and algo.name == "dynamic"
    if spec.fusion != "auto" and not _pallas_dynamic:
        raise ValueError(
            f"fusion={spec.fusion!r} is a pallas-backend knob of the "
            "'dynamic' algorithm (EM and the jnp backend have no fused "
            "megakernel); leave fusion='auto'")
    if spec.stream_dtype != "fp32" and not _pallas_dynamic:
        raise ValueError(
            f"stream_dtype={spec.stream_dtype!r} requires the 'dynamic' "
            "algorithm on the pallas backend (the jnp path and the EM "
            "kernels stream fp32)")
    if spec.fusion == "procedure" and plan.axes:
        raise ValueError(
            "fusion='procedure' is shard-local (the megakernel keeps b/v/s "
            "in VMEM across iterations and cannot surface for the Table-2 "
            "psums); use fusion='auto' or 'iteration' with sharded plans")
    # --- deep-edge tier (DESIGN.md §Quantized-routing): int8 û streaming
    # and early exit exist only in the forward procedure megakernel
    if spec.early_exit_eps is not None:
        eps = spec.early_exit_eps
        if not isinstance(eps, (int, float)) or isinstance(eps, bool) \
                or not float(eps) >= 0.0:
            raise ValueError(
                f"early_exit_eps must be a float >= 0 (the ‖Δb‖∞ "
                f"convergence threshold; 0 keeps the fixed grid) or None; "
                f"got {eps!r}")
        if not _pallas_dynamic:
            raise ValueError(
                "early_exit_eps is a pallas-backend knob of the 'dynamic' "
                "algorithm (only the procedure megakernel tracks per-tile "
                "convergence); leave early_exit_eps=None")
        if spec.fusion == "iteration":
            raise ValueError(
                "early_exit_eps requires the procedure megakernel: "
                "fusion='iteration' has no per-tile convergence scratch; "
                "use fusion='auto' or 'procedure'")
        if plan.axes:
            raise ValueError(
                "early-exit routing is shard-local: the per-tile "
                "convergence scratch lives in the procedure megakernel, "
                "which cannot surface for the Table-2 psums; use an "
                "unsharded plan (plan=None or 'auto')")
        if spec.differentiable:
            raise ValueError(
                "differentiable=True requires early_exit_eps=None: the "
                "recompute-b backward replays the fixed-grid schedule "
                "(data-dependent tile skipping has no replay); train "
                "fixed-grid, serve early-exit")
    if spec.stream_dtype == "int8":
        if spec.differentiable:
            raise ValueError(
                "differentiable=True requires stream_dtype 'fp32' or "
                "'bf16': int8 û quantization rounds to the nearest code "
                "(no derivative) and the backward megakernel has no "
                "dequant path; train fp32/bf16, serve int8")
        if spec.fusion == "iteration":
            raise ValueError(
                "stream_dtype='int8' requires the procedure megakernel "
                "(per-tile scales and dequant are megakernel-only); use "
                "fusion='auto' or 'procedure'")
        if plan.axes:
            raise ValueError(
                "stream_dtype='int8' is shard-local: only the procedure "
                "megakernel has a dequant path, and it cannot surface for "
                "the Table-2 psums; use an unsharded plan (plan=None or "
                "'auto')")
    if spec.differentiable and spec.backend == "pallas":
        # DESIGN.md §Training: the recompute-b custom VJP exists for the
        # 'dynamic' procedure megakernel only
        if algo.name != "dynamic":
            raise ValueError(
                "differentiable=True on the pallas backend requires the "
                "'dynamic' algorithm — only the procedure megakernel has a "
                "custom VJP; use backend='jnp' for differentiable "
                f"{algo.name!r} routing")
        if spec.use_approx:
            raise ValueError(
                "differentiable=True requires use_approx=False: the §5.2.2 "
                "bit-manipulation approximations have no derivative "
                "(bitcast is not differentiable); train exact, serve "
                "approx")
        if spec.fusion == "iteration":
            raise ValueError(
                "fusion='iteration' has no custom VJP; the differentiable "
                "fused form is the procedure megakernel — use "
                "fusion='auto' or 'procedure' with differentiable=True")
        if plan.axes or plan.pipeline is not None:
            raise ValueError(
                "differentiable pallas routing is shard-local: the "
                "stage-split sharded/pipelined forms have no custom VJP "
                "(the Table-2 psums would need their own transpose rules); "
                "train with backend='jnp' under sharded/pipelined plans, "
                "or use plan=None/'auto' (auto resolves unsharded when "
                "differentiable)")
    bad = [d for d, _ in plan.axes if d not in algo.sharded_dims]
    if bad:
        raise ValueError(
            f"algorithm {algo.name!r} cannot shard dims {bad} "
            f"(shardable: {algo.sharded_dims})")
    if plan.pipeline is not None:
        # any registered algorithm pipelines: the stage hand-off is the
        # algorithm's input tuple (multi-input hand-off, DESIGN.md
        # §Serving), and the routing stage may shard over any number of
        # non-pipe mesh axes (multi-dim sharded pipeline stages).
        if any(a == plan.pipeline_axis for _, a in plan.axes):
            raise ValueError(
                f"mesh axis {plan.pipeline_axis!r} is the pipeline's stage "
                "axis; shard the routing stage over a different axis (or "
                "rename pipeline_axis)")
        if plan.pipeline == "two_stage":
            mesh = plan.mesh
            if mesh is None or plan.pipeline_axis not in mesh.axis_names:
                raise ValueError("pipeline='two_stage' needs a mesh "
                                 f"containing axis {plan.pipeline_axis!r}")


def build_router(spec: RouterSpec = RouterSpec(), plan=None) -> Router:
    """One entry point: algorithm x backend x distribution plan -> callable.

    spec: RouterSpec (or left default: unsharded exact dynamic routing).
    plan: None (unsharded) | "auto" (§5.1.2 planner, default mesh) |
          ExecutionPlan (explicit mesh/axes/pipeline/auto).

    Returns a ``Router`` — call it like the underlying algorithm
    (``router(u_hat)`` for dynamic, ``router(votes, a_in)`` for EM); with a
    pipeline plan it consumes stacked microbatches: a pytree whose leaves
    are ``(n_micro, ...)`` (axes/auto then distribute the routing stage
    inside the pipeline — DESIGN.md §Serving).
    """
    return Router(spec, _normalize_plan(plan))


def as_router(spec=None, plan=None, *, default_iterations: int = 3):
    """Coerce the (spec, plan) surface of runtime entry points to a Router.

    spec: None (default RouterSpec at ``default_iterations``), a RouterSpec,
    or an already-built Router/callable — in which case ``plan`` must be
    None (a built Router carries its ExecutionPlan).
    """
    if spec is None:
        spec = RouterSpec(iterations=default_iterations)
    if callable(spec) and not isinstance(spec, RouterSpec):
        if plan is not None:
            raise ValueError("pass plan only with a RouterSpec; a prebuilt "
                             "Router already carries its ExecutionPlan")
        return spec
    return build_router(spec, plan)


# ---------------------------------------------------------------------------
# Legacy bridge (deprecation shims in core.routing / core.em_routing)
# ---------------------------------------------------------------------------

def from_routing_config(cfg: routing_lib.RoutingConfig,
                        mesh: Optional[jax.sharding.Mesh] = None) -> Router:
    """RoutingConfig -> Router (deprecation bridge, DESIGN.md §Shims)."""
    spec = RouterSpec(algorithm="dynamic",
                      backend="pallas" if cfg.fused else "jnp",
                      iterations=cfg.iterations, use_approx=cfg.use_approx)
    axes = tuple(cfg.axes or ())
    if not axes and cfg.sharded_dim is not None:
        axes = ((cfg.sharded_dim, cfg.axis_name),)
    plan = ExecutionPlan(mesh=mesh, axes=axes) if axes else None
    return build_router(spec, plan)
