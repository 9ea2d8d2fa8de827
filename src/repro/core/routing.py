"""Dynamic routing procedure (paper Algorithm 1 / Eq.1-5), distribution-aware.

The routing procedure routes L low-level capsules to H high-level capsules:

    u_hat[k,i,j]   = u[k,i] @ W[i,j]                       (Eq.1, done by caller)
    repeat I times:
        c[i,j]     = softmax_j(b[i,j])                     (Eq.5)
        s[k,j]     = sum_i u_hat[k,i,j] * c[i,j]           (Eq.2)
        v[k,j]     = squash(s[k,j])                        (Eq.3)
        b[i,j]    += sum_k <v[k,j], u_hat[k,i,j]>          (Eq.4)

Distribution (paper §5.1): every equation is independently parallel along at
least one of {B, L, H} (paper Table 2) but no dimension parallelises all five,
so sharding one dimension leaves a small set of cross-shard aggregations:

    shard B  ->  Eq.4's sum over k crosses shards          (psum of b-updates)
    shard L  ->  Eq.2's sum over i crosses shards          (psum of s)
    shard H  ->  Eq.5's softmax denominator crosses shards (psum of max/sum)

``dynamic_routing`` is written so the same code runs (a) unsharded, (b) under
``jax.shard_map`` with any one of the three logical dims mapped to a mesh axis
— the caller passes ``sharded_dim`` + ``axis_name`` and the required psum is
inserted exactly where the paper's inter-vault aggregation happens.  The
pre-aggregation optimisation (paper §5.1.2: combine per-vault partial b before
the global aggregation) is what ``lax.psum`` of the locally-summed update does.
"""
from __future__ import annotations

from typing import Literal, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import approx

ShardedDim = Optional[Literal["B", "L", "H"]]


class RoutingConfig(NamedTuple):
    """Static routing configuration.

    iterations:   paper Table 1 "Iter" (3..9).
    use_approx:   paper §5.2.2 PE approximations for exp / rsqrt / div.
    sharded_dim:  which logical dimension is sharded across the mesh axis
                  ``axis_name`` (paper §5.1 inter-vault distribution choice).
    axes:         multi-dimensional generalization (beyond-paper, §Perf):
                  {"B": axis, "L": axis, ...} shards several logical dims at
                  once (e.g. B over "data" x L over "model" on the 2D
                  torus); overrides sharded_dim/axis_name when set.
    fused:        route via the Pallas kernels (kernels/routing); pure-jnp
                  path otherwise.  Composes with sharded_dim/axes: the
                  stage-split sharded-fused form inserts the cross-shard
                  psums between per-shard Pallas stages (DESIGN.md
                  §Sharded-fused).
    """
    iterations: int = 3
    use_approx: bool = False
    sharded_dim: ShardedDim = None
    axis_name: Optional[str] = None
    fused: bool = False
    axes: Optional[tuple] = None    # tuple of (dim, axis_name) pairs

    def axis_of(self, dim: str) -> Optional[str]:
        if self.axes is not None:
            for d, a in self.axes:
                if d == dim:
                    return a
            return None
        return self.axis_name if self.sharded_dim == dim else None


def _softmax(b: jax.Array, cfg: RoutingConfig) -> jax.Array:
    """softmax over the H dim of b:(L,H); cross-shard when H is sharded."""
    h_axis = cfg.axis_of("H")
    if h_axis is not None:
        m = lax.pmax(jnp.max(b, axis=-1, keepdims=True), h_axis)
        e = (approx.fast_exp(b - m) if cfg.use_approx
             else jnp.exp(b - m))
        denom = lax.psum(jnp.sum(e, axis=-1, keepdims=True), h_axis)
        if cfg.use_approx:
            return e * approx.fast_reciprocal(denom)
        return e / denom
    if cfg.use_approx:
        return approx.approx_softmax(b, axis=-1)
    return jax.nn.softmax(b, axis=-1)


def _squash(s: jax.Array, cfg: RoutingConfig) -> jax.Array:
    if cfg.use_approx:
        return approx.approx_squash(s, axis=-1)
    return approx.exact_squash(s, axis=-1)


def routing_iteration(u_hat: jax.Array, b: jax.Array, cfg: RoutingConfig
                      ) -> tuple[jax.Array, jax.Array]:
    """One full routing iteration. u_hat:(B,L,H,C)  b:(L,H) -> (v, new_b)."""
    c = _softmax(b, cfg)                                   # Eq.5
    s = jnp.einsum("blhc,lh->bhc", u_hat, c)               # Eq.2
    l_axis = cfg.axis_of("L")
    if l_axis is not None:
        s = lax.psum(s, l_axis)                            # inter-vault aggregation
    v = _squash(s, cfg)                                    # Eq.3
    db = jnp.einsum("blhc,bhc->lh", u_hat, v)              # Eq.4 (local pre-agg)
    b_axis = cfg.axis_of("B")
    if b_axis is not None:
        db = lax.psum(db, b_axis)                          # inter-vault aggregation
    return v, b + db


def dynamic_routing(u_hat: jax.Array, cfg: RoutingConfig = RoutingConfig()
                    ) -> jax.Array:
    """Run the full routing procedure.  u_hat:(B,L,H,C) -> v:(B,H,C).

    The iteration loop is a ``lax.scan`` carrying b (the paper's strong
    sequential dependency, §2.2 summary point (1)).  The final iteration's v
    is the routed H-capsule output.
    """
    if cfg.fused:
        from repro.kernels.routing import ops as routing_ops
        axes = dict(cfg.axes or ())
        if not axes and cfg.sharded_dim is not None:
            axes = {cfg.sharded_dim: cfg.axis_name}
        if axes:
            # sharded-fused (DESIGN.md §Sharded-fused): stage-split kernels
            # with the Table-2 psums inserted on the ambient mesh axes.
            # (Historically this raised — the single-pass fused kernel
            # cannot insert cross-shard psums.)
            return routing_ops.dynamic_routing_fused_sharded(
                u_hat, axes=axes, iterations=cfg.iterations,
                use_approx=cfg.use_approx)
        return routing_ops.dynamic_routing_fused(
            u_hat, iterations=cfg.iterations, use_approx=cfg.use_approx)

    v, _ = _scan_routing(u_hat, cfg)
    return v


def _scan_routing(u_hat: jax.Array, cfg: RoutingConfig
                  ) -> tuple[jax.Array, jax.Array]:
    """The jnp iteration loop shared by ``dynamic_routing`` and
    ``dynamic_routing_with_stats``: a ``lax.scan`` carrying b, so the trace
    stays one iteration long no matter how many iterations run.  Returns
    (final v, final b)."""
    u_hat = u_hat.astype(jnp.float32)
    B, L, H, C = u_hat.shape
    b0 = jnp.zeros((L, H), jnp.float32)

    def step(b, _):
        v, b_new = routing_iteration(u_hat, b, cfg)
        return b_new, v

    b, vs = lax.scan(step, b0, None, length=cfg.iterations)
    return vs[-1], b


def dynamic_routing_with_stats(u_hat: jax.Array,
                               cfg: RoutingConfig = RoutingConfig()):
    """Like ``dynamic_routing`` but also returns (b, c) for inspection/tests
    (jnp path only — the fused kernels keep b on-chip).  Shares the
    scan-based loop with ``dynamic_routing``."""
    v, b = _scan_routing(u_hat, cfg)
    return v, b, _softmax(b, cfg)


def make_sharded_routing(mesh: jax.sharding.Mesh, dim: ShardedDim,
                         axis_name: str, cfg: RoutingConfig):
    """DEPRECATED shim — use ``repro.core.router.build_router`` instead.

    Wraps dynamic_routing in shard_map with ``dim`` sharded over
    ``axis_name``: the executable form of the paper's inter-vault
    distribution.  Kept so pre-Router call sites keep working; delegates to
    the unified Router API (DESIGN.md §Router, deprecation policy §Shims).
    """
    return make_multi_sharded_routing(mesh, ((dim, axis_name),), cfg)


def make_multi_sharded_routing(mesh: jax.sharding.Mesh, axes, cfg):
    """DEPRECATED shim — use ``repro.core.router.build_router`` instead.

    Multi-dim generalization (e.g. B over "data" x L over "model" on the
    pod's 2D torus).  axes: tuple of (dim, mesh_axis) pairs.
    """
    from repro.core import router as router_lib
    spec = router_lib.RouterSpec(
        algorithm="dynamic",
        backend="pallas" if cfg.fused else "jnp",
        iterations=cfg.iterations, use_approx=cfg.use_approx)
    plan = router_lib.ExecutionPlan(mesh=mesh, axes=tuple(axes))
    return router_lib.build_router(spec, plan)
