"""PIM-CapsNet core: routing procedure, distribution planner, approximations.

The paper's primary contribution as a composable JAX module — see DESIGN.md.
"""
from repro.core.routing import (RoutingConfig, dynamic_routing,
                                routing_iteration)
from repro.core.distribution import (RPShape, DeviceModel, plan, score_table,
                                     workload_E, comm_M, execution_score,
                                     moe_plan, MoEShape, rmas_optimal_grant)
from repro.core.router import (Algorithm, ExecutionPlan, Router, RouterSpec,
                               as_router, build_router, register_algorithm,
                               registered_algorithms)
from repro.core import approx, capsule_layers, em_routing, pipeline, router

__all__ = [
    # unified Router API (DESIGN.md §Router) — the preferred entry point
    "RouterSpec", "ExecutionPlan", "Router", "build_router", "as_router",
    "Algorithm", "register_algorithm", "registered_algorithms", "router",
    # the jnp reference path and the §5.1 planner
    "RoutingConfig", "dynamic_routing", "routing_iteration",
    "RPShape", "DeviceModel", "plan", "score_table",
    "workload_E", "comm_M", "execution_score", "moe_plan", "MoEShape",
    "rmas_optimal_grant", "approx", "capsule_layers", "em_routing", "pipeline",
]
