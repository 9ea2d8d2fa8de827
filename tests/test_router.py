"""Unified Router API (core.router): registry dispatch, plan="auto" vs the
offline §5.1.2 planner, backend parity, sharded-vs-unsharded equivalence,
legacy-shim equivalence, and the error surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import distribution as D
from repro.core import em_routing, routing
from repro.core.router import (Algorithm, ExecutionPlan, RouterSpec,
                               build_router, plan_axes, register_algorithm,
                               registered_algorithms)


@pytest.fixture()
def u_hat(key):
    return jax.random.normal(key, (4, 32, 8, 16))


@pytest.fixture()
def em_inputs(key):
    votes = jax.random.normal(key, (4, 32, 5, 8))
    a_in = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 1),
                                            (4, 32)))
    return votes, a_in


def test_registry_has_both_paper_algorithms():
    assert set(registered_algorithms()) >= {"dynamic", "em"}


def test_dispatch_dynamic_matches_legacy(u_hat):
    """spec.algorithm='dynamic' == core.routing.dynamic_routing."""
    router = build_router(RouterSpec(algorithm="dynamic", iterations=3))
    want = routing.dynamic_routing(u_hat, routing.RoutingConfig(iterations=3))
    np.testing.assert_allclose(np.asarray(router(u_hat)), np.asarray(want),
                               rtol=1e-6)


def test_dispatch_em_matches_legacy(em_inputs):
    """spec.algorithm='em' == core.em_routing.em_routing (same registry,
    different algorithm — the paper's §2.2 generality claim)."""
    votes, a_in = em_inputs
    router = build_router(RouterSpec(algorithm="em", iterations=3))
    pose, act = router(votes, a_in)
    pose_ref, act_ref = em_routing.em_routing(
        votes, a_in, em_routing.EMRoutingConfig(iterations=3))
    np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(act), np.asarray(act_ref),
                               rtol=1e-6)


def test_unknown_algorithm_and_backend_raise():
    with pytest.raises(KeyError, match="unknown routing algorithm"):
        build_router(RouterSpec(algorithm="quantum"))
    with pytest.raises(ValueError, match="unknown backend"):
        build_router(RouterSpec(backend="triton"))
    # an algorithm that registers no pallas kernel still fails loudly
    from repro.core import router as router_mod
    register_algorithm(Algorithm(
        name="_jnp_only",
        run=lambda args, spec, axes: args[0],
        in_specs=lambda ax: (jax.sharding.PartitionSpec(),),
        out_specs=lambda ax: jax.sharding.PartitionSpec()))
    try:
        with pytest.raises(ValueError, match="no 'pallas' backend"):
            build_router(RouterSpec(algorithm="_jnp_only", backend="pallas"))
    finally:
        del router_mod._REGISTRY["_jnp_only"]


def test_unshardable_dim_rejected_at_build_time():
    """EM + H-sharded plan fails at build_router, not at first call."""
    mesh = compat.make_mesh((1,), ("x",))
    with pytest.raises(ValueError, match="cannot shard dims"):
        build_router(RouterSpec(algorithm="em"),
                     ExecutionPlan(mesh=mesh, axes=(("H", "x"),)))


# ---------------------------------------------------------------------------
# plan="auto" — the §5.1.2 planner closing into execution
# ---------------------------------------------------------------------------

def test_auto_plan_matches_offline_planner_table4():
    """plan='auto' picks the same dimension as distribution.plan() at the
    paper's Table-4 HMC operating point (Caps-MN1 shape)."""
    s = D.RPShape(n_b=100, n_l=1152, n_h=10, c_l=8, c_h=16, iters=3)
    hmc = D.DeviceModel.hmc()
    router = build_router(
        RouterSpec(iterations=s.iters),
        ExecutionPlan(auto=True, device=hmc, rp_shape=s))
    axes = router.resolve(jnp.zeros((s.n_b, s.n_l, s.n_h, s.c_h)))
    assert len(axes) == 1
    assert axes[0][0] == D.plan(s, hmc)


class _FakeMesh:
    """Shape-only mesh stand-in: plan_axes only reads axis_names/shape and
    the device kind, and the test process has a single real device, so a
    4-shard mesh can't be constructed in-process."""
    axis_names = ("vault",)
    shape = {"vault": 4}
    devices = np.array(jax.devices() * 4)


def test_auto_plan_feasibility_filter():
    """Auto never shards a dim whose extent doesn't divide the mesh axis."""
    spec = RouterSpec(iterations=3)
    # only B (=8) divides 4: L=6 and H=10 don't — auto must pick B no
    # matter what the scores say
    axes = plan_axes(spec, ExecutionPlan(mesh=_FakeMesh(), auto=True),
                     ((8, 6, 10, 16),))
    assert axes == (("B", "vault"),)
    # nothing divides 4 -> unsharded
    axes = plan_axes(spec, ExecutionPlan(mesh=_FakeMesh(), auto=True),
                     ((6, 6, 10, 16),))
    assert axes == ()
    # 1-device mesh: everything divides; resolution is the pure argmax
    mesh = compat.make_mesh((1,), ("vault",))
    axes = plan_axes(spec, ExecutionPlan(mesh=mesh, auto=True),
                     ((8, 32, 10, 16),))
    assert len(axes) == 1 and axes[0][1] == "vault"


def test_auto_plan_executes_and_matches_unsharded(u_hat):
    router = build_router(RouterSpec(iterations=3), "auto")
    want = routing.dynamic_routing(u_hat, routing.RoutingConfig(iterations=3))
    np.testing.assert_allclose(np.asarray(router(u_hat)), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_auto_plan_em_avoids_h(em_inputs):
    """EM cannot shard H; auto must resolve within {B, L}."""
    votes, a_in = em_inputs
    router = build_router(RouterSpec(algorithm="em"), "auto")
    axes = router.resolve(votes, a_in)
    assert all(d in ("B", "L") for d, _ in axes)
    pose, act = router(votes, a_in)
    pose_ref, act_ref = em_routing.em_routing(votes, a_in)
    np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# backend parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_approx", [False, True])
def test_jnp_vs_pallas_backend_parity(key, use_approx):
    u_hat = jax.random.normal(key, (2, 32, 6, 8))
    spec = RouterSpec(iterations=3, use_approx=use_approx)
    v_jnp = build_router(spec)(u_hat)
    v_pal = build_router(spec._replace(backend="pallas"))(u_hat)
    np.testing.assert_allclose(np.asarray(v_jnp), np.asarray(v_pal),
                               rtol=1e-5, atol=1e-5)


def test_pallas_matches_prerefactor_fused_path(key):
    from repro.kernels.routing import ops as rt_ops
    u_hat = jax.random.normal(key, (2, 32, 6, 8))
    v = build_router(RouterSpec(backend="pallas", iterations=3))(u_hat)
    want = rt_ops.dynamic_routing_fused(u_hat, iterations=3)
    np.testing.assert_allclose(np.asarray(v), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# sharded-vs-unsharded through build_router (1-device mesh in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim", ["B", "L", "H"])
def test_sharded_equals_unsharded_1dev(u_hat, dim):
    mesh = compat.make_mesh((1,), ("x",))
    spec = RouterSpec(iterations=3)
    want = build_router(spec)(u_hat)
    got = build_router(spec, ExecutionPlan(mesh=mesh,
                                           axes=((dim, "x"),)))(u_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_multi_dim_sharded_1dev(u_hat):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    spec = RouterSpec(iterations=3)
    want = build_router(spec)(u_hat)
    got = build_router(
        spec, ExecutionPlan(mesh=mesh, axes=(("B", "data"),
                                             ("L", "model"))))(u_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_em_sharded_equals_unsharded_1dev(em_inputs):
    votes, a_in = em_inputs
    mesh = compat.make_mesh((1,), ("x",))
    pose_ref, act_ref = em_routing.em_routing(votes, a_in)
    for dim in ("B", "L"):
        router = build_router(RouterSpec(algorithm="em"),
                              ExecutionPlan(mesh=mesh, axes=((dim, "x"),)))
        pose, act = router(votes, a_in)
        np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(act), np.asarray(act_ref),
                                   rtol=1e-5, atol=1e-6)


def test_router_is_jittable(u_hat):
    router = build_router(RouterSpec(iterations=3))
    want = router(u_hat)
    got = jax.jit(router)(u_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# pipeline plans
# ---------------------------------------------------------------------------

def test_software_pipeline_plan(key):
    micro = jax.random.normal(key, (4, 2, 8, 4, 8))
    spec = RouterSpec(iterations=3)
    router = build_router(spec, ExecutionPlan(pipeline="software"))
    got = router(micro)
    core = build_router(spec)
    want = jnp.stack([core(m) for m in micro])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_composes_with_sharded_plan(key):
    """Pipeline x sharded-plan composition (DESIGN.md §Serving): the
    distribution applies to the routing stage inside the pipeline."""
    micro = jax.random.normal(key, (4, 2, 8, 4, 8))
    spec = RouterSpec(iterations=3)
    want = jnp.stack([build_router(spec)(m) for m in micro])
    mesh = compat.make_mesh((1,), ("x",))
    for plan in (ExecutionPlan(mesh=mesh, axes=(("B", "x"),),
                               pipeline="software"),
                 ExecutionPlan(mesh=mesh, auto=True, pipeline="software")):
        got = build_router(spec, plan)(micro)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    # the multi-device two_stage form is covered in
    # tests/test_serving.py::test_two_stage_sharded_pipeline_composition


def test_pipeline_plan_invalid_combos_still_raise():
    mesh = compat.make_mesh((1,), ("x",))
    # two dims on one mesh axis is never legal (pipelined or not)
    with pytest.raises(ValueError, match="duplicate mesh axes"):
        ExecutionPlan(mesh=mesh, axes=(("B", "x"), ("L", "x")),
                      pipeline="software")
    with pytest.raises(ValueError, match="duplicate logical dims"):
        ExecutionPlan(mesh=mesh, axes=(("B", "x"), ("B", "x")))
    with pytest.raises(ValueError, match="stage axis"):
        build_router(RouterSpec(),
                     ExecutionPlan(mesh=mesh, axes=(("B", "x"),),
                                   pipeline="software", pipeline_axis="x"))


def test_multi_dim_sharded_software_pipeline(key):
    """Pipelined plans now shard the routing stage over >= 2 mesh axes
    (multi-dim sharded pipeline stages, DESIGN.md §Serving)."""
    micro = jax.random.normal(key, (3, 2, 8, 4, 8))
    spec = RouterSpec(iterations=3)
    want = jnp.stack([build_router(spec)(m) for m in micro])
    mesh = compat.make_mesh((1, 1), ("x", "y"))
    plan = ExecutionPlan(mesh=mesh, axes=(("B", "x"), ("L", "y")),
                         pipeline="software")
    got = build_router(spec, plan)(micro)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # the multi-device two_stage form is covered in
    # tests/test_serving.py::test_multi_dim_and_em_two_stage_pipeline


def test_em_pipelined_matches_unpipelined(key):
    """EM routing runs as pipeline stages through build_router: stage A
    hands the (votes, a_in) tuple across the pipe (multi-input hand-off)
    and the pipelined arm matches the unpipelined arm <= 1e-5."""
    micro = jax.random.normal(key, (3, 2, 8, 4, 6))
    stage_a = lambda x: (jnp.tanh(x),                       # noqa: E731
                         jax.nn.sigmoid(x[..., 0, 0]))
    spec = RouterSpec(algorithm="em", iterations=2)
    core = build_router(spec)
    refs = [core(*stage_a(m)) for m in micro]
    want_pose = jnp.stack([r[0] for r in refs])
    want_act = jnp.stack([r[1] for r in refs])
    mesh = compat.make_mesh((1,), ("x",))
    for plan in (ExecutionPlan(pipeline="software", stage_a=stage_a),
                 ExecutionPlan(mesh=mesh, pipeline="software",
                               stage_a=stage_a, axes=(("L", "x"),))):
        pose, act = build_router(spec, plan)(micro)
        assert float(jnp.max(jnp.abs(pose - want_pose))) <= 1e-5
        assert float(jnp.max(jnp.abs(act - want_act))) <= 1e-5


# ---------------------------------------------------------------------------
# sharded-fused: pallas backend x sharded ExecutionPlan (DESIGN.md
# §Sharded-fused) — stage-split kernels + Table-2 psums
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dim", ["B", "L", "H"])
def test_sharded_fused_dynamic_matches_jnp_1dev(u_hat, dim):
    """pallas + sharded plan no longer raises; matches the unsharded jnp
    backend to <=1e-5 for every shardable dim (acceptance criterion)."""
    mesh = compat.make_mesh((1,), ("x",))
    want = build_router(RouterSpec(iterations=3))(u_hat)
    got = build_router(
        RouterSpec(backend="pallas", iterations=3),
        ExecutionPlan(mesh=mesh, axes=((dim, "x"),)))(u_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_approx", [False, True])
def test_sharded_fused_dynamic_torus(u_hat, use_approx):
    """2D-torus plan (B x L) through the stage-split pallas path."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    spec = RouterSpec(iterations=3, use_approx=use_approx)
    want = build_router(spec)(u_hat)
    got = build_router(
        spec._replace(backend="pallas"),
        ExecutionPlan(mesh=mesh, axes=(("B", "data"),
                                       ("L", "model"))))(u_hat)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_sharded_fused_em_matches_jnp_1dev(em_inputs):
    """EM pallas backend: unsharded + B/L-sharded all match the jnp path."""
    votes, a_in = em_inputs
    mesh = compat.make_mesh((1,), ("x",))
    pose_ref, act_ref = em_routing.em_routing(votes, a_in)
    plans = [None,
             ExecutionPlan(mesh=mesh, axes=(("B", "x"),)),
             ExecutionPlan(mesh=mesh, axes=(("L", "x"),))]
    for plan in plans:
        router = build_router(RouterSpec(algorithm="em", backend="pallas"),
                              plan)
        pose, act = router(votes, a_in)
        np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(act), np.asarray(act_ref),
                                   rtol=1e-4, atol=1e-5)
    # em + H-sharded stays invalid (per-H Gaussian statistics)
    with pytest.raises(ValueError, match="cannot shard dims"):
        build_router(RouterSpec(algorithm="em", backend="pallas"),
                     ExecutionPlan(mesh=mesh, axes=(("H", "x"),)))


def test_auto_plan_may_pick_sharded_fused():
    """plan='auto' + pallas resolves to a *sharded* execution (regression:
    plan_axes used to force () for the pallas backend) and resolve()
    reports it."""
    spec = RouterSpec(backend="pallas", iterations=3)
    axes = plan_axes(spec, ExecutionPlan(mesh=_FakeMesh(), auto=True),
                     ((8, 128, 10, 16),))
    assert axes == (("B", "vault"),) or (len(axes) == 1
                                         and axes[0][1] == "vault")
    router = build_router(spec, "auto")
    u = jax.random.normal(jax.random.PRNGKey(0), (4, 32, 8, 16))
    reported = router.resolve(u)
    assert len(reported) == 1 and reported[0][0] in ("B", "L", "H")
    want = build_router(RouterSpec(iterations=3))(u)
    np.testing.assert_allclose(np.asarray(router(u)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# procedure fusion + stream dtype (DESIGN.md §Procedure-fused)
# ---------------------------------------------------------------------------

def test_fusion_procedure_matches_jnp(key):
    """fusion='procedure' routes through the whole-procedure megakernel and
    matches the jnp backend <=1e-5 (acceptance criterion)."""
    u = jax.random.normal(key, (2, 64, 6, 8))
    want = build_router(RouterSpec(iterations=3))(u)
    router = build_router(RouterSpec(backend="pallas", iterations=3,
                                     fusion="procedure"))
    np.testing.assert_allclose(np.asarray(router(u)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    resolved = router.resolve(u)
    assert resolved.fusion == "procedure"
    assert resolved.stream_dtype == "fp32"
    assert tuple(resolved) == ()      # still the historical axes tuple


def test_fusion_auto_picks_procedure_when_unsharded(key):
    """The default fusion='auto' resolves to the megakernel for a
    shard-local plan whose VMEM working set fits."""
    u = jax.random.normal(key, (2, 64, 6, 8))
    router = build_router(RouterSpec(backend="pallas", iterations=3))
    assert router.resolve(u).fusion == "procedure"
    want = build_router(RouterSpec(iterations=3))(u)
    np.testing.assert_allclose(np.asarray(router(u)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bf16_stream_through_router(key):
    """stream_dtype='bf16' halves the û DMA (modeled) and stays within the
    documented bf16 tolerance of the fp32 jnp backend."""
    u = jax.random.normal(key, (2, 64, 6, 8))
    want = build_router(RouterSpec(iterations=3))(u)
    router = build_router(RouterSpec(backend="pallas", iterations=3,
                                     fusion="procedure",
                                     stream_dtype="bf16"))
    resolved = router.resolve(u)
    assert resolved.fusion == "procedure"
    assert resolved.stream_dtype == "bf16"
    np.testing.assert_allclose(np.asarray(router(u)), np.asarray(want),
                               rtol=5e-2, atol=1e-2)


def test_resolve_reports_stage_split_and_jnp_none(u_hat):
    """resolve() reports the concrete execution form: stage_split under a
    sharded plan, None for the jnp backend — and keeps behaving like the
    historical (dim, axis) tuple."""
    mesh = compat.make_mesh((1,), ("x",))
    sharded = build_router(RouterSpec(backend="pallas", iterations=3),
                           ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))
    resolved = sharded.resolve(u_hat)
    assert resolved.fusion == "stage_split"
    assert resolved.stream_dtype == "fp32"
    assert tuple(resolved) == (("L", "x"),)
    auto = build_router(RouterSpec(backend="pallas", iterations=3), "auto")
    r_auto = auto.resolve(u_hat)
    assert r_auto.fusion in ("procedure", "iteration", "stage_split")
    jnp_r = build_router(RouterSpec(iterations=3), "auto").resolve(u_hat)
    assert jnp_r.fusion is None and jnp_r.stream_dtype is None


def test_resolve_without_args_static_plan(u_hat):
    """No-arg resolve() on a static plan keeps working (regression: it
    raised IndexError reading shapes[0]); fusion resolves wherever the
    votes shape isn't needed and reports None where it is."""
    mesh = compat.make_mesh((1,), ("x",))
    sharded = build_router(RouterSpec(backend="pallas", iterations=3),
                           ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))
    resolved = sharded.resolve()
    assert tuple(resolved) == (("L", "x"),)
    assert resolved.fusion == "stage_split"
    forced = build_router(RouterSpec(backend="pallas", iterations=3,
                                     fusion="procedure"))
    assert forced.resolve().fusion == "procedure"
    auto = build_router(RouterSpec(backend="pallas", iterations=3))
    assert auto.resolve().fusion is None    # auto fit needs the votes shape
    assert auto.resolve(u_hat).fusion == "procedure"


def test_fusion_and_stream_dtype_error_surface():
    mesh = compat.make_mesh((1,), ("x",))
    with pytest.raises(ValueError, match="shard-local"):
        build_router(RouterSpec(backend="pallas", fusion="procedure"),
                     ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))
    with pytest.raises(ValueError, match="unknown fusion"):
        build_router(RouterSpec(backend="pallas", fusion="mega"))
    with pytest.raises(ValueError, match="unknown stream_dtype"):
        build_router(RouterSpec(backend="pallas", stream_dtype="fp16"))
    with pytest.raises(ValueError, match="pallas-backend knob"):
        build_router(RouterSpec(fusion="procedure"))          # jnp backend
    with pytest.raises(ValueError, match="pallas-backend knob"):
        build_router(RouterSpec(algorithm="em", backend="pallas",
                                fusion="iteration"))          # em: no knob
    with pytest.raises(ValueError, match="requires the 'dynamic'"):
        build_router(RouterSpec(stream_dtype="bf16"))         # jnp backend


def test_deep_edge_error_surface():
    """int8 and early-exit composition limits are build-time errors with
    actionable messages (DESIGN.md §Quantized-routing)."""
    mesh = compat.make_mesh((1,), ("x",))
    pall = RouterSpec(algorithm="dynamic", backend="pallas")
    # early_exit_eps value / backend / algorithm surface
    with pytest.raises(ValueError, match="must be a float >= 0"):
        build_router(pall._replace(early_exit_eps=-1.0))
    with pytest.raises(ValueError, match="must be a float >= 0"):
        build_router(pall._replace(early_exit_eps=True))
    with pytest.raises(ValueError, match="pallas-backend knob"):
        build_router(RouterSpec(early_exit_eps=0.1))          # jnp backend
    with pytest.raises(ValueError, match="pallas-backend knob"):
        build_router(RouterSpec(algorithm="em", backend="pallas",
                                early_exit_eps=0.1))
    # both deep-edge knobs need the procedure megakernel ...
    with pytest.raises(ValueError, match="procedure megakernel"):
        build_router(pall._replace(fusion="iteration", early_exit_eps=0.1))
    with pytest.raises(ValueError, match="procedure megakernel"):
        build_router(pall._replace(fusion="iteration", stream_dtype="int8"))
    # ... which is shard-local ...
    sharded = ExecutionPlan(mesh=mesh, axes=(("L", "x"),))
    with pytest.raises(ValueError, match="shard-local"):
        build_router(pall._replace(early_exit_eps=0.1), sharded)
    with pytest.raises(ValueError, match="shard-local"):
        build_router(pall._replace(stream_dtype="int8"), sharded)
    # ... and forward-only: the recompute-b VJP replays the fixed grid
    # and has no dequant path
    with pytest.raises(ValueError, match="early_exit_eps=None"):
        build_router(pall._replace(differentiable=True, early_exit_eps=0.1))
    with pytest.raises(ValueError, match="serve int8"):
        build_router(pall._replace(differentiable=True, stream_dtype="int8"))


def test_deep_edge_resolved_plan_roundtrip(key):
    """plan='auto' with int8 + early_exit_eps resolves shard-local to the
    procedure megakernel and ResolvedPlan reports both knobs — even
    before the votes shape is known (the deep-edge resolution is
    unconditional), and even when a mesh is available to the planner."""
    u_hat = jax.random.normal(key, (2, 96, 6, 8))
    want = routing.dynamic_routing(u_hat, routing.RoutingConfig())
    spec = RouterSpec(algorithm="dynamic", backend="pallas",
                      stream_dtype="int8", early_exit_eps=1e-3)
    for plan in (None, "auto"):
        router = build_router(spec, plan)
        for resolved in (router.resolve(), router.resolve(u_hat)):
            assert tuple(resolved) == ()
            assert resolved.fusion == "procedure"
            assert resolved.stream_dtype == "int8"
            assert resolved.differentiable is False
            assert resolved.early_exit_eps == 1e-3
        assert "early_exit_eps=0.001" in repr(resolved)
        np.testing.assert_allclose(np.asarray(router(u_hat)),
                                   np.asarray(want), atol=6e-2, rtol=0.0)
    # exact-dtype early exit alone: same resolution, fp32 stream reported
    ee = build_router(RouterSpec(algorithm="dynamic", backend="pallas",
                                 early_exit_eps=0.0), "auto")
    r = ee.resolve(u_hat)
    assert (r.fusion, r.stream_dtype, r.early_exit_eps) == \
        ("procedure", "fp32", 0.0)
    # the non-deep-edge paths keep reporting early_exit_eps=None
    assert build_router(RouterSpec()).resolve(u_hat).early_exit_eps is None
    mesh = compat.make_mesh((1,), ("x",))
    sh = build_router(RouterSpec(backend="pallas"),
                      ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))
    assert sh.resolve(u_hat).early_exit_eps is None


def test_legacy_fused_sharded_delegates(u_hat):
    """RoutingConfig(fused=True) + sharded dims now runs the sharded-fused
    path through the legacy shims (previously a ValueError)."""
    mesh = compat.make_mesh((1,), ("x",))
    want = routing.dynamic_routing(u_hat, routing.RoutingConfig(iterations=3))
    cfg = routing.RoutingConfig(iterations=3, fused=True)
    routed = routing.make_sharded_routing(mesh, "L", "x", cfg)
    np.testing.assert_allclose(np.asarray(routed(u_hat)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    pose_ref, act_ref = em_routing.em_routing(*_em_like(u_hat))
    votes, a_in = _em_like(u_hat)
    routed_em = em_routing.make_sharded_em_routing(mesh, "L", "x",
                                                   backend="pallas")
    pose, act = routed_em(votes, a_in)
    np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                               rtol=1e-4, atol=1e-5)


def _em_like(u_hat):
    key = jax.random.PRNGKey(7)
    votes = jax.random.normal(key, (4, 32, 5, 8))
    a_in = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 1),
                                            (4, 32)))
    return votes, a_in


# ---------------------------------------------------------------------------
# legacy shims
# ---------------------------------------------------------------------------


def test_legacy_shims_delegate_to_router(u_hat, em_inputs):
    """make_sharded_routing / make_sharded_em_routing still work and agree
    with the pre-refactor semantics (they now build Routers internally)."""
    mesh = compat.make_mesh((1,), ("x",))
    cfg = routing.RoutingConfig(iterations=3)
    want = routing.dynamic_routing(u_hat, cfg)
    routed = routing.make_sharded_routing(mesh, "L", "x", cfg)
    np.testing.assert_allclose(np.asarray(routed(u_hat)), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    routed2 = routing.make_multi_sharded_routing(
        mesh, (("B", "x"),), cfg)
    np.testing.assert_allclose(np.asarray(routed2(u_hat)), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    votes, a_in = em_inputs
    pose_ref, act_ref = em_routing.em_routing(votes, a_in)
    routed3 = em_routing.make_sharded_em_routing(mesh, "L", "x")
    pose, act = routed3(votes, a_in)
    np.testing.assert_allclose(np.asarray(pose), np.asarray(pose_ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# runtime entry points built on the Router
# ---------------------------------------------------------------------------

def test_capsnet_forward_router_kwarg(key):
    from repro.configs.caps_benchmarks import smoke_caps
    from repro.models import capsnet
    cfg = smoke_caps()
    params = capsnet.init_capsnet(key, cfg)
    images = jax.random.uniform(jax.random.fold_in(key, 7),
                                (2, cfg.image_hw, cfg.image_hw,
                                 cfg.image_channels))
    out_legacy = capsnet.forward(
        params, images, cfg,
        routing_cfg=routing.RoutingConfig(iterations=cfg.routing_iters))
    out_router = capsnet.forward(
        params, images, cfg,
        router=build_router(RouterSpec(iterations=cfg.routing_iters)))
    np.testing.assert_allclose(np.asarray(out_router["v"]),
                               np.asarray(out_legacy["v"]), rtol=1e-6)


def test_capsnet_serve_and_train_entry_points(key):
    from repro.configs.caps_benchmarks import smoke_caps
    from repro.models import capsnet
    from repro.optim import adamw_init
    from repro.runtime import serve_loop, train_loop
    cfg = smoke_caps()
    params = capsnet.init_capsnet(key, cfg)
    images = jax.random.uniform(jax.random.fold_in(key, 3),
                                (5, cfg.image_hw, cfg.image_hw,
                                 cfg.image_channels))
    # a prebuilt Router carries its plan — passing another is an error
    with pytest.raises(ValueError, match="prebuilt Router"):
        serve_loop.make_capsnet_classifier(
            params, cfg, spec=build_router(RouterSpec()),
            plan="auto")
    with pytest.raises(ValueError, match="prebuilt Router"):
        train_loop.make_capsnet_train_step(
            cfg, spec=build_router(RouterSpec()), plan="auto")

    classify, stats = serve_loop.make_capsnet_classifier(
        params, cfg, max_batch=4)
    preds = classify(images)
    assert preds.shape == (5,) and stats.requests == 5
    assert stats.batches == 2 and stats.padded_waste == 3

    labels = jax.random.randint(jax.random.fold_in(key, 4), (4,), 0,
                                cfg.num_h_caps)
    step = jax.jit(train_loop.make_capsnet_train_step(cfg))
    opt = adamw_init(params)
    p2, opt2, metrics = step(params, opt, images[:4], labels)
    assert bool(jnp.isfinite(metrics["loss"]))
    moved = any(bool(jnp.any(a != b)) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved


# ---------------------------------------------------------------------------
# Differentiable fused routing (DESIGN.md §Training)
# ---------------------------------------------------------------------------


def test_differentiable_router_grad_matches_jnp(key, u_hat):
    """jax.grad through the differentiable pallas router (recompute-b
    custom VJP) == jax.grad through the jnp-backend autodiff reference,
    and resolve() reports the fused-differentiable execution."""
    fused = build_router(RouterSpec(backend="pallas", differentiable=True))
    ref = build_router(RouterSpec())
    resolved = fused.resolve(u_hat)
    assert resolved.fusion == "procedure" and resolved.differentiable
    assert not ref.resolve(u_hat).differentiable
    w = jax.random.normal(jax.random.fold_in(key, 9), (4, 8, 16))
    g_f = jax.grad(lambda u: jnp.vdot(fused(u), w))(u_hat)
    g_r = jax.grad(lambda u: jnp.vdot(ref(u), w))(u_hat)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r), atol=1e-4)


def test_differentiable_auto_plan_resolves_shard_local(u_hat):
    """plan='auto' + differentiable resolves UNSHARDED procedure fusion
    (the §5.1.2 planner's sharded pick would force the VJP-less
    stage-split form), while the same auto plan without differentiable
    keeps the planner's distribution choice."""
    spec = RouterSpec(backend="pallas", differentiable=True)
    resolved = build_router(spec, "auto").resolve(u_hat)
    assert tuple(resolved) == ()
    assert resolved.fusion == "procedure" and resolved.differentiable
    fwd_only = build_router(RouterSpec(backend="pallas"), "auto")
    assert not fwd_only.resolve(u_hat).differentiable


def test_differentiable_validation_errors():
    """The documented composition errors: sharded/pipelined plans,
    use_approx, fusion='iteration', and non-dynamic algorithms have no
    custom VJP."""
    spec = RouterSpec(backend="pallas", differentiable=True)
    mesh = compat.make_mesh((jax.device_count(),), ("x",))
    with pytest.raises(ValueError, match="shard-local"):
        build_router(spec, ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))
    with pytest.raises(ValueError, match="no derivative"):
        build_router(spec._replace(use_approx=True))
    with pytest.raises(ValueError, match="no custom VJP"):
        build_router(spec._replace(fusion="iteration"))
    with pytest.raises(ValueError, match="'dynamic' algorithm"):
        build_router(RouterSpec(algorithm="em", backend="pallas",
                                differentiable=True))
    # jnp backend is differentiable by construction: no restrictions
    build_router(RouterSpec(differentiable=True),
                 ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))


def test_differentiable_vmem_fallback_is_jnp(monkeypatch):
    """When the procedure form does not fit VMEM, the differentiable
    router must fall back to jnp autodiff (reported as the jnp triple),
    never to a forward-only kernel that would fail under jax.grad."""
    from repro.kernels.routing import ops as rt_ops
    monkeypatch.setattr(rt_ops, "PROCEDURE_VMEM_BUDGET", 1024)
    router = build_router(RouterSpec(backend="pallas", differentiable=True))
    u = jnp.ones((2, 64, 6, 8))
    resolved = router.resolve(u)
    assert resolved.fusion is None and not resolved.differentiable
    g = jax.grad(lambda x: jnp.sum(router(x) ** 2))(u)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_differentiable_resolution_checks_backward_fit():
    """The differentiable megakernel is chosen on its *backward's* VMEM
    fit (the larger working set): Caps-MN1 at its Table-1 batch trains
    through the custom VJP, while Caps-EN3 (H·C = 992 lanes) at batch 100
    has a backward that no tile fits and falls back to jnp autodiff."""
    from repro.configs.caps_benchmarks import CAPS_BENCHMARKS
    from repro.kernels.routing import ops as rt_ops
    router = build_router(RouterSpec(backend="pallas", differentiable=True))
    for name, fused in (("Caps-MN1", True), ("Caps-EN3", False)):
        c = CAPS_BENCHMARKS[name]
        shape = (c.batch_size, c.num_l_caps, c.num_h_caps, c.h_caps_dim)
        assert rt_ops.procedure_train_fits(*shape) == fused
        resolved = router.resolve(jax.ShapeDtypeStruct(shape, jnp.float32))
        assert resolved.differentiable == fused
        assert resolved.fusion == ("procedure" if fused else None)


def test_capsnet_train_step_auto_plan_trains_fused(key):
    """make_capsnet_train_step(plan='auto') resolves to the
    fused-differentiable backend and one train step strictly decreases
    the loss on its own batch."""
    from repro.configs.caps_benchmarks import smoke_caps
    from repro.models import capsnet
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime import train_loop
    cfg = smoke_caps()
    step = train_loop.make_capsnet_train_step(
        cfg, plan="auto", opt_cfg=AdamWConfig(weight_decay=0.0),
        warmup=1, total_steps=100)
    assert step.router.spec.backend == "pallas"
    assert step.router.spec.differentiable
    votes_shape = (4, cfg.num_l_caps, cfg.num_h_caps, cfg.h_caps_dim)
    resolved = step.router.resolve(jnp.zeros(votes_shape))
    assert resolved.fusion == "procedure" and resolved.differentiable
    assert tuple(resolved) == ()

    params = capsnet.init_capsnet(key, cfg)
    images = jax.random.uniform(jax.random.fold_in(key, 1),
                                (4, cfg.image_hw, cfg.image_hw,
                                 cfg.image_channels))
    labels = jax.random.randint(jax.random.fold_in(key, 2), (4,), 0,
                                cfg.num_h_caps)
    p1, _, metrics = jax.jit(step)(params, adamw_init(params), images,
                                   labels)
    loss_after = capsnet.loss_fn(p1, images, labels, cfg,
                                 router=step.router)[0]
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(loss_after) < float(metrics["loss"])


def test_capsnet_train_step_sharded_fused_raises():
    """Explicit fused spec + sharded plan under grad: the documented
    error, raised at build time (not a silent VJP-less composition)."""
    from repro.configs.caps_benchmarks import smoke_caps
    from repro.runtime import train_loop
    mesh = compat.make_mesh((jax.device_count(),), ("x",))
    with pytest.raises(ValueError, match="shard-local"):
        train_loop.make_capsnet_train_step(
            smoke_caps(), spec=RouterSpec(backend="pallas"),
            plan=ExecutionPlan(mesh=mesh, axes=(("L", "x"),)))


def test_train_step_opt_cfg_isolation():
    """Regression for the shared-mutable-default bug class (PR-5
    ServeConfig): every default-built step gets a FRESH AdamWConfig; a
    custom config on one build never leaks into another."""
    import repro.configs as C
    from repro.configs.caps_benchmarks import smoke_caps
    from repro.optim import AdamWConfig
    from repro.runtime import train_loop
    cfg = smoke_caps()
    s1 = train_loop.make_capsnet_train_step(cfg)
    s2 = train_loop.make_capsnet_train_step(cfg,
                                            opt_cfg=AdamWConfig(lr=9.0))
    s3 = train_loop.make_capsnet_train_step(cfg)
    assert s1.opt_cfg == AdamWConfig() == s3.opt_cfg
    assert s2.opt_cfg.lr == 9.0 and s3.opt_cfg.lr != 9.0
    lm_cfg = C.get_smoke_config("granite-3-2b")
    t1 = train_loop.make_train_step(lm_cfg)
    t2 = train_loop.make_train_step(lm_cfg, opt_cfg=AdamWConfig(lr=9.0))
    t3 = train_loop.make_train_step(lm_cfg)
    assert t1.opt_cfg == AdamWConfig() == t3.opt_cfg
    assert t2.opt_cfg.lr == 9.0
