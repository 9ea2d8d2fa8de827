"""Paper §5.1.2 E/M cost models, execution-score planner, Fig.18 behaviour,
§5.3.2 RMAS optimum, and the beyond-paper MoE planner."""
import math

import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # vendored fallback (tests/_hypothesis_compat.py)
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import distribution as D
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS


def shapes():
    return [D.RPShape.from_caps_config(c) for c in CAPS_BENCHMARKS.values()]


def test_E_closed_forms_positive_and_scale():
    dev = D.DeviceModel.hmc()
    for s in shapes():
        for dim in D.DIMS:
            e = D.workload_E(dim, s, dev.n_vault)
            assert e > 0
    # doubling L doubles E on every dimension (all forms are linear in N_L)
    s = shapes()[0]
    s2 = D.RPShape(s.n_b, 2 * s.n_l, s.n_h, s.c_l, s.c_h, s.iters)
    for dim in ("B", "H"):
        assert D.workload_E(dim, s2, 32) == pytest.approx(
            2 * D.workload_E(dim, s, 32), rel=1e-6)


def test_E_B_dimension_eq7():
    """Eq.7: E_B = ceil(N_B/nv) * N_L * N_H * ((4I-1)C_H + 2C_L·C_H - I)."""
    s = D.RPShape(n_b=100, n_l=1152, n_h=10, c_l=8, c_h=16, iters=3)
    want = math.ceil(100 / 32) * 1152 * 10 * ((4 * 3 - 1) * 16
                                              + 2 * 8 * 16 - 3)
    assert D.workload_E("B", s, 32) == pytest.approx(want)


def test_M_H_smallest_for_caps_mnist():
    """For Caps-MN1 geometry the H-dim moves the least data (Eq.12 has no
    N_B or N_H factor in its first term)."""
    s = D.RPShape(n_b=100, n_l=1152, n_h=10, c_l=8, c_h=16, iters=3)
    ms = {d: D.comm_M(d, s, 32) for d in D.DIMS}
    assert ms["H"] < ms["B"] and ms["H"] < ms["L"]


def test_plan_picks_argmax_score():
    dev = D.DeviceModel.hmc()
    for s in shapes():
        table = D.score_table(s, dev)
        assert D.plan(s, dev) == max(table, key=table.__getitem__)


def test_plan_shifts_with_device_coefficients():
    """Fig.18: the chosen dimension depends on the compute/comm balance.
    A compute-rich device weights M higher (pick min-comm dim); a
    bandwidth-rich device weights E higher (pick min-work dim)."""
    s = D.RPShape(n_b=100, n_l=576, n_h=10, c_l=8, c_h=16, iters=9)
    fast_compute = D.DeviceModel(alpha=1e-15, beta=1e-9, n_vault=32)
    fast_comm = D.DeviceModel(alpha=1e-9, beta=1e-15, n_vault=32)
    pick_fc = D.plan(s, fast_compute)
    pick_fm = D.plan(s, fast_comm)
    ms = {d: D.comm_M(d, s, 32) for d in D.DIMS}
    es = {d: D.workload_E(d, s, 32) for d in D.DIMS}
    assert pick_fc == min(ms, key=ms.__getitem__)
    assert pick_fm == min(es, key=es.__getitem__)


@settings(max_examples=50, deadline=None)
@given(nb=st.integers(1, 512), nl=st.integers(32, 8192),
       nh=st.integers(2, 128), i=st.integers(1, 9))
def test_property_scores_finite_positive(nb, nl, nh, i):
    s = D.RPShape(n_b=nb, n_l=nl, n_h=nh, c_l=8, c_h=16, iters=i)
    dev = D.DeviceModel.tpu_v5e(n_vault=16)
    for d in D.DIMS:
        sc = D.execution_score(d, s, dev)
        assert sc > 0 and math.isfinite(sc)


def test_rmas_optimum_near_argmin():
    """The paper's closed form floors the continuous optimum (Eq.15), so it
    may land one below the integer argmin — assert it's within one step and
    within 5% of the true minimum."""
    n_max, q, gv, gh = 12, 3.5, 1.0, 2.0
    star = D.rmas_optimal_grant(n_max, q, gv, gh)
    best = min(range(1, n_max + 1),
               key=lambda n: D.rmas_overhead(n, n_max, q, gv, gh))
    assert abs(star - best) <= 1
    assert D.rmas_overhead(star, n_max, q, gv, gh) <= \
        1.05 * D.rmas_overhead(best, n_max, q, gv, gh)


def test_rmas_bounds():
    assert D.rmas_optimal_grant(8, 1e9, 1.0, 1.0) == 0 or \
        D.rmas_optimal_grant(8, 1e9, 1.0, 1.0) >= 0
    assert D.rmas_optimal_grant(8, 1e-9, 1.0, 1.0) == 8


def test_moe_planner_prefers_expert_sharding_at_production_shape():
    """qwen3-30B geometry on the 16-way model axis: expert-sharded dispatch
    (psum combine) should beat all-to-all at modest top-k token volume."""
    s = D.MoEShape(tokens=4096, d_model=2048, d_ff=768, n_experts=128,
                   top_k=8)
    dev = D.DeviceModel.tpu_v5e(n_vault=16)
    t = D.moe_plan(s, dev)
    assert set(t) == {"expert", "token", "a2a"}
    assert all(v > 0 for v in t.values())


def test_estimated_time_consistent():
    s = shapes()[0]
    dev = D.DeviceModel.hmc()
    for d in D.DIMS:
        assert D.estimated_time_s(d, s, dev) == pytest.approx(
            1.0 / D.execution_score(d, s, dev))


def test_device_model_follows_device_kind():
    """The planner's default operating point is looked up by JAX
    device_kind: v5e ("TPU v5 lite") and the CPU rehearsal stand-in get the
    v5e coefficients; an unknown TPU kind is an error, not a default."""
    want = D.DeviceModel.tpu_v5e(4)
    assert D.DeviceModel.for_device_kind("TPU v5 lite", 4) == want
    assert D.DeviceModel.for_device_kind("cpu", 4) == want
    with pytest.raises(ValueError, match="no planner DeviceModel"):
        D.DeviceModel.for_device_kind("TPU v4", 4)
