"""Per-kernel allclose vs the ref.py oracles (interpret mode), with shape /
dtype sweeps as the task spec requires."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # vendored fallback (tests/_hypothesis_compat.py)
    from _hypothesis_compat import given, settings, strategies as st

from _gradcheck import (check_grad_finite_difference, check_vjp_parity,
                        grad_tol)
from repro.configs.caps_benchmarks import CAPS_BENCHMARKS
from repro.core import approx
from repro.kernels.fastmath import ops as fm_ops
from repro.kernels.fastmath import ref as fm_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.routing import ops as rt_ops
from repro.kernels.routing import ref as rt_ref
from repro.kernels.routing.kernel import routing_iteration_fused
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan import ref as ssm_ref


# ---------------------------------------------------------------------------
# routing kernel
# ---------------------------------------------------------------------------

ROUTING_SHAPES = [
    (2, 64, 4, 8),      # tiny
    (4, 128, 10, 16),   # caps-MNIST-like geometry (scaled down)
    (1, 256, 11, 16),   # CIFAR-like H
    (8, 128, 5, 8),     # batch-heavy
]


@pytest.mark.parametrize("shape", ROUTING_SHAPES)
@pytest.mark.parametrize("l_tile", [32, 64])
def test_routing_iteration_vs_ref(key, shape, l_tile):
    B, L, H, C = shape
    u_hat = jax.random.normal(key, shape)
    b = jax.random.normal(jax.random.fold_in(key, 1), (L, H))
    v_prev = jax.random.normal(jax.random.fold_in(key, 2), (B, H, C))
    s_k, b_k = routing_iteration_fused(u_hat, b, v_prev, l_tile=l_tile)
    s_r, b_r = rt_ref.routing_iteration_ref(u_hat, b, v_prev)
    np.testing.assert_allclose(s_k, s_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(b_k, b_r, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("iters", [1, 3])
@pytest.mark.parametrize("use_approx", [False, True])
def test_routing_full_vs_ref(key, iters, use_approx):
    u_hat = jax.random.normal(key, (4, 128, 10, 16))
    v_k = rt_ops.dynamic_routing_fused(u_hat, iterations=iters,
                                       use_approx=use_approx)
    v_r = rt_ref.dynamic_routing_ref(u_hat, iters, use_approx)
    np.testing.assert_allclose(v_k, v_r, rtol=1e-4, atol=1e-5)


def test_routing_fused_matches_core(key):
    """kernels path == core.routing (two independent implementations)."""
    from repro.core import routing as core_routing
    u_hat = jax.random.normal(key, (2, 128, 10, 16))
    v_core = core_routing.dynamic_routing(
        u_hat, core_routing.RoutingConfig(iterations=3))
    v_fused = rt_ops.dynamic_routing_fused(u_hat, iterations=3)
    np.testing.assert_allclose(v_core, v_fused, rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 4), lt=st.sampled_from([16, 32]),
       nl=st.integers(2, 6), h=st.integers(2, 12), c=st.integers(4, 16))
def test_property_routing_kernel(b, lt, nl, h, c):
    L = lt * nl
    key = jax.random.PRNGKey(b * 7 + L)
    u_hat = jax.random.normal(key, (b, L, h, c))
    bmat = jnp.zeros((L, h))
    v0 = jnp.zeros((b, h, c))
    s_k, b_k = routing_iteration_fused(u_hat, bmat, v0, l_tile=lt)
    s_r, b_r = rt_ref.routing_iteration_ref(u_hat, bmat, v0)
    np.testing.assert_allclose(s_k, s_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(b_k, b_r, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# whole-procedure routing megakernel (DESIGN.md §Procedure-fused)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("iters", [1, 3, 9])
@pytest.mark.parametrize("use_approx", [False, True])
@pytest.mark.parametrize("stream_dtype", ["fp32", "bf16"])
def test_routing_procedure_fused_vs_jnp(key, iters, use_approx,
                                        stream_dtype):
    """Parity of the one-pallas_call whole-procedure kernel vs the jnp
    oracle across iterations x approx x stream dtype (acceptance: <=1e-5
    for fp32)."""
    u_hat = jax.random.normal(key, (2, 64, 6, 8))
    v_k = rt_ops.dynamic_routing_procedure_fused(
        u_hat, iterations=iters, use_approx=use_approx,
        stream_dtype=stream_dtype)
    if stream_dtype == "fp32":
        want = rt_ref.dynamic_routing_ref(u_hat, iters, use_approx)
        tol = 5e-5 if use_approx else 1e-5  # approx: fused-op reordering
        np.testing.assert_allclose(v_k, want, rtol=tol, atol=tol)
    else:
        # tight vs the oracle on the bf16-rounded û: all in-kernel math is
        # fp32, so only the streamed operand's rounding differs
        pre = u_hat.astype(jnp.bfloat16).astype(jnp.float32)
        np.testing.assert_allclose(
            v_k, rt_ref.dynamic_routing_ref(pre, iters, use_approx),
            rtol=1e-4, atol=5e-5)
        # documented looser bf16 tolerance vs the full-precision oracle:
        # 8 mantissa bits -> ~0.4% per û element, and the routing loop
        # *sharpens* agreement so the rounding compounds with iterations
        # (measured: 3e-3 at 3 iters, 2.3e-2 at 9)
        np.testing.assert_allclose(
            v_k, rt_ref.dynamic_routing_ref(u_hat, iters, use_approx),
            rtol=5e-2, atol=5e-2)


def test_routing_procedure_matches_iteration_fused(key):
    """Megakernel == the per-iteration kernel loop (same lazy schedule,
    same tile order) to float tolerance."""
    u_hat = jax.random.normal(key, (4, 128, 10, 16))
    v_p = rt_ops.dynamic_routing_procedure_fused(u_hat, iterations=3)
    v_i = rt_ops.dynamic_routing_fused(u_hat, iterations=3)
    np.testing.assert_allclose(v_p, v_i, rtol=1e-6, atol=1e-6)


def test_routing_procedure_non_divisible_l_fallback(key):
    """L=136 does not divide by the preferred 128-tile: the auto picker
    must fall back to a real divisor that keeps the fp32 sublane tiling
    (8 — 68 and 34 are not multiples of 8) and stay correct; an explicit
    non-divisor l_tile fails loudly."""
    u_hat = jax.random.normal(key, (2, 136, 6, 8))
    assert rt_ops.pick_l_tile(136, 8 * 2 ** 20, 2 * 6 * 8 * 4) == 8
    v_k = rt_ops.dynamic_routing_procedure_fused(u_hat, iterations=3)
    want = rt_ref.dynamic_routing_ref(u_hat, 3)
    np.testing.assert_allclose(v_k, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="not divisible"):
        rt_ops.dynamic_routing_procedure_fused(u_hat, iterations=3,
                                               l_tile=50)


def test_pick_l_tile_matches_bruteforce():
    """The O(sqrt L) divisor enumeration == a 1..L scan over the legal
    tiles (sublane-aligned divisors, or L itself), for every alignment."""
    def brute(L, budget, row, preferred=128, align=8):
        cap = max(1, budget // max(row, 1))
        legal = [t for t in range(1, L + 1)
                 if L % t == 0 and (t % align == 0 or t == L)]
        fitting = [t for t in legal if t <= min(preferred, cap)]
        return max(fitting) if fitting else min(legal)

    for L in (1, 2, 17, 64, 96, 136, 576, 1152, 2304):
        for budget, row in ((8 * 2 ** 20, 2 * 6 * 8 * 4),
                            (4096, 512), (64, 512)):
            for align in (8, 16, 32):
                assert rt_ops.pick_l_tile(L, budget, row, align=align) \
                    == brute(L, budget, row, align=align)


@pytest.mark.parametrize("network", sorted(CAPS_BENCHMARKS))
def test_l_tiles_keep_sublane_tiling_table1(network):
    """Every tile picker returns a divisor of L that keeps the stream
    dtype's sublane tiling (8 fp32 / 16 bf16 / 32 int8 rows) — or L
    itself — for all 12 Table-1 networks at the serving microbatch and the
    Table-1 batch, and the megakernel's pick fits the VMEM budget."""
    cfg = CAPS_BENCHMARKS[network]
    L, H, C = cfg.num_l_caps, cfg.num_h_caps, cfg.h_caps_dim

    def legal(lt, align):
        return L % lt == 0 and (lt % align == 0 or lt == L)

    for B in (8, cfg.batch_size):
        for sd, align in (("fp32", 8), ("bf16", 16), ("int8", 32)):
            lt = rt_ops.procedure_l_tile(B, L, H, C, sd)
            assert rt_ops.sublane_tile(sd) == align
            assert legal(lt, align), (sd, B, lt)
            assert rt_ops.procedure_vmem_bytes(B, L, H, C, lt, sd) \
                <= rt_ops.PROCEDURE_VMEM_BUDGET
            assert legal(rt_ops.procedure_l_tile(B, L, H, C, sd,
                                                 early_exit=True), align)
            if sd != "int8":
                assert legal(rt_ops.auto_l_tile(B, L, H, C, sd), align)
                assert legal(rt_ops.procedure_train_l_tile(
                    B, L, H, C, cfg.routing_iters, sd), align)
        assert legal(rt_ops.em_l_tile(B, L, H, C), 8)


def test_resolve_fusion_levels():
    """fusion='auto' picks the megakernel iff shard-local + VMEM fit."""
    small = (2, 64, 6, 8)
    assert rt_ops.resolve_fusion("auto", small) == "procedure"
    assert rt_ops.resolve_fusion("iteration", small) == "iteration"
    assert rt_ops.resolve_fusion("auto", small, sharded=True) == \
        "stage_split"
    # (B,H,C) blocks alone blow the budget -> per-iteration fallback
    big = (512, 1024, 32, 128)
    assert rt_ops.procedure_vmem_bytes(*big[:4], l_tile=1) \
        > rt_ops.PROCEDURE_VMEM_BUDGET
    assert rt_ops.resolve_fusion("auto", big) == "iteration"
    with pytest.raises(ValueError, match="shard-local"):
        rt_ops.resolve_fusion("procedure", small, sharded=True)
    with pytest.raises(ValueError, match="unknown fusion"):
        rt_ops.resolve_fusion("mega", small)


def test_resolve_fusion_capbound_shrinks_tile():
    """A cap-bound (large B·H·C row) shape must shrink the megakernel's
    l_tile to fit the total budget, not fall back to the per-iteration
    kernel (regression: the 8MB-per-buffer pick structurally overflowed
    2x into the 14MB budget whenever the cap bound bit).  L = 8192 makes
    the megakernel's resident (L, H) logits — 4 MiB once padded to 128
    lanes — cost it rows the per-iteration kernel keeps."""
    capbound = (128, 8192, 16, 16)         # row = 128*16*16*4 = 128 KiB
    lt = rt_ops.procedure_l_tile(*capbound)
    assert lt < rt_ops.auto_l_tile(*capbound, "fp32")
    assert rt_ops.procedure_vmem_bytes(*capbound, l_tile=lt) \
        <= rt_ops.PROCEDURE_VMEM_BUDGET
    assert rt_ops.resolve_fusion("auto", capbound) == "procedure"


def test_fused_paths_stream_bf16_without_promotion(key):
    """The modeled DMA halving is real only if the pallas_call consumes the
    bf16 operand itself: no full-size fp32 copy of û may appear in the
    jaxpr of either fused path (regression: the iteration wrapper used to
    astype(f32) right before the call)."""
    from repro.kernels.routing.kernel import routing_iteration_fused
    u = jax.random.normal(key, (2, 64, 6, 8)).astype(jnp.bfloat16)
    b0, v0 = jnp.zeros((64, 6)), jnp.zeros((2, 6, 8))
    it_jaxpr = str(jax.make_jaxpr(functools.partial(
        routing_iteration_fused, l_tile=32))(u, b0, v0))
    assert "f32[2,64,6,8]" not in it_jaxpr
    # L=256 > l_tile=128 so an in-kernel fp32 *block* (legitimate) can't
    # alias the full-array shape the assertion hunts for
    u_l = jax.random.normal(key, (2, 256, 6, 8)).astype(jnp.bfloat16)
    proc_jaxpr = str(jax.make_jaxpr(functools.partial(
        rt_ops.dynamic_routing_procedure_fused, iterations=2,
        stream_dtype="bf16"))(u_l))
    assert "f32[2,256,6,8]" not in proc_jaxpr
    assert "f32[2,256,48]" not in proc_jaxpr    # lane-packed full copy
    # and the bf16 iteration path matches the oracle on the rounded û
    v = rt_ops.dynamic_routing_fused(u, iterations=3, stream_dtype="bf16")
    pre = u.astype(jnp.float32)
    np.testing.assert_allclose(v, rt_ref.dynamic_routing_ref(pre, 3),
                               rtol=1e-4, atol=5e-5)


def test_dma_model_three_forms():
    """The DMA model's acceptance invariants: procedure-fusion eliminates
    the per-iteration (L,H)/(B,H,C) round-trips, bf16 halves û stream
    bytes, stage-split pays the distribution double-stream."""
    B, L, H, C, iters = 4, 128, 10, 16, 3
    it = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="iteration")
    pr = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure")
    ss = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="stage_split")
    bf = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                   stream_dtype="bf16")
    assert pr["roundtrip_bytes"] == B * H * C * 4
    assert it["roundtrip_bytes"] == iters * (2 * L * H + 4 * B * H * C) * 4
    assert pr["total_bytes"] < it["total_bytes"] < ss["total_bytes"]
    assert bf["u_hat_stream_bytes"] * 2 == pr["u_hat_stream_bytes"]
    assert bf["roundtrip_bytes"] == pr["roundtrip_bytes"]  # fp32 roundtrip
    assert ss["u_hat_stream_bytes"] == 2 * it["u_hat_stream_bytes"]
    with pytest.raises(ValueError, match="unknown form"):
        rt_ops.dma_bytes_per_call(B, L, H, C, form="fused")


def test_dma_model_stage_split_fold():
    """The fold variant of the stage_split form matches the fold kernel's
    actual traffic (regression for the L-sharded arm, which took the fold
    path but was modeled with the unfolded 6·LH logit terms — an
    iters·2·L·H·4-byte overstatement).

    Per iteration the fold path's non-û crossings, read straight off the
    two kernels' BlockSpecs (kernel.py):

        routing_stage_votes:        c in (LH) ........... s out (BHC)
        routing_stage_update_fold:  s in (BHC), b in (LH)
                                    -> v out (BHC), b out (LH), c out (LH)

    i.e. 4·LH + 3·BHC fp32 words — no db ever crosses (the kernel folds
    Eq.4+5 and emits the next iteration's c directly)."""
    B, L, H, C, iters = 4, 128, 10, 16, 3
    f = 4
    kernel_traffic = iters * ((4 * L * H + 3 * B * H * C) * f)
    fold = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="stage_split",
                                     fold=True)
    plain = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="stage_split")
    assert fold["roundtrip_bytes"] == kernel_traffic
    assert fold["fold"] is True and plain["fold"] is False
    # û still streams twice per iteration — folding only kills the logit
    # round-trip, never the distribution double-stream
    assert fold["u_hat_stream_bytes"] == plain["u_hat_stream_bytes"]
    assert (plain["total_bytes"] - fold["total_bytes"]
            == iters * 2 * L * H * 4)
    with pytest.raises(ValueError, match="fold"):
        rt_ops.dma_bytes_per_call(B, L, H, C, form="procedure", fold=True)


# ---------------------------------------------------------------------------
# deep-edge tier: int8 û streaming + per-capsule early exit
# (DESIGN.md §Quantized-routing; parity sweeps live in tests/test_quant.py)
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(b=st.integers(1, 4), lt=st.sampled_from([16, 32]),
       nl=st.integers(2, 4), iters=st.integers(1, 4),
       stream_dtype=st.sampled_from(["fp32", "bf16", "int8"]))
def test_property_early_exit_eps0_bit_identical(b, lt, nl, iters,
                                                stream_dtype):
    """ε = 0 early exit is BIT-identical to the fixed-grid megakernel for
    every stream dtype: ‖Δb‖∞ < 0 is never true, so no tile ever freezes
    and the frozen-c scratch round-trip (f32, exact) reproduces the same
    fp32 op sequence.  Exact equality, not allclose — that is the
    acceptance criterion."""
    L = lt * nl
    key = jax.random.PRNGKey(b * 131 + L + iters)
    u_hat = jax.random.normal(key, (b, L, 6, 8))
    base = rt_ops.dynamic_routing_procedure_fused(
        u_hat, iterations=iters, l_tile=lt, stream_dtype=stream_dtype)
    v0, eff0 = rt_ops.dynamic_routing_procedure_stats(
        u_hat, iterations=iters, l_tile=lt, stream_dtype=stream_dtype,
        early_exit_eps=0.0)
    assert int(eff0) == iters * nl          # full fixed-grid work
    assert np.array_equal(np.asarray(v0), np.asarray(base)), (
        np.abs(np.asarray(v0) - np.asarray(base)).max())


@pytest.mark.parametrize("fusion", ["auto", "procedure"])
@pytest.mark.parametrize("stream_dtype", ["fp32", "bf16", "int8"])
def test_early_exit_eps0_bit_identical_through_router(key, fusion,
                                                      stream_dtype):
    """Same bit-identity through the Router across fusion x stream_dtype
    (both fusion levels that can reach the megakernel; the shape is small
    enough that the early-exit VMEM model picks the same l_tile, which the
    resolved plans pin down)."""
    from repro.core.router import RouterSpec, build_router
    u_hat = jax.random.normal(key, (2, 96, 6, 8))
    spec = RouterSpec(algorithm="dynamic", backend="pallas",
                      fusion=fusion, stream_dtype=stream_dtype)
    base = build_router(spec)
    ee = build_router(spec._replace(early_exit_eps=0.0))
    assert base.resolve(u_hat).fusion == ee.resolve(u_hat).fusion \
        == "procedure"
    assert np.array_equal(np.asarray(base(u_hat)), np.asarray(ee(u_hat)))


@settings(max_examples=6, deadline=None)
@given(b=st.integers(1, 4), lt=st.sampled_from([16, 32]),
       nl=st.integers(2, 4), iters=st.integers(2, 3),
       scale=st.floats(0.25, 4.0))
def test_property_early_exit_monotone_work(b, lt, nl, iters, scale):
    """effective-tile-iterations is monotone non-increasing in ε.

    iterations <= 3 makes this exact, not statistical: every tile works
    at it=0 (flags start clear) and at it=1 computes from ε-independent
    state (flags are only *set* at it >= 1, affecting it >= 2), so the
    set of tiles frozen after it=1 — the only skips a 3-iteration grid
    can have — is nested across ε by construction.  The endpoints are
    exact too: ε=0 is the full grid and ε=∞-ish freezes everything after
    it=1 (2·n_l_tiles cells — every tile must work twice before its
    first ‖Δb‖ check can fire, since it=0's v_prev=0 makes Δb ≡ 0)."""
    L = lt * nl
    key = jax.random.PRNGKey(b * 977 + L + iters)
    u_hat = scale * jax.random.normal(key, (b, L, 6, 8))
    ladder = [0.0, 1e-3, 1e-1, 1.0, 10.0, 1e6]
    effs = []
    for eps in ladder:
        _, eff = rt_ops.dynamic_routing_procedure_stats(
            u_hat, iterations=iters, l_tile=lt, early_exit_eps=eps)
        effs.append(int(eff))
    assert all(a >= b_ for a, b_ in zip(effs, effs[1:])), (ladder, effs)
    assert effs[0] == iters * nl
    assert effs[-1] == min(iters, 2) * nl


def test_early_exit_small_eps_near_parity(key):
    """A genuinely-converged freeze is benign: at a small ε the skipped
    logit updates are < ε per element per iteration, so v drifts by at
    most the softmax/squash amplification of that — orders below the
    lossy-stream tolerances."""
    u_hat = jax.random.normal(key, (2, 128, 6, 8))
    base = rt_ops.dynamic_routing_procedure_fused(u_hat, l_tile=32)
    v, _ = rt_ops.dynamic_routing_procedure_stats(
        u_hat, l_tile=32, early_exit_eps=1e-4)
    np.testing.assert_allclose(np.asarray(v), np.asarray(base), atol=1e-3)


def test_early_exit_rejects_bad_eps(key):
    u_hat = jax.random.normal(key, (2, 64, 6, 8))
    with pytest.raises(ValueError, match="early_exit_eps must be >= 0"):
        rt_ops.dynamic_routing_procedure_fused(u_hat, l_tile=32,
                                               early_exit_eps=-0.5)


def test_dma_model_int8_and_early_exit():
    """The deep-edge rows of the DMA model (bench_rp_speedup cross-checks
    the same invariants per shape): int8 quarters the û stream and only
    the û stream; early_exit_work_fraction scales the û stream and only
    the û stream; both are procedure-form-only."""
    B, L, H, C, iters = 4, 128, 10, 16, 3
    pr = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure")
    i8 = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                   stream_dtype="int8")
    assert i8["u_hat_stream_bytes"] * 4 == pr["u_hat_stream_bytes"]
    assert i8["roundtrip_bytes"] == pr["roundtrip_bytes"]  # fp32 roundtrip
    ee = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                   early_exit_work_fraction=0.5)
    assert ee["u_hat_stream_bytes"] * 2 == pr["u_hat_stream_bytes"]
    assert ee["roundtrip_bytes"] == pr["roundtrip_bytes"]
    assert ee["early_exit_work_fraction"] == 0.5
    # fraction 1.0 (ε=0 / nothing converged) is exactly the fixed grid
    full = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                     early_exit_work_fraction=1.0)
    assert full["total_bytes"] == pr["total_bytes"]
    # int8 x early-exit compose: both knobs hit the same û term
    both = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                     stream_dtype="int8",
                                     early_exit_work_fraction=0.5)
    assert both["u_hat_stream_bytes"] * 8 == pr["u_hat_stream_bytes"]
    with pytest.raises(ValueError, match="procedure-megakernel tier"):
        rt_ops.dma_bytes_per_call(B, L, H, C, form="iteration",
                                  stream_dtype="int8")
    with pytest.raises(ValueError, match="forward procedure"):
        rt_ops.dma_bytes_per_call(B, L, H, C, form="iteration",
                                  early_exit_work_fraction=0.5)
    with pytest.raises(ValueError, match="in \\(0, 1\\]"):
        rt_ops.dma_bytes_per_call(B, L, H, C, form="procedure",
                                  early_exit_work_fraction=1.5)


def test_vmem_model_early_exit_and_int8():
    """procedure_vmem_bytes grows by exactly the frozen-c scratch (its
    (L, H=10) block padded to 128 lanes) + the early-exit branch's fp32
    temporaries under early exit — the converged flags and the work
    counter live in SMEM; the int8 tile pick can never be smaller than
    the fp32 pick (1-byte rows fit more VMEM)."""
    B, L, H, C, lt = 4, 128, 10, 16, 32
    base = rt_ops.procedure_vmem_bytes(B, L, H, C, lt)
    ee = rt_ops.procedure_vmem_bytes(B, L, H, C, lt, early_exit=True)
    u_rows = lt * B * 256 * 4                 # H·C = 160 -> 256 lanes
    assert ee - base == L * 128 * 4 + int(rt_ops._EARLY_EXIT_TEMPS * u_rows)
    i8 = rt_ops.procedure_vmem_bytes(B, L, H, C, lt, "int8")
    assert i8 < base
    assert (rt_ops.procedure_l_tile(B, L, H, C, "int8")
            >= rt_ops.procedure_l_tile(B, L, H, C, "fp32"))


def test_resolve_fusion_deep_edge_forms():
    """int8 / early-exit resolve "auto" to "procedure" even for the
    VMEM-overfull shape that fp32 auto sends to the iteration kernel, and
    raise for the forms that cannot host them."""
    big = (512, 1024, 32, 128)
    assert rt_ops.resolve_fusion("auto", big, "fp32") == "iteration"
    assert rt_ops.resolve_fusion("auto", big, "int8") == "procedure"
    assert rt_ops.resolve_fusion("auto", big, "fp32",
                                 early_exit=True) == "procedure"
    # no shape needed: the deep-edge resolution is unconditional
    assert rt_ops.resolve_fusion("auto", None, "int8") == "procedure"
    with pytest.raises(ValueError, match="fusion='auto' or 'procedure'"):
        rt_ops.resolve_fusion("iteration", big, "int8")
    with pytest.raises(ValueError, match="fusion='auto' or 'procedure'"):
        rt_ops.resolve_fusion("iteration", big, "fp32", early_exit=True)
    with pytest.raises(ValueError, match="shard-local"):
        rt_ops.resolve_fusion("auto", big, "int8", sharded=True)
    with pytest.raises(ValueError, match="shard-local"):
        rt_ops.resolve_fusion("auto", big, "fp32", sharded=True,
                              early_exit=True)


def test_stage_update_fold_matches_split(key):
    """routing_stage_update_fold == routing_stage_update + host softmax
    (the folded Eq.5 path the sharded form takes when B/H are unsharded)."""
    from repro.kernels.routing.kernel import (routing_stage_update,
                                              routing_stage_update_fold)
    B, L, H, C = 2, 64, 5, 8
    u = jax.random.normal(key, (B, L, H, C))
    s = jax.random.normal(jax.random.fold_in(key, 1), (B, H, C))
    b = jax.random.normal(jax.random.fold_in(key, 2), (L, H))
    v_f, b_f, c_f = routing_stage_update_fold(u, s, b, l_tile=32)
    v_u, db = routing_stage_update(u, s, l_tile=32)
    np.testing.assert_allclose(v_f, v_u, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(b_f, b + db, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c_f, jax.nn.softmax(b + db, axis=-1),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# routing procedure custom VJP (DESIGN.md §Training)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("iters", [1, 2, 3])
@pytest.mark.parametrize("stream_dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("L", [64, 136])   # 136: non-divisible -> l_tile 68
def test_procedure_vjp_grad_parity(key, iters, stream_dtype, L):
    """jax.grad through the recompute-b backward megakernel vs jnp
    autodiff of the oracle, per stream dtype's GRAD_ATOL (the ISSUE's
    ≤1e-4 fp32 / ≤2e-2 bf16 per-element acceptance), across iteration
    counts and a non-divisible L tiling."""
    u_hat = jax.random.normal(key, (2, L, 6, 8))
    f = functools.partial(rt_ops.dynamic_routing_procedure_train,
                          iterations=iters, stream_dtype=stream_dtype)
    f_ref = functools.partial(rt_ref.dynamic_routing_ref, iterations=iters)
    check_vjp_parity(f, f_ref, u_hat, atol=grad_tol(stream_dtype))


def test_procedure_vjp_saves_only_u_hat(key):
    """The recompute-b claim itself: the VJP's residual set is û alone —
    no per-iteration (L,H)/(B,H,C) intermediate survives the forward as
    an autodiff residual."""
    u_hat = jax.random.normal(key, (2, 64, 6, 8))
    B, L, H, C = u_hat.shape
    _, f_vjp = jax.vjp(functools.partial(
        rt_ops.dynamic_routing_procedure_train, iterations=3), u_hat)
    residuals = [l for l in jax.tree.leaves(f_vjp)
                 if hasattr(l, "shape") and hasattr(l, "dtype")]
    big = [r.shape for r in residuals if r.size > B * H * C]
    assert big == [u_hat.shape], (
        "recompute-b must keep û as the only large residual; "
        f"found {[r.shape for r in residuals]}")
    # jnp autodiff of the oracle, by contrast, drags per-iteration
    # O(B·L·H·C) residuals along — that contrast is the point
    _, ref_vjp = jax.vjp(functools.partial(
        rt_ref.dynamic_routing_ref, iterations=3), u_hat)
    ref_big = [l for l in jax.tree.leaves(ref_vjp)
               if hasattr(l, "size") and l.size > B * H * C]
    assert len(ref_big) > 1


def test_procedure_vjp_finite_difference(key):
    """Reference-free directional finite-difference probe (catches the
    both-paths-wrong-the-same-way failure parity tests can't)."""
    u_hat = jax.random.normal(key, (2, 64, 5, 8))
    check_grad_finite_difference(
        functools.partial(rt_ops.dynamic_routing_procedure_train,
                          iterations=3), u_hat)


def test_procedure_bwd_dma_model():
    """Backward DMA model invariants: 2T û streams + one û-sized ∂û write
    + the (B,H,C) cotangent read; bf16 halves both û-sized terms; fused
    backward beats the modeled unfused-autodiff bill; non-procedure forms
    have no backward model."""
    B, L, H, C, iters = 4, 128, 10, 16, 3
    bw = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                   backward=True)
    fw = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure")
    bf = rt_ops.dma_bytes_per_call(B, L, H, C, iters, form="procedure",
                                   stream_dtype="bf16", backward=True)
    u = B * L * H * C * 4
    assert bw["u_hat_stream_bytes"] == 2 * fw["u_hat_stream_bytes"]
    assert bw["du_stream_bytes"] == u
    assert bw["roundtrip_bytes"] == B * H * C * 4
    assert bw["total_bytes"] == 2 * iters * u + u + B * H * C * 4
    assert 2 * bf["u_hat_stream_bytes"] == bw["u_hat_stream_bytes"]
    assert 2 * bf["du_stream_bytes"] == bw["du_stream_bytes"]
    assert bw["total_bytes"] < bw["naive_bytes"]
    assert bw["backward"] is True and fw["backward"] is False
    with pytest.raises(ValueError, match="no custom VJP"):
        rt_ops.dma_bytes_per_call(B, L, H, C, form="iteration",
                                  backward=True)


@settings(max_examples=6, deadline=None)
@given(n_pad=st.integers(min_value=1, max_value=3),
       iters=st.integers(min_value=1, max_value=3),
       stream_dtype=st.sampled_from(["fp32", "bf16"]))
def test_property_procedure_vjp_padding_and_determinism(n_pad, iters,
                                                       stream_dtype):
    """Training analogue of serving's padding bit-invariance: batch lanes
    that are zero (padding) and receive a zero cotangent get EXACTLY zero
    gradient — no cross-lane leakage through the backward's (L,H)
    reductions — and the VJP is bitwise deterministic across calls."""
    B = 4
    key = jax.random.PRNGKey(n_pad * 31 + iters)
    u_hat = jax.random.normal(key, (B, 64, 5, 8))
    u_hat = u_hat.at[B - n_pad:].set(0.0)
    ct = jax.random.normal(jax.random.fold_in(key, 1), (B, 5, 8))
    ct = ct.at[B - n_pad:].set(0.0)    # the loss reads real lanes only
    f = functools.partial(rt_ops.dynamic_routing_procedure_train,
                          iterations=iters, stream_dtype=stream_dtype)
    g = jax.vjp(f, u_hat)[1](ct)[0]
    g2 = jax.vjp(f, u_hat)[1](ct)[0]
    pad = np.asarray(g[B - n_pad:], np.float32)
    assert not pad.any(), "padding lanes leaked gradient"
    assert np.asarray(g[:B - n_pad], np.float32).any()
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g2),
                                  err_msg="VJP not deterministic")


# ---------------------------------------------------------------------------
# fastmath kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8,), (100,), (16, 32), (3, 5, 7)])
@pytest.mark.parametrize("op,ref,tol", [
    ("exp", fm_ref.exp_ref, 0.045),
    ("inv_sqrt", fm_ref.inv_sqrt_ref, 0.005),
    ("reciprocal", fm_ref.reciprocal_ref, 0.02),
])
def test_fastmath_vs_ref(key, shape, op, ref, tol):
    x = jax.random.uniform(key, shape, minval=0.1, maxval=8.0)
    if op == "exp":
        x = x - 4.0    # exercise negatives
    got = getattr(fm_ops, op)(x)
    want = ref(x)
    rel = np.abs(np.asarray(got) - np.asarray(want)) / np.abs(want)
    assert rel.max() < tol
    assert got.shape == x.shape


def test_fastmath_matches_core_approx(key):
    """kernel path == core.approx bit-level functions (same algorithm;
    rtol covers fma-fusion op-ordering differences)."""
    x = jax.random.uniform(key, (64, 64), minval=-5, maxval=5)
    np.testing.assert_allclose(fm_ops.exp(x), approx.fast_exp(x),
                               rtol=5e-5, atol=1e-8)
    xp = jnp.abs(x) + 0.1
    np.testing.assert_allclose(fm_ops.inv_sqrt(xp), approx.fast_inv_sqrt(xp),
                               rtol=1e-6)
    np.testing.assert_allclose(fm_ops.reciprocal(xp),
                               approx.fast_reciprocal(xp), rtol=1e-6)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Hq, Hkv, S, D, causal)
    (1, 2, 2, 128, 32, True),
    (2, 4, 2, 128, 64, True),       # GQA group=2
    (1, 8, 2, 256, 64, True),       # GQA group=4
    (2, 2, 2, 128, 32, False),      # bidirectional
    (1, 2, 1, 64, 128, True),       # small S < block
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_ref(key, case):
    B, Hq, Hkv, S, D, causal = case
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, Hkv, S, D), jnp.float32)
    got = fa_ops.attention(q, k, v, causal=causal)
    want = fa_ref.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(key, dtype, tol):
    q = jax.random.normal(key, (1, 2, 128, 32)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, 2, 128, 32)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (1, 2, 128, 32)).astype(dtype)
    got = fa_ops.attention(q, k, v, causal=True)
    want = fa_ref.mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)
    assert got.dtype == dtype


BWD_CASES = [
    (1, 2, 2, 64, 16, True),
    (2, 4, 2, 64, 16, True),      # GQA group=2 (dk/dv group-sum)
    (1, 8, 2, 64, 32, True),      # GQA group=4
    (1, 2, 1, 128, 32, False),    # bidirectional
]


@pytest.mark.parametrize("case", BWD_CASES)
def test_flash_attention_backward_vs_ref(key, case):
    """custom_vjp over the Pallas fwd/bwd kernels == jax.grad of the dense
    reference, for o/dq/dk/dv."""
    B, Hq, Hkv, S, D, causal = case
    kq, kk, kv, kd = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, Hq, S, D))
    k = jax.random.normal(kk, (B, Hkv, S, D))
    v = jax.random.normal(kv, (B, Hkv, S, D))
    do = jax.random.normal(kd, (B, Hq, S, D))
    o, vjp = jax.vjp(lambda q, k, v: fa_ops.attention_train(q, k, v, causal),
                     q, k, v)
    dq, dk, dv = vjp(do)
    o_r, vjp_r = jax.vjp(
        lambda q, k, v: fa_ref.mha_ref(q, k, v, causal=causal), q, k, v)
    dq_r, dk_r, dv_r = vjp_r(do)
    for name, a, b in [("o", o, o_r), ("dq", dq, dq_r), ("dk", dk, dk_r),
                       ("dv", dv, dv_r)]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_flash_attention_lse_matches_dense(key):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd_lse
    q = jax.random.normal(key, (1, 2, 64, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 16))
    _, lse = flash_attention_fwd_lse(q, k, v, causal=True, block_q=32,
                                     block_k=32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 16 ** 0.5
    mask = jnp.tril(jnp.ones((64, 64), bool))
    logits = jnp.where(mask, logits, -jnp.inf)
    want = jax.nn.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 64)])
def test_flash_attention_block_sweep(key, bq, bk):
    q = jax.random.normal(key, (1, 2, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))
    got = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    want = fa_ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# ssm scan kernel
# ---------------------------------------------------------------------------

SSM_CASES = [
    # (B, T, Din, N, chunk)
    (1, 64, 16, 8, 16),
    (2, 128, 32, 16, 32),
    (2, 64, 8, 4, 64),      # chunk == T
    (1, 96, 16, 8, 32),     # T = 3 chunks
]


def _ssm_inputs(key, B, T, Din, N):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (B, T, Din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Din)))
    A = -jnp.abs(jax.random.normal(ks[2], (Din, N)))
    Bm = jax.random.normal(ks[3], (B, T, N))
    Cm = jax.random.normal(ks[4], (B, T, N))
    Dv = jax.random.normal(ks[5], (Din,))
    return x, dt, A, Bm, Cm, Dv


@pytest.mark.parametrize("case", SSM_CASES)
def test_ssm_scan_vs_ref(key, case):
    from repro.kernels.ssm_scan.kernel import selective_scan
    B, T, Din, N, chunk = case
    x, dt, A, Bm, Cm, Dv = _ssm_inputs(key, B, T, Din, N)
    chunk = min(chunk, T)
    while T % chunk:
        chunk //= 2
    got = selective_scan(x, dt, A, Bm, Cm, Dv, chunk=chunk)
    want, _ = ssm_ref.selective_scan_ref(x, dt, A, Bm, Cm, Dv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ssm_ops_wrapper(key):
    x, dt, A, Bm, Cm, Dv = _ssm_inputs(key, 2, 96, 16, 8)
    got = ssm_ops.scan(x, dt, A, Bm, Cm, Dv)
    want, _ = ssm_ref.selective_scan_ref(x, dt, A, Bm, Cm, Dv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ssm_step_matches_scan(key):
    """Token-by-token selective_step_ref == full-scan ref (state carry)."""
    x, dt, A, Bm, Cm, Dv = _ssm_inputs(key, 2, 16, 8, 4)
    want, h_last = ssm_ref.selective_scan_ref(x, dt, A, Bm, Cm, Dv)
    h = jnp.zeros((2, 8, 4))
    ys = []
    for t in range(16):
        y, h = ssm_ref.selective_step_ref(h, x[:, t], dt[:, t], A,
                                          Bm[:, t], Cm[:, t], Dv)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h, h_last, rtol=1e-4, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(b=st.integers(1, 2), nch=st.integers(1, 3),
       din=st.sampled_from([8, 16]), n=st.sampled_from([4, 8]))
def test_property_ssm_scan(b, nch, din, n):
    from repro.kernels.ssm_scan.kernel import selective_scan
    T = nch * 16
    key = jax.random.PRNGKey(b * 100 + T + din)
    x, dt, A, Bm, Cm, Dv = _ssm_inputs(key, b, T, din, n)
    got = selective_scan(x, dt, A, Bm, Cm, Dv, chunk=16)
    want, _ = ssm_ref.selective_scan_ref(x, dt, A, Bm, Cm, Dv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
