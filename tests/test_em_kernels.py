"""EM routing's stage kernels (kernels/routing/kernel.py: the M-step
statistics pass ``em_stage_stats`` and the ``em_stage_estep`` pass) with
their segment sums and broadcasts run as the exact three-pass bf16 split
``_seg_dot``: the split against an exact float64 product, and the whole
routing (``ops.em_routing_fused``) against the same kernels with the
segment dots at ``HIGHEST`` and against the plain jnp
``core.em_routing.em_routing``.  Kernels run in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import em_routing
from repro.kernels.routing import kernel as rt_kernel
from repro.kernels.routing import ops as rt_ops

# (H, C): the emnorb layers' widths (H = 32 and the class layer's 5, C =
# 16) and a Caps-MN1-like H = 10
SHAPES = [(32, 16), (5, 16), (10, 16)]


def _wide(key, shape):
    """float32 values whose magnitudes span 2^-20 .. 2^20, both signs:
    every one of a value's 24 significant bits matters."""
    k1, k2 = jax.random.split(key)
    mag = jnp.exp2(jax.random.uniform(k1, shape, minval=-20, maxval=20))
    return mag * jax.random.normal(k2, shape)


@pytest.mark.parametrize("h,c", SHAPES)
def test_seg_dot_broadcast_is_exact(key, h, c):
    """Broadcasting through the split copies each value bit for bit: the
    three bf16 terms sum back to the float32 value."""
    y = _wide(key, (64, h))
    got = rt_kernel._caps_bcast(y, c, rt_kernel._seg_dot)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.repeat(np.asarray(y), c, axis=-1))


@pytest.mark.parametrize("h,c", SHAPES)
def test_seg_dot_sum_matches_float64(key, h, c):
    """Each capsule's sum over its C lanes is the float64 sum to float32
    accumulation: C rounding steps of the absolute sum."""
    x = _wide(key, (64, h * c))
    got = np.asarray(rt_kernel._caps_sum(x, h, c, rt_kernel._seg_dot))
    x64 = np.asarray(x, np.float64).reshape(64, h, c)
    tol = c * 2.0 ** -23 * np.abs(x64).sum(-1)
    assert np.all(np.abs(got - x64.sum(-1)) <= tol)


# (layer, β per parent type, logits, iterations): each value of each
# knob appears at least once, with and without the cross-window fold
CASES = [("fc", False, False, 3), ("fc", True, True, 1),
         ("fc", True, False, 2), ("conv", True, True, 3),
         ("conv", False, False, 2), ("conv", True, False, 1),
         ("conv", False, True, 3)]
GEOM = em_routing.ConvGeometry(3, 2, 7, 7)      # 3x3 windows, 2 images


def _inputs(layer, per_type):
    """fc: B=8, L=64, H=5, C=16 (80 lanes); conv: 2 images x 9 windows of
    3x3x4 children, H=4, C=16."""
    rows, L, H = (8, 64, 5) if layer == "fc" else (2 * GEOM.windows, 36, 4)
    k = jax.random.split(jax.random.key(len(layer) + per_type), 4)
    votes = jax.random.normal(k[0], (rows, L, H, 16))
    a_in = jax.nn.sigmoid(jax.random.normal(k[1], (rows, L)))
    if not per_type:
        return votes, a_in, 1.0, 1.0
    return (votes, a_in, jax.random.normal(k[2], (H,)),
            jax.random.normal(k[3], (H,)))


@pytest.mark.parametrize("layer,per_type,logits,iters", CASES)
def test_em_split_dots_match_highest_and_jnp(monkeypatch, layer, per_type,
                                             logits, iters):
    """μ and a_out (or the activation logits) of the stage kernels with
    the split segment dots match the same kernels at ``HIGHEST`` and the
    jnp reference: float32 summed in another order, 1e-5 of the largest
    entry."""
    votes, a_in, beta_a, beta_u = _inputs(layer, per_type)
    conv = GEOM if layer == "conv" else None

    def run():
        return rt_ops.em_routing_fused(
            votes, a_in, axes={}, iterations=iters, beta_a=beta_a,
            beta_u=beta_u, conv=conv, logits=logits)

    split = run()
    monkeypatch.setattr(rt_kernel, "_seg_dot", rt_kernel._dot)
    jax.clear_caches()
    highest = run()
    jax.clear_caches()
    want = em_routing.em_routing(
        votes, a_in, em_routing.EMRoutingConfig(iterations=iters, conv=conv,
                                                logits=logits),
        beta_a, beta_u)
    for got, hi, ref in zip(split, highest, want):
        atol = 1e-5 * float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(hi),
                                   rtol=0, atol=atol)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=atol)
