"""Matrix capsules with EM routing (``models.matrix_capsnet``) on the CPU.

* the model against the benchmark's plain reference
  (``bench/reference_em.py``, which imports nothing of the program) on
  seeded weights at a small size, on the jnp path and on the pallas path
  in interpret mode;
* the convolutional E-step against a brute-force softmax over each
  child's parents, for both strides of the network;
* padding invariance of a partial serving wave;
* a fully connected "em" call with scalar β gives what the routing gave
  before per-type β, convolutional connectivity and the logits option.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import em_routing
from repro.core.router import ExecutionPlan, RouterSpec, build_router
from repro.models import matrix_capsnet as mc
from repro.runtime.caps_serve import MatrixCapsAdapter
from repro.runtime.wave_serve import ServeConfig, WaveServer

_REF = pathlib.Path(__file__).resolve().parents[1] / "bench" / \
    "reference_em.py"
_spec = importlib.util.spec_from_file_location("reference_em", _REF)
reference_em = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference_em)

# 24x24 -> conv1 10x10 -> ConvCaps1 4x4 -> ConvCaps2 2x2 -> 3 classes
CFG = mc.MatrixCapsConfig(image_hw=24, A=4, B=4, C=4, D=4, E=3)
REF_CFG = {"image_channels": 1, "conv_kernel": 5, "conv_stride": 2, "A": 4,
           "B": 4, "C": 4, "D": 4, "E": 3, "caps_kernel": 3,
           "caps_strides": [2, 1], "routing_iters": 3, "inv_temp": 1.0,
           "eps": 1e-9}


def _lane_gap(got, want):
    """Widest |got − want| of each lane over its largest |want|."""
    got = np.asarray(got, np.float64).reshape(len(got), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    return float((np.abs(got - want).max(1)
                  / np.maximum(np.abs(want).max(1), 1e-30)).max())


@pytest.fixture(scope="module")
def weights():
    # the reference's weights (β random per type) in the program's layout
    return reference_em.init_params(REF_CFG, 7)


@pytest.fixture(scope="module")
def images():
    return jax.random.normal(jax.random.key(3), (4, 24, 24, 1))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_model_matches_the_plain_reference(weights, images, backend):
    """Class logits and poses, float32 on the CPU.  Tolerance 5e-4 of the
    lane's largest value: three chained EM layers amplify float32 rounding
    (the logits are λ·r_sum·Σ log σ, sums over hundreds of children; the
    pallas M-step recombines σ² from E[V²] − μ², which cancels) — the
    readings are 1e-5 to 7e-5; the per-window softmax moves them by
    order 1 (tests/bench/test_bench_em.py)."""
    mask = jnp.ones((4,))
    spec = RouterSpec(algorithm="em", backend=backend)
    got = jax.jit(lambda p, x, m: mc.forward(p, x, m, CFG, spec))(
        weights, images, mask)
    want = jax.jit(lambda p, x, m: reference_em.forward(p, x, m, REF_CFG))(
        weights, images, mask)
    assert got[0].shape == (4, 3) and got[1].shape == (4, 3, 16)
    assert _lane_gap(got[0], want[0]) < 5e-4
    assert _lane_gap(got[1], want[1]) < 5e-4


def _brute_force(logits, geom, child_types):
    """Each child's softmax over every (window, parent type) entry that
    holds it, found by walking the windows."""
    n_w = geom.windows
    oh, ow = geom.out_hw
    k, s = geom.kernel, geom.stride
    logits = np.asarray(logits, np.float64)
    rows, L, H = logits.shape
    owner = {}
    for r in range(rows):
        img, w = divmod(r, n_w)
        wy, wx = divmod(w, ow)
        for l in range(L):
            ky, rest = divmod(l, k * child_types)
            kx, t = divmod(rest, child_types)
            child = (img, s * wy + ky, s * wx + kx, t)
            owner.setdefault(child, []).append((r, l))
    out = np.zeros_like(logits)
    counts = np.zeros((rows, L))
    for entries in owner.values():
        x = np.stack([logits[r, l] for r, l in entries])
        e = np.exp(x - x.max())
        e /= e.sum()
        for (r, l), v in zip(entries, e):
            out[r, l] = v
            counts[r, l] = len(entries)
    return out, counts


@pytest.mark.parametrize("stride", [2, 1])
def test_cross_window_softmax_matches_brute_force(stride):
    """6x6 grid of 2 child types, 3x3 windows, 3 parent types, 2 images.
    float32 exp and sums against float64: 1e-6."""
    geom = em_routing.ConvGeometry(3, stride, 6, 6)
    rows, L, H = 2 * geom.windows, 9 * 2, 3
    logits = 4.0 * jax.random.normal(jax.random.key(stride), (rows, L, H))
    want, counts = _brute_force(logits, geom, 2)
    got = em_routing.cross_window_softmax(logits, geom)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    r0 = em_routing.initial_responsibilities((rows, L, H), geom)
    np.testing.assert_allclose(np.asarray(r0[..., 0]), 1 / (H * counts),
                               rtol=1e-6)
    # the per-window softmax is another model: it differs
    local = jax.nn.softmax(logits, axis=-1)
    assert np.abs(np.asarray(local) - want).max() > 0.1


def test_padding_invariance_of_a_partial_wave(weights, images):
    """Padded lanes of a partial wave, even holding other images, do not
    move the real lanes: their mask zeroes their child activations in
    every layer and EM keeps nothing across lanes.  The real lanes equal
    those of a wave in which every lane is real (1e-6: the same per-lane
    arithmetic)."""
    scfg = ServeConfig(microbatch=2, n_micro=2, pipeline=None)
    adapter = MatrixCapsAdapter(weights, CFG, RouterSpec(algorithm="em"))
    wave = adapter.make_wave_fn(scfg)
    x = np.asarray(images)
    packed = adapter.pack(list(x[:3]), scfg)
    noisy = dict(packed, images=packed["images"].at[1, 1].set(
        100.0 * jnp.ones((24, 24, 1))))
    full = adapter.pack(list(x), scfg)
    a, b, c = (wave(w) for w in (packed, noisy, full))
    for out in (b, c):
        for got, want in zip(out, a):
            got = np.asarray(got).reshape((4,) + got.shape[2:])[:3]
            want = np.asarray(want).reshape((4,) + want.shape[2:])[:3]
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert adapter.unpack(a, 3) == adapter.unpack(c, 3)
    server = WaveServer(adapter, cfg=scfg)
    server.submit(x[:3])
    done = server.drain()
    assert [d.pred for d in done] == adapter.unpack(a, 3)


def _em_before(votes, a_in, iterations, beta_a, beta_u, inv_temp, eps):
    """The fully connected EM routing as it was before this module took
    per-type β and convolutional connectivity (its last E-step ran)."""
    B, L, H, C = votes.shape
    r = jnp.full((B, L, H), 1.0 / H, jnp.float32)
    for it in range(iterations):
        lam = inv_temp * (1.0 - 0.95 ** (it + 1))
        rw = r * a_in[..., None]
        r_sum = jnp.sum(rw, axis=1) + eps
        mu = jnp.einsum("blh,blhc->bhc", rw, votes) / r_sum[..., None]
        diff2 = jnp.square(votes - mu[:, None])
        sigma2 = jnp.einsum("blh,blhc->bhc", rw, diff2) / r_sum[..., None] \
            + eps
        cost = (beta_u + 0.5 * jnp.log(sigma2)) * r_sum[..., None]
        a_out = jax.nn.sigmoid(lam * (beta_a - jnp.sum(cost, axis=-1)))
        log_p = -0.5 * jnp.sum(jnp.log(2.0 * jnp.pi * sigma2[:, None])
                               + diff2 / sigma2[:, None], axis=-1)
        r = jax.nn.softmax(jnp.log(a_out[:, None] + eps) + log_p, axis=-1)
    return mu, a_out


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fully_connected_scalar_beta_is_unchanged(backend):
    """Scalar β options: the earlier results (jnp: the same ops, 1e-6;
    pallas: σ² from sufficient statistics, 1e-4 as the existing EM kernel
    tests allow).  β per type equal to the scalar gives the same, and the
    logits option gives the sigmoid's argument."""
    k1, k2 = jax.random.split(jax.random.key(11))
    votes = jax.random.normal(k1, (3, 64, 6, 16))
    a_in = jax.random.uniform(k2, (3, 64))
    spec = RouterSpec(algorithm="em", backend=backend).with_options(
        beta_a=0.5, beta_u=-0.3, inv_temp=2.0)
    want = _em_before(votes, a_in, 3, 0.5, -0.3, 2.0, 1e-9)
    tol = 1e-6 if backend == "jnp" else 1e-4
    got = build_router(spec)(votes, a_in)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=tol,
                                   rtol=tol)
    typed = build_router(spec)(votes, a_in, jnp.full((6,), 0.5),
                               jnp.full((6,), -0.3))
    for g, w in zip(typed, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6,
                                   rtol=1e-6)
    pose, logit = build_router(spec.with_options(logits=True))(votes, a_in)
    np.testing.assert_allclose(np.asarray(jax.nn.sigmoid(logit)),
                               np.asarray(got[1]), atol=1e-6)


def test_convolutional_em_is_shard_local():
    geom = em_routing.ConvGeometry(3, 2, 6, 6)
    spec = RouterSpec(algorithm="em").with_options(conv=geom)
    with pytest.raises(ValueError, match="shard-local"):
        build_router(spec, ExecutionPlan(auto=True))
    with pytest.raises(ValueError, match="ConvGeometry"):
        build_router(RouterSpec(algorithm="em").with_options(conv=(3, 2)))


def test_jnp_and_pallas_convolutional_layers_agree():
    """One convolutional EM call (patch batches of a 6x6 grid, stride 2)
    through build_router on both backends, per-type β: the stage kernels
    with the fold against the jnp spec, 1e-4 (σ² recombined)."""
    geom = em_routing.ConvGeometry(3, 2, 6, 6)
    rows, L, H = 2 * geom.windows, 9 * 8, 4
    k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
    votes = jax.random.normal(k1, (rows, L, H, 16))
    a_in = jax.random.uniform(k2, (rows, L))
    beta_a, beta_u = jax.random.normal(k3, (2, H))
    outs = [build_router(RouterSpec(algorithm="em", backend=b).with_options(
        conv=geom, logits=True))(votes, a_in, beta_a, beta_u)
        for b in ("jnp", "pallas")]
    for g, w in zip(*outs):
        assert _lane_gap(g, w) < 1e-4
