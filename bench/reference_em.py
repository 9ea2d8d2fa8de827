"""Plain jax.numpy matrix capsules with EM routing: the benchmark's own
weights and reference for ``bench/configs/emcaps-norb.json``.

Imports nothing of the program under test.  Follows Hinton, Sabour and
Frosst, "Matrix capsules with EM routing" (ICLR 2018), §2-§5 and Fig. 1,
the smallNORB network:

    conv1 (5x5, stride 2, A, ReLU) -> PrimaryCaps (1x1: B types, 4x4 pose
    + sigmoid activation) -> ConvCaps1 (3x3, stride 2, C) -> ConvCaps2
    (3x3, stride 1, D) -> class capsules (E; transforms shared across
    positions, coordinate addition), EM routing (Procedure 1) in each
    routed layer.

The weights are made here, on the device, in one jitted call from the
seed, in the parameter layout the program reads (a dict of float32
arrays):

    conv1/{w,b}                 (5, 5, 1, A), (A,)
    primary/{w,b}               (1, 1, A, B·17), (B·17,): B·16 pose
                                entries (type-major), then B activations
    convcaps1/W                 (3·3·B, C, 4, 4): (kernel row, kernel
                                column, child type) x parent type
    convcaps2/W                 (3·3·C, D, 4, 4)
    class/W                     (D, E, 4, 4)
    <layer>/beta_a, beta_u      (parent types,)

Departures and choices the paper leaves open (the configuration's
``assumed`` lists them): λ_t = inv_temp · (1 − 0.95^(t+1)); β_a, β_u one
per parent type; cost_h = (β_u + log σ_h) · Σ_i R_ij (the paper's); a
child's R is normalised over every parent whose window covers it, across
windows (§3), and starts at 1 / their number; the coordinate added to the
class votes is the centre of the child's position, (row + ½)/4 and
(column + ½)/4, on the first two entries of the vote's right-hand column;
ε = 1e-9 under σ² and Σ R.  What is compared: the class layer's activation
logits λ(β_a − Σ_h cost_h) and its poses μ.

Each routed layer's windows are gathered by index arrays, and each
child's entries meet again in a scatter over child ids: no code is shared
with the program's strided slices and pads.  The matmul precision acts on
the two convolutions and the 4x4 vote products; sums over children are
products summed in float32.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

POSE = 4


def init_params(cfg: Mapping, seed: int):
    """Seeded weights on the default device, one jitted call."""
    return _init(_shape_key(cfg), jax.random.key(seed % (2 ** 32)))


def _shape_key(cfg: Mapping) -> tuple:
    return tuple(cfg[k] for k in ("image_channels", "conv_kernel", "A", "B",
                                  "C", "D", "E", "caps_kernel"))


@functools.partial(jax.jit, static_argnums=0)
def _init(shape, key):
    cin, k1, A, B, C, D, E, K = shape
    ks = jax.random.split(key, 11)

    def normal(k, shp, fan_in):
        return jax.random.normal(k, shp, jnp.float32) / jnp.sqrt(fan_in)

    caps = {}
    for i, (name, children, parents) in enumerate(
            (("convcaps1", K * K * B, C), ("convcaps2", K * K * C, D),
             ("class", D, E))):
        caps[name] = {
            "W": normal(ks[2 + 3 * i], (children, parents, POSE, POSE), POSE),
            "beta_a": jax.random.normal(ks[3 + 3 * i], (parents,)),
            "beta_u": jax.random.normal(ks[4 + 3 * i], (parents,))}
    return {"conv1": {"w": normal(ks[0], (k1, k1, cin, A), k1 * k1 * cin),
                      "b": jnp.zeros((A,), jnp.float32)},
            "primary": {"w": normal(ks[1], (1, 1, A, B * 17), A),
                        "b": jnp.zeros((B * 17,), jnp.float32)},
            **caps}


def _conv(x, w, b, stride):
    y = lax.conv_general_dilated(x, w, (stride, stride), "VALID",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _windows(g: int, k: int, s: int) -> np.ndarray:
    """(out, k) child row of each (window row, kernel row)."""
    out = (g - k) // s + 1
    return s * np.arange(out)[:, None] + np.arange(k)[None, :]


def _votes(pose, W):
    """pose (..., 16) · W (..., H, 4, 4) -> (..., H, 16): M_i W_ij."""
    m = pose.reshape(pose.shape[:-1] + (POSE, POSE))
    v = jnp.einsum("...pq,...hqr->...hpr", m, W)
    return v.reshape(v.shape[:-2] + (POSE * POSE,))


def em(votes, a_in, child, n_children, beta_a, beta_u, cfg: Mapping):
    """EM routing (Procedure 1) of one image's layer.

    votes (P, L, H, 16) and a_in (P, L): P parent positions, each with its
    L children; ``child`` (P, L) the id of the child capsule each entry is
    (the same child under several windows shares an id).  Returns μ
    (P, H, 16) and the activation logits (P, H)."""
    P, L, H, _ = votes.shape
    eps = cfg["eps"]
    dt = votes.dtype
    cid = child.reshape(-1)
    count = jnp.zeros((n_children,), dt).at[cid].add(1.0)
    R = jnp.broadcast_to((1.0 / (count[child] * H))[..., None], (P, L, H))
    for t in range(cfg["routing_iters"]):
        lam = cfg["inv_temp"] * (1.0 - 0.95 ** (t + 1))
        # M-step
        Ra = R * a_in[..., None]                                # (P, L, H)
        Rsum = jnp.sum(Ra, axis=1) + eps                        # (P, H)
        mu = jnp.sum(Ra[..., None] * votes, axis=1) / Rsum[..., None]
        dev2 = jnp.square(votes - mu[:, None])
        sigma2 = jnp.sum(Ra[..., None] * dev2, axis=1) / Rsum[..., None] \
            + eps
        cost = (beta_u[:, None] + 0.5 * jnp.log(sigma2)) * Rsum[..., None]
        logit = lam * (beta_a - jnp.sum(cost, axis=-1))         # (P, H)
        if t + 1 == cfg["routing_iters"]:
            return mu, logit
        # E-step: a_j p_j normalised over every parent of each child
        log_p = -0.5 * jnp.sum(jnp.log(2 * jnp.pi * sigma2)[:, None]
                               + dev2 / sigma2[:, None], axis=-1)
        x = jnp.log(jax.nn.sigmoid(logit) + eps)[:, None] + log_p
        top = jnp.full((n_children,), -jnp.inf, dt).at[cid].max(
            jnp.max(x, axis=-1).reshape(-1))
        e = jnp.exp(x - top[child][..., None])
        total = jnp.zeros((n_children,), dt).at[cid].add(
            jnp.sum(e, axis=-1).reshape(-1))
        R = e / total[child][..., None]


def _conv_caps(pose, act, p, cfg: Mapping, stride: int):
    """One image's convolutional capsule layer: pose (g, g, T, 16), act
    (g, g, T) -> pose (o, o, H, 16), activations (o, o, H)."""
    g, _, T, _ = pose.shape
    K = cfg["caps_kernel"]
    rows = _windows(g, K, stride)                               # (o, K)
    o = rows.shape[0]
    iy = rows[:, None, :, None]                                 # (o,1,K,1)
    ix = rows[None, :, None, :]                                 # (1,o,1,K)
    pp = pose[iy, ix].reshape(o * o, K * K * T, POSE * POSE)
    ap = act[iy, ix].reshape(o * o, K * K * T)
    child = ((iy * g + ix)[..., None] * T + np.arange(T)).reshape(
        o * o, K * K * T)
    W = p["W"].astype(pose.dtype)
    v = _votes(pp, W)
    mu, logit = em(v, ap, jnp.asarray(child), g * g * T, p["beta_a"],
                   p["beta_u"], cfg)
    H = W.shape[1]
    return (mu.reshape(o, o, H, POSE * POSE),
            jax.nn.sigmoid(logit).reshape(o, o, H))


def _class_caps(pose, act, p, cfg: Mapping):
    """pose (g, g, D, 16), act (g, g, D) -> (logits (E,), poses (E, 16))."""
    g, _, D, _ = pose.shape
    W = p["W"].astype(pose.dtype)                               # (D, E, 4, 4)
    v = _votes(pose, jnp.broadcast_to(W, (g, g) + W.shape))    # (g,g,D,E,16)
    coord = (jnp.arange(g, dtype=pose.dtype) + 0.5) / g
    v = v.at[..., POSE - 1].add(coord[:, None, None, None])
    v = v.at[..., 2 * POSE - 1].add(coord[None, :, None, None])
    L = g * g * D
    mu, logit = em(v.reshape(1, L, W.shape[1], POSE * POSE),
                   act.reshape(1, L), jnp.arange(L).reshape(1, L), L,
                   p["beta_a"], p["beta_u"], cfg)
    return logit[0], mu[0]


def forward(params, images, mask, cfg: Mapping, dtype=jnp.float32):
    """A microbatch: images (N, hw, hw, cin), mask (N,) -> class logits
    (N, E) and poses (N, E, 16).  A masked lane's activations are 0."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    h = jax.nn.relu(_conv(images.astype(dtype), p["conv1"]["w"],
                          p["conv1"]["b"], cfg["conv_stride"]))
    y = _conv(h, p["primary"]["w"], p["primary"]["b"], 1)
    n, g = y.shape[0], y.shape[1]
    B = cfg["B"]
    pose = y[..., :B * 16].reshape(n, g, g, B, 16)
    act = jax.nn.sigmoid(y[..., B * 16:])

    def one(args):
        pose, act, m = args
        s1, s2 = cfg["caps_strides"]
        pose, act = _conv_caps(pose, act * m, p["convcaps1"], cfg, s1)
        pose, act = _conv_caps(pose, act * m, p["convcaps2"], cfg, s2)
        return _class_caps(pose, act * m, p["class"], cfg)

    return jax.vmap(one)((pose, act, mask.astype(dtype)))


def wave_outputs(params, images, mask, cfg: Mapping, dtype=jnp.float32,
                 precision: str = "highest"):
    """(logits, poses) of a packed wave: images (n_micro, mb, hw, hw, cin)
    and mask (n_micro, mb), one microbatch at a time."""
    def one(args):
        return forward(params, *args, cfg, dtype)
    with jax.default_matmul_precision(precision):
        logits, poses = lax.map(one, (images, mask))
    return logits.astype(jnp.float32), poses.astype(jnp.float32)
