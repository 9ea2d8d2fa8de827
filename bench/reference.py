"""Plain jax.numpy CapsNet: the benchmark's own weights and reference.

Imports nothing of the program under test.  The weights are made here, on
the device, in one jitted call from the seed, in the parameter layout the
program reads (a nested dict of float32 arrays):

    primary/conv1/{w,b}       (k, k, C_in, conv_channels)   conv1, ReLU
    primary/caps_conv/{w,b}   (k, k, conv, caps·C_L)        PrimaryCaps
    digit/W                   (L, H, C_L, C_H)              Eq.1 weights
    decoder/fc{0,1,2}/{w,b}                                 3-FC decoder

The forward pass follows Sabour et al. 2017 §4 and arXiv:1911.03451
Eq.1-5: conv1 -> PrimaryCaps conv -> squash -> votes -> dynamic routing
with the logits b shared by the images of one routing call.  Scores are
the capsule lengths ||v||.  ``dtype`` selects the arithmetic and
``precision`` the matmul precision: float32 at the configuration's
``reference_precision`` is the reference; bfloat16 is the control a
lower-precision path must not pass.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax

SQUASH_EPS = 1e-9


def init_params(cfg: Mapping, seed: int):
    """Seeded weights on the default device, one jitted call."""
    return _init(_shape_key(cfg), jax.random.key(seed % (2 ** 32)))


def _shape_key(cfg: Mapping) -> tuple:
    keys = ("image_channels", "conv_channels", "conv_kernel",
            "caps_channels", "caps_kernel", "l_caps_dim", "num_l_caps",
            "num_h_caps", "h_caps_dim", "image_hw")
    return tuple(cfg[k] for k in keys) + (tuple(cfg["decoder_hidden"]),)


@functools.partial(jax.jit, static_argnums=0)
def _init(shape, key):
    (cin, conv, k1, caps, k2, cl, n_l, n_h, ch, hw, hidden) = shape
    ks = jax.random.split(key, 6)

    def normal(k, shp, fan_in):
        return jax.random.normal(k, shp, jnp.float32) / jnp.sqrt(fan_in)

    dims = [n_h * ch, *hidden, hw * hw * cin]
    return {
        "primary": {
            "conv1": {"w": normal(ks[0], (k1, k1, cin, conv), k1 * k1 * cin),
                      "b": jnp.zeros((conv,), jnp.float32)},
            "caps_conv": {"w": normal(ks[1], (k2, k2, conv, caps * cl),
                                      k2 * k2 * conv),
                          "b": jnp.zeros((caps * cl,), jnp.float32)},
        },
        "digit": {"W": normal(ks[2], (n_l, n_h, cl, ch), cl)},
        "decoder": {
            f"fc{i}": {"w": normal(jax.random.fold_in(ks[3], i),
                                   (dims[i], dims[i + 1]), dims[i]),
                       "b": jnp.zeros((dims[i + 1],), jnp.float32)}
            for i in range(len(dims) - 1)},
    }


def squash(s, axis=-1):
    n2 = jnp.sum(s * s, axis=axis, keepdims=True)
    return s * (n2 / (1 + n2)) / jnp.sqrt(n2 + SQUASH_EPS)


def _conv(x, w, b, stride):
    y = lax.conv_general_dilated(x, w, (stride, stride), "VALID",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def capsules(params, images, cfg: Mapping, dtype):
    """(B, hw, hw, C) images -> v (B, H, C_H) of one routing call."""
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = images.astype(dtype)
    h = jax.nn.relu(_conv(x, p["primary"]["conv1"]["w"],
                          p["primary"]["conv1"]["b"], 1))
    h = _conv(h, p["primary"]["caps_conv"]["w"],
              p["primary"]["caps_conv"]["b"], cfg["caps_stride"])
    n = h.shape[0]
    u = squash(h.reshape(n, -1, cfg["l_caps_dim"]))
    n_l = cfg["num_l_caps"]
    if u.shape[1] < n_l:
        u = jnp.tile(u, (1, -(-n_l // u.shape[1]), 1))
    u = u[:, :n_l]
    return u, p


def routed(params, images, mask, cfg: Mapping, dtype):
    u, p = capsules(params, images, cfg, dtype)
    u_hat = jnp.einsum("blc,lhcd->blhd", u, p["digit"]["W"])
    u_hat = u_hat * mask.astype(dtype)[:, None, None, None]
    b = jnp.zeros(u_hat.shape[1:3], dtype)
    for it in range(cfg["routing_iters"]):
        c = jax.nn.softmax(b, axis=-1)                       # Eq.5
        s = jnp.einsum("blhc,lh->bhc", u_hat, c)            # Eq.2
        v = squash(s)                                        # Eq.3
        if it + 1 < cfg["routing_iters"]:
            b = b + jnp.einsum("blhc,bhc->lh", u_hat, v)    # Eq.4
    return v


def wave_scores(params, images, mask, cfg: Mapping, dtype=jnp.float32,
                precision: str = "highest"):
    """Class scores of a packed wave: images (n_micro, mb, hw, hw, C) and
    mask (n_micro, mb); each microbatch is one routing call."""
    def one(args):
        x, m = args
        return jnp.linalg.norm(routed(params, x, m, cfg, dtype), axis=-1)
    with jax.default_matmul_precision(precision):
        return lax.map(one, (images, mask)).astype(jnp.float32)


def loss(params, images, labels, cfg: Mapping, dtype=jnp.float32):
    """Margin loss + 0.0005 x reconstruction MSE (Sabour et al. Eq.4)."""
    v = routed(params, images, jnp.ones(images.shape[:1]), cfg, dtype)
    m_pos, m_neg, lam = cfg["train"]["margin"]
    norms = jnp.linalg.norm(v, axis=-1)
    t = jax.nn.one_hot(labels, cfg["num_h_caps"], dtype=norms.dtype)
    margin = jnp.mean(jnp.sum(
        t * jnp.square(jnp.maximum(0.0, m_pos - norms))
        + lam * (1 - t) * jnp.square(jnp.maximum(0.0, norms - m_neg)), -1))
    dec = jax.tree.map(lambda a: a.astype(dtype), params["decoder"])
    h = (v * t[..., None]).reshape(v.shape[0], -1)
    for i in range(len(dec)):
        h = h @ dec[f"fc{i}"]["w"] + dec[f"fc{i}"]["b"]
        h = jax.nn.relu(h) if i < len(dec) - 1 else jax.nn.sigmoid(h)
    flat = images.reshape(images.shape[0], -1).astype(dtype)
    recon = jnp.mean(jnp.square(h - flat))
    return (margin + cfg["train"]["recon_weight"] * recon).astype(jnp.float32)


def lr_scale(step, tr: Mapping):
    warm = jnp.minimum(1.0, step / max(tr["warmup"], 1))
    span = max(tr["total_steps"] - tr["warmup"], 1)
    t = jnp.clip(jnp.maximum(step - tr["warmup"], 0) / span, 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    return warm * (tr["final_frac"] + (1 - tr["final_frac"]) * cos)


def train_step(params, state, images, labels, cfg: Mapping,
               dtype=jnp.float32, precision: str = "highest"):
    """One AdamW step with global-norm clipping.  ``state`` is
    (step, mu, nu).  Returns (params, state, loss, clipped grads)."""
    tr = cfg["train"]
    with jax.default_matmul_precision(precision):
        value, grads = jax.value_and_grad(loss)(params, images, labels, cfg,
                                                dtype)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, tr["max_grad_norm"]
                                  / jnp.maximum(gnorm, 1e-9)), grads)
    step, mu, nu = state
    t = step + 1
    lr = tr["lr"] * lr_scale(t, tr)
    bc1 = 1 - tr["b1"] ** t.astype(jnp.float32)
    bc2 = 1 - tr["b2"] ** t.astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: tr["b1"] * m + (1 - tr["b1"]) * g,
                      mu, grads)
    nu = jax.tree.map(lambda v, g: tr["b2"] * v + (1 - tr["b2"]) * g * g,
                      nu, grads)

    def update(p, m, v):
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + tr["eps"])
        if p.ndim >= 2:
            delta = delta + tr["weight_decay"] * p
        return p - lr * delta

    params = jax.tree.map(update, params, mu, nu)
    return params, (t, mu, nu), value, grads


def init_state(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return (jnp.zeros((), jnp.int32), zeros, jax.tree.map(jnp.zeros_like,
                                                          params))
