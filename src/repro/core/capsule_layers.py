"""CapsNet layers (paper §2.1): Conv stack, PrimaryCaps, CapsLayer (Eq.1 + RP).

Parameters are plain pytrees (nested dicts) created by ``init_*`` functions;
forward passes are pure functions — the repo-wide convention (DESIGN.md §3).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import routing as routing_lib
from repro.core.approx import exact_squash


def conv2d(x: jax.Array, w: jax.Array, b: jax.Array, stride: int = 1,
           padding: str = "VALID") -> jax.Array:
    """NHWC conv. w: (kh, kw, cin, cout)."""
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def init_conv(key, kh, kw, cin, cout, scale=None):
    scale = scale or (1.0 / jnp.sqrt(kh * kw * cin))
    kw_, kb_ = jax.random.split(key)
    return {"w": jax.random.normal(kw_, (kh, kw, cin, cout), jnp.float32) * scale,
            "b": jnp.zeros((cout,), jnp.float32)}


class PrimaryCapsConfig(NamedTuple):
    """Conv -> PrimaryCaps mapping (paper Fig.2; CapsNet-MNIST defaults)."""
    conv1_channels: int = 256
    conv1_kernel: int = 9
    caps_channels: int = 32      # capsule map count
    caps_dim: int = 8            # C_L
    caps_kernel: int = 9
    caps_stride: int = 2


def init_primary_caps(key, in_channels: int, cfg: PrimaryCapsConfig):
    k1, k2 = jax.random.split(key)
    return {
        "conv1": init_conv(k1, cfg.conv1_kernel, cfg.conv1_kernel,
                           in_channels, cfg.conv1_channels),
        "caps_conv": init_conv(k2, cfg.caps_kernel, cfg.caps_kernel,
                               cfg.conv1_channels,
                               cfg.caps_channels * cfg.caps_dim),
    }


def primary_caps_forward(params, x: jax.Array, cfg: PrimaryCapsConfig
                         ) -> jax.Array:
    """x: (B,H,W,C) image -> u: (B, N_L, C_L) squashed primary capsules."""
    h = jax.nn.relu(conv2d(x, params["conv1"]["w"], params["conv1"]["b"]))
    h = conv2d(h, params["caps_conv"]["w"], params["caps_conv"]["b"],
               stride=cfg.caps_stride)
    B, H, W, _ = h.shape
    u = h.reshape(B, H * W * cfg.caps_channels, cfg.caps_dim)
    return exact_squash(u, axis=-1)


def init_caps_layer(key, n_l: int, n_h: int, c_l: int, c_h: int):
    """W: (N_L, N_H, C_L, C_H) — the Eq.1 prediction weight."""
    scale = 1.0 / jnp.sqrt(c_l)
    return {"W": jax.random.normal(key, (n_l, n_h, c_l, c_h),
                                   jnp.float32) * scale}


def pack_vote_weights(w: jax.Array) -> jax.Array:
    """W (L, H, C_L, C_H) -> its lane-packed view (L, C_L, H·C_H)."""
    n_l, n_h, c_l, c_h = w.shape
    return w.transpose(0, 2, 1, 3).reshape(n_l, c_l, n_h * c_h)


def project_votes(u: jax.Array, w_packed: jax.Array, n_h: int) -> jax.Array:
    """Eq.1 against W's lane-packed view: u (B, L, C_L) -> û (B, L, H, C_H).

    The matmul writes û lane-dense, (B, L, H·C_H), the layout the routing
    kernels read (``kernel._pack`` undoes the final reshape as a bitcast);
    the (L, H, C_L, C_H) form writes it with H on the lanes, padded to
    128, and every consumer then pays two relayout copies."""
    votes = jnp.einsum("blc,lck->blk", u, w_packed)
    return votes.reshape(votes.shape[:2] + (n_h, votes.shape[-1] // n_h))


def predict_votes(params, u: jax.Array) -> jax.Array:
    """Eq.1: u_hat[k,i,j] = u[k,i] @ W[i,j].   u:(B,L,C_L) -> (B,L,H,C_H)."""
    with jax.named_scope("capsnet.encode"):
        w = params["W"]
        return project_votes(u, pack_vote_weights(w), w.shape[1])


def caps_layer_forward(params, u: jax.Array, route) -> jax.Array:
    """Full Caps layer: Eq.1 votes + routing procedure.  -> v:(B,H,C_H).

    ``route`` selects the routing execution (DESIGN.md §Router):
      * a built ``repro.core.router.Router`` (or any callable u_hat -> v),
      * a ``RouterSpec`` (built on the spot, unsharded plan),
      * a ``RoutingConfig`` — runs the jnp ``dynamic_routing`` directly so
        ambient-axis collectives still work when the caller is already
        inside its own shard_map (e.g. the full-train dry-run cell).
    """
    u_hat = predict_votes(params, u)
    with jax.named_scope("capsnet.routing"):
        if isinstance(route, routing_lib.RoutingConfig):
            return routing_lib.dynamic_routing(u_hat, route)
        from repro.core import router as router_lib
        if isinstance(route, router_lib.RouterSpec):
            return router_lib.build_router(route)(u_hat)
        if callable(route):
            return route(u_hat)
    raise TypeError(
        f"route must be a Router/callable, RouterSpec, or RoutingConfig; "
        f"got {type(route).__name__}")


# --- decoding stage (paper §2.1: FC reconstruction decoder) ----------------

def init_dense(key, din, dout):
    return {"w": jax.random.normal(key, (din, dout), jnp.float32)
            / jnp.sqrt(din),
            "b": jnp.zeros((dout,), jnp.float32)}


def init_decoder(key, n_h: int, c_h: int, out_dim: int,
                 hidden=(512, 1024)):
    keys = jax.random.split(key, len(hidden) + 1)
    dims = [n_h * c_h, *hidden, out_dim]
    return {f"fc{i}": init_dense(keys[i], dims[i], dims[i + 1])
            for i in range(len(dims) - 1)}


def decoder_forward(params, v: jax.Array, labels: jax.Array | None = None
                    ) -> jax.Array:
    """Reconstruction decoder: mask all but the (label|longest) capsule."""
    B, H, C = v.shape
    with jax.named_scope("capsnet.decoder"):
        norms = jnp.linalg.norm(v, axis=-1)
        idx = jnp.argmax(norms, axis=-1) if labels is None else labels
        mask = jax.nn.one_hot(idx, H, dtype=v.dtype)[..., None]
        h = (v * mask).reshape(B, H * C)
        n = len(params)
        for i in range(n):
            p = params[f"fc{i}"]
            h = h @ p["w"] + p["b"]
            h = jax.nn.relu(h) if i < n - 1 else jax.nn.sigmoid(h)
        return h


def margin_loss(v: jax.Array, labels: jax.Array, n_classes: int,
                m_pos: float = 0.9, m_neg: float = 0.1,
                lam: float = 0.5) -> jax.Array:
    """CapsNet margin loss [Sabour et al. 2017, Eq.4]."""
    norms = jnp.linalg.norm(v, axis=-1)  # (B, H)
    t = jax.nn.one_hot(labels, n_classes, dtype=norms.dtype)
    l_pos = t * jnp.square(jnp.maximum(0.0, m_pos - norms))
    l_neg = lam * (1.0 - t) * jnp.square(jnp.maximum(0.0, norms - m_neg))
    return jnp.mean(jnp.sum(l_pos + l_neg, axis=-1))
