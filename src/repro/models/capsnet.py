"""CapsNet (paper §2.1 / Fig.2): Conv → PrimaryCaps → DigitCaps(+RP) → decoder.

The paper's own model family, parameterised by the Table-1 benchmark configs
(``configs.caps_benchmarks``).  Encoding stage = conv stack + primary caps +
one Caps layer whose capsule-to-capsule mapping runs the routing procedure;
decoding stage = 3-FC reconstruction decoder.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.caps_benchmarks import CapsConfig
from repro.core import capsule_layers as CL
from repro.core import router as router_lib


def init_capsnet(key, cfg: CapsConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    pc_cfg = CL.PrimaryCapsConfig(
        conv1_channels=cfg.conv_channels, caps_channels=cfg.caps_channels,
        caps_dim=cfg.l_caps_dim)
    return {
        "primary": CL.init_primary_caps(k1, cfg.image_channels, pc_cfg),
        "digit": CL.init_caps_layer(k2, cfg.num_l_caps, cfg.num_h_caps,
                                    cfg.l_caps_dim, cfg.h_caps_dim),
        "decoder": CL.init_decoder(k3, cfg.num_h_caps, cfg.h_caps_dim,
                                   cfg.image_hw * cfg.image_hw
                                   * cfg.image_channels),
    }


def primary_caps(params, images: jax.Array, cfg: CapsConfig) -> jax.Array:
    """Conv stack + PrimaryCaps.  images: (B,H,W,C) -> u: (B, N_L, C_L).

    If the conv pipeline's spatial output doesn't match num_l_caps exactly
    (the Table-1 configs imply differing caps-map counts), the capsule grid
    is cropped/tiled to the configured N_L — the routing-procedure workload
    (the paper's subject) is always exactly (N_L, N_H, C_L, C_H).
    """
    pc_cfg = CL.PrimaryCapsConfig(
        conv1_channels=cfg.conv_channels, caps_channels=cfg.caps_channels,
        caps_dim=cfg.l_caps_dim)
    with jax.named_scope("capsnet.encode"):
        u = CL.primary_caps_forward(params["primary"], images, pc_cfg)
        n = u.shape[1]
        if n < cfg.num_l_caps:
            reps = -(-cfg.num_l_caps // n)
            u = jnp.tile(u, (1, reps, 1))
        return u[:, :cfg.num_l_caps]


def encode_votes(params, images: jax.Array, cfg: CapsConfig) -> jax.Array:
    """The §4 pipeline's host ("encoder") stage as one function: conv stack +
    PrimaryCaps + the Eq.1 vote projection — everything *before* the routing
    procedure.  images (B,H,W,C) -> u_hat (B, N_L, N_H, C_H).

    This is the ``stage_a`` the serving path hands to a pipelined
    ``ExecutionPlan`` (DESIGN.md §Serving): the routing stage then consumes
    the votes on its own device group, exactly the paper's GPU‖HMC split.
    """
    u = primary_caps(params, images, cfg)
    return CL.predict_votes(params["digit"], u)


def forward(params, images: jax.Array, cfg: CapsConfig, router=None,
            labels: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """Full inference: returns {v, class_probs, reconstruction}.

    ``router``: what ``caps_layer_forward`` takes — a built Router or any
    callable, a ``RouterSpec``, or a ``RoutingConfig`` (the jnp reference,
    which also runs inside a caller's own shard_map); default the
    unsharded jnp RouterSpec at ``cfg.routing_iters``.
    """
    if router is None:
        router = router_lib.RouterSpec(iterations=cfg.routing_iters)
    u = primary_caps(params, images, cfg)
    v = CL.caps_layer_forward(params["digit"], u, router)   # (B, H, C_H)
    probs = jnp.linalg.norm(v, axis=-1)
    recon = CL.decoder_forward(params["decoder"], v, labels)
    return {"v": v, "class_probs": probs, "reconstruction": recon}


def loss_fn(params, images: jax.Array, labels: jax.Array, cfg: CapsConfig,
            router=None, recon_weight: float = 0.0005):
    out = forward(params, images, cfg, router, labels)
    margin = CL.margin_loss(out["v"], labels, cfg.num_h_caps)
    flat = images.reshape(images.shape[0], -1)
    recon = jnp.mean(jnp.square(out["reconstruction"] - flat))
    loss = margin + recon_weight * recon
    acc = jnp.mean((jnp.argmax(out["class_probs"], -1) == labels)
                   .astype(jnp.float32))
    return loss, {"margin": margin, "recon": recon, "accuracy": acc}
