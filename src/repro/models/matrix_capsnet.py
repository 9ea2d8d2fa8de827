"""Matrix capsules with EM routing [Hinton, Sabour, Frosst, ICLR 2018]: the
smallNORB network of §5 / Fig. 1, at any of its widths.

    conv1       K1 x K1 conv, stride s1, A channels, ReLU
    PrimaryCaps 1x1 conv: B capsule types, a 4x4 pose and a sigmoid
                activation each
    ConvCaps1   K x K windows, stride strides[0], C types, EM routing
    ConvCaps2   K x K windows, stride strides[1], D types, EM routing
    Class caps  E classes, transforms shared across positions, coordinate
                addition, EM routing

A vote is the child's 4x4 pose times a learned 4x4 matrix of its (kernel
offset, child type, parent type) — (child type, class) in the class layer
— a matmul at the configured matmul precision.
The convolutional layers hand the router patch batches (one row per image
and window, ``core.em_routing.ConvGeometry``); every routed layer runs
through ``core.router.build_router``'s "em" entry with its β_a, β_u per
parent type as arguments.  The class layer's router returns the activation
logits λ(β_a − Σ cost): a saturated sigmoid would hide their differences.

Scopes: each routed layer under ``emcaps.<layer>``, its votes under
``capsnet.encode`` and its route call under ``capsnet.routing`` (the
cross-window normalisation inside it under ``routing.em_fold``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import em_routing as em_lib
from repro.core import router as router_lib

POSE = 4                     # a pose is POSE x POSE
ROUTED = ("convcaps1", "convcaps2", "class")


@dataclasses.dataclass(frozen=True)
class MatrixCapsConfig:
    """Widths of the network; the defaults are the paper's smallNORB
    model (A=B=C=D=32, E=5, 3 EM iterations, 32x32 grey images)."""
    image_hw: int = 32
    image_channels: int = 1
    conv_kernel: int = 5
    conv_stride: int = 2
    A: int = 32
    B: int = 32
    C: int = 32
    D: int = 32
    E: int = 5
    caps_kernel: int = 3
    strides: Tuple[int, int] = (2, 1)
    iterations: int = 3
    inv_temp: float = 1.0    # λ_t = inv_temp · (1 − 0.95^(t+1))
    eps: float = 1e-9

    @property
    def primary_hw(self) -> int:
        return (self.image_hw - self.conv_kernel) // self.conv_stride + 1

    def geometry(self, layer: str) -> em_lib.ConvGeometry:
        """Connectivity of ``convcaps1`` or ``convcaps2``."""
        hw = self.primary_hw
        g1 = em_lib.ConvGeometry(self.caps_kernel, self.strides[0], hw, hw)
        if layer == "convcaps1":
            return g1
        oh, ow = g1.out_hw
        return em_lib.ConvGeometry(self.caps_kernel, self.strides[1], oh, ow)

    @property
    def class_grid(self) -> Tuple[int, int]:
        return self.geometry("convcaps2").out_hw

    def layer_shape(self, layer: str) -> Tuple[int, int, int]:
        """(windows per image, children per window L, parent types H)."""
        kk = self.caps_kernel ** 2
        if layer == "convcaps1":
            return self.geometry(layer).windows, kk * self.B, self.C
        if layer == "convcaps2":
            return self.geometry(layer).windows, kk * self.C, self.D
        gh, gw = self.class_grid
        return 1, gh * gw * self.D, self.E


def init_params(key, cfg: MatrixCapsConfig) -> Dict:
    """Normal weights scaled by 1/sqrt(fan-in), zero biases, β zero."""
    ks = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)

    k1, cin, kk = cfg.conv_kernel, cfg.image_channels, cfg.caps_kernel ** 2
    caps = {}
    for i, (layer, children, parents) in enumerate(
            (("convcaps1", kk * cfg.B, cfg.C), ("convcaps2", kk * cfg.C,
                                                 cfg.D),
             ("class", cfg.D, cfg.E))):
        caps[layer] = {
            "W": normal(ks[2 + i], (children, parents, POSE, POSE), POSE),
            "beta_a": jnp.zeros((parents,), jnp.float32),
            "beta_u": jnp.zeros((parents,), jnp.float32)}
    return {
        "conv1": {"w": normal(ks[0], (k1, k1, cin, cfg.A), k1 * k1 * cin),
                  "b": jnp.zeros((cfg.A,), jnp.float32)},
        "primary": {"w": normal(ks[1], (1, 1, cfg.A, cfg.B * (POSE ** 2 + 1)),
                                cfg.A),
                    "b": jnp.zeros((cfg.B * (POSE ** 2 + 1),), jnp.float32)},
        **caps}


def _conv(x, w, b, stride):
    y = lax.conv_general_dilated(x, w, (stride, stride), "VALID",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def primary_caps(params, images, cfg: MatrixCapsConfig):
    """images (N, hw, hw, cin) -> poses (N, g, g, B, 16), activations
    (N, g, g, B)."""
    h = jax.nn.relu(_conv(images, params["conv1"]["w"], params["conv1"]["b"],
                          cfg.conv_stride))
    y = _conv(h, params["primary"]["w"], params["primary"]["b"], 1)
    n, g = y.shape[0], y.shape[1]
    pose = y[..., :cfg.B * POSE ** 2].reshape(n, g, g, cfg.B, POSE ** 2)
    return pose, jax.nn.sigmoid(y[..., cfg.B * POSE ** 2:])


def transform(pose: jax.Array, W: jax.Array) -> jax.Array:
    """Votes: child poses (..., L, 16) times W (L, H, 4, 4) ->
    (..., L, H, 16), M_i W_ij for every parent type j.

    One matmul per child against W laid out block-diagonally,
    B[l, (p', q), (j, p, r)] = [p' = p] W[l, j, q, r], so that each vote's
    16 entries come out side by side on the lanes, the layout the EM
    kernels stream, at the configured matmul precision."""
    L, H = W.shape[:2]
    eye = jnp.eye(POSE, dtype=W.dtype)
    block = (eye[None, :, None, None, :, None]
             * jnp.transpose(W, (0, 2, 1, 3))[:, None, :, :, None, :])
    block = block.reshape(L, POSE ** 2, H * POSE ** 2)
    out = jnp.einsum("...lk,lkj->...lj", pose, block)
    return out.reshape(out.shape[:-1] + (H, POSE ** 2))


def _coordinates(gh: int, gw: int) -> jax.Array:
    """Coordinate addition: per class-layer child (position-major, then
    type), the centre of its position, (row + ½)/gh and (column + ½)/gw,
    on the first two entries of the vote's right-hand column."""
    rows = (jnp.arange(gh, dtype=jnp.float32) + 0.5) / gh
    cols = (jnp.arange(gw, dtype=jnp.float32) + 0.5) / gw
    add = jnp.zeros((gh, gw, POSE, POSE), jnp.float32)
    add = add.at[:, :, 0, POSE - 1].set(rows[:, None])
    add = add.at[:, :, 1, POSE - 1].set(cols[None, :])
    return add.reshape(gh * gw, 1, 1, POSE ** 2)


def layer_spec(spec: router_lib.RouterSpec, cfg: MatrixCapsConfig,
               layer: str) -> router_lib.RouterSpec:
    """The "em" spec of one routed layer: the model's λ schedule and ε,
    the layer's connectivity, and the class layer's logits."""
    opts = {"inv_temp": cfg.inv_temp, "eps": cfg.eps}
    if layer == "class":
        opts["logits"] = True
    else:
        opts["conv"] = cfg.geometry(layer)
    return spec._replace(algorithm="em",
                         iterations=cfg.iterations).with_options(**opts)


def layer_inputs(params, pose, act, cfg: MatrixCapsConfig, layer: str):
    """A routed layer's votes and child activations from its children's
    pose and activation grids: patch batches for a convolutional layer,
    (N, L, H, 16) with coordinate addition for the class layer."""
    W = params[layer]["W"]
    if layer == "class":
        n, gh, gw, d = act.shape
        W = jnp.broadcast_to(W[None], (gh * gw,) + W.shape).reshape(
            (gh * gw * d,) + W.shape[1:])
        votes = transform(pose.reshape(n, gh * gw * d, POSE ** 2), W)
        votes = (votes.reshape((n, gh * gw, d) + votes.shape[2:])
                 + _coordinates(gh, gw)).reshape(votes.shape)
        return votes, act.reshape(n, gh * gw * d)
    geom = cfg.geometry(layer)
    rows = act.shape[0] * geom.windows
    pp = em_lib.patches(pose, geom).reshape(rows, W.shape[0], POSE ** 2)
    ap = em_lib.patches(act, geom).reshape(rows, W.shape[0])
    return transform(pp, W), ap


def forward(params, images, mask, cfg: MatrixCapsConfig,
            spec: router_lib.RouterSpec):
    """One microbatch: images (N, hw, hw, cin), lane mask (N,) ->
    (class activation logits (N, E), class poses (N, E, 16)).  A lane
    with mask 0 has zero child activations in every layer."""
    n = images.shape[0]
    with jax.named_scope("capsnet.encode"):
        pose, act = primary_caps(params, images, cfg)
    lane = mask.astype(jnp.float32)
    for layer in ROUTED:
        with jax.named_scope(f"emcaps.{layer}"):
            with jax.named_scope("capsnet.encode"):
                act = act * lane.reshape((n,) + (1,) * (act.ndim - 1))
                votes, a_in = layer_inputs(params, pose, act, cfg, layer)
            router = router_lib.build_router(layer_spec(spec, cfg, layer))
            p = params[layer]
            with jax.named_scope("capsnet.routing"):
                pose, act = router(votes, a_in, p["beta_a"], p["beta_u"])
            if layer != "class":
                oh, ow = cfg.geometry(layer).out_hw
                pose = pose.reshape(n, oh, ow, -1, POSE ** 2)
                act = act.reshape(n, oh, ow, -1)
    return act, pose


def make_wave_fn(params, cfg: MatrixCapsConfig,
                 spec: router_lib.RouterSpec):
    """The serving wave: {"images": (n_micro, mb, hw, hw, cin), "mask":
    (n_micro, mb)} -> (logits (n_micro, mb, E), poses (n_micro, mb, E,
    16)), the microbatches one after another (``lax.map``).  The weights
    are an argument of the compiled program, not constants in it."""
    def wave(p, micro):
        return lax.map(lambda m: forward(p, m["images"], m["mask"], cfg,
                                         spec), micro)
    compiled = jax.jit(wave)
    return lambda micro: compiled(params, micro)
