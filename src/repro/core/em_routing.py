"""EM routing [Hinton, Sabour, Frosst 2018] — paper §2.2: "the routing
algorithms (e.g., Dynamic Routing, Expectation-Maximization Routing) share the
similar execution pattern", and PIM-CapsNet's optimisations "can be easily
applied to other routing algorithms with simple adjustment".

We implement matrix-capsule EM routing over the same (B,L,H,C) vote layout so
that the distribution planner (core.distribution) and the sharded execution
path (psum placement) carry over: the E-step aggregates over H (softmax-like),
the M-step aggregates over L — the same Table-2 dimension structure.  A
convolutional layer (``ConvGeometry``) hands over its windows as rows of
that layout; its E-step normalises each child across every window that
holds it (``cross_window_softmax``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class ConvGeometry(NamedTuple):
    """Convolutional connectivity of an EM layer (Hinton et al. 2018 §3):
    a ``kernel`` x ``kernel`` window at ``stride`` over a ``height`` x
    ``width`` grid of child capsules, VALID.

    Votes then come as patch batches: (N·W, L, H, C) with the rows ordered
    (image, window row, window column) and L = kernel·kernel·child types
    ordered (kernel row, kernel column, child type).  A child sends votes
    to every parent whose window covers it, so its responsibilities are
    normalised over all of them, across windows; each window's M-step
    aggregates its own L children."""
    kernel: int
    stride: int
    height: int
    width: int

    @property
    def out_hw(self) -> Tuple[int, int]:
        k, s = self.kernel, self.stride
        return (self.height - k) // s + 1, (self.width - k) // s + 1

    @property
    def windows(self) -> int:
        oh, ow = self.out_hw
        return oh * ow


class EMRoutingConfig(NamedTuple):
    iterations: int = 3
    beta_a: float = 1.0          # activation bias
    beta_u: float = 1.0          # per-dim cost bias
    inv_temp: float = 1.0        # lambda schedule base
    sharded_dim: Optional[str] = None   # "B" | "L" | None
    axis_name: Optional[str] = None
    eps: float = 1e-9
    conv: Optional[ConvGeometry] = None  # None: fully connected
    logits: bool = False         # return λ(β_a − Σ cost), not its sigmoid


def patches(x: jax.Array, geom: ConvGeometry) -> jax.Array:
    """Child grid (N, height, width, ...) -> the windows' children
    (N, oh, ow, kernel, kernel, ...): one strided slice per kernel offset."""
    k, s = geom.kernel, geom.stride
    oh, ow = geom.out_hw
    rest = x.shape[3:]

    def offset(ky, kx):
        return lax.slice(x, (0, ky, kx) + (0,) * len(rest),
                         (x.shape[0], ky + s * (oh - 1) + 1,
                          kx + s * (ow - 1) + 1) + rest,
                         (1, s, s) + (1,) * len(rest))

    rows = [jnp.stack([offset(ky, kx) for kx in range(k)], axis=3)
            for ky in range(k)]
    return jnp.stack(rows, axis=3)


def fold(p: jax.Array, geom: ConvGeometry, combine: str) -> jax.Array:
    """The transpose of :func:`patches`: (N, oh, ow, kernel, kernel, ...)
    -> (N, height, width, ...), the entries of one child combined by
    "add" or "max" (a child no window covers reads 0 or -inf)."""
    k, s = geom.kernel, geom.stride
    oh, ow = geom.out_hw
    fill = 0.0 if combine == "add" else -jnp.inf
    out = None
    for ky in range(k):
        for kx in range(k):
            piece = p[:, :, :, ky, kx]
            pads = [(0, 0, 0),
                    (ky, geom.height - ky - s * (oh - 1) - 1, s - 1),
                    (kx, geom.width - kx - s * (ow - 1) - 1, s - 1)]
            pads += [(0, 0, 0)] * (piece.ndim - 3)
            piece = lax.pad(piece, jnp.asarray(fill, piece.dtype), pads)
            out = piece if out is None else (
                out + piece if combine == "add" else jnp.maximum(out, piece))
    return out


def _patch_grid(x: jax.Array, geom: ConvGeometry) -> jax.Array:
    """(N·W, L) in patch layout -> (N, oh, ow, kernel, kernel, child types)."""
    oh, ow = geom.out_hw
    k = geom.kernel
    return x.reshape(-1, oh, ow, k, k, x.shape[-1] // (k * k))


def _each_child(x: jax.Array, geom: ConvGeometry, combine: str) -> jax.Array:
    """Combine the (N·W, L) entries of each child over every window that
    holds it, and hand each entry its child's result."""
    grid = fold(_patch_grid(x, geom), geom, combine)
    return patches(grid, geom).reshape(x.shape)


def cross_window_softmax(logits: jax.Array, geom: ConvGeometry) -> jax.Array:
    """E-step normalisation of a convolutional layer: patch-layout logits
    (N·W, L, H) -> responsibilities, each child's softmax over every
    (window, parent type) pair it votes for, shifted by the child's own
    largest logit."""
    with jax.named_scope("routing.em_fold"):
        top = _each_child(jnp.max(logits, axis=-1), geom, "max")
        e = jnp.exp(logits - top[..., None])
        total = _each_child(jnp.sum(e, axis=-1), geom, "add")
        return e / total[..., None]


def initial_responsibilities(shape: Tuple[int, int, int],
                             geom: Optional[ConvGeometry]) -> jax.Array:
    """R before the first M-step, (B, L, H): 1 / the number of parents a
    child sends to — H fully connected; H x the windows that cover it
    under ``geom``."""
    B, L, H = shape
    if geom is None:
        return jnp.full(shape, 1.0 / H, jnp.float32)
    n = B // geom.windows
    count = _each_child(jnp.ones((geom.windows, L), jnp.float32), geom, "add")
    r = 1.0 / (H * count)
    return jnp.broadcast_to(jnp.tile(r, (n, 1))[..., None], shape)


def em_routing(votes: jax.Array, a_in: jax.Array,
               cfg: EMRoutingConfig = EMRoutingConfig(),
               beta_a: Optional[jax.Array] = None,
               beta_u: Optional[jax.Array] = None):
    """votes: (B,L,H,C) vote vectors; a_in: (B,L) L-capsule activations;
    ``beta_a``, ``beta_u``: scalars or one per parent type (H,), else the
    config's scalars.  With ``cfg.conv`` the rows are patch batches
    (:class:`ConvGeometry`).

    Returns (pose (B,H,C), a_out (B,H)) — with ``cfg.logits`` the
    activation logits λ(β_a − Σ cost) in place of a_out.
    """
    votes = votes.astype(jnp.float32)
    B, L, H, C = votes.shape
    b_a = jnp.asarray(cfg.beta_a if beta_a is None else beta_a, jnp.float32)
    b_u = jnp.asarray(cfg.beta_u if beta_u is None else beta_u,
                      jnp.float32)[..., None]
    r = initial_responsibilities((B, L, H), cfg.conv)

    def psum_l(x):
        if cfg.sharded_dim == "L":
            return lax.psum(x, cfg.axis_name)
        return x

    for it in range(cfg.iterations):
        lam = cfg.inv_temp * (1.0 - 0.95 ** (it + 1))
        # ---- M-step: per-H Gaussian stats, aggregation over L ----
        rw = r * a_in[..., None]                       # (B,L,H)
        r_sum = psum_l(jnp.sum(rw, axis=1)) + cfg.eps  # (B,H)
        mu = psum_l(jnp.einsum("blh,blhc->bhc", rw, votes)) / r_sum[..., None]
        diff2 = jnp.square(votes - mu[:, None])
        sigma2 = psum_l(jnp.einsum("blh,blhc->bhc", rw, diff2)) \
            / r_sum[..., None] + cfg.eps
        cost = (b_u + 0.5 * jnp.log(sigma2)) * r_sum[..., None]
        logit = lam * (b_a - jnp.sum(cost, axis=-1))
        a_out = jax.nn.sigmoid(logit)
        if it + 1 == cfg.iterations:
            break                   # the last E-step's R has no reader
        # ---- E-step: responsibilities, softmax over each child's parents
        log_p = -0.5 * jnp.sum(jnp.log(2.0 * jnp.pi * sigma2[:, None])
                               + diff2 / sigma2[:, None], axis=-1)  # (B,L,H)
        logits = jnp.log(a_out[:, None] + cfg.eps) + log_p
        r = (jax.nn.softmax(logits, axis=-1) if cfg.conv is None
             else cross_window_softmax(logits, cfg.conv))
    return mu, (logit if cfg.logits else a_out)

