"""Shape-generic jitted wrappers for the fastmath kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fastmath.kernel import fastmath_2d


def _as_2d(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    # pad to a 2D tile multiple (rows of 512)
    cols = 512 if n >= 512 else n
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, cols), shape


def _apply(x: jax.Array, op: str, recover: bool,
           interpret: bool | None) -> jax.Array:
    x2d, shape = _as_2d(x)
    r, c = x2d.shape
    out = fastmath_2d(x2d, op=op, recover=recover,
                      block_rows=min(256, r), block_cols=c,
                      interpret=interpret)
    n = 1
    for d in shape:
        n *= d
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("recover", "interpret"))
def exp(x: jax.Array, recover: bool = True,
        interpret: bool | None = None):
    return _apply(x, "exp", recover, interpret)


@functools.partial(jax.jit, static_argnames=("recover", "interpret"))
def inv_sqrt(x: jax.Array, recover: bool = True,
             interpret: bool | None = None):
    return _apply(x, "inv_sqrt", recover, interpret)


@functools.partial(jax.jit, static_argnames=("recover", "interpret"))
def reciprocal(x: jax.Array, recover: bool = True,
               interpret: bool | None = None):
    return _apply(x, "reciprocal", recover, interpret)
