"""Paper §5.2.2 PE special-function unit as an elementwise Pallas kernel.

The PIM-CapsNet PE realises exp / inverse-sqrt / division with adders,
multipliers and bit-shifters (paper Fig.11/12).  This kernel is the TPU
transcription: the FP32<->int32 reinterpret (``lax.bitcast_convert_type``)
plays the shifter network, one fused multiply-add plays the MAC stage, and
the accuracy-recovery multiplier (§5.2.2) is folded into the same pass.

Elementwise and embarrassingly tiled: BlockSpec (block_rows, 128·k) slabs,
one grid step per slab — bandwidth-bound by construction, so the only tuning
knob is block volume (big enough to amortise DMA issue overhead).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.approx import (EXP_AVG, EXP_RECOVERY, INV_SQRT_RECOVERY,
                               LOG2E, RECIP_RECOVERY, _F32_BIAS, _F32_MANT)
from repro.kernels import resolve_interpret

_OPS = ("exp", "inv_sqrt", "reciprocal")


def _fastmath_kernel(x_ref, o_ref, *, op: str, recover: bool):
    x = x_ref[...].astype(jnp.float32)
    if op == "exp":
        y = LOG2E * x + (_F32_BIAS + EXP_AVG)
        y = jnp.clip(y, 0.0, 254.999)
        out = lax.bitcast_convert_type((y * _F32_MANT).astype(jnp.int32),
                                       jnp.float32)
        if recover:
            out = out * jnp.float32(EXP_RECOVERY)
    elif op == "inv_sqrt":
        i = jnp.int32(0x5F3759DF) - (lax.bitcast_convert_type(x, jnp.int32) >> 1)
        out = lax.bitcast_convert_type(i, jnp.float32)
        out = out * (1.5 - 0.5 * x * out * out)
        if recover:
            out = out * jnp.float32(INV_SQRT_RECOVERY)
    elif op == "reciprocal":
        i = jnp.int32(0x7EF311C2) - lax.bitcast_convert_type(x, jnp.int32)
        out = lax.bitcast_convert_type(i, jnp.float32)
        out = out * (2.0 - x * out)
        if recover:
            out = out * jnp.float32(RECIP_RECOVERY)
    else:
        raise ValueError(f"op must be one of {_OPS}, got {op}")
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("op", "recover", "block_rows",
                                             "block_cols", "interpret"))
def fastmath_2d(x: jax.Array, *, op: str, recover: bool = True,
                block_rows: int = 256, block_cols: int = 512,
                interpret: bool | None = None) -> jax.Array:
    """Apply a PE-approximated special function over a 2D array."""
    R, Ccols = x.shape
    br = min(block_rows, R)
    bc = min(block_cols, Ccols)
    if R % br or Ccols % bc:
        raise ValueError(f"shape {x.shape} not divisible by block ({br},{bc})")
    return pl.pallas_call(
        functools.partial(_fastmath_kernel, op=op, recover=recover),
        grid=(R // br, Ccols // bc),
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, Ccols), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x.astype(jnp.float32))
