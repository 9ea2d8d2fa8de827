"""Chunked selective scan (Mamba-1 recurrence) as a Pallas TPU kernel.

Not a paper contribution — the perf-critical layer of falcon-mamba-7b /
zamba2-7b (DESIGN.md §6).  The recurrence is sequential in T, so the grid
iterates (batch, T/chunk) with the chunk dimension innermost and the SSM
state (Din, N) carried in VMEM scratch between chunk steps; within a chunk a
``fori_loop`` steps the diagonal recurrence.  All chunk-local operands
(x, dt, B, C slabs) are VMEM-resident; HBM traffic is exactly one pass over
the inputs + one write of y — the same stream-once property as the routing
kernel, which is what the memory-bound SSM needs (arithmetic intensity
~2N FLOP per input element).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssm_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = A_ref[...].astype(jnp.float32)               # (Din, N)
    D = D_ref[...].astype(jnp.float32)               # (1, Din)

    def step(t, h):
        x_t = x_ref[0, t].astype(jnp.float32)        # (Din,)
        dt_t = dt_ref[0, t].astype(jnp.float32)      # (Din,)
        b_t = B_ref[0, t].astype(jnp.float32)        # (N,)
        c_t = C_ref[0, t].astype(jnp.float32)        # (N,)
        a = jnp.exp(dt_t[:, None] * A)               # (Din, N)
        h = a * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=-1) + D[0] * x_t
        y_ref[0, t] = y_t.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, D: jax.Array, *, chunk: int = 64,
                   interpret: bool | None = None) -> jax.Array:
    """x, dt: (Bt, T, Din); A: (Din, N); B, C: (Bt, T, N); D: (Din,).

    Returns y: (Bt, T, Din).  VMEM per step ≈ chunk·(2·Din + 2·N)·4B plus the
    (Din, N) state scratch.
    """
    Bt, T, Din = x.shape
    N = A.shape[1]
    if T % chunk:
        raise ValueError(f"T={T} not divisible by chunk={chunk}")
    grid = (Bt, T // chunk)
    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, Din), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, Din), lambda b, c: (b, c, 0)),
            pl.BlockSpec((Din, N), lambda b, c: (0, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Din), lambda b, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, Din), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, T, Din), x.dtype),
        scratch_shapes=[pltpu.VMEM((Din, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, A, B, C, D.reshape(1, Din))
