"""Public wrapper for the selective-scan kernel."""
from __future__ import annotations

import jax

from repro.kernels.ssm_scan.kernel import selective_scan


def scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
         C: jax.Array, D: jax.Array, *,
         interpret: bool | None = None) -> jax.Array:
    T = x.shape[1]
    chunk = 64
    while T % chunk:
        chunk //= 2
    return selective_scan(x, dt, A, B, C, D, chunk=chunk, interpret=interpret)
