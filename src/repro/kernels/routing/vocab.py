"""Routing-kernel vocabulary — the spec-level constants, importable light.

``core.router._validate`` needs only the legal ``stream_dtype`` vocabulary
to reject a bad ``RouterSpec`` at construction; importing it from
``ops.py`` dragged the whole Pallas kernel package (kernel.py →
``jax.experimental.pallas``) into every ``build_router`` call (ROADMAP
item 5 nit).  This module holds the vocabulary with no kernel imports —
``ops.py`` re-exports it, so kernel code and historical callers see the
same names.
"""
from __future__ import annotations

import jax.numpy as jnp

# û streaming dtypes on the pallas backend: accumulation is always fp32;
# bf16 halves the DMA bytes of the only O(B·L·H·C) operand, int8 quarters
# them (per-L-tile symmetric scale, dequantized in-kernel — the "deep edge"
# tier, DESIGN.md §Quantized-routing).  int8 is procedure-megakernel-only
# and inference-only; ops.resolve_fusion / router._validate enforce both.
STREAM_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


def stream_itemsize(stream_dtype: str) -> int:
    """Bytes per û element at ``stream_dtype`` (validates the name)."""
    if stream_dtype not in STREAM_DTYPES:
        raise ValueError(f"unknown stream_dtype {stream_dtype!r}; expected "
                         f"one of {sorted(STREAM_DTYPES)}")
    return jnp.dtype(STREAM_DTYPES[stream_dtype]).itemsize
