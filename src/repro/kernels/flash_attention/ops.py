"""Public wrappers: flash attention with automatic block-size selection,
inference (fwd-only) and training (custom_vjp over the Pallas fwd/bwd
kernel pair)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import (flash_attention,
                                                  flash_attention_bwd,
                                                  flash_attention_fwd_lse)


def _block(S: int) -> int:
    block = 128
    while S % block:
        block //= 2
    return block


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              interpret: bool | None = None) -> jax.Array:
    """Pick MXU-aligned blocks (<=128) that divide S, then call the kernel."""
    block = _block(q.shape[2])
    return flash_attention(q, k, v, causal=causal, block_q=block,
                           block_k=block, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def attention_train(q, k, v, causal: bool = True,
                    interpret: bool | None = None):
    """Differentiable flash attention (FlashAttention-2 fwd/bwd kernels).

    q: (B, Hq, S, D); k, v: (B, Hkv, S, D) with GQA Hq % Hkv == 0.
    """
    block = _block(q.shape[2])
    o, _ = flash_attention_fwd_lse(q, k, v, causal=causal, block_q=block,
                                   block_k=block, interpret=interpret)
    return o


def _attn_fwd(q, k, v, causal, interpret):
    block = _block(q.shape[2])
    o, lse = flash_attention_fwd_lse(q, k, v, causal=causal, block_q=block,
                                     block_k=block, interpret=interpret)
    return o, (q, k, v, o, lse)


def _attn_bwd(causal, interpret, res, do):
    q, k, v, o, lse = res
    block = _block(q.shape[2])
    dq, dk, dv = flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                     block_q=block, block_k=block,
                                     interpret=interpret)
    return dq, dk, dv


attention_train.defvjp(_attn_fwd, _attn_bwd)
