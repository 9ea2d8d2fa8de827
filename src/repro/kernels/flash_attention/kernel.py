"""Blocked causal flash attention (fwd) for the LM architectures.

Not a paper contribution — the perf-critical hot-spot of the assigned LM
archs (DESIGN.md §6).  Standard IO-aware schedule [FlashAttention,
arXiv:2205.14135] adapted to TPU: (block_q × d) and (block_k × d) VMEM tiles,
MXU matmuls, online-softmax running (m, l) kept in VMEM scratch across the
innermost k-block grid dimension; causal upper-triangle blocks are skipped
with ``pl.when`` (no DMA is saved for them by this simple index map, but all
compute is).  GQA folds the q-head → kv-head mapping into the k/v index_maps.

Grid: (B, Hq, S/block_q, S/block_k), k innermost (sequential accumulation).
VMEM per step ≈ (2·block_q·D + 2·block_k·D + block_q·block_k)·4B.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  num_kb: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip k-blocks that lie entirely above the diagonal (first k
    # column beyond this q-block's last row) — valid for any block_q/block_k
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "scale", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, scale: float | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D).  Returns (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    if S % block_q or S % block_k:
        raise ValueError(f"S={S} not divisible by blocks ({block_q},{block_k})")
    scale_v = float(scale) if scale is not None else float(1.0 / D ** 0.5)
    num_kb = S // block_k
    grid = (B, Hq, S // block_q, num_kb)
    kernel = functools.partial(_flash_kernel, scale=scale_v, block_q=block_q,
                               block_k=block_k, causal=causal, num_kb=num_kb)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


# ---------------------------------------------------------------------------
# Training path: forward-with-lse + backward kernels (FlashAttention-2
# schedule).  dq accumulates across k-blocks (k innermost, same-block
# revisits contiguous); dk/dv accumulate across q-blocks in a second kernel
# (q innermost) per *query* head — the GQA group-sum happens outside, which
# costs group x dk/dv memory but keeps every output block's revisits
# contiguous (a Pallas requirement).
# ---------------------------------------------------------------------------


def _flash_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                          m_ref, l_ref, *, scale, block_q, block_k, causal,
                          num_kb):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_kb - 1)
    def _fin():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(safe))[:, 0]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, scale, block_q, block_k,
                         causal, num_kb):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal \
        else (ki >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse)                             # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kb - 1)
    def _fin():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                          block_q, block_k, causal, num_qb):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: q-blocks entirely above the k-block's first row are masked out
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal \
        else (qi >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_qb - 1)
    def _fin():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "scale", "interpret"))
def flash_attention_fwd_lse(q, k, v, *, causal=True, block_q=128,
                            block_k=128, scale=None, interpret=None):
    """Forward returning (o, lse) for the training path.
    q: (B,Hq,S,D); k,v: (B,Hkv,S,D) -> o (B,Hq,S,D), lse (B,Hq,S) fp32."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale_v = float(scale) if scale is not None else float(1.0 / D ** 0.5)
    num_kb = S // block_k
    grid = (B, Hq, S // block_q, num_kb)
    kernel = functools.partial(_flash_fwd_lse_kernel, scale=scale_v,
                               block_q=block_q, block_k=block_k,
                               causal=causal, num_kb=num_kb)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "scale", "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, block_q=128,
                        block_k=128, scale=None, interpret=None):
    """Backward: returns (dq (B,Hq,S,D), dk, dv (B,Hkv,S,D))."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale_v = float(scale) if scale is not None else float(1.0 / D ** 0.5)
    num_qb, num_kb = S // block_q, S // block_k
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                                 # (B,Hq,S)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale_v,
                          block_q=block_q, block_k=block_k, causal=causal,
                          num_kb=num_kb),
        grid=(B, Hq, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)

    # per-Q-head dk/dv (grid: q innermost so each (h,kb) block's revisits
    # are contiguous), group-summed to KV heads afterwards
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale_v,
                          block_q=block_q, block_k=block_k, causal=causal,
                          num_qb=num_qb),
        grid=(B, Hq, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, ki, qi: (b, h, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, ki, qi: (b, h, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(B, Hkv, group, S, D).sum(2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, S, D).sum(2).astype(v.dtype)
    return dq, dk, dv
