"""Fused dynamic-routing kernels (per-iteration and whole-procedure) as
Pallas TPU kernels.

Paper hook (§5.2, DESIGN.md §2): the intra-vault PEs process the RP chain next
to the data so intermediates never cross the off-chip boundary.  Two TPU-native
forms live here:

* ``routing_iteration_fused`` — one ``pallas_call`` per routing iteration that
  streams the only large operand — the prediction vectors ``u_hat`` (B,L,H,C)
  — HBM→VMEM exactly once, and keeps every intermediate (b-update, softmax,
  weighted partial sums) VMEM-resident.  The (L,H) logits and (B,H,C) vote
  sums still surface to HBM between iterations and squash runs outside.
* ``routing_procedure_fused`` — ONE ``pallas_call`` for the *whole* procedure
  (DESIGN.md §Procedure-fused): grid = (iterations, num_L_tiles) with the
  logits ``b`` (L,H), the previous-iteration ``v`` and the vote-sum
  accumulator ``s`` held in VMEM *scratch* across all grid steps; squash
  (Eq.3) runs in-kernel at the last L-tile of each iteration.  Nothing but
  the final v ever crosses back to HBM — the paper's "intermediates never
  leave the vault" claim, whole-procedure.  û may be streamed in bf16
  (``stream_dtype``) with fp32 in-kernel accumulation — halving the DMA
  bytes of the memory-bound operand.

Lane packing (every kernel here): û streams as (B, L, H·C) so the trailing
dim fills the 128-lane vregs (C alone, 8..16, under-fills them), and it
STAYS packed inside the kernel — Mosaic cannot split a 160-lane row into
(10, 16).  Per-capsule reductions over C and the broadcast of a per-capsule
value over its C lanes are matmuls with a constant 0/1 segment matrix
(``_caps_sum`` / ``_caps_bcast``) on the MXU at fp32 contract precision;
every (B,H,C) vote-sized block is carried packed as (B, H·C).

The naive formulation (ref.py / the paper's GPU baseline) materialises
O(B·L·H·C) intermediates per iteration *twice* (c·û products and agreement
tensors) and re-reads û twice; both fused forms read û once per iteration and
write nothing bigger than (B,H,C)/(L,H).

Lazy-update schedule (proved equivalent in ref.py): when a tile of L rows is
resident for iteration t we first fold in iteration t-1's agreement update for
those rows (db = Σ_k û·v_prev), then softmax, then accumulate s.  This is what
collapses two û passes per iteration into one.

Arithmetic intensity of the fused op: 4 FLOP per 4-byte û element — firmly
memory-bound, matching the paper's characterisation; the kernels therefore
optimise DMA volume, not MXU utilisation.

VMEM: every ``pallas_call`` here passes ``VMEM_LIMIT_BYTES`` to Mosaic as
its scoped-VMEM limit; ops.py sizes the L tiles against the same number
with (sublane, 128-lane) tile padding counted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.approx import (EXP_AVG, EXP_RECOVERY, INV_SQRT_RECOVERY,
                               LOG2E, RECIP_RECOVERY, _F32_BIAS, _F32_MANT)
from repro.kernels import resolve_interpret

# Scoped-VMEM limit handed to Mosaic for every routing kernel — and the
# budget ops.py's tile model sizes against (one number, two readers).  A
# v5e TensorCore has 128 MiB of VMEM; Mosaic's default scope is 16 MiB.
VMEM_LIMIT_BYTES = 32 * 2 ** 20
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _fast_exp_inkernel(x):
    y = LOG2E * x + (_F32_BIAS + EXP_AVG)
    y = jnp.clip(y, 0.0, 254.999)
    bits = (y * _F32_MANT).astype(jnp.int32)
    return lax.bitcast_convert_type(bits, jnp.float32) * jnp.float32(EXP_RECOVERY)


def _fast_recip_inkernel(x):
    i = jnp.int32(0x7EF311C2) - lax.bitcast_convert_type(x, jnp.int32)
    y = lax.bitcast_convert_type(i, jnp.float32)
    y = y * (2.0 - x * y)
    return y * jnp.float32(RECIP_RECOVERY)


def _fast_rsqrt_inkernel(x):
    i = jnp.int32(0x5F3759DF) - (lax.bitcast_convert_type(x, jnp.int32) >> 1)
    y = lax.bitcast_convert_type(i, jnp.float32)
    y = y * (1.5 - 0.5 * x * y * y)
    return y * jnp.float32(INV_SQRT_RECOVERY)


# --- lane-packed capsule algebra -------------------------------------------


def _segment(h: int, c_dim: int, transpose: bool = False):
    """Constant 0/1 segment matrix: (H·C, H) with entry (x, k) = 1 iff lane
    x belongs to capsule k — or its (H, H·C) transpose."""
    shape = (h, h * c_dim) if transpose else (h * c_dim, h)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1 if transpose else 0)
    first = lax.broadcasted_iota(jnp.int32, shape, 0 if transpose else 1)
    first = first * c_dim
    return ((lane >= first) & (lane < first + c_dim)).astype(jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _caps_sum(x, h: int, c_dim: int):
    """(..., H·C) -> (..., H): the sum over each capsule's C lanes."""
    lead = x.shape[:-1]
    y = _dot(x.reshape(-1, h * c_dim), _segment(h, c_dim))
    return y.reshape(lead + (h,))


def _caps_bcast(y, c_dim: int):
    """(..., H) -> (..., H·C): each capsule's value on its C lanes."""
    lead, h = y.shape[:-1], y.shape[-1]
    x = _dot(y.reshape(-1, h), _segment(h, c_dim, transpose=True))
    return x.reshape(lead + (h * c_dim,))


def squash_packed(s, h: int, c_dim: int, use_approx: bool):
    """Eq.3 squash of packed (..., H·C) vote sums, per capsule, mirroring
    approx.exact_squash / approx.approx_squash so backend parity holds for
    both modes.  Plain jnp: the per-iteration path squashes with it on the
    host, so every fused form shares one squash."""
    n2 = _caps_sum(s * s, h, c_dim)
    if use_approx:
        n2 = n2 + 1e-9
        return s * _caps_bcast(n2 * _fast_rsqrt_inkernel(n2)
                               * _fast_recip_inkernel(1.0 + n2), c_dim)
    return (s * _caps_bcast(n2 / (1.0 + n2), c_dim)
            / _caps_bcast(jnp.sqrt(n2 + 1e-9), c_dim))


def _squash_vjp_inkernel(s, gv, h: int, c_dim: int):
    """Transpose of the *exact* squash at s, applied to the cotangent gv
    (both packed).  With v = s·g(n2), g = n2 / ((1+n2)·√(n2+ε)):
        ∂s = gv·g(n2) + 2·s·g'(n2)·Σ_C(gv·s),
        g'(n2) = q·r·(q − ½·n2·r²),  q = 1/(1+n2),  r = 1/√(n2+ε)
    — the closed form of jax.vjp of approx.exact_squash (finite at n2=0)."""
    n2 = _caps_sum(s * s, h, c_dim)
    q = 1.0 / (1.0 + n2)
    r = 1.0 / jnp.sqrt(n2 + 1e-9)
    g = n2 * q * r
    dg = q * r * (q - 0.5 * n2 * r * r)
    coef = 2.0 * dg * _caps_sum(gv * s, h, c_dim)
    return gv * _caps_bcast(g, c_dim) + s * _caps_bcast(coef, c_dim)


def _softmax_h_inkernel(b, use_approx: bool):
    """Eq.5 softmax over the trailing H dim (rows independent; H resident)."""
    m = jnp.max(b, axis=-1, keepdims=True)
    if use_approx:
        e = _fast_exp_inkernel(b - m)
        return e * _fast_recip_inkernel(jnp.sum(e, axis=-1, keepdims=True))
    e = jnp.exp(b - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _agreement(u, v, h: int, c_dim: int):
    """Deferred Eq.4 for one tile: db[l,h] = Σ_{k,c} û[k,l,h,c]·v[k,h,c]
    with û (B, L_t, H·C) and v (B, H·C) packed -> (L_t, H)."""
    return _caps_sum(jnp.sum(u * v[:, None, :], axis=0), h, c_dim)


def _votes(u, coup, c_dim: int):
    """Eq.2 partial sum for one tile: s[k,h,c] = Σ_l c[l,h]·û[k,l,h,c] with
    û (B, L_t, H·C) and c (L_t, H) -> packed (B, H·C)."""
    return jnp.sum(u * _caps_bcast(coup, c_dim)[None], axis=1)


def _as_stream(u_hat: jax.Array) -> jax.Array:
    """Kernels stream û in its incoming dtype (fp32 or bf16 — the caller
    hoists the stream-dtype cast out of the iteration loop); anything else
    is promoted to fp32.  All in-kernel accumulation is fp32."""
    if u_hat.dtype in (jnp.float32, jnp.bfloat16):
        return u_hat
    return u_hat.astype(jnp.float32)


def _pack(x: jax.Array) -> jax.Array:
    """(..., H, C) -> (..., H·C): the lane-packed layout every kernel uses."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _check_tile(L: int, l_tile: int):
    if L % l_tile != 0:
        raise ValueError(f"L={L} not divisible by l_tile={l_tile}")


def _rows(j, l_tile: int):
    """The L rows of grid tile j (tile-aligned dynamic sublane slice)."""
    return pl.ds(pl.multiple_of(j * l_tile, l_tile), l_tile)


def _routing_iter_kernel(u_ref, b_ref, v_ref, s_ref, b_out_ref, *,
                         h: int, c_dim: int, use_approx: bool):
    """One grid step = one L tile.

    u_ref: (B, L_t, H·C) û tile           (streamed, read once)
    b_ref: (L_t, H) routing logits tile   (read)
    v_ref: (B, H·C) previous v            (small, replicated across steps)
    s_ref: (B, H·C) output partial sums   (accumulated across grid steps)
    b_out_ref: (L_t, H) updated logits    (written once per tile)
    """
    u = u_ref[...].astype(jnp.float32)
    b_new = b_ref[...] + _agreement(u, v_ref[...], h, c_dim)
    b_out_ref[...] = b_new
    s_part = _votes(u, _softmax_h_inkernel(b_new, use_approx), c_dim)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s_ref[...] = s_part

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        s_ref[...] += s_part


@functools.partial(jax.jit,
                   static_argnames=("l_tile", "use_approx", "interpret"))
def routing_iteration_fused(u_hat: jax.Array, b: jax.Array, v_prev: jax.Array,
                            *, l_tile: int = 128, use_approx: bool = False,
                            interpret: bool | None = None):
    """One fused routing iteration.  Returns (s (B,H,C), b_new (L,H)).

    l_tile sizes the VMEM working set — the double-buffered (B, l_tile,
    H·C) û block plus its fp32 temporaries, with tile padding counted:
    ops.py::auto_l_tile picks it against ``VMEM_LIMIT_BYTES``.
    """
    B, L, H, C = u_hat.shape
    _check_tile(L, l_tile)
    kernel = functools.partial(_routing_iter_kernel, h=H, c_dim=C,
                               use_approx=use_approx)
    s, b_new = pl.pallas_call(
        kernel,
        grid=(L // l_tile,),
        in_specs=[
            pl.BlockSpec((B, l_tile, H * C), lambda i: (0, i, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H * C), jnp.float32),
            jax.ShapeDtypeStruct((L, H), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(_as_stream(u_hat)), b.astype(jnp.float32),
      _pack(v_prev.astype(jnp.float32)))
    return s.reshape(B, H, C), b_new


# ---------------------------------------------------------------------------
# Whole-procedure megakernel (DESIGN.md §Procedure-fused)
# ---------------------------------------------------------------------------


def _routing_procedure_kernel(*refs, h: int, c_dim: int, l_tile: int,
                              n_l_tiles: int, iterations: int,
                              use_approx: bool, quantized: bool,
                              early_exit_eps):
    """One grid step = one (iteration, L-tile) cell; grid is row-major so the
    L-tiles of iteration t all run before iteration t+1.

    Positional refs, in pallas order (inputs, outputs, scratch); optional
    refs appear only when the matching static flag is set:

    u_ref:     (B, L_t, H·C) lane-packed û tile (streamed, read once per
               iteration; fp32/bf16, or int8 codes when ``quantized``)
    scale_ref: (L/L_t,) SMEM per-L-tile dequant scales     [quantized only]
    v_out_ref: (B, H·C) final routed output (written at the last grid step)
    cnt_ref:   (1,) SMEM int32 effective-tile-iterations   [early-exit only]
    b_scr:     (L, H) routing logits       — VMEM-resident ALL iterations
    v_scr:     (B, H·C) previous v         — VMEM-resident ALL iterations
    s_scr:     (B, H·C) vote-sum accum     — VMEM-resident ALL iterations
    c_scr:     (L, H) frozen couplings     [early-exit only]
    conv_scr:  (L/L_t,) SMEM int32 converged flags  [early-exit only]

    Unlike the per-iteration kernel, b/v/s never cross back to HBM between
    iterations and squash (Eq.3) runs in-kernel at the last L-tile of each
    iteration — the only HBM write of the whole procedure is the final v
    (plus the 4-byte work counter under early exit).

    Early exit (DESIGN.md §Quantized-routing): a tile whose deferred-Eq.4
    logit update satisfied ‖Δb‖∞ < ε at some iteration t ≥ 1 skips the
    Eq.4/Eq.5 work (db, b update, softmax) for every iteration > t; its
    coupling coefficients stay frozen in c_scr and the Eq.2 vote-sum pass
    — which every tile of every iteration must contribute to — reads them
    from there.  With ε = 0 no tile ever converges (‖Δb‖∞ < 0 is never
    true), so the computation is bit-identical to the fixed-grid path.
    Iteration 0 is exempt from the check: v_prev = 0 there makes Δb ≡ 0,
    which would trivially "converge" every tile at any ε > 0.
    """
    refs = list(refs)
    u_ref = refs.pop(0)
    scale_ref = refs.pop(0) if quantized else None
    v_out_ref = refs.pop(0)
    cnt_ref = refs.pop(0) if early_exit_eps is not None else None
    b_scr, v_scr, s_scr = refs.pop(0), refs.pop(0), refs.pop(0)
    c_scr = refs.pop(0) if early_exit_eps is not None else None
    conv_scr = refs.pop(0) if early_exit_eps is not None else None

    it = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((it == 0) & (j == 0))
    def _reset():
        # iteration 0 of the lazy-update schedule starts from b = 0 and
        # v_prev = 0 (ref.py proves this equals Algorithm 1's eager form).
        b_scr[...] = jnp.zeros_like(b_scr)
        v_scr[...] = jnp.zeros_like(v_scr)
        if cnt_ref is not None:
            cnt_ref[0] = 0

    if conv_scr is not None:
        @pl.when(it == 0)
        def _clear_flag():
            conv_scr[j] = 0

    u = u_ref[...].astype(jnp.float32)           # fp32 accumulation
    if quantized:
        u = u * scale_ref[j]                     # symmetric per-tile dequant
    rows = _rows(j, l_tile)

    def _eq4_eq5():
        """Deferred Eq.4 logit update + Eq.5 softmax for this tile."""
        db = _agreement(u, v_scr[...], h, c_dim)             # (L_t, H)
        b_new = b_scr[rows, :] + db
        b_scr[rows, :] = b_new
        return db, _softmax_h_inkernel(b_new, use_approx)    # (L_t, H)

    if early_exit_eps is None:
        _, coup = _eq4_eq5()
    else:
        @pl.when(conv_scr[j] == 0)
        def _work():
            db, coup_new = _eq4_eq5()
            c_scr[rows, :] = coup_new
            # ε = 0 stays fixed-grid: ‖db‖∞ ≥ 0 is never < 0.  Iteration 0
            # (db ≡ 0, see docstring) never sets the flag.
            delta = jnp.max(jnp.abs(db))
            frozen = (delta < early_exit_eps) & (it > 0)
            conv_scr[j] = frozen.astype(jnp.int32)
            cnt_ref[0] += 1

        # converged tiles reuse the coupling frozen at their last worked
        # iteration; f32 scratch round-trips are exact, so the ε = 0 path
        # computes with bit-identical coup values.
        coup = c_scr[rows, :]

    # --- Eq.2 partial weighted sum, accumulated in scratch (ALWAYS runs:
    # frozen tiles still contribute their Eq.2 term, keeping the s
    # accumulation structure — and hence ε = 0 bit-identity — intact)
    s_part = _votes(u, coup, c_dim)                          # (B, H·C)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = s_part

    @pl.when(j != 0)
    def _acc():
        s_scr[...] += s_part

    # --- Eq.3 squash in-kernel at the last L-tile of the iteration
    @pl.when(j == n_l_tiles - 1)
    def _finish_iteration():
        v = squash_packed(s_scr[...], h, c_dim, use_approx)
        v_scr[...] = v

        @pl.when(it == iterations - 1)
        def _emit():
            v_out_ref[...] = v


@functools.partial(jax.jit, static_argnames=("iterations", "l_tile",
                                             "use_approx", "interpret",
                                             "early_exit_eps"))
def routing_procedure_fused(u_hat: jax.Array, scales: jax.Array | None = None,
                            *, iterations: int = 3,
                            l_tile: int = 128, use_approx: bool = False,
                            interpret: bool | None = None,
                            early_exit_eps: float | None = None):
    """Whole routing procedure in ONE pallas_call.

    Returns v (B, H, C), or ``(v, effective_tile_iterations)`` — the int32
    count of (iteration, L-tile) grid cells that did Eq.4/Eq.5 work — when
    ``early_exit_eps`` is set (fixed grid ≡ iterations · L/l_tile).

    u_hat: (B, L, H, C) in fp32 or bf16 — the *input dtype* is the stream
    dtype (ops.py::dynamic_routing_procedure_fused picks it) — or int8
    codes with ``scales`` (L/l_tile, 1) fp32 per-tile symmetric scales from
    ops.py::quantize_u_stream (DESIGN.md §Quantized-routing); all in-kernel
    arithmetic and the b/v/s scratch are fp32.  The scales, the converged
    flags and the work counter live in SMEM.  VMEM working set:
    ops.py::procedure_vmem_bytes.
    """
    B, L, H, C = u_hat.shape
    _check_tile(L, l_tile)
    n_l_tiles = L // l_tile
    quantized = scales is not None
    if quantized:
        if u_hat.dtype != jnp.int8:
            raise ValueError(f"per-tile scales given but û dtype is "
                             f"{u_hat.dtype} — expected int8 codes from "
                             f"quantize_u_stream")
        if scales.shape != (n_l_tiles, 1):
            raise ValueError(f"scales shape {scales.shape} != "
                             f"(L/l_tile, 1) = ({n_l_tiles}, 1)")
    elif u_hat.dtype == jnp.int8:
        raise ValueError("int8 û stream needs per-tile scales "
                         "(ops.quantize_u_stream)")
    elif u_hat.dtype not in (jnp.float32, jnp.bfloat16):
        u_hat = u_hat.astype(jnp.float32)
    early_exit = early_exit_eps is not None
    if early_exit and not (float(early_exit_eps) >= 0.0):
        raise ValueError(f"early_exit_eps must be >= 0, got {early_exit_eps}")

    kernel = functools.partial(
        _routing_procedure_kernel, h=H, c_dim=C, l_tile=l_tile,
        n_l_tiles=n_l_tiles, iterations=iterations, use_approx=use_approx,
        quantized=quantized,
        early_exit_eps=float(early_exit_eps) if early_exit else None)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [pl.BlockSpec((B, l_tile, H * C), lambda it, j: (0, j, 0))]
    inputs = [_pack(u_hat)]
    if quantized:
        in_specs.append(smem)
        inputs.append(scales.astype(jnp.float32).reshape(n_l_tiles))
    out_specs = pl.BlockSpec((B, H * C), lambda it, j: (0, 0))
    out_shape = jax.ShapeDtypeStruct((B, H * C), jnp.float32)
    scratch = [
        pltpu.VMEM((L, H), jnp.float32),      # b   — all iterations
        pltpu.VMEM((B, H * C), jnp.float32),  # v   — all iterations
        pltpu.VMEM((B, H * C), jnp.float32),  # s   — per-iteration accum
    ]
    if early_exit:
        out_specs = [out_specs, smem]
        out_shape = [out_shape, jax.ShapeDtypeStruct((1,), jnp.int32)]
        scratch += [
            pltpu.VMEM((L, H), jnp.float32),         # frozen couplings
            pltpu.SMEM((n_l_tiles,), jnp.int32),     # converged flags
        ]
    out = pl.pallas_call(
        kernel,
        grid=(iterations, n_l_tiles),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(*inputs)
    if early_exit:
        v, cnt = out
        return v.reshape(B, H, C), cnt[0]
    return out.reshape(B, H, C)


# ---------------------------------------------------------------------------
# Whole-procedure backward megakernel (DESIGN.md §Training)
# ---------------------------------------------------------------------------
# Recompute-b backward: the forward saves ONLY û (and the incoming cotangent
# ∂v) — none of the per-iteration intermediates (b, c, s, v) spill to HBM as
# autodiff residuals.  The backward is one pallas_call whose grid prepends a
# replay phase to a reverse phase:
#
#     grid = (2·iterations, n_l_tiles)
#       rows 0..T-1   REPLAY  — re-run the forward schedule from VMEM,
#                               snapshotting the *small* per-iteration state
#                               (c_t (L,H), s_t and v_{t-1} (B,H·C)) into
#                               VMEM scratch,
#       rows T..2T-1  REVERSE — walk iterations T-1..0 carrying the cotangent
#                               ∂v (B,H·C) and the accumulated logit
#                               cotangent ∂b (L,H), both fp32.
#
# Per reverse iteration t (derived from the lazy-update schedule; verified
# against jnp autodiff in tests/_gradcheck.py users):
#
#     gs   = squash_vjp(s_t, gv)                                  (B,H,C)
#     gc   = Σ_{k,c} û·gs                                         (L,H)
#     gb  += c_t ⊙ (gc − Σ_H c_t·gc)      softmax_H vjp, Eq.5     (L,H)
#     ∂û  += c_t ⊗ gs  +  gb ⊗ v_{t-1}    Eq.2 + deferred Eq.4 terms
#     gv   = Σ_l gb·û                     carry to iteration t-1  (B,H,C)
#
# At t = 0 the v_{-1} = 0 start kills the Eq.4 term and b₀ = 0 receives no
# input gradient, so ∂û is complete after the t = 0 reverse row.  ∂û is
# accumulated in fp32 from the per-iteration snapshots and written to HBM
# exactly once per L-tile (at the final grid row), at the û stream dtype —
# the backward's DMA bill is 2T û-streams in + 1 û-sized ∂û out (see
# ops.py::dma_bytes_per_call(backward=True)).


def _routing_procedure_bwd_kernel(u_ref, g_ref, du_ref,
                                  b_scr, v_scr, s_scr,
                                  c_all, s_all, vp_all, gs_all, gb_all, *,
                                  h: int, c_dim: int, l_tile: int,
                                  n_l_tiles: int, iterations: int,
                                  use_approx: bool):
    """One grid step = one (phase-row, L-tile) cell.

    u_ref:  (B, L_t, H·C) lane-packed û tile (streamed once per grid row)
    g_ref:  (B, H·C) incoming cotangent ∂v (same block every step)
    du_ref: (B, L_t, H·C) ∂û tile, written once at the final grid row

    Scratch (all fp32, VMEM-resident across the whole grid):
    b_scr:  (L, H)      replay: logits b      | reverse: accumulated ∂b
    v_scr:  (B, H·C)    replay: previous v    | reverse: carried ∂v
    s_scr:  (B, H·C)    replay: vote-sum s    | reverse: next ∂v accumulator
    c_all:  (T, L, H)   per-iteration coupling coefficients c_t
    s_all:  (T, B, H·C) per-iteration pre-squash vote sums s_t
    vp_all: (T, B, H·C) per-iteration previous v (v_{t-1})
    gs_all: (T, B, H·C) per-iteration ∂s (squash vjp of the carried ∂v)
    gb_all: (T, L, H)   snapshot of accumulated ∂b after folding row t

    The phase row index is compared against *Python* constants only
    (static unroll via ``pl.when(row == k)``) so every scratch slot index
    is a static int — no dynamically-indexed VMEM addressing.
    """
    row = pl.program_id(0)
    j = pl.program_id(1)
    u = u_ref[...].astype(jnp.float32)           # fp32 accumulation
    rows = _rows(j, l_tile)

    def _replay(t: int):
        """Forward iteration t, mirroring _routing_procedure_kernel but
        snapshotting (v_{t-1}, c_t, s_t) into the per-iteration scratch."""
        if t == 0:
            @pl.when(j == 0)
            def _reset():
                b_scr[...] = jnp.zeros_like(b_scr)
                v_scr[...] = jnp.zeros_like(v_scr)

        @pl.when(j == 0)
        def _snap_vprev():
            vp_all[t] = v_scr[...]

        b_new = b_scr[rows, :] + _agreement(u, v_scr[...], h, c_dim)
        b_scr[rows, :] = b_new
        coup = _softmax_h_inkernel(b_new, use_approx)        # (L_t, H)
        c_all[t, rows, :] = coup
        s_part = _votes(u, coup, c_dim)

        @pl.when(j == 0)
        def _init():
            s_scr[...] = s_part

        @pl.when(j != 0)
        def _acc():
            s_scr[...] += s_part

        @pl.when(j == n_l_tiles - 1)
        def _finish_iteration():
            s_all[t] = s_scr[...]
            v_scr[...] = squash_packed(s_scr[...], h, c_dim, use_approx)

    def _reverse(t: int):
        """Backward through forward iteration t (t runs T-1 .. 0)."""
        @pl.when(j == 0)
        def _start_iteration():
            if t == iterations - 1:
                # seed the reverse sweep: ∂v := incoming cotangent, ∂b := 0
                v_scr[...] = g_ref[...].astype(jnp.float32)
                b_scr[...] = jnp.zeros_like(b_scr)
            # Eq.3 transpose of the *exact* squash at the replayed s_t
            # (use_approx mode gets the exact-surrogate gradient; the
            # Router refuses differentiable+approx anyway).
            gs_all[t] = _squash_vjp_inkernel(s_all[t], v_scr[...], h, c_dim)
            s_scr[...] = jnp.zeros_like(s_scr)   # next ∂v accumulator

        gs = gs_all[t]                                       # (B, H·C)
        # Eq.2 transpose into the logits: gc[l,h] = Σ_{k,c} û·gs
        gc = _agreement(u, gs, h, c_dim)                     # (L_t, H)
        coup = c_all[t, rows, :]
        # Eq.5 softmax_H vjp, folded into the running ∂b
        gb = b_scr[rows, :] + coup * (
            gc - jnp.sum(coup * gc, axis=-1, keepdims=True))
        b_scr[rows, :] = gb
        if t > 0:
            gb_all[t, rows, :] = gb
        # deferred-Eq.4 transpose: carry ∂v_{t-1}[k,h,c] += Σ_l gb·û
        s_scr[...] += _votes(u, gb, c_dim)

        @pl.when(j == n_l_tiles - 1)
        def _finish_iteration():
            v_scr[...] = s_scr[...]              # becomes ∂v for row t-1

        if t == 0:
            # ∂û for this L-tile, summed over all iterations from the
            # snapshots:  ∂û = Σ_t [ c_t ⊗ gs_t + gb_t ⊗ v_{t-1} ]
            # (the t = 0 Eq.4 term vanishes: v_{-1} = 0).
            acc = _caps_bcast(coup, c_dim)[None] * gs[:, None]
            for tp in range(1, iterations):
                acc += (_caps_bcast(c_all[tp, rows, :], c_dim)[None]
                        * gs_all[tp][:, None])
                acc += (_caps_bcast(gb_all[tp, rows, :], c_dim)[None]
                        * vp_all[tp][:, None])
            du_ref[...] = acc.astype(du_ref.dtype)

    for t in range(iterations):                  # replay rows 0..T-1
        pl.when(row == t)(functools.partial(_replay, t))
    for t in range(iterations - 1, -1, -1):      # reverse rows T..2T-1
        pl.when(row == 2 * iterations - 1 - t)(functools.partial(_reverse, t))


@functools.partial(jax.jit, static_argnames=("iterations", "l_tile",
                                             "use_approx", "interpret"))
def routing_procedure_bwd(u_hat: jax.Array, g: jax.Array, *,
                          iterations: int = 3, l_tile: int = 128,
                          use_approx: bool = False,
                          interpret: bool | None = None) -> jax.Array:
    """Backward of :func:`routing_procedure_fused`: (û (B,L,H,C), ∂v (B,H,C))
    -> ∂û (B,L,H,C) at û's (stream) dtype.

    ONE pallas_call, grid (2·iterations, L/l_tile): replay rows reconstruct
    the per-iteration b/c/s/v from VMEM, reverse rows accumulate ∂û in fp32
    (see the module-level derivation above _routing_procedure_bwd_kernel).
    VMEM working set beyond the forward's: the per-iteration snapshots —
    ops.py::procedure_bwd_vmem_bytes, which procedure_train_l_tile sizes
    the tile against.
    """
    B, L, H, C = u_hat.shape
    _check_tile(L, l_tile)
    if u_hat.dtype not in (jnp.float32, jnp.bfloat16):
        u_hat = u_hat.astype(jnp.float32)
    T = iterations
    HC = H * C
    kernel = functools.partial(
        _routing_procedure_bwd_kernel, h=H, c_dim=C, l_tile=l_tile,
        n_l_tiles=L // l_tile, iterations=T, use_approx=use_approx)
    du = pl.pallas_call(
        kernel,
        grid=(2 * T, L // l_tile),
        in_specs=[
            pl.BlockSpec((B, l_tile, HC), lambda it, j: (0, j, 0)),
            pl.BlockSpec((B, HC), lambda it, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, l_tile, HC), lambda it, j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L, HC), u_hat.dtype),
        scratch_shapes=[
            pltpu.VMEM((L, H), jnp.float32),         # b     | ∂b
            pltpu.VMEM((B, HC), jnp.float32),        # v     | ∂v carry
            pltpu.VMEM((B, HC), jnp.float32),        # s     | ∂v accum
            pltpu.VMEM((T, L, H), jnp.float32),      # c_t snapshots
            pltpu.VMEM((T, B, HC), jnp.float32),     # s_t snapshots
            pltpu.VMEM((T, B, HC), jnp.float32),     # v_{t-1} snapshots
            pltpu.VMEM((T, B, HC), jnp.float32),     # ∂s_t snapshots
            pltpu.VMEM((T, L, H), jnp.float32),      # ∂b snapshots
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(u_hat), _pack(g.astype(jnp.float32)))
    return du.reshape(B, L, H, C)


# ---------------------------------------------------------------------------
# Stage-split kernels — sharded-fused routing (DESIGN.md §Sharded-fused)
# ---------------------------------------------------------------------------
# The single-pass lazy-update kernel above assumes every Table-2 aggregation
# is shard-local.  Under an inter-vault distribution (a sharded
# ExecutionPlan) the iteration must surface at the aggregation points so the
# host can insert the cross-shard ``lax.psum``:
#
#     c  = softmax(b)         host (O(L·H), psum-aware when H is sharded)
#     s  = Σ_l c·û            STAGE 1 (pallas)   -> psum over L's axis
#     v  = squash(s)          STAGE 2 (pallas)
#     db = Σ_k û·v            STAGE 2 (pallas)   -> psum over B's axis
#
# Each stage streams the only large operand (û) HBM→VMEM exactly once and
# keeps its O(B·L·H·C) intermediates (c·û products, agreement terms)
# VMEM-resident — the in-vault PE chain, split exactly where the paper's
# inter-vault aggregations happen.  Cost vs the fused kernel: û crosses the
# memory boundary twice per iteration instead of once; that is the price of
# distribution, not an implementation artifact (the paper's vaults pay the
# crossbar traffic M at the same points).


def _stage_votes_kernel(u_ref, c_ref, s_ref, *, c_dim: int):
    """STAGE 1, one grid step = one L tile: s_partial[k,h,c] += Σ_l c·û.

    u_ref: (B, L_t, H·C) û tile (streamed, read once)
    c_ref: (L_t, H) coupling coefficients (Eq.5, computed on the host)
    s_ref: (B, H·C) partial vote-sums, accumulated across grid steps
    """
    s_part = _votes(u_ref[...].astype(jnp.float32), c_ref[...], c_dim)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s_ref[...] = s_part

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        s_ref[...] += s_part


def _stage_update_fold_kernel(u_ref, s_ref, b_ref, v_ref, b_out_ref, c_ref, *,
                              h: int, c_dim: int, use_approx: bool):
    """STAGE 2 with the next iteration's softmax folded in (the
    iteration-resident treatment extended to the stage-split path): legal
    only when neither B nor H is sharded — a B-shard would need the db psum
    *before* the b-update and an H-shard a cross-shard softmax denominator,
    both of which must happen on the host between stages.

    u_ref:     (B, L_t, H·C) û tile (streamed, read once)
    s_ref:     (B, H·C) complete vote-sums (post cross-shard psum)
    b_ref:     (L_t, H) current logits tile
    v_ref:     (B, H·C) squashed output (written at step 0)
    b_out_ref: (L_t, H) updated logits
    c_ref:     (L_t, H) NEXT iteration's coupling coefficients (Eq.5) —
               replaces the host-side ``_softmax_h`` launch between
               iterations (O(L·H), folded into the same û pass).
    """
    u = u_ref[...].astype(jnp.float32)
    v = squash_packed(s_ref[...], h, c_dim, use_approx)

    @pl.when(pl.program_id(0) == 0)
    def _write_v():
        v_ref[...] = v

    b_new = b_ref[...] + _agreement(u, v, h, c_dim)
    b_out_ref[...] = b_new
    c_ref[...] = _softmax_h_inkernel(b_new, use_approx)


def _stage_update_kernel(u_ref, s_ref, v_ref, db_ref, *, h: int, c_dim: int,
                         use_approx: bool):
    """STAGE 2, one grid step = one L tile: squash + logit update.

    u_ref:  (B, L_t, H·C) û tile (streamed, read once)
    s_ref:  (B, H·C) complete vote-sums (post cross-shard psum)
    v_ref:  (B, H·C) squashed output (written at step 0; same block
            every step)
    db_ref: (L_t, H) partial logit updates db[l,h] = Σ_{k,c} û·v
    """
    # O(B·H·C) squash recomputed per tile to stay VMEM-resident
    v = squash_packed(s_ref[...], h, c_dim, use_approx)

    @pl.when(pl.program_id(0) == 0)
    def _write_v():
        v_ref[...] = v

    db_ref[...] = _agreement(u_ref[...].astype(jnp.float32), v, h, c_dim)


@functools.partial(jax.jit, static_argnames=("l_tile", "interpret"))
def routing_stage_votes(u_hat: jax.Array, c: jax.Array, *, l_tile: int = 128,
                        interpret: bool | None = None):
    """STAGE 1 wrapper: (û (B,L,H,C), c (L,H)) -> s_partial (B,H,C)."""
    B, L, H, C = u_hat.shape
    _check_tile(L, l_tile)
    s = pl.pallas_call(
        functools.partial(_stage_votes_kernel, c_dim=C),
        grid=(L // l_tile,),
        in_specs=[
            pl.BlockSpec((B, l_tile, H * C), lambda i: (0, i, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((B, H * C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H * C), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(_as_stream(u_hat)), c.astype(jnp.float32))
    return s.reshape(B, H, C)


@functools.partial(jax.jit,
                   static_argnames=("l_tile", "use_approx", "interpret"))
def routing_stage_update(u_hat: jax.Array, s: jax.Array, *, l_tile: int = 128,
                         use_approx: bool = False,
                         interpret: bool | None = None):
    """STAGE 2 wrapper: (û (B,L,H,C), s (B,H,C)) -> (v (B,H,C), db (L,H))."""
    B, L, H, C = u_hat.shape
    _check_tile(L, l_tile)
    kernel = functools.partial(_stage_update_kernel, h=H, c_dim=C,
                               use_approx=use_approx)
    v, db = pl.pallas_call(
        kernel,
        grid=(L // l_tile,),
        in_specs=[
            pl.BlockSpec((B, l_tile, H * C), lambda i: (0, i, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H * C), jnp.float32),
            jax.ShapeDtypeStruct((L, H), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(_as_stream(u_hat)), _pack(s.astype(jnp.float32)))
    return v.reshape(B, H, C), db


@functools.partial(jax.jit,
                   static_argnames=("l_tile", "use_approx", "interpret"))
def routing_stage_update_fold(u_hat: jax.Array, s: jax.Array, b: jax.Array,
                              *, l_tile: int = 128, use_approx: bool = False,
                              interpret: bool | None = None):
    """STAGE 2 + folded Eq.5 wrapper: (û (B,L,H,C), s (B,H,C), b (L,H)) ->
    (v (B,H,C), b_new (L,H), c_next (L,H)).  Only legal when B and H are
    unsharded (see _stage_update_fold_kernel)."""
    B, L, H, C = u_hat.shape
    _check_tile(L, l_tile)
    kernel = functools.partial(_stage_update_fold_kernel, h=H, c_dim=C,
                               use_approx=use_approx)
    v, b_new, c_next = pl.pallas_call(
        kernel,
        grid=(L // l_tile,),
        in_specs=[
            pl.BlockSpec((B, l_tile, H * C), lambda i: (0, i, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
            pl.BlockSpec((l_tile, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H * C), jnp.float32),
            jax.ShapeDtypeStruct((L, H), jnp.float32),
            jax.ShapeDtypeStruct((L, H), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(_as_stream(u_hat)), _pack(s.astype(jnp.float32)),
      b.astype(jnp.float32))
    return v.reshape(B, H, C), b_new, c_next


# --- EM routing stage kernels (same Table-2 structure: the M-step
# --- aggregates over L, the E-step's softmax is over H) ---------------------


def _em_stats_kernel(v_ref, rw_ref, rsum_ref, rv_ref, rv2_ref, *, c_dim: int):
    """EM M-step sufficient statistics, one grid step = one L tile.

    v_ref:  (B, L_t, H·C) votes tile (streamed, read once)
    rw_ref: (B, L_t, H) activation-weighted responsibilities r·a
    rsum_ref: (B, H)    Σ_l r·a                  (accumulated)
    rv_ref:   (B, H·C)  Σ_l r·a·votes            (accumulated)
    rv2_ref:  (B, H·C)  Σ_l r·a·votes²           (accumulated)

    The naive M-step materialises diff² = (votes-μ)² — a second full-size
    tensor — because it needs μ first.  Streaming the *sufficient
    statistics* (Σrw, Σrw·v, Σrw·v²) instead lets one û-sized pass serve
    both μ and σ² (σ² = E[v²] - μ² form, recombined on the host after the
    cross-shard psum).
    """
    v = v_ref[...].astype(jnp.float32)
    rw = rw_ref[...]
    rw_c = _caps_bcast(rw, c_dim)                            # (B, L_t, H·C)
    rsum_p = jnp.sum(rw, axis=1)                             # (B, H)
    rv_p = jnp.sum(rw_c * v, axis=1)                         # (B, H·C)
    rv2_p = jnp.sum(rw_c * (v * v), axis=1)                  # (B, H·C)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        rsum_ref[...] = rsum_p
        rv_ref[...] = rv_p
        rv2_ref[...] = rv2_p

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        rsum_ref[...] += rsum_p
        rv_ref[...] += rv_p
        rv2_ref[...] += rv2_p


def _em_estep_kernel(v_ref, mu_ref, isig_ref, bias_ref, r_ref, *, h: int,
                     c_dim: int):
    """EM E-step, one grid step = one L tile: responsibilities.

    v_ref:    (B, L_t, H·C) votes tile (streamed, read once)
    mu_ref:   (B, H·C) component means
    isig_ref: (B, H·C) 1/σ² (host precomputes the reciprocal so the
              kernel is MAC-only, like the paper's PE datapath)
    bias_ref: (B, H) log a_out - ½ Σ_c log(2πσ²) (host-precomputed)
    r_ref:    (B, L_t, H) output responsibilities (softmax over H; H is
              fully resident — EM never shards H)
    """
    d = v_ref[...].astype(jnp.float32) - mu_ref[...][:, None]
    dist = _caps_sum(d * d * isig_ref[...][:, None], h, c_dim)  # (B, L_t, H)
    logits = bias_ref[...][:, None] - 0.5 * dist
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    r_ref[...] = e / jnp.sum(e, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("l_tile", "interpret"))
def em_stage_stats(votes: jax.Array, r: jax.Array, a_in: jax.Array, *,
                   l_tile: int = 128, interpret: bool | None = None):
    """EM M-step stats: -> (Σrw (B,H), Σrw·v (B,H,C), Σrw·v² (B,H,C)).

    The activation weighting rw = r·a (B, L, H) is formed before the call:
    an (B, L_t) activation block would put L_t on the lanes."""
    B, L, H, C = votes.shape
    _check_tile(L, l_tile)
    rw = r.astype(jnp.float32) * a_in.astype(jnp.float32)[..., None]
    rsum, rv, rv2 = pl.pallas_call(
        functools.partial(_em_stats_kernel, c_dim=C),
        grid=(L // l_tile,),
        in_specs=[
            pl.BlockSpec((B, l_tile, H * C), lambda i: (0, i, 0)),
            pl.BlockSpec((B, l_tile, H), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, H), lambda i: (0, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H * C), jnp.float32),
            jax.ShapeDtypeStruct((B, H * C), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(votes.astype(jnp.float32)), rw)
    return rsum, rv.reshape(B, H, C), rv2.reshape(B, H, C)


@functools.partial(jax.jit, static_argnames=("l_tile", "interpret"))
def em_stage_estep(votes: jax.Array, mu: jax.Array, inv_sigma2: jax.Array,
                   bias: jax.Array, *, l_tile: int = 128,
                   interpret: bool | None = None):
    """EM E-step: -> responsibilities r (B, L, H)."""
    B, L, H, C = votes.shape
    _check_tile(L, l_tile)
    return pl.pallas_call(
        functools.partial(_em_estep_kernel, h=H, c_dim=C),
        grid=(L // l_tile,),
        in_specs=[
            pl.BlockSpec((B, l_tile, H * C), lambda i: (0, i, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((B, H * C), lambda i: (0, 0)),
            pl.BlockSpec((B, H), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, l_tile, H), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, L, H), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=resolve_interpret(interpret),
    )(_pack(votes.astype(jnp.float32)), _pack(mu.astype(jnp.float32)),
      _pack(inv_sigma2.astype(jnp.float32)), bias.astype(jnp.float32))
