"""Jitted public wrappers for the fused routing kernels.

Three execution shapes (DESIGN.md §Procedure-fused, §Sharded-fused):

* ``dynamic_routing_procedure_fused`` — the whole-procedure megakernel: ONE
  ``pallas_call`` with grid (iterations, L_tiles); b/v/s live in VMEM
  scratch across all iterations, squash runs in-kernel, and only the final
  v crosses back to HBM.  Optional bf16 û streaming (fp32 accumulation)
  halves the DMA bytes of the only large operand; int8 streaming
  (per-L-tile symmetric scale, ``quantize_u_stream``) quarters them, and
  ``early_exit_eps`` skips converged L-tiles' Eq.4/Eq.5 work
  (``dynamic_routing_procedure_stats`` reports the effective work —
  DESIGN.md §Quantized-routing).  Shard-local only.
* ``dynamic_routing_fused`` — the single-pass per-iteration kernel; every
  Table-2 aggregation is shard-local, so it only runs unsharded.  Kept as
  the fallback when the procedure kernel's VMEM working set does not fit.
* ``dynamic_routing_fused_sharded`` / ``em_routing_fused`` — the stage-split
  form: per-shard Pallas stages compute the heavy O(B·L·H·C) passes, and
  this module inserts the cross-shard ``lax.psum`` between them at exactly
  the paper's inter-vault aggregation points.  Both run inside a
  ``shard_map`` body (the Router's ``_core_fn``) or any enclosing ambient
  mesh axes; with no sharded axes the psums are identity and the stage-split
  form is algebraically identical to the fused kernel.  When neither B nor
  H is sharded, the next iteration's Eq.5 softmax folds into the STAGE-2
  kernel (``routing_stage_update_fold``) — the iteration-resident treatment
  extended to the distributed path.

``resolve_fusion`` is the single source of truth for the Router's
``fusion="auto"`` knob: procedure-fusion when the plan is shard-local and
``procedure_vmem_bytes`` fits the budget, per-iteration fusion otherwise.
"""
from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import routing as routing_lib
from repro.kernels.routing.kernel import (VMEM_LIMIT_BYTES, em_stage_estep,
                                          em_stage_stats,
                                          routing_iteration_fused,
                                          routing_procedure_bwd,
                                          routing_procedure_fused,
                                          routing_stage_update,
                                          routing_stage_update_fold,
                                          routing_stage_votes, squash_packed)
# spec-level vocabulary lives in vocab.py (importable without pallas —
# core.router._validate uses it); re-exported here for kernel code and
# historical callers.
from repro.kernels.routing.vocab import (FUSION_LEVELS, STREAM_DTYPES,
                                         stream_itemsize as _stream_itemsize)

# VMEM model (DESIGN.md §Procedure-fused).  Every routing kernel runs under
# one scoped-VMEM limit (kernel.VMEM_LIMIT_BYTES, passed to Mosaic as
# vmem_limit_bytes); the tile pickers below size the L tile against the
# same budget.  Buffers are counted as Mosaic lays them out: a (rows, cols)
# block occupies whole (sublane, 128-lane) tiles — sublane 8 for fp32, 16
# for bf16, 32 for int8 — so a lane-packed û row of H·C = 160 takes 256
# lanes and an (L, H=10) logit array takes 128.
PROCEDURE_VMEM_BUDGET = VMEM_LIMIT_BYTES
_LANES = 128
# Beyond the buffers counted below, a compile for v5e needs Mosaic's
# internal scratch (~1 MiB) and the kernels' (B, H·C)-sized temporaries
# (squash, segment sums: ~16 of them live) — measured at Caps-MN1 and
# Caps-EN3 widths, B = 8 and 100, by bisecting vmem_limit_bytes.
_MOSAIC_SCRATCH = 2 ** 20
_VOTE_TEMPS = 16
# fp32 (B, L_t, H·C)-sized in-kernel temporaries per kernel, in units of
# one fp32 û block (the dequantised / up-cast û and the products the
# reductions consume), measured the same way.
_FWD_TEMPS = 1.25          # procedure megakernel
_EARLY_EXIT_TEMPS = 1.25   # + the early-exit branch's live values
_BWD_TEMPS = 2.5           # recompute-b backward megakernel
_STAGE_TEMPS = 1.25        # per-iteration and stage-split kernels
_EM_TEMPS = 3.0            # EM M-step / E-step kernels


def sublane_tile(stream_dtype: str) -> int:
    """Rows per (sublane, 128-lane) tile at the stream dtype: 8 fp32,
    16 bf16, 32 int8 — the alignment an L tile of û must keep."""
    return 32 // _stream_itemsize(stream_dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """Bytes a (rows, cols) VMEM buffer occupies in whole tiles."""
    return (_round_up(rows, 32 // itemsize) * _round_up(cols, _LANES)
            * itemsize)


def _slack(B: int, H: int, C: int) -> int:
    return _MOSAIC_SCRATCH + _VOTE_TEMPS * _vmem_bytes(B, H * C)


def _u_row_bytes(B: int, H: int, C: int, itemsize: int = 4) -> int:
    """One L row of a lane-packed (B, L_t, H·C) block."""
    return B * _round_up(H * C, _LANES) * itemsize


def pick_l_tile(L: int, bytes_budget: int, row_bytes: int,
                preferred: int = 128, align: int = 8) -> int:
    """Largest legal L tile <= preferred that fits the VMEM budget.

    Legal tiles are the divisors of L that are multiples of ``align`` (the
    stream dtype's sublane tiling, :func:`sublane_tile`) and L itself — a
    block spanning the whole dim is always legal.  When no legal tile fits,
    the smallest legal one is returned (the caller's fit check then
    decides).  Divisors are enumerated in O(√L)."""
    cap = max(1, bytes_budget // max(row_bytes, 1))
    lim = min(preferred, cap)
    legal = set()
    i = 1
    while i * i <= L:
        if L % i == 0:
            legal.update(d for d in (i, L // i) if d % align == 0 or d == L)
        i += 1
    fitting = [d for d in legal if d <= lim]
    return max(fitting) if fitting else min(legal)


def auto_l_tile(B: int, L: int, H: int, C: int, stream_dtype: str) -> int:
    """The l_tile the per-iteration / stage-split wrappers auto-pick —
    public so benchmarks can record the exact provenance they ran with.
    Per L row: the double-buffered û block, its fp32 temporaries and up to
    three double-buffered (L_t, H) logit/coupling blocks."""
    isz = _stream_itemsize(stream_dtype)
    row = (2 * _u_row_bytes(B, H, C, isz)
           + _STAGE_TEMPS * _u_row_bytes(B, H, C)
           + 3 * 2 * _round_up(H, _LANES) * 4)
    fixed = 4 * _vmem_bytes(B, H * C) + _slack(B, H, C)
    return pick_l_tile(L, PROCEDURE_VMEM_BUDGET - fixed, int(row),
                       align=sublane_tile(stream_dtype))


def em_l_tile(B: int, L: int, H: int, C: int) -> int:
    """l_tile for the EM stage kernels (fp32 votes): per L row the
    double-buffered votes block, the double-buffered (B, L_t, H)
    responsibility block and the fp32 temporaries."""
    row = (2 * _u_row_bytes(B, H, C) + _EM_TEMPS * _u_row_bytes(B, H, C)
           + 2 * B * _round_up(H, _LANES) * 4)
    fixed = 8 * _vmem_bytes(B, H * C) + _slack(B, H, C)
    return pick_l_tile(L, PROCEDURE_VMEM_BUDGET - fixed, int(row))


def _procedure_terms(B: int, L: int, H: int, C: int, stream_dtype: str,
                     early_exit: bool):
    """(bytes per L row of the tile, fixed bytes) of the megakernel."""
    isz = _stream_itemsize(stream_dtype)
    temps = _FWD_TEMPS + (_EARLY_EXIT_TEMPS if early_exit else 0.0)
    row = 2 * _u_row_bytes(B, H, C, isz) + temps * _u_row_bytes(B, H, C)
    fixed = (_vmem_bytes(L, H) * (2 if early_exit else 1)   # b (+ frozen c)
             + 4 * _vmem_bytes(B, H * C)                     # v, s, out x2
             + _slack(B, H, C))
    return int(row), fixed


def procedure_vmem_bytes(B: int, L: int, H: int, C: int, l_tile: int,
                         stream_dtype: str = "fp32",
                         early_exit: bool = False) -> int:
    """VMEM working set of the whole-procedure megakernel, tile padding
    counted: the double-buffered û stream block and its fp32 temporaries,
    the resident b/v/s scratch and the output block (all fp32 regardless of
    stream dtype), plus Mosaic's own scratch.  Early exit adds the (L,H)
    frozen-coupling scratch and the live values of its branch; the int8
    scales, the converged flags and the work counter live in SMEM."""
    row, fixed = _procedure_terms(B, L, H, C, stream_dtype, early_exit)
    return _round_up(l_tile, sublane_tile(stream_dtype)) * row + fixed


def procedure_l_tile(B: int, L: int, H: int, C: int,
                     stream_dtype: str = "fp32", *,
                     early_exit: bool = False) -> int:
    """l_tile for the megakernel: the û rows get whatever the budget
    leaves after the resident b/v/s scratch — so a cap-bound (large B·H·C)
    shape gets a smaller tile instead of disqualifying procedure fusion."""
    row, fixed = _procedure_terms(B, L, H, C, stream_dtype, early_exit)
    return pick_l_tile(L, PROCEDURE_VMEM_BUDGET - fixed, row,
                       align=sublane_tile(stream_dtype))


def _procedure_bwd_terms(B: int, L: int, H: int, C: int, iterations: int,
                         stream_dtype: str):
    isz = _stream_itemsize(stream_dtype)
    T = iterations
    row = (4 * _u_row_bytes(B, H, C, isz)            # û + ∂û, double-buffered
           + _BWD_TEMPS * _u_row_bytes(B, H, C))
    fixed = ((2 * T + 1) * _vmem_bytes(L, H)         # b/∂b + c_t + ∂b_t snaps
             + (3 * T + 4) * _vmem_bytes(B, H * C)   # v, s, s_t/v_{t-1}/∂s_t,
             + _slack(B, H, C))                      # ∂v block x2
    return int(row), fixed


def procedure_bwd_vmem_bytes(B: int, L: int, H: int, C: int, l_tile: int,
                             iterations: int = 3,
                             stream_dtype: str = "fp32") -> int:
    """VMEM working set of the backward megakernel
    (kernel.py::routing_procedure_bwd), tile padding counted:
    double-buffered û *and* ∂û stream blocks with their fp32 temporaries,
    the b/v/s scratch (reused as ∂b / ∂v carry / ∂v accumulator in the
    reverse phase) plus the per-iteration snapshots — 2T logit-sized
    (c_t, ∂b_t) and 3T vote-sized (s_t, v_{t-1}, ∂s_t) — and the (B,H·C)
    cotangent block."""
    row, fixed = _procedure_bwd_terms(B, L, H, C, iterations, stream_dtype)
    return _round_up(l_tile, sublane_tile(stream_dtype)) * row + fixed


def procedure_train_l_tile(B: int, L: int, H: int, C: int,
                           iterations: int = 3,
                           stream_dtype: str = "fp32") -> int:
    """l_tile for the *differentiable* megakernel: sized against the
    backward's working set (the larger of the two) — the forward reuses
    the same tile so fwd and bwd share one stream layout."""
    row, fixed = _procedure_bwd_terms(B, L, H, C, iterations, stream_dtype)
    return pick_l_tile(L, PROCEDURE_VMEM_BUDGET - fixed, row,
                       align=sublane_tile(stream_dtype))


def procedure_train_fits(B: int, L: int, H: int, C: int,
                         iterations: int = 3,
                         stream_dtype: str = "fp32") -> bool:
    """Whether the differentiable megakernel fits VMEM: its backward — the
    larger working set — at :func:`procedure_train_l_tile`."""
    l_tile = procedure_train_l_tile(B, L, H, C, iterations, stream_dtype)
    return (procedure_bwd_vmem_bytes(B, L, H, C, l_tile, iterations,
                                     stream_dtype)
            <= PROCEDURE_VMEM_BUDGET)


def resolve_fusion(fusion: str, shape, stream_dtype: str = "fp32",
                   sharded: bool = False, early_exit: bool = False) -> str:
    """Resolve a RouterSpec ``fusion`` knob to the concrete kernel form.

    Returns "procedure" | "iteration" for shard-local execution and
    "stage_split" under a sharded plan (where the per-iteration stage-split
    kernels are the only legal form — the megakernel cannot surface for the
    Table-2 psums).  ``fusion="auto"`` picks procedure-fusion whenever the
    plan is shard-local and ``procedure_vmem_bytes`` at the
    budget-shrunk ``procedure_l_tile`` fits; ``shape`` is only consulted on
    that branch.

    The deep-edge knobs (DESIGN.md §Quantized-routing) are
    procedure-megakernel-only: int8 dequant and the per-tile convergence
    scratch exist nowhere else, so ``stream_dtype="int8"`` or
    ``early_exit=True`` resolve "auto" to "procedure" unconditionally —
    a VMEM-overflow shape runs with a budget-shrunk (worst case 1-row)
    tile rather than falling back — and raise under a sharded plan or an
    explicit ``fusion="iteration"``.
    """
    if fusion not in FUSION_LEVELS:
        raise ValueError(f"unknown fusion level {fusion!r}; expected one of "
                         f"{FUSION_LEVELS}")
    deep_edge = stream_dtype == "int8" or early_exit
    if sharded:
        if fusion == "procedure":
            raise ValueError(
                "fusion='procedure' is shard-local (the megakernel keeps "
                "b/v/s in VMEM and cannot surface for the Table-2 psums); "
                "use fusion='auto' or 'iteration' with sharded plans")
        if stream_dtype == "int8":
            raise ValueError(
                "stream_dtype='int8' is shard-local: only the procedure "
                "megakernel has a dequant path, and it cannot surface for "
                "the Table-2 psums; use an unsharded plan (plan=None or "
                "'auto')")
        if early_exit:
            raise ValueError(
                "early-exit routing is shard-local: the per-tile "
                "convergence scratch lives in the procedure megakernel, "
                "which cannot surface for the Table-2 psums; use an "
                "unsharded plan (plan=None or 'auto')")
        return "stage_split"
    if fusion != "auto":
        if fusion == "iteration" and deep_edge:
            knob = ("stream_dtype='int8'" if stream_dtype == "int8"
                    else "early_exit_eps")
            raise ValueError(
                f"{knob} requires the procedure megakernel; "
                "fusion='iteration' has no "
                + ("dequant path" if stream_dtype == "int8"
                   else "per-tile convergence scratch")
                + " — use fusion='auto' or 'procedure'")
        return fusion
    if deep_edge:
        return "procedure"
    if shape is None:
        raise ValueError("fusion='auto' needs the votes shape to resolve")
    B, L, H, C = shape
    l_tile = procedure_l_tile(B, L, H, C, stream_dtype)
    fits = (procedure_vmem_bytes(B, L, H, C, l_tile, stream_dtype)
            <= PROCEDURE_VMEM_BUDGET)
    return "procedure" if fits else "iteration"


def dma_bytes_per_call(B: int, L: int, H: int, C: int,
                       iterations: int = 3, *, form: str = "iteration",
                       stream_dtype: str = "fp32",
                       fold: bool = False,
                       backward: bool = False,
                       early_exit_work_fraction: float | None = None) -> dict:
    """HBM<->VMEM traffic per routing call, derived from the BlockSpecs of
    each kernel form (kernel.py):

    * ``iteration`` — per iteration the grid streams the û tile set once
      (B·L·H·C at the stream itemsize), reads+writes the (L,H) logits and
      the (B,H,C) v/s blocks, and the host squash round-trips (B,H,C) twice
      more: roundtrip = iterations · (2·LH + 4·BHC) · 4.
    * ``procedure`` — û still streams once per iteration (it does not fit
      VMEM), but b/v/s stay in scratch across ALL iterations and squash is
      in-kernel, so the only non-stream traffic is the single final v
      write: roundtrip = BHC · 4.  This is exactly the (L,H)/(B,H,C)
      round-trip traffic the megakernel eliminates.
    * ``stage_split`` — û crosses twice per iteration (once per stage; the
      price of distribution) and the inter-stage tensors cross at each
      host/psum boundary: c and db written+read (4·LH), b read+written
      (2·LH), s written+read and v written (3·BHC) per iteration.
      ``fold=True`` models the softmax-folded STAGE 2
      (``routing_stage_update_fold`` — taken whenever neither B nor H is
      sharded, e.g. the L-only plan): the kernel emits the next
      iteration's c directly and no db crosses, so the logit-sized terms
      drop from 6·LH to 4·LH (c written+read, b read+written) — the
      non-fold model overstates that path by iterations·2·L·H·4 bytes.

    bf16 streaming (``stream_dtype="bf16"``) halves the û term — the only
    O(B·L·H·C) one — and leaves the fp32 roundtrip terms unchanged; int8
    quarters it (the per-L-tile fp32 scales are O(L/l_tile) bytes —
    noise — and are not modeled).  int8 is procedure-form-only and has no
    backward (DESIGN.md §Quantized-routing).

    ``early_exit_work_fraction`` (procedure form, forward only) scales the
    û stream term by the measured effective-tile-iterations fraction
    eff / (iterations · L_tiles) ∈ (0, 1]: the ideal where a converged
    tile's û block is never fetched.  The interpret-mode fixed-grid
    executor still fetches every block (only the Eq.4/Eq.5 FLOPs are
    skipped), so like every number here this is the modeled DMA bound,
    not a wall-clock claim.

    The naive jnp path (ref.py) touches û twice per iteration (Eq.2 + Eq.4
    einsums) plus materialised intermediates — measured ~5x the fused bound
    on the pod dry-run (EXPERIMENTS.md §Perf routing cell).

    ``backward=True`` models the recompute-b backward megakernel
    (DESIGN.md §Training) — defined for ``form="procedure"`` only (the
    other forms have no custom VJP).  û streams 2T times (T replay + T
    reverse rows), a û-sized ∂û is written once at the stream dtype
    (``du_stream_bytes``) and the only other traffic is the (B,H,C) fp32
    cotangent read — every per-iteration residual (b, c, s, v and their
    cotangents) stays in VMEM.  ``naive_bytes`` then models unfused jnp
    autodiff of the same procedure: û re-read twice per iteration by the
    einsum transposes, a û-sized ∂û accumulator read+written per
    iteration, and the per-iteration b/c/s/v residuals spilled forward
    and re-read backward.
    """
    f = 4  # fp32: logits / vote-sum / output blocks are always fp32
    u = B * L * H * C * _stream_itemsize(stream_dtype)
    bh = L * H * f
    vhc = B * H * C * f
    u_f32 = B * L * H * C * 4
    if stream_dtype == "int8" and form != "procedure":
        raise ValueError(
            "stream_dtype='int8' is a procedure-megakernel tier (no other "
            f"form has a dequant path); got form={form!r}")
    if early_exit_work_fraction is not None:
        if form != "procedure" or backward:
            raise ValueError(
                "early_exit_work_fraction models the forward procedure "
                f"megakernel only; got form={form!r}, backward={backward}")
        if not 0.0 < early_exit_work_fraction <= 1.0:
            raise ValueError(
                "early_exit_work_fraction must be in (0, 1] (= eff / "
                f"(iterations * L_tiles)); got {early_exit_work_fraction}")
    if backward:
        if form != "procedure":
            raise ValueError(
                "backward=True models the recompute-b VJP of the procedure "
                f"megakernel only (form={form!r} has no custom VJP)")
        if stream_dtype == "int8":
            raise ValueError(
                "backward=True has no int8 form: quantization rounding is "
                "non-differentiable and the backward megakernel has no "
                "dequant path (DESIGN.md §Quantized-routing)")
        return {
            "form": form,
            "fold": fold,
            "stream_dtype": stream_dtype,
            "backward": True,
            "u_hat_stream_bytes": 2 * iterations * u,
            "du_stream_bytes": u,
            "roundtrip_bytes": vhc,
            "total_bytes": 2 * iterations * u + u + vhc,
            "u_hat_bytes": u_f32,
            "naive_bytes": iterations * (2 * u_f32 + 2 * u_f32
                                         + 2 * (2 * bh + 2 * vhc)),
        }
    if form == "iteration":
        u_stream = iterations * u
        roundtrip = iterations * (2 * bh + 4 * vhc)
    elif form == "procedure":
        u_stream = iterations * u
        if early_exit_work_fraction is not None:
            u_stream = int(round(u_stream * early_exit_work_fraction))
        roundtrip = vhc
    elif form == "stage_split":
        u_stream = iterations * 2 * u
        roundtrip = iterations * ((4 if fold else 6) * bh + 3 * vhc)
    else:
        raise ValueError(f"unknown form {form!r}; expected 'iteration', "
                         "'procedure' or 'stage_split'")
    if fold and form != "stage_split":
        raise ValueError("fold=True models the softmax-folded STAGE 2 of "
                         f"the stage_split form only; got form={form!r}")
    return {
        "form": form,
        "fold": fold,
        "stream_dtype": stream_dtype,
        "backward": False,
        "early_exit_work_fraction": early_exit_work_fraction,
        "u_hat_stream_bytes": u_stream,
        "roundtrip_bytes": roundtrip,
        "total_bytes": u_stream + roundtrip,
        "u_hat_bytes": u_f32,
        "naive_bytes": iterations * (2 * u_f32 + 2 * bh + 4 * vhc
                                     + 2 * B * L * H * f),
    }


@functools.partial(jax.jit, static_argnames=("iterations", "use_approx",
                                             "l_tile", "stream_dtype",
                                             "interpret"))
def dynamic_routing_fused(u_hat: jax.Array, *, iterations: int = 3,
                          use_approx: bool = False, l_tile: int | None = None,
                          stream_dtype: str = "fp32",
                          interpret: bool | None = None) -> jax.Array:
    """Full routing procedure built from the fused per-iteration kernel.

    u_hat: (B, L, H, C) -> v: (B, H, C).  û crosses HBM→VMEM once per
    iteration (at the stream dtype; accumulation is fp32); squash (Eq.3,
    O(B·H·C)) runs outside the kernel, through the megakernel's own
    ``squash_packed``.
    """
    u_hat = u_hat.astype(STREAM_DTYPES[stream_dtype])
    B, L, H, C = u_hat.shape
    if l_tile is None:
        l_tile = auto_l_tile(B, L, H, C, stream_dtype)
    b = jnp.zeros((L, H), jnp.float32)
    v = jnp.zeros((B, H, C), jnp.float32)
    for _ in range(iterations):
        s, b = routing_iteration_fused(u_hat, b, v, l_tile=l_tile,
                                       use_approx=use_approx,
                                       interpret=interpret)
        v = squash_packed(s.reshape(B, H * C), H, C,
                          use_approx).reshape(B, H, C)
    return v


@functools.partial(jax.jit, static_argnames=("l_tile",))
def quantize_u_stream(u_hat: jax.Array, l_tile: int):
    """Per-L-tile symmetric int8 quantization of the û stream
    (DESIGN.md §Quantized-routing).

    Each contiguous block of ``l_tile`` L-rows — exactly one megakernel
    grid tile — shares one fp32 scale: scale_j = max|û_tile_j| / 127, so
    codes span the full [-127, 127] range of the tile and dequantization
    (code · scale, in-kernel) has per-element error <= scale/2.  An
    all-zero tile gets the scale floor 1/127 (codes are all 0 either way;
    the floor keeps the scale finite).

    Returns (codes int8 (B, L, H, C), scales fp32 (L/l_tile, 1)).
    """
    B, L, H, C = u_hat.shape
    if L % l_tile != 0:
        raise ValueError(f"L={L} not divisible by l_tile={l_tile}")
    n = L // l_tile
    u = u_hat.astype(jnp.float32).reshape(B, n, l_tile, H, C)
    absmax = jnp.max(jnp.abs(u), axis=(0, 2, 3, 4))          # (n,)
    scale = jnp.where(absmax > 0.0, absmax, 1.0) / 127.0
    q = jnp.clip(jnp.round(u / scale[None, :, None, None, None]),
                 -127.0, 127.0).astype(jnp.int8)
    return q.reshape(B, L, H, C), scale.reshape(n, 1)


def _procedure_call(u_hat, iterations, use_approx, l_tile, stream_dtype,
                    interpret, early_exit_eps):
    """Shared megakernel dispatch: tile pick, stream cast / int8 quantize,
    kernel call.  Returns (v, effective_tile_iterations int32) — the
    counter is the static fixed-grid count when early exit is off."""
    B, L, H, C = u_hat.shape
    early_exit = early_exit_eps is not None
    if l_tile is None:
        l_tile = procedure_l_tile(B, L, H, C, stream_dtype,
                                  early_exit=early_exit)
    if stream_dtype == "int8":
        q, scales = quantize_u_stream(u_hat, l_tile)
        out = routing_procedure_fused(q, scales, iterations=iterations,
                                      l_tile=l_tile, use_approx=use_approx,
                                      interpret=interpret,
                                      early_exit_eps=early_exit_eps)
    else:
        u_hat = u_hat.astype(STREAM_DTYPES[stream_dtype])
        out = routing_procedure_fused(u_hat, iterations=iterations,
                                      l_tile=l_tile, use_approx=use_approx,
                                      interpret=interpret,
                                      early_exit_eps=early_exit_eps)
    if early_exit:
        return out
    return out, jnp.asarray(iterations * (L // l_tile), jnp.int32)


@functools.partial(jax.jit, static_argnames=("iterations", "use_approx",
                                             "l_tile", "stream_dtype",
                                             "interpret", "early_exit_eps"))
def dynamic_routing_procedure_fused(u_hat: jax.Array, *, iterations: int = 3,
                                    use_approx: bool = False,
                                    l_tile: int | None = None,
                                    stream_dtype: str = "fp32",
                                    interpret: bool | None = None,
                                    early_exit_eps: float | None = None
                                    ) -> jax.Array:
    """Whole-procedure megakernel (DESIGN.md §Procedure-fused).

    u_hat: (B, L, H, C) -> v: (B, H, C).  One pallas_call for all
    iterations: b/v/s never cross the off-chip boundary, squash runs
    in-kernel, û streams lane-packed (B, L, H·C) at ``stream_dtype``
    ("fp32" | "bf16" | "int8"; accumulation is always fp32).  "int8"
    quantizes û per L-tile (symmetric scale, :func:`quantize_u_stream`)
    and dequantizes in-kernel — the quarter-DMA deep-edge tier.
    ``early_exit_eps`` skips the Eq.4/Eq.5 work of L-tiles whose logit
    update has converged (‖Δb‖∞ < ε after iteration 0); ε=0 is
    bit-identical to the fixed grid (DESIGN.md §Quantized-routing).  Use
    :func:`dynamic_routing_procedure_stats` to also get the
    effective-tile-iterations counter.
    """
    v, _ = _procedure_call(u_hat, iterations, use_approx, l_tile,
                           stream_dtype, interpret, early_exit_eps)
    return v


@functools.partial(jax.jit, static_argnames=("iterations", "use_approx",
                                             "l_tile", "stream_dtype",
                                             "interpret", "early_exit_eps"))
def dynamic_routing_procedure_stats(u_hat: jax.Array, *, iterations: int = 3,
                                    use_approx: bool = False,
                                    l_tile: int | None = None,
                                    stream_dtype: str = "fp32",
                                    interpret: bool | None = None,
                                    early_exit_eps: float | None = None):
    """:func:`dynamic_routing_procedure_fused` plus the work counter.

    Returns (v (B, H, C), effective_tile_iterations int32) — the number of
    (iteration, L-tile) grid cells that did Eq.4/Eq.5 work.  Without early
    exit this is the fixed-grid constant iterations · L/l_tile; with
    ``early_exit_eps`` > 0 it is the measured data-dependent work, the
    quantity ``dma_bytes_per_call(early_exit_work_fraction=...)`` models.
    """
    return _procedure_call(u_hat, iterations, use_approx, l_tile,
                           stream_dtype, interpret, early_exit_eps)


# ---------------------------------------------------------------------------
# Differentiable procedure megakernel (DESIGN.md §Training)
# ---------------------------------------------------------------------------
# The recompute-b custom VJP: the forward is routing_procedure_fused
# unchanged; the only residual it saves is û itself (the backward replays
# the cheap routing loop from VMEM — kernel.py::routing_procedure_bwd — so
# none of the per-iteration b/c/s/v ever spill to HBM as autodiff
# residuals).  ∂û comes back at û's stream dtype with fp32 in-kernel
# accumulation; the differentiable stream-dtype cast in
# dynamic_routing_procedure_train transposes it back to the caller's fp32.


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _procedure_train_core(u_hat, iterations, l_tile, use_approx, interpret):
    return routing_procedure_fused(u_hat, iterations=iterations,
                                   l_tile=l_tile, use_approx=use_approx,
                                   interpret=interpret)


def _procedure_train_fwd(u_hat, iterations, l_tile, use_approx, interpret):
    v = routing_procedure_fused(u_hat, iterations=iterations, l_tile=l_tile,
                                use_approx=use_approx, interpret=interpret)
    return v, u_hat      # recompute-b: û is the ONLY saved residual


def _procedure_train_bwd(iterations, l_tile, use_approx, interpret,
                         u_hat, g):
    du = routing_procedure_bwd(u_hat, g, iterations=iterations,
                               l_tile=l_tile, use_approx=use_approx,
                               interpret=interpret)
    return (du,)


_procedure_train_core.defvjp(_procedure_train_fwd, _procedure_train_bwd)


@functools.partial(jax.jit, static_argnames=("iterations", "use_approx",
                                             "l_tile", "stream_dtype",
                                             "interpret"))
def dynamic_routing_procedure_train(u_hat: jax.Array, *, iterations: int = 3,
                                    use_approx: bool = False,
                                    l_tile: int | None = None,
                                    stream_dtype: str = "fp32",
                                    interpret: bool | None = None) -> jax.Array:
    """Differentiable whole-procedure megakernel (DESIGN.md §Training).

    Same contract as :func:`dynamic_routing_procedure_fused` — u_hat
    (B, L, H, C) -> v (B, H, C) — but ``jax.grad`` flows through a custom
    VJP whose backward is a second megakernel replaying the routing loop
    from VMEM (recompute-b), not jnp autodiff.  ``stream_dtype`` applies to
    both directions: û streams at it in the 2T backward rows and ∂û is
    written once at it (fp32 accumulation throughout).  The tile is sized
    by :func:`procedure_train_l_tile` so forward and backward share one
    stream layout that fits the backward's larger VMEM working set.

    ``use_approx=True`` is accepted for forward parity but its gradient is
    the exact-squash/softmax surrogate (the §5.2.2 bit-manipulation
    approximations have no derivative); the Router refuses
    ``differentiable=True`` + ``use_approx`` for this reason.
    """
    if stream_dtype == "int8":
        raise ValueError(
            "stream_dtype='int8' has no custom VJP: per-tile quantization "
            "rounds û (round-to-nearest has no derivative) and the backward "
            "megakernel has no dequant path (DESIGN.md §Quantized-routing); "
            "train at 'fp32'/'bf16' and serve int8")
    u_hat = u_hat.astype(STREAM_DTYPES[stream_dtype])
    B, L, H, C = u_hat.shape
    if l_tile is None:
        l_tile = procedure_train_l_tile(B, L, H, C, iterations, stream_dtype)
    return _procedure_train_core(u_hat, iterations, l_tile, use_approx,
                                 interpret)


# ---------------------------------------------------------------------------
# Sharded-fused routing (DESIGN.md §Sharded-fused)
# ---------------------------------------------------------------------------

def _softmax_h(b: jax.Array, h_axis: Optional[str],
               use_approx: bool) -> jax.Array:
    """Eq.5 softmax over H of b:(L,H), cross-shard when H is sharded.

    O(L·H) — negligible next to the O(B·L·H·C) Pallas stages, so it runs
    on the host between them, through the same psum-aware implementation
    as the jnp backend (exact parity by construction).  When neither B nor
    H is sharded this launch disappears entirely: the fold kernel emits the
    next iteration's c from the same û pass (routing_stage_update_fold)."""
    cfg = routing_lib.RoutingConfig(
        use_approx=use_approx,
        axes=(("H", h_axis),) if h_axis is not None else None)
    return routing_lib._softmax(b, cfg)


def _psum_if(x: jax.Array, axis_name: Optional[str]) -> jax.Array:
    return lax.psum(x, axis_name) if axis_name is not None else x


def dynamic_routing_fused_sharded(u_hat: jax.Array, *,
                                  axes: Mapping[str, str],
                                  iterations: int = 3,
                                  use_approx: bool = False,
                                  l_tile: int | None = None,
                                  stream_dtype: str = "fp32",
                                  interpret: bool | None = None) -> jax.Array:
    """Stage-split fused routing with cross-shard aggregation (Table 2).

    u_hat: the *per-shard* (B, L, H, C) block — this function runs inside a
    ``shard_map`` body (or under ambient mesh axes).  ``axes`` maps each
    sharded logical dim ("B" | "L" | "H") to its mesh axis name; the
    matching psum is inserted at the paper's inter-vault aggregation point:

        shard L -> psum of the partial vote-sums s   (after STAGE 1)
        shard B -> psum of the logit updates db      (after STAGE 2)
        shard H -> psum inside the softmax denominator (host, O(L·H))

    Per iteration û crosses HBM→VMEM twice (once per stage) instead of the
    unsharded kernel's once — the distribution cost the paper pays as
    crossbar traffic M.  The stream-dtype cast is hoisted out of the
    iteration loop (one cast feeds every stage of every iteration) and,
    when neither B nor H is sharded, STAGE 2 folds the next iteration's
    softmax into its û pass.  Returns v (B_local, H_local, C).
    """
    # hoisted û re-cast: one stream-dtype cast outside the loop instead of
    # a fresh astype per stage per iteration
    u_hat = u_hat.astype(STREAM_DTYPES[stream_dtype])
    B, L, H, C = u_hat.shape
    if l_tile is None:
        l_tile = auto_l_tile(B, L, H, C, stream_dtype)
    b_axis, h_axis, l_axis = axes.get("B"), axes.get("H"), axes.get("L")
    # the fold needs the complete db (no pending B psum) and a shard-local
    # softmax denominator (no H psum) inside the kernel
    fold = b_axis is None and h_axis is None
    b = jnp.zeros((L, H), jnp.float32)
    v = jnp.zeros((B, H, C), jnp.float32)
    c = None
    for i in range(iterations):
        if c is None:
            c = _softmax_h(b, h_axis, use_approx)              # Eq.5
        s = routing_stage_votes(u_hat, c, l_tile=l_tile,
                                interpret=interpret)           # Eq.2
        s = _psum_if(s, l_axis)
        if fold:
            v, b, c = routing_stage_update_fold(
                u_hat, s, b, l_tile=l_tile, use_approx=use_approx,
                interpret=interpret)                           # Eq.3+4+5
        else:
            v, db = routing_stage_update(u_hat, s, l_tile=l_tile,
                                         use_approx=use_approx,
                                         interpret=interpret)  # Eq.3+4
            b = b + _psum_if(db, b_axis)
            c = None                                # host softmax next iter
    return v


def em_routing_fused(votes: jax.Array, a_in: jax.Array, *,
                     axes: Mapping[str, str],
                     iterations: int = 3, beta_a: float = 1.0,
                     beta_u: float = 1.0, inv_temp: float = 1.0,
                     eps: float = 1e-9, l_tile: int | None = None,
                     interpret: bool | None = None):
    """EM routing via the stage-split Pallas kernels (paper §2.2 generality).

    votes: per-shard (B, L, H, C); a_in: per-shard (B, L).  ``axes`` maps
    sharded dims to mesh axes — "L" psums the M-step sufficient statistics
    (the Table-2 aggregation); "B" shards are fully independent (EM keeps
    no cross-batch state, so no collective is needed); H is rejected by the
    Router (per-H Gaussian statistics cannot split).

    σ² is recombined from streamed sufficient statistics
    (Σrw·v² - 2μ·Σrw·v + μ²·Σrw — one votes pass instead of the naive
    two with a materialised (votes-μ)² tensor), clamped at 0 before the
    +eps floor against catastrophic cancellation.  Matches
    ``core.em_routing.em_routing`` to float tolerance.

    Returns (pose μ (B, H, C), a_out (B, H)).
    """
    votes = votes.astype(jnp.float32)
    B, L, H, C = votes.shape
    if l_tile is None:
        l_tile = em_l_tile(B, L, H, C)
    l_axis = axes.get("L")
    r = jnp.full((B, L, H), 1.0 / H, jnp.float32)
    mu = jnp.zeros((B, H, C), jnp.float32)
    a_out = jnp.zeros((B, H), jnp.float32)
    for it in range(iterations):
        lam = inv_temp * (1.0 - 0.95 ** (it + 1))
        # ---- M-step: one streamed pass + cross-shard psum over L ----
        rsum_raw, rv, rv2 = em_stage_stats(votes, r, a_in, l_tile=l_tile,
                                           interpret=interpret)
        rsum_raw = _psum_if(rsum_raw, l_axis)
        rv = _psum_if(rv, l_axis)
        rv2 = _psum_if(rv2, l_axis)
        r_sum = rsum_raw + eps                                  # (B, H)
        mu = rv / r_sum[..., None]
        var = rv2 - 2.0 * mu * rv + jnp.square(mu) * rsum_raw[..., None]
        sigma2 = jnp.maximum(var, 0.0) / r_sum[..., None] + eps
        cost = (beta_u + 0.5 * jnp.log(sigma2)) * r_sum[..., None]
        a_out = jax.nn.sigmoid(lam * (beta_a - jnp.sum(cost, axis=-1)))
        # ---- E-step: host precomputes the Gaussian constants so the
        # ---- kernel pass is MAC-only ----
        bias = jnp.log(a_out + eps) - 0.5 * jnp.sum(
            jnp.log(2.0 * jnp.pi * sigma2), axis=-1)            # (B, H)
        r = em_stage_estep(votes, mu, 1.0 / sigma2, bias, l_tile=l_tile,
                           interpret=interpret)
    return mu, a_out
