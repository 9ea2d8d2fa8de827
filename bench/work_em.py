"""Operations and least bytes of matrix-capsule EM work, from a
configuration's shapes (``bench/configs/emcaps-norb.json``).

As in ``bench/work.py``, everything follows from the logical shapes:
nothing depends on which kernels or which layout run the work, so a PR
that changes the implementation cannot change the yardstick.

One routed layer's EM call over a microbatch of ``batch`` images has
rows = batch · windows (1 window for the class layer), L children per
window, H parent types and C = 16 pose entries.  Counting rules (a
multiply-add is 2 operations):

* M-step, every iteration: R·a and Σ R·a over L (2·rows·L·H), Σ R·a·V
  (2 per vote entry) and Σ R·a·V² (3 per vote entry), then μ, σ², log σ
  and the cost per (row, H, C) entry (10·rows·H·C).
* E-step, every iteration but the last (its R has no reader): V − μ, its
  square, the product with 1/σ² and the sum over C (4 per vote entry),
  then the normalisation over a child's parents (6·rows·L·H: bias, max,
  subtract, exp, sum, divide).
* Least bytes of the call: the smaller of reading the votes once or
  reading what forms them (the children's poses, each once, and the
  layer's transforms), plus the child activations and the output poses
  and activations.
* The forward: conv1 and PrimaryCaps (VALID convolutions), the votes
  (4 multiply-adds a vote entry) and the three EM calls.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping

F32 = 4
POSE = 16


@dataclasses.dataclass(frozen=True)
class EMCall:
    """Logical shape of one routed layer's EM call."""
    batch: int          # images
    windows: int        # parent positions per image
    children: int       # L, per window
    parents: int        # H, types
    iterations: int
    grid: int           # distinct child capsules per image
    transforms: int     # learned 4x4 matrices of the layer

    @property
    def rows(self) -> int:
        return self.batch * self.windows

    @property
    def vote_entries(self) -> int:
        return self.rows * self.children * self.parents * POSE


def _out(size: int, kernel: int, stride: int) -> int:
    return (size - kernel) // stride + 1


def em_calls(cfg: Mapping, batch: int) -> Dict[str, EMCall]:
    """The three routed layers' EM calls on a microbatch of ``batch``."""
    K, (s1, s2) = cfg["caps_kernel"], cfg["caps_strides"]
    g0 = _out(cfg["image_hw"], cfg["conv_kernel"], cfg["conv_stride"])
    g1 = _out(g0, K, s1)
    g2 = _out(g1, K, s2)
    T = cfg["routing_iters"]
    B, C, D, E = cfg["B"], cfg["C"], cfg["D"], cfg["E"]
    return {
        "convcaps1": EMCall(batch, g1 * g1, K * K * B, C, T, g0 * g0 * B,
                            K * K * B * C),
        "convcaps2": EMCall(batch, g2 * g2, K * K * C, D, T, g1 * g1 * C,
                            K * K * C * D),
        "class": EMCall(batch, 1, g2 * g2 * D, E, T, g2 * g2 * D, D * E),
    }


def em_flops(call: EMCall) -> int:
    rows, L, H = call.rows, call.children, call.parents
    m_step = 2 * rows * L * H + 5 * call.vote_entries + 10 * rows * H * POSE
    e_step = 4 * call.vote_entries + 6 * rows * L * H
    return call.iterations * m_step + (call.iterations - 1) * e_step


def em_bytes(call: EMCall) -> int:
    votes = call.vote_entries * F32
    inputs = (call.batch * call.grid + call.transforms) * POSE * F32
    a_in = call.batch * call.grid * F32
    out = call.rows * call.parents * (POSE + 1) * F32
    return min(votes, inputs) + a_in + out


def encoder_flops_per_image(cfg: Mapping) -> Dict[str, int]:
    """conv1, PrimaryCaps and each routed layer's votes, one image."""
    k1 = cfg["conv_kernel"]
    g0 = _out(cfg["image_hw"], k1, cfg["conv_stride"])
    out = {"conv1": 2 * g0 * g0 * k1 * k1 * cfg["image_channels"] * cfg["A"],
           "primary_caps": 2 * g0 * g0 * cfg["A"] * cfg["B"] * (POSE + 1)}
    for name, call in em_calls(cfg, 1).items():
        out[f"votes.{name}"] = 2 * 4 * call.vote_entries
    return out


def forward_flops_per_image(cfg: Mapping, batch: int) -> float:
    """Model FLOPs of serving one image whose EM calls hold ``batch``."""
    enc = sum(encoder_flops_per_image(cfg).values())
    return enc + sum(em_flops(c) for c in em_calls(cfg, batch).values()) \
        / batch
