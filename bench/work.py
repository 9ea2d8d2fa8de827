"""Operations and least bytes of CapsNet work, from a configuration's shapes.

Everything here is computed from the logical shapes in a configuration file
(``bench/configs/<name>.json``): image size, conv widths, capsule counts
and routing iterations.  Nothing depends on which kernel form (procedure,
iteration, stage-split) or which distribution plan runs the work, so a PR
that changes the implementation cannot change the yardstick.

Counting rules (one multiply-add is 2 operations):

* conv1 and PrimaryCaps are VALID convolutions; Eq.1 forms the votes
  u_hat[k,i,j] = u[k,i] @ W[i,j].
* Dynamic routing (Eq.2-5), per iteration on a call of B images:
  Eq.5 softmax over H      5·L·H    (max, subtract, exp, sum, divide)
  Eq.2 weighted vote sum   2·B·L·H·C
  Eq.3 squash              B·H·(3·C + 4)
  Eq.4 agreement + b add   2·B·L·H·C + L·H
* Least bytes of one routing call: the smaller of reading u_hat once or
  reading u and W (to form u_hat next to the data), plus writing v.  The
  backward call also reads dv and writes the smaller of du_hat or (du, dW).
* Training counts forward and backward: the backward of a layer is twice
  its forward, except conv1, whose input needs no gradient (once).
  Recomputation is not counted.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

F32 = 4


@dataclasses.dataclass(frozen=True)
class RoutingCall:
    """Logical shape of one routing procedure call."""
    batch: int
    l_caps: int
    h_caps: int
    l_dim: int
    h_dim: int
    iterations: int


def routing_call(cfg: Mapping, batch: int) -> RoutingCall:
    return RoutingCall(batch, cfg["num_l_caps"], cfg["num_h_caps"],
                       cfg["l_caps_dim"], cfg["h_caps_dim"],
                       cfg["routing_iters"])


def _conv_out(size: int, kernel: int, stride: int) -> int:
    return (size - kernel) // stride + 1


def encoder_flops_per_image(cfg: Mapping) -> dict:
    """conv1, PrimaryCaps conv and Eq.1 votes of one image."""
    k1, k2 = cfg["conv_kernel"], cfg["caps_kernel"]
    s1 = _conv_out(cfg["image_hw"], k1, 1)
    s2 = _conv_out(s1, k2, cfg["caps_stride"])
    caps_out = cfg["caps_channels"] * cfg["l_caps_dim"]
    return {
        "conv1": 2 * s1 * s1 * k1 * k1 * cfg["image_channels"]
        * cfg["conv_channels"],
        "primary_caps": 2 * s2 * s2 * k2 * k2 * cfg["conv_channels"]
        * caps_out,
        "votes": 2 * cfg["num_l_caps"] * cfg["num_h_caps"]
        * cfg["l_caps_dim"] * cfg["h_caps_dim"],
    }


def decoder_flops_per_image(cfg: Mapping) -> int:
    dims = [cfg["num_h_caps"] * cfg["h_caps_dim"], *cfg["decoder_hidden"],
            cfg["image_hw"] ** 2 * cfg["image_channels"]]
    return sum(2 * a * b for a, b in zip(dims, dims[1:]))


def routing_flops(call: RoutingCall) -> int:
    b, l, h, c = call.batch, call.l_caps, call.h_caps, call.h_dim
    per_iter = (5 * l * h + 2 * b * l * h * c + b * h * (3 * c + 4)
                + 2 * b * l * h * c + l * h)
    return call.iterations * per_iter


def _votes_or_inputs_bytes(call: RoutingCall) -> int:
    b, l, h = call.batch, call.l_caps, call.h_caps
    u_hat = b * l * h * call.h_dim * F32
    u_and_w = (b * l * call.l_dim + l * h * call.l_dim * call.h_dim) * F32
    return min(u_hat, u_and_w)


def routing_bytes(call: RoutingCall) -> int:
    """Least bytes of the forward call: u_hat (or u and W) once, v out."""
    v = call.batch * call.h_caps * call.h_dim * F32
    return _votes_or_inputs_bytes(call) + v


def routing_bwd_flops(call: RoutingCall) -> int:
    return 2 * routing_flops(call)


def routing_bwd_bytes(call: RoutingCall) -> int:
    """Least bytes of the backward call: u_hat (or u and W) and dv in,
    du_hat (or du and dW) out."""
    dv = call.batch * call.h_caps * call.h_dim * F32
    return 2 * _votes_or_inputs_bytes(call) + dv


def least_seconds(flops: float, nbytes: float, peak: Mapping) -> float:
    """The least time one chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def forward_flops_per_image(cfg: Mapping, batch: int) -> float:
    """Model FLOPs of serving one image whose routing call holds ``batch``
    images (routing's L·H terms are shared by the call)."""
    enc = sum(encoder_flops_per_image(cfg).values())
    return enc + routing_flops(routing_call(cfg, batch)) / batch


def train_flops_per_image(cfg: Mapping, batch: int) -> float:
    """Model FLOPs of one training image: forward, decoder and backward."""
    enc = encoder_flops_per_image(cfg)
    fwd = (sum(enc.values()) + decoder_flops_per_image(cfg)
           + routing_flops(routing_call(cfg, batch)) / batch)
    return 3 * fwd - enc["conv1"]
