"""Paper §4 — host || PIM pipelined execution, TPU form.

The paper overlaps the host GPU (Conv/FC layers of batch k+1) with the HMC
(routing procedure of batch k).  On a homogeneous TPU mesh the idiomatic
equivalent (DESIGN.md §2) is a two-stage pipeline over *disjoint device
groups*: one mesh axis ("pipe", e.g. the production mesh's "pod" axis) hosts
the stages, microbatches flow through with a one-tick skew, and the hand-off
is a ``lax.ppermute`` — compute of both stages overlaps exactly like the
paper's Fig.8 timeline.

Two entry points:
  * ``two_stage_pipeline``     — shard_map program for a 2-sized mesh axis
                                 (stage 0 = encoder / "host", stage 1 =
                                 routing / "PIM").
  * ``software_pipeline_scan`` — single-group microbatch overlap expressed as
                                 a skewed ``lax.scan`` (XLA overlaps the
                                 independent stage ops; used on 1-axis meshes
                                 and in tests).

Both accept *pytrees* of stacked microbatches (every leaf shaped
``(n_micro, ...)``) so stages can consume auxiliary per-lane operands — the
serving path (DESIGN.md §Serving) threads a padding mask next to the images
this way.  The stage hand-off is equally a pytree: a multi-input stage B
(EM routing's ``(votes, a_in)`` pair) receives exactly the tuple stage A
returned, every leaf crossing the carry/ppermute together, and stage B may
return a tuple too (EM's ``(pose, a_out)``) — the stacked outputs mirror
that structure leaf-by-leaf.  ``two_stage_pipeline`` additionally composes
with a routing stage that is itself sharded over one or more *further* mesh
axes (the paper's §5.1 inter-vault distribution running inside the §4
pipeline's PIM stage): pass ``in_spec``/``out_spec`` partitioning the
non-pipe axes and set ``stage_b_collectives=True`` so stage B's cross-vault
``lax.psum``s execute uniformly on every pipe rank instead of under a
per-rank ``lax.cond``.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat

P = jax.sharding.PartitionSpec


def _n_micro(micro_inputs) -> int:
    leaves = jax.tree.leaves(micro_inputs)
    if not leaves:
        raise ValueError("micro_inputs pytree has no leaves")
    counts = {l.shape[0] for l in leaves}
    if len(counts) != 1:
        raise ValueError("micro_inputs leaves disagree on n_micro "
                         f"(leading dims {sorted(counts)}); every leaf "
                         "must stack the same number of microbatches")
    return leaves[0].shape[0]


def _at(micro_inputs, t):
    """Microbatch t of a stacked pytree (every leaf (n_micro, ...))."""
    return jax.tree.map(lambda x: x[t], micro_inputs)


def software_pipeline_scan(stage_a: Callable, stage_b: Callable,
                           micro_inputs) -> Any:
    """Skewed scan: at tick t, stage_b consumes stage_a's output from t-1
    while stage_a produces t's — the two are data-independent within a tick,
    so XLA's scheduler may overlap them (on one device this documents the
    dependence structure; on two pipeline shards use ``two_stage_pipeline``).

    micro_inputs: pytree of (n_micro, ...) stacked microbatches (a bare
    array is the single-leaf case).  stage_a's output is handed to stage_b
    with its values and shapes as returned — return a tuple for a
    multi-input stage B (EM's (votes, a_in)) and it crosses the carry
    whole.  stage_b may itself be a shard_map program (a sharded routing
    stage) — collectives trace fine under the scan — and may return a
    pytree (EM's (pose, a_out)).  Returns stacked stage_b outputs, each
    leaf (n_micro, ...).

    The carry holds each vote leaf (rank >= 4, (B, L, H, C)) with its two
    minor dims merged, (B, L, H·C), and stage_b gets the shape back: a
    4-D carry is laid out with its small minor dims padded to full tiles,
    which costs a relayout copy on each side of the hand-off, while
    (B, L, H·C) is the lane-packed layout the routing kernels read, so a
    producer that writes it (``capsule_layers.project_votes``) crosses
    the carry with no copy.  Leaves of lower rank (EM's (B, L) a_in)
    cross as they are.
    """
    a0 = stage_a(_at(micro_inputs, 0))
    rest = jax.tree.map(lambda x: x[1:], micro_inputs)

    def carried(h):
        return jax.tree.map(
            lambda x: x.reshape(x.shape[:-2] + (-1,)) if x.ndim >= 4 else x,
            h)

    def handed(h):
        return jax.tree.map(lambda x, like: x.reshape(like.shape), h, a0)

    def tick(carry, x_next):
        b_out = stage_b(handed(carry))   # bubble-filled stage B
        a_out = stage_a(x_next)          # independent of b_out
        return carried(a_out), b_out

    last_a, outs = lax.scan(tick, carried(a0), rest)
    final = stage_b(handed(last_a))
    return jax.tree.map(lambda o, f: jnp.concatenate([o, f[None]], axis=0),
                        outs, final)


def two_stage_pipeline(stage_a: Callable, stage_b: Callable,
                       mesh: jax.sharding.Mesh, axis: str,
                       a_out_shape, *,
                       in_spec: Any = None, out_spec: Any = None,
                       stage_b_collectives: bool = False):
    """Build a pipelined runner over a 2-sized mesh axis.

    stage_a: microbatch -> hidden        (runs on pipe rank 0, the "host")
    stage_b: hidden -> output            (runs on pipe rank 1, the "PIM")

    ``hidden`` and ``output`` are pytrees: a multi-input stage B (EM's
    (votes, a_in)) takes the tuple stage A returned — every leaf ppermutes
    across the pipe hand-off together — and multi-output stage Bs (EM's
    (pose, a_out)) stack leaf-by-leaf.

    Returns f(micro_inputs) -> stacked outputs; micro_inputs is a pytree
    whose leaves are (n_micro, ...) stacked microbatches.  Hidden states
    cross stages via ppermute.  n_micro ticks + 1 bubble tick; at every
    interior tick both stages execute concurrently on their own devices
    (paper Fig.8 overlap).

    By default inputs/outputs live replicated on every mesh axis.  To run a
    *sharded* stage B inside the pipeline (DESIGN.md §Serving — the §5.1
    vault distribution inside the §4 PIM stage) pass:

      in_spec / out_spec       PartitionSpecs (or pytree prefixes thereof)
                               for the stacked inputs/outputs over the
                               non-pipe mesh axes; leading dim = n_micro.
      a_out_shape              the *per-shard* hidden ShapeDtypeStruct
                               (pytree ok).
      stage_b_collectives      True when stage_b psums over a second mesh
                               axis: stage B then runs unconditionally on
                               both pipe ranks (rank 0 on a zero inbox, its
                               result discarded by the final pipe-psum mask)
                               so its collectives stay uniform per vault
                               group instead of sitting under a per-rank
                               ``lax.cond``.
    """
    if mesh.shape[axis] != 2:
        raise ValueError(f"two_stage_pipeline needs |{axis}| == 2, "
                         f"got {mesh.shape[axis]}")
    in_spec = P(None) if in_spec is None else in_spec
    out_spec = P() if out_spec is None else out_spec

    def per_device(micro_inputs):
        stage = lax.axis_index(axis)
        n = _n_micro(micro_inputs)
        zero_hidden = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), a_out_shape)

        def tick(carry, t):
            inbox = carry
            # stage 0 computes A on microbatch t (guard t<n for drain tick)
            xa = _at(micro_inputs, jnp.minimum(t, n - 1))
            a_out = lax.cond(
                stage == 0,
                lambda: jax.tree.map(lambda h, s: h.astype(s.dtype),
                                     stage_a(xa), a_out_shape),
                lambda: zero_hidden)
            # stage 1 computes B on what arrived last tick
            if stage_b_collectives:
                b_out = stage_b(inbox)
            else:
                b_out = lax.cond(
                    stage == 1,
                    lambda: stage_b(inbox),
                    lambda: jax.tree.map(jnp.zeros_like,
                                         stage_b(zero_hidden)))
            # hand-off: rank0 -> rank1
            new_inbox = jax.tree.map(
                lambda h: lax.ppermute(h, axis, [(0, 1)]), a_out)
            return new_inbox, b_out

        _, b_hist = lax.scan(tick, zero_hidden, jnp.arange(n + 1))
        # tick t emitted B(microbatch t-1); drop the bubble tick 0.
        outs = jax.tree.map(lambda h: h[1:], b_hist)
        # results live on stage 1; broadcast so out_spec needn't carry the
        # pipe axis.
        return jax.tree.map(
            lambda h: lax.psum(
                jnp.where(stage == 1, h, jnp.zeros_like(h)), axis),
            outs)

    return jax.jit(compat.shard_map(per_device, mesh, (in_spec,), out_spec))
