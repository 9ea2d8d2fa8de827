"""Pallas TPU kernels for the perf-critical compute layers (DESIGN.md §6).

Each subpackage follows the repo convention:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  ops.py    — jitted public wrapper (block-size selection, shape handling)
  ref.py    — pure-jnp oracle the kernel is tested against

Kernels are written for TPU: on a TPU backend they compile through Mosaic;
on any other backend (the CPU test runs) they execute in Pallas interpret
mode.  Every wrapper takes ``interpret=None`` by default and resolves it
through ``resolve_interpret`` — one probe decides, so no caller can run
interpreted on the chip by leaving the argument out.
"""
from __future__ import annotations

import jax


def pallas_interpret_mode() -> bool:
    """Capability probe shared by every pallas entry point: compiled
    ``pallas_call`` needs a TPU; everywhere else (CPU/GPU hosts, tests)
    kernels run in interpret mode — same code path, interpreted."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A wrapper's ``interpret`` argument: an explicit bool wins (tests
    compile for a described TPU with ``interpret=False``); None follows
    :func:`pallas_interpret_mode`."""
    return pallas_interpret_mode() if interpret is None else bool(interpret)
