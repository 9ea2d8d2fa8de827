"""The comparisons that decide ``correct``.

Each comparison gives one number; a run is correct when every number is
finite and at most its limit.  The limits live in the configuration file
(``limits``), set from readings of the program and of the control (see
PERF.md).  Copied in spirit from the repository's chip smoke test; the
reference side runs at the matmul precision the configuration states for
it (``reference_precision``).

* ``score_gap``: the widest absolute gap between a served class score and
  the reference's score for the same lane of the same wave.
* ``missing``: requests due in the window that never completed.
* ``wave_errors`` / ``guard_trips``: waves that raised, and waves whose
  non-finite output the server re-ran through its jnp reference; both
  hide a fault of the timed path, so both are held to 0.
* ``path_form`` / ``kernels_absent``: the program resolved another routing
  kernel form than the configuration states, or (traced runs) no routing
  kernel ran in the window (``common.path_readings``).
* ``loss_gap``: the widest relative gap between a train step's loss and the
  reference's loss, over the first steps.
* ``grad_gap`` / ``update_gap``: per leaf, the gap between the program's
  norm and the reference's norm (of the first clipped gradient, or of the
  parameters' change over the first steps), over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

Reading = Tuple[str, float, float]


def score_gap(got: np.ndarray, ref: np.ndarray, mask: np.ndarray) -> float:
    """Widest |got - ref| over the real (mask = 1) lanes."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    real = np.asarray(mask).reshape(-1) > 0
    gap = np.abs(got - ref).reshape(-1, got.shape[-1])[real]
    if gap.size == 0:
        return math.nan
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return math.inf
    return float(gap.max())


def loss_gap(got: Sequence[float], ref: Sequence[float]) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.isfinite(got).all():
        return math.inf
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def leaf_norms(leaves: Iterable[np.ndarray]) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in leaves])


def norm_gap(got: Sequence[np.ndarray], ref: Sequence[np.ndarray],
             keep: Sequence[bool] | None = None) -> float:
    """Worst leaf's |‖got‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref‖).
    ``keep`` leaves out leaves (the reference's gradient is nought to
    rounding there)."""
    g, r = leaf_norms(got), leaf_norms(ref)
    if keep is not None:
        keep = np.asarray(keep, bool)
        g, r = g[keep], r[keep]
    if not np.isfinite(g).all():
        return math.inf
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(g - r) / scale))


def moving_leaves(ref_grads: Sequence[np.ndarray]) -> List[bool]:
    """Leaves whose reference gradient norm is at least a thousandth of
    the median leaf's: the others move under Adam by round-off alone."""
    norms = leaf_norms(ref_grads)
    return list(norms >= 1e-3 * np.median(norms))


def verdict(readings: Sequence[Reading]) -> bool:
    return all(math.isfinite(v) and v <= limit for _, v, limit in readings)


def as_json(readings: Sequence[Reading]) -> Dict[str, Dict[str, float]]:
    """Strict JSON: a number that is not finite is printed as 1e300."""
    return {name: {"value": value if math.isfinite(value) else 1e300,
                   "limit": limit}
            for name, value, limit in readings}

