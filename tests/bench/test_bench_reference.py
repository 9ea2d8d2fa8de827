"""bench/reference.py against the program's own serving wave (Pallas
routing kernels, interpreted on the CPU) at the program's smoke widths,
and bench/check.py's comparisons on it."""
import numpy as np
import pytest

from bench import check, common, reference, registry
from repro.runtime.caps_serve import CapsAdapter, ServeConfig
from _cells import SMALL


@pytest.fixture(scope="module")
def served():
    cfg = registry.config("caps-mn1")
    cfg.update(SMALL)
    params = reference.init_params(cfg, 2 ** 31 + 11)
    scfg = ServeConfig(microbatch=8, n_micro=2, pipeline="software")
    adapter = CapsAdapter(params, common.caps_config(cfg),
                          common.router_spec(cfg))
    rng = np.random.default_rng(0)
    images = rng.random((11, 28, 28, 1), np.float32)
    wave = adapter.pack(list(images), scfg)
    got = np.asarray(adapter.make_wave_fn(scfg)(wave))
    want = np.asarray(reference.wave_scores(params, wave["images"],
                                            wave["mask"], cfg))
    return cfg, got, want, np.asarray(wave["mask"])


def test_program_wave_matches_the_reference(served):
    cfg, got, want, mask = served
    gap = check.score_gap(got, want, mask)
    assert gap < 1e-5
    assert gap <= cfg["limits"]["score_gap"]


def test_a_perturbed_score_fails(served):
    cfg, got, want, mask = served
    bad = got.copy()
    bad[1, 2, 3] += 4 * cfg["limits"]["score_gap"]       # a real lane
    assert check.score_gap(bad, want, mask) > cfg["limits"]["score_gap"]
    padded = got.copy()
    padded[1, 7, :] += 1.0                               # a padded lane
    assert check.score_gap(padded, want, mask) == check.score_gap(
        got, want, mask)


def test_norm_gap_takes_the_worst_leaf_over_the_median_scale():
    ref = [np.full(4, 1.0), np.full(4, 2.0), np.full(4, 1e-9)]
    got = [np.full(4, 1.01), np.full(4, 2.0), np.full(4, 0.5)]
    # norms 2.02, 4, 1 against 2, 4, 2e-9: the tiny leaf's gap is measured
    # against the median leaf's norm (2), not its own
    assert check.norm_gap(got, ref) == pytest.approx((1.0 - 2e-9) / 2)
    keep = check.moving_leaves(ref)
    assert keep == [True, True, False]
    # the median of the leaves kept (2, 4) is 3
    assert check.norm_gap(got, ref, keep) == pytest.approx(0.02 / 3)
    assert check.verdict([("a", 0.1, 0.2)])
    assert not check.verdict([("a", float("nan"), 0.2)])
