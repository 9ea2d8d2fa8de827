"""The ``emnorb-serve-rate`` cell on the CPU at a small size, and
``bench/work_em.py``'s counts.

The cell runs through bench/run.py's main with the look for a chip
skipped, as ``_cells.py`` does for the other cells: matrix capsules at
A=B=C=D=4, E=3 on 24x24 images, waves of 2 microbatches of 8, jnp
routing.  The unbroken run is correct; the per-window E-step softmax and
one EM iteration fewer are not.
"""
import dataclasses
import json

import jax
import pytest

from bench import registry, work_em
from bench import run as harness

PEAK = json.loads((registry.ROOT / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
CONFIG, MIX = registry.config, registry.mix
CELL = {"name": "emnorb-serve-rate", "config": "emcaps-norb",
        "traffic": "em-serve-rate", "chips": 1}
SMALL = {"image_hw": 24, "A": 4, "B": 4, "C": 4, "D": 4, "E": 3}
FULL = CONFIG("emcaps-norb")


def _small_config(name, root=registry.ROOT):
    cfg = CONFIG(name, root)
    cfg.update(SMALL)
    cfg["router"] = dict(cfg["router"], backend="jnp",
                         forms={k: None for k in cfg["router"]["forms"]})
    return cfg


def _small_mix(name, root=registry.ROOT):
    return dict(MIX(name, root), n_micro=2, pool_images=64)


def run_cell(monkeypatch, capsys, control=None):
    """Run the cell through bench/run.py's main; returns the result line."""
    monkeypatch.setattr(harness, "device_check",
                        lambda cell, peaks: (jax.devices(), PEAK))
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(registry, "config", _small_config)
    monkeypatch.setattr(registry, "mix", _small_mix)
    monkeypatch.setattr(registry, "cell", lambda bench, name: CELL)
    drv = registry.driver("serve_em")
    if control is not None:
        run = drv.run
        monkeypatch.setattr(drv, "run", lambda ctx: run(
            dataclasses.replace(ctx, control=control)))
    monkeypatch.setattr(registry, "driver", lambda name, root=None: drv)
    assert harness.main(["--workload", CELL["name"], "--seed",
                         str(2 ** 31 + 11), "--seconds", "1", "--trace",
                         "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_unbroken_run_is_correct(monkeypatch, capsys):
    result = run_cell(monkeypatch, capsys)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["checks"]) == {"score_gap", "pose_gap", "missing",
                                     "wave_errors", "guard_trips",
                                     "path_form"}
    assert result["metrics"]["serve_images_per_s"]["value"] > 0


@pytest.mark.parametrize("control", ["window_local", "fewer_iters", "bf16"])
def test_faults_and_the_control_are_caught(monkeypatch, capsys, control):
    result = run_cell(monkeypatch, capsys, control)
    assert result["correct"] is False
    gap = result["checks"]["score_gap"]
    assert gap["value"] > gap["limit"]


def test_work_counts_match_a_hand_count_of_convcaps2():
    """ConvCaps2 at the published widths, a microbatch of 8: 4x4 windows
    of 3x3x32 children on a 6x6x32 grid, 32 parent types, 3 iterations."""
    call = work_em.em_calls(FULL, 8)["convcaps2"]
    rows, L, H, C = 8 * 16, 288, 32, 16
    assert (call.rows, call.children, call.parents) == (rows, L, H)
    votes = rows * L * H * C
    assert call.vote_entries == votes
    flops = (3 * (2 * rows * L * H + 5 * votes + 10 * rows * H * C)
             + 2 * (4 * votes + 6 * rows * L * H))
    assert work_em.em_flops(call) == flops
    # the children's poses and the 3x3x32x32 transforms are smaller than
    # the votes (9.4 MB an image)
    poses = 8 * 6 * 6 * 32 * C * 4 + 3 * 3 * 32 * 32 * C * 4
    assert work_em.em_bytes(call) == (poses + 8 * 6 * 6 * 32 * 4
                                      + rows * H * (C + 1) * 4)
    assert votes * 4 // 8 == 9437184


def test_vote_volume_per_image():
    """Votes per image in fp32: 21.2 + 9.4 + 0.16 MB = 30.8 MB."""
    total = sum(c.vote_entries for c in work_em.em_calls(FULL, 1).values())
    assert total * 4 == 21233664 + 9437184 + 163840
