"""A run with its timed path broken underneath reads ``correct: false``.

Each test drives bench/run.py's main on the CPU at a small size (the
program's smoke widths: L=288, 64 conv channels, jnp routing for the
serving waves) with the look for a chip skipped, and plants one fault in
what the window drives.  The unbroken run is correct; every fault that a
cell can have is caught:

* serving: an answer altered where it is produced; half of each routing
  call's lanes left out; one routing iteration fewer; a router that
  resolves to another kernel form than the configuration states; a wave
  that errs or trips the output guard;
* training: a step that returns its state unchanged; half of the batch
  left out (the mean over the rest); an answer (the loss) altered.

The exchange between chips is not covered: no cell of the benchmark runs
on several chips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from _cells import run_cell

def _wrap_wave(fault):
    def patch(monkeypatch, drv):
        build = drv.build_wave_fn

        def broken(adapter, params, scfg, ctx):
            real = build(adapter, params, scfg, ctx)
            return lambda wave: fault(real, wave)
        monkeypatch.setattr(drv, "build_wave_fn", broken)
    return patch


def _answer_altered(real, wave):
    return real(wave).at[:, 0, 0].add(0.05)


def _half_calls(real, wave):
    mb = wave["mask"].shape[1]
    keep = (jnp.arange(mb) < mb // 2).astype(jnp.float32)
    return real({"images": wave["images"] * keep[:, None, None, None],
                 "mask": wave["mask"] * keep})


def _wrap_step(fault):
    def patch(monkeypatch, drv):
        build = drv.build_step

        def broken(cfg, mix, ctx):
            real = build(cfg, mix, ctx)
            return lambda p, o, x, y: fault(real, p, o, x, y)
        monkeypatch.setattr(drv, "build_step", broken)
    return patch


def _state_unchanged(real, p, o, x, y):
    # the step donates its inputs: hand back copies taken before it ran
    p0, o0 = jax.tree.map(jnp.copy, (p, o))
    _, _, metrics = real(p, o, x, y)
    return p0, o0, metrics


def _half_batch(real, p, o, x, y):
    half = x.shape[0] // 2
    return real(p, o, x[:half], y[:half])


def _loss_altered(real, p, o, x, y):
    p, o, metrics = real(p, o, x, y)
    return p, o, dict(metrics, loss=metrics["loss"] * 1.05)


@pytest.mark.parametrize("workload", ["mn1-serve-tail", "en3-serve-rate",
                                      "mn1-train"])
def test_the_unbroken_run_is_correct(monkeypatch, capsys, workload):
    result = run_cell(monkeypatch, capsys, workload)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("workload", ["mn1-serve-tail", "en3-serve-rate"])
@pytest.mark.parametrize("fault", [_answer_altered, _half_calls],
                         ids=["answer_altered", "half_calls"])
def test_serving_faults_are_caught(monkeypatch, capsys, workload, fault):
    result = run_cell(monkeypatch, capsys, workload, _wrap_wave(fault))
    assert result["correct"] is False
    assert result["checks"]["score_gap"]["value"] > \
        result["checks"]["score_gap"]["limit"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _loss_altered],
                         ids=["state_unchanged", "half_batch",
                              "loss_altered"])
def test_training_faults_are_caught(monkeypatch, capsys, fault):
    result = run_cell(monkeypatch, capsys, "mn1-train", _wrap_step(fault))
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


def _fewer_iters(monkeypatch, drv):
    run = drv.run
    monkeypatch.setattr(drv, "run", lambda ctx: run(
        dataclasses.replace(ctx, control="fewer_iters")))


def _other_form(monkeypatch, drv):
    monkeypatch.setattr(drv, "resolved_form", lambda *a: "iteration")


def _wave_fails_once(monkeypatch, drv):
    build = drv.build_wave_fn

    def broken(adapter, params, scfg, ctx):
        real, calls = build(adapter, params, scfg, ctx), []

        def wave(w):
            calls.append(1)
            if len(calls) == 4:          # the first wave after warm-up
                raise RuntimeError("planted wave error")
            return real(w)
        return wave
    monkeypatch.setattr(drv, "build_wave_fn", broken)


def _non_finite_once(monkeypatch, drv):
    build = drv.build_wave_fn

    def broken(adapter, params, scfg, ctx):
        real, calls = build(adapter, params, scfg, ctx), []

        def wave(w):
            calls.append(1)
            out = real(w)
            return out * jnp.nan if len(calls) == 4 else out
        return wave
    monkeypatch.setattr(drv, "build_wave_fn", broken)


@pytest.mark.parametrize("patch,reading", [
    (_fewer_iters, "score_gap"), (_other_form, "path_form"),
    (_wave_fails_once, "wave_errors"), (_non_finite_once, "guard_trips")],
    ids=["fewer_iters", "other_form", "wave_error", "guard_trip"])
def test_serving_path_faults_are_caught(monkeypatch, capsys, patch,
                                        reading):
    result = run_cell(monkeypatch, capsys, "mn1-serve-tail", patch)
    assert result["correct"] is False
    assert result["checks"][reading]["value"] > \
        result["checks"][reading]["limit"]


def test_a_train_step_off_its_path_is_caught(monkeypatch, capsys):
    result = run_cell(monkeypatch, capsys, "mn1-train", _other_form)
    assert result["correct"] is False
    assert result["checks"]["path_form"]["value"] == 1.0
