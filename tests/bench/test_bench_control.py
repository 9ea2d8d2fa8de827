"""The control, the reference in bfloat16 put in the program's place,
reads ``correct: false`` in every cell (the configurations state float32).
On the chip it was read at the cells' own sizes (PERF.md); here it runs at
the small CPU size of _cells.py."""
import dataclasses

import pytest

from _cells import run_cell


def _control(monkeypatch, drv):
    run = drv.run
    monkeypatch.setattr(
        drv, "run", lambda ctx: run(dataclasses.replace(ctx,
                                                        control="bf16")))


@pytest.mark.parametrize("workload", ["mn1-serve-tail", "en3-serve-rate",
                                      "mn1-train"])
def test_the_bf16_control_is_not_correct(monkeypatch, capsys, workload):
    result = run_cell(monkeypatch, capsys, workload, _control)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
