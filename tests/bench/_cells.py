"""Drive bench/run.py's main on the CPU at a small size, with the look
for a chip skipped: the program's smoke widths (L=288, 64 conv channels)
and jnp routing for the serving waves."""
import json

import jax

from bench import registry
from bench import run as harness

PEAK = json.loads((registry.ROOT / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
CONFIG, MIX = registry.config, registry.mix
# The cells the tests drive, whether or not BENCHMARK.json holds them yet.
CELLS = {"mn1-serve-tail": ("caps-mn1", "serve-tail"),
         "en3-serve-rate": ("caps-en3", "serve-rate"),
         "mn1-train": ("caps-mn1", "train")}
SMALL = {"num_l_caps": 288, "caps_channels": 8, "conv_channels": 64,
         "table1_batch": 16}


def _small_config(name):
    cfg = CONFIG(name)
    cfg.update(SMALL)
    cfg["router"] = dict(cfg["router"], backend="jnp", form=None)
    return cfg


def _small_mix(name):
    mix = MIX(name)
    if name == "serve-tail":
        mix["rate_per_s"] = 300
    if name == "serve-rate":
        mix["n_micro"] = 4
    return mix


def run_cell(monkeypatch, capsys, workload, patch=None, control=None):
    """Run ``workload`` through bench/run.py's main; ``patch(driver)``
    plants a fault.  Returns the result line."""
    monkeypatch.setattr(harness, "device_check",
                        lambda cell, peaks: (jax.devices(), PEAK))
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(registry, "config", _small_config)
    monkeypatch.setattr(registry, "mix", _small_mix)
    config, traffic = CELLS[workload]
    cell = {"name": workload, "config": config, "traffic": traffic,
            "chips": 1}
    monkeypatch.setattr(registry, "cell", lambda bench, name: cell)
    drv = registry.driver(_small_mix(cell["traffic"])["driver"])
    if patch is not None:
        patch(monkeypatch, drv)
    monkeypatch.setattr(registry, "driver", lambda name, root=None: drv)
    assert harness.main(["--workload", workload, "--seed", str(2 ** 31 + 5),
                         "--seconds", "1", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
