"""Model FLOPs utilisation, % of the chips' bf16 peak (layer: model step).

Model FLOPs of the images completed in the window (``bench/work.py``:
padded lanes and recomputation do not count) over the summed wall time of
the waves or steps that did them, times the chips, times the peak of
``bench/peaks.json``."""


def read(outcome, ctx):
    c = outcome.counters
    if not c.get("wave_wall_s") or not c.get("model_flops"):
        return None
    return 100.0 * c["model_flops"] / (
        c["wave_wall_s"] * ctx.chips * ctx.peak["bf16_flops_per_s"])
