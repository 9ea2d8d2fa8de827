"""Device idle share of the traced window, % (layer: device).

1 - (union of the device's op intervals / traced window), the mean over
the cell's chips (``bench/trace.py``)."""

from bench import trace


def read(outcome, ctx):
    if outcome.trace is None or not outcome.trace["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_fraction(outcome.trace))
