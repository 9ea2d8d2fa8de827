"""Host milliseconds per served wave (layer: serving core).

The benchmark's own spans around ``WaveServer.step``: the step's wall time
less the time it waited for the wave's result, averaged over the waves of
the window.  Nothing to read where no wave was served."""


def read(outcome, ctx):
    c = outcome.counters
    if not c.get("waves") or "host_s" not in c:
        return None
    return 1e3 * c["host_s"] / c["waves"]
