"""Engine scan layer (``repro.core.engine``): device-busy microseconds per
simulated tick.  The lanes of one call advance together, so a tick is one
step of the scan whatever the lane count."""


def read(ctx):
    calls = ctx["reduction"].calls
    if not calls:
        return None
    busy = sum(b for _, b in calls)
    return busy / (len(calls) * ctx["ticks"]) * 1e6
