"""Engine scan layer (``repro.core.engine``): the ``tick/finish`` phase's
share of the device op time in the traced calls, in percent, by the scope
its operations carry.  A share survives a trace that drops events evenly
across ticks."""

from bench.spans import phase_share


def read(ctx):
    return phase_share(ctx, "TICK_FINISH")
