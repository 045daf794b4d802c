"""Engine scan layer (``repro.core.engine``): host-clock microseconds per
simulated tick that the host waits for the device, from the program's own
``engine.device_wait`` spans summed over calls times ticks.  The host clock
bounds it, so a device trace that drops events cannot shrink it."""


def read(ctx):
    red = ctx.get("spans")
    if red is None or not red.device_wait_s:
        return None
    waits = red.device_wait_s
    return sum(waits) / (len(waits) * ctx["ticks"]) * 1e6
